"""Substrate tests: synthetic data, quality transforms, partitions,
optimizers, checkpointing."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data import (apply_quality, gaussian_blur, iid_partition,
                        make_dataset, mixed_quality_dataset, noniid_partition,
                        sharpen, train_test_split)
from repro.optim import adamw, sgd, apply_updates, clip_by_global_norm
from repro.checkpoint import save_checkpoint, restore_checkpoint


# ---------------------------------------------------------------------------
def test_synth_dataset_shapes_and_determinism():
    d1 = make_dataset("synthmnist", 64, seed=3)
    d2 = make_dataset("synthmnist", 64, seed=3)
    assert d1["x"].shape == (64, 28, 28, 1)
    np.testing.assert_array_equal(d1["x"], d2["x"])
    assert set(np.unique(d1["y"])) <= set(range(10))


def test_synth_classes_are_separable():
    """Nearest-class-template classification beats chance by a wide margin
    — the datasets are learnable, supporting the FL experiments."""
    d = make_dataset("synthcifar", 256, seed=0)
    x = d["x"].reshape(256, -1)
    y = d["y"]
    means = np.stack([x[y == c].mean(0) for c in range(10)])
    pred = np.argmin(
        ((x[:, None, :] - means[None]) ** 2).sum(-1), axis=1)
    assert (pred == y).mean() > 0.5


def test_blur_reduces_sharpen_increases_detail():
    d = make_dataset("synthcifar", 16, seed=1)
    x = d["x"]

    def hf_energy(a):
        gx = np.diff(a, axis=1)
        return float((gx ** 2).mean())

    assert hf_energy(gaussian_blur(x, 1.5)) < hf_energy(x)
    assert hf_energy(sharpen(x)) > hf_energy(x)


def test_mixed_quality_covers_all_levels():
    d = make_dataset("synthmnist", 100, seed=2)
    m = mixed_quality_dataset(d)
    assert set(np.unique(m["q"])) == {0, 1, 2, 3, 4}
    # level-0 samples untouched
    np.testing.assert_array_equal(m["x"][m["q"] == 0], d["x"][m["q"] == 0])


@settings(max_examples=10, deadline=None)
@given(n_workers=st.sampled_from([10, 20]), imbalance=st.floats(0.6, 0.9))
def test_noniid_partition_imbalance(n_workers, imbalance):
    labels = np.random.RandomState(0).randint(0, 10, size=2000)
    parts = noniid_partition(labels, n_workers, imbalance, seed=1)
    all_idx = np.concatenate(parts)
    assert len(np.unique(all_idx)) == len(all_idx)          # disjoint
    # early workers draw from full class pools: tight bound; late workers
    # may hit drained pools (greedy fallback): loose bound
    for k, p in enumerate(parts):
        dom = k % 10
        frac = (labels[p] == dom).mean()
        bound = 0.05 if k < 10 else 0.3
        assert frac > imbalance - bound, (k, frac)


def test_iid_partition_disjoint_and_complete():
    parts = iid_partition(100, 7, seed=0)
    cat = np.sort(np.concatenate(parts))
    np.testing.assert_array_equal(cat, np.arange(100))


# ---------------------------------------------------------------------------
def test_adamw_matches_closed_form_first_step():
    opt = adamw(lr=0.1, b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0)
    p = {"w": jnp.array([2.0])}
    g = {"w": jnp.array([0.5])}
    st_ = opt.init(p)
    upd, st_ = opt.update(g, st_, p)
    # first step: m_hat = g, v_hat = g^2 -> update = lr * g/(|g|+eps) = lr
    np.testing.assert_allclose(float(upd["w"][0]), 0.1, rtol=1e-5)
    p2 = apply_updates(p, upd)
    np.testing.assert_allclose(float(p2["w"][0]), 1.9, rtol=1e-5)


def test_sgd_momentum_accumulates():
    opt = sgd(lr=1.0, momentum=0.5)
    p = {"w": jnp.zeros(1)}
    g = {"w": jnp.ones(1)}
    s = opt.init(p)
    u1, s = opt.update(g, s, p)
    u2, s = opt.update(g, s, p)
    np.testing.assert_allclose(float(u1["w"][0]), 1.0)
    np.testing.assert_allclose(float(u2["w"][0]), 1.5)


def test_clip_by_global_norm():
    g = {"a": jnp.ones(4) * 3.0}        # norm 6
    clipped, norm = clip_by_global_norm(g, 3.0)
    np.testing.assert_allclose(float(norm), 6.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(clipped["a"]), np.ones(4) * 1.5,
                               rtol=1e-5)


def test_adamw_converges_quadratic():
    opt = adamw(lr=0.1)
    p = {"w": jnp.array([5.0])}
    s = opt.init(p)
    for _ in range(200):
        g = {"w": 2 * p["w"]}
        u, s = opt.update(g, s, p)
        p = apply_updates(p, u)
    assert abs(float(p["w"][0])) < 0.05


# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    from repro.models.attention import KVCache
    tree = {
        "a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
        "nested": {"b": jnp.ones((4,), jnp.bfloat16), "c": None},
        "tup": (jnp.zeros(2), KVCache(k=jnp.ones((1, 2)), v=jnp.zeros((1, 2)))),
    }
    path = os.path.join(tmp_path, "ckpt.npz")
    save_checkpoint(path, tree, metadata={"step": 7})
    restored = restore_checkpoint(path, tree)
    flat1 = jax.tree.leaves(tree)
    flat2 = jax.tree.leaves(restored)
    for a, b in zip(flat1, flat2):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert isinstance(restored["tup"][1], KVCache)
