"""Double-buffered round engine (fl/engine.py prefetch ring +
server/runtime staging seams): the cross-mode equivalence harness.

The overlapped path stages round r+1's cohort tensors while round r's
fused program runs on device; a staged cohort is *value-validated*
against the actual call inputs at consume time, so a hit is bit-exact
by construction and any mismatch falls back to the eager pack. These
tests prove overlapped == eager — 0 ulp on params and history — across
sync/async × cnn/transformer × selection policies × fault chaos, that
prefetch adds zero compiled programs, that mid-run policy/fleet/mode
mutation flushes the ring instead of replaying stale cohorts, and that
a checkpoint taken with a staged cohort in flight resumes bit-exactly.
"""
import dataclasses
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.checkpoint.fleet import (restore_fleet_checkpoint,
                                    save_fleet_checkpoint, snapshot_server)
from repro.configs import ARCHS, reduced
from repro.configs.paper_cnn import CNNConfig
from repro.fl import CFLConfig, CFLSession
from repro.fl.faults import FaultPlan

CFG = CNNConfig(name="overlap-test", in_channels=1, image_size=28,
                stem_channels=8, stages=((16, 2), (32, 2)),
                groupnorm_groups=4, elastic_widths=(0.5, 1.0))


def _param_err(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(jnp.max(jnp.abs(x - y))), a, b)))


def _hist_eq(a, b):
    """Recursive history equality with NaN == NaN (round-0 fairness
    stats are NaN before any client reports an accuracy)."""
    if isinstance(a, dict):
        return set(a) == set(b) and all(_hist_eq(a[k], b[k]) for k in a)
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(_hist_eq(x, y)
                                        for x, y in zip(a, b))
    if isinstance(a, float) and isinstance(b, float):
        return (a != a and b != b) or a == b
    return bool(np.array_equal(np.asarray(a), np.asarray(b)))


def _session(seed=0, *, overlap=False, algorithm="cfl", mode="sync",
             selection="uniform", cfg=CFG, kind="synthmnist", **fl_kw):
    fl = CFLConfig(n_workers=4, local_epochs=1, batch_size=32, lr=0.05,
                   seed=seed, mode=mode, selection=selection,
                   overlap=overlap, **fl_kw)
    return CFLSession.from_synthetic(
        cfg, kind=kind, n_workers=4, n_samples=400,
        heterogeneity="quality", fl_cfg=fl, seed=seed,
        algorithm=algorithm)


def _ab(rounds=3, **kw):
    """One eager and one overlapped session over the same population;
    returns (eager, overlapped) after running both."""
    a = _session(overlap=False, **kw)
    b = _session(overlap=True, **kw)
    a.run(rounds)
    b.run(rounds)
    return a, b


def _assert_bit_exact(a, b, *, want_hits=None):
    err = _param_err(a.server.params, b.server.params)
    assert err == 0.0, f"overlapped diverged from eager: {err}"
    assert _hist_eq(a.server.history, b.server.history)
    stats = b.server.engine.prefetch_stats()
    if want_hits is not None:
        assert stats["hits"] >= want_hits, stats


# ---------------------------------------------------------------------------
# overlapped == eager: the core equivalence sweep (sync and async)
# ---------------------------------------------------------------------------
@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 100),
       selection=st.sampled_from(["full", "uniform", "latency"]))
def test_overlap_matches_eager_sync(seed, selection):
    """Sync rounds with prefetch on are bit-exact vs eager for every
    stateless selection policy, and the ring actually hits (the staged
    cohort is consumed, not just built and discarded)."""
    a, b = _ab(rounds=3, seed=seed, selection=selection)
    _assert_bit_exact(a, b, want_hits=1)


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 100),
       selection=st.sampled_from(["full", "uniform"]))
def test_overlap_matches_eager_async(seed, selection):
    """Async buffered rounds: the DISPATCH-seam staging path is
    bit-exact vs the eager async run."""
    a, b = _ab(rounds=3, seed=seed, selection=selection, mode="async")
    _assert_bit_exact(a, b, want_hits=1)


@settings(max_examples=2, deadline=None)
@given(seed=st.integers(0, 100))
def test_overlap_matches_eager_fedavg(seed):
    a, b = _ab(rounds=3, seed=seed, algorithm="fedavg")
    _assert_bit_exact(a, b, want_hits=1)


def test_overlap_fairness_policy_is_conservative():
    """Fairness selection is state-dependent (round r+1's draw depends
    on round r's record), so the engine must not speculate: nothing is
    staged, nothing can go stale, and the run still matches eager."""
    a, b = _ab(rounds=3, selection="fairness")
    _assert_bit_exact(a, b)
    assert b.server.engine.prefetch_stats()["staged"] == 0


@pytest.mark.slow
def test_overlap_matches_eager_transformer():
    """The equivalence holds for the transformer zoo family too (the
    staged stream/gather tensors are family-agnostic)."""
    cfg = reduced(ARCHS["granite-3-8b"], n_layers=2, d_model=64)
    a, b = _ab(rounds=2, cfg=cfg, kind="synthlm", selection="uniform")
    _assert_bit_exact(a, b, want_hits=1)


# ---------------------------------------------------------------------------
# fault chaos: staged cohorts under drops/stragglers/corruption
# ---------------------------------------------------------------------------
@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 50),
       drop=st.sampled_from([0.0, 0.2, 0.35]),
       corrupt=st.sampled_from([0.0, 0.15]))
def test_overlap_matches_eager_under_faults(seed, drop, corrupt):
    """Fault injection keys off (plan.seed, engagement id) and the
    faulty path always trains the padded subset cohort; the staged
    subset must replay the identical faults, misses and quarantines."""
    plan = FaultPlan(seed=seed, drop_rate=drop, straggle_rate=0.2,
                     corrupt_rate=corrupt)
    a, b = _ab(rounds=4, seed=seed, faults=plan)
    _assert_bit_exact(a, b)
    # miss accounting is part of history equality, but assert the
    # columns exist so a silent accounting rewrite can't pass
    assert all("dropped" in r and "quarantined" in r
               for r in b.server.history)


def test_overlap_matches_eager_async_faults():
    a, b = _ab(rounds=4, seed=7, mode="async", async_buffer=2,
               faults="drop=0.25,straggle=0.2,corrupt=0.15,seed=7")
    _assert_bit_exact(a, b)


# ---------------------------------------------------------------------------
# program-count invariant: prefetch is data movement, not compilation
# ---------------------------------------------------------------------------
def test_overlap_adds_zero_compiled_programs():
    """Staging reuses the eager pack/gather/shard code paths, so the
    fused train+eval program count must not grow when prefetch is on.
    A subset-only run stays at the single fused program (the faults-lane
    invariant); churn that alternates full/subset cohorts compiles the
    same two leading-dim variants eagerly or overlapped — never more."""
    sess = _session(overlap=True)
    sess.run(4)
    eng = sess.server.engine
    assert eng.prefetch_stats()["hits"] > 0
    get = getattr(eng._train_eval, "_cache_size", None)
    if not callable(get):
        pytest.skip("jit._cache_size accessor unavailable")
    assert get() == 1                      # uniform-only: one program

    def churn(overlap):
        s = _session(overlap=overlap, selection="full")
        s.run(2)
        s.run(2, selection="uniform")
        s.run(2, selection="full")
        return s.server.engine._train_eval._cache_size()

    assert churn(True) == churn(False)     # prefetch adds zero


# ---------------------------------------------------------------------------
# staged-state invalidation: policy / fleet / mode churn mid-run
# ---------------------------------------------------------------------------
def test_mid_run_policy_mutation_flushes_staged_cohort():
    """set_selection mid-run invalidates the staged next cohort: the
    ring is flushed (no stale replay) and the run stays bit-exact vs an
    eager session mutated identically."""
    a = _session(overlap=False)
    b = _session(overlap=True)
    a.run(2)
    b.run(2)
    assert len(b.server.engine._prefetch_ring) > 0   # staged, in flight
    a.server.set_selection("full")
    b.server.set_selection("full")
    assert len(b.server.engine._prefetch_ring) == 0  # invalidated
    a.run(2)
    b.run(2)
    _assert_bit_exact(a, b)
    assert b.server.engine.prefetch_stats()["flushes"] >= 1


def test_mid_run_fleet_mutation_flushes_staged_cohort():
    """set_fleet re-registers the population; the tracker invalidate
    hook must drop whatever was staged under the old fleet."""
    b = _session(overlap=True)
    b.run(2)
    assert len(b.server.engine._prefetch_ring) > 0
    b.server.tracker.set_fleet(b.server.clients)
    assert len(b.server.engine._prefetch_ring) == 0


def test_mid_run_mode_switch_flushes_and_stays_exact():
    a = _session(overlap=False)
    b = _session(overlap=True)
    a.run(2)
    b.run(2)
    a.server.set_mode("async")
    b.server.set_mode("async")
    assert len(b.server.engine._prefetch_ring) == 0
    a.run(2)
    b.run(2)
    a.server.set_mode("sync")
    b.server.set_mode("sync")
    a.run(2)
    b.run(2)
    _assert_bit_exact(a, b)


def test_stale_staged_cohort_is_rejected_not_replayed():
    """A hand-planted wrong staged entry (wrong seeds) must fail value
    validation: counted as a miss, ring flushed, results identical to
    eager — the validation layer is what makes speculation safe."""
    a = _session(overlap=False)
    b = _session(overlap=True)
    a.run(1)
    b.run(1)
    eng = b.server.engine
    eng.flush_prefetch("test")
    eng.stage_cohort(b.server.round_idx + 1, b.server.client_data,
                     batch_size=b.server.fl.batch_size,
                     epochs=b.server.fl.local_epochs,
                     seeds=[999] * len(b.server.clients),
                     eval_datasets=b.server.test_data)
    a.run(2)
    b.run(2)
    _assert_bit_exact(a, b)
    assert eng.prefetch_stats()["misses"] >= 1


def test_run_overlap_kwarg_toggles_prefetch():
    """session.run(overlap=...) flips the knob between calls and both
    halves still match an all-eager run."""
    a = _session(overlap=False)
    b = _session(overlap=False)
    a.run(4)
    b.run(2, overlap=True)
    assert b.server.engine.prefetch_enabled
    b.run(2, overlap=False)
    assert not b.server.engine.prefetch_enabled
    _assert_bit_exact(a, b)


def test_overlap_requires_batched_engine():
    seq = _session(batched_rounds=False)
    with pytest.raises(ValueError, match="batched"):
        seq.server.set_overlap(True)
    seq.server.set_overlap(False)        # disabling is always fine
    il = _session(algorithm="il", selection="full")
    with pytest.raises(ValueError, match="IL"):
        il.run(1, overlap=True)


def test_prefetch_ring_depth_and_disable():
    """enable_prefetch(depth) bounds the ring; depth<=0 disables and
    flushes; stage_cohort is a no-op while disabled."""
    sess = _session(overlap=True, prefetch_depth=2)
    eng = sess.server.engine
    assert eng.prefetch_enabled and eng._prefetch_depth == 2
    sess.run(2)
    eng.enable_prefetch(1)
    assert len(eng._prefetch_ring) <= 1
    eng.enable_prefetch(0)
    assert not eng.prefetch_enabled and not eng._prefetch_ring
    eng.stage_cohort(0, sess.server.client_data, batch_size=32,
                     epochs=1, seeds=[0] * len(sess.server.clients))
    assert not eng._prefetch_ring


# ---------------------------------------------------------------------------
# checkpoint with a staged cohort in flight
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["sync", "async"])
def test_checkpoint_resume_with_staged_cohort(mode, tmp_path):
    """Kill-resume parity with the ring non-empty at the checkpoint:
    the snapshot carries the staged cohort's *derivation* and restore
    re-stages it bit-exactly against the restored packs."""
    ref = _session(seed=3, overlap=True, mode=mode)
    ref.run(5)
    a = _session(seed=3, overlap=True, mode=mode)
    a.run(2)
    assert len(a.server.engine._prefetch_ring) > 0
    path = os.fspath(tmp_path / "staged.ckpt")
    save_fleet_checkpoint(path, a.server)
    b = _session(seed=3, overlap=True, mode=mode)
    info = restore_fleet_checkpoint(path, b.server)
    assert not info["resharded"]
    assert (len(b.server.engine._prefetch_ring)
            == len(a.server.engine._prefetch_ring))
    b.run(3)
    err = _param_err(ref.server.params, b.server.params)
    assert err == 0.0, f"resume with staged cohort not bit-exact: {err}"
    assert _hist_eq(ref.server.history, b.server.history)


def test_snapshot_prefetch_is_derivational_not_tensors():
    """The snapshot must hold seeds/selection metadata, never the staged
    device buffers (restore re-derives them from the resident packs)."""
    sess = _session(overlap=True)
    sess.run(2)
    snap = snapshot_server(sess.server)
    assert snap["prefetch"]["entries"], "ring empty at snapshot"
    for e in snap["prefetch"]["entries"]:
        assert set(e) == {"round_idx", "batch_size", "epochs", "seeds",
                          "has_eval", "sel"}


def test_restore_without_prefetch_key_keeps_engine_usable():
    """A snapshot written by an eager run restores into an overlapped
    server without touching its configured depth."""
    a = _session(seed=5, overlap=False)
    a.run(2)
    snap = snapshot_server(a.server)
    assert snap["prefetch"] == {"depth": 0, "entries": [],
                                "stats": {"staged": 0, "hits": 0,
                                          "misses": 0, "flushes": 0}}
    b = _session(seed=5, overlap=True)
    from repro.checkpoint.fleet import restore_server
    snap.pop("prefetch")
    snap["prefetch"] = None          # pre-overlap writer shape
    restore_server(b.server, snap)
    assert b.server.engine.prefetch_enabled   # depth survives
    b.run(2)
    assert b.server.engine.prefetch_stats()["staged"] > 0
