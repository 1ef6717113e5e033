"""CFL core properties: extraction/alignment algebra (Alg. 3), GA search
bounds (Alg. 1), predictor learning (Alg. 2), latency monotonicity."""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs.paper_cnn import CNNConfig
from repro.core import (AccuracyPredictor, LatencyTable, SubmodelSpec,
                        aggregate, aggregate_coverage, coverage_cnn,
                        extract_cnn, full_spec, pad_cnn, random_spec,
                        search_submodel, sub_cnn_config, train_step_latency,
                        EDGE_FLEET)
from repro.models import cnn

CFG = CNNConfig(stages=((16, 3), (32, 3)), stem_channels=8,
                groupnorm_groups=4, in_channels=3, image_size=16)


def _spec_strategy():
    return st.tuples(
        st.tuples(st.integers(1, 3), st.integers(1, 3)),
        st.tuples(st.sampled_from(CFG.elastic_widths),
                  st.sampled_from(CFG.elastic_widths)),
    ).map(lambda t: SubmodelSpec(depth=t[0], width=t[1]))


@settings(max_examples=15, deadline=None)
@given(spec=_spec_strategy())
def test_extract_pad_roundtrip(spec):
    """pad(extract(p)) == p on covered entries, 0 elsewhere (Fig. 2/3)."""
    params = cnn.init_params(jax.random.PRNGKey(0), CFG)
    sub = extract_cnn(params, CFG, spec)
    padded = pad_cnn(sub, params, CFG, spec)
    cov = coverage_cnn(params, CFG, spec)
    err_cov = jax.tree.map(
        lambda p, q, c: float(jnp.max(jnp.abs(p * c - q))), params, padded,
        cov)
    assert max(jax.tree.leaves(err_cov)) == 0.0
    outside = jax.tree.map(lambda q, c: float(jnp.max(jnp.abs(q * (1 - c)))),
                           padded, cov)
    assert max(jax.tree.leaves(outside)) == 0.0


@settings(max_examples=15, deadline=None)
@given(spec=_spec_strategy())
def test_submodel_forward_runs(spec):
    params = cnn.init_params(jax.random.PRNGKey(1), CFG)
    sub = extract_cnn(params, CFG, spec)
    scfg = sub_cnn_config(CFG, spec)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 16, 3))
    logits, _ = cnn.forward(sub, scfg, x)
    assert logits.shape == (2, 10)
    assert bool(jnp.all(jnp.isfinite(logits)))


@settings(max_examples=10, deadline=None)
@given(w1=st.floats(0.1, 10.0), w2=st.floats(0.1, 10.0))
def test_aggregate_is_weighted_mean(w1, w2):
    params = cnn.init_params(jax.random.PRNGKey(3), CFG)
    d1 = jax.tree.map(jnp.ones_like, params)
    d2 = jax.tree.map(lambda a: 3.0 * jnp.ones_like(a), params)
    agg = aggregate([d1, d2], [w1, w2])
    expect = (w1 + 3.0 * w2) / (w1 + w2)
    leaf = jax.tree.leaves(agg)[0]
    np.testing.assert_allclose(float(leaf.flatten()[0]), expect, rtol=1e-5)


def test_aggregate_full_specs_equals_fedavg():
    """With all-full submodels, Alg. 3 degenerates to plain FedAvg."""
    params = cnn.init_params(jax.random.PRNGKey(4), CFG)
    fs = full_spec(CFG)
    deltas = [jax.tree.map(
        lambda a, i=i: (i + 1.0) * jnp.ones_like(a), params)
        for i in range(3)]
    padded = [pad_cnn(extract_cnn(d, CFG, fs), params, CFG, fs)
              for d in deltas]
    agg = aggregate(padded, [1.0, 1.0, 2.0])
    np.testing.assert_allclose(
        float(jax.tree.leaves(agg)[0].flatten()[0]), (1 + 2 + 3 * 2) / 4.0,
        rtol=1e-6)


def test_coverage_aggregation_no_dilution():
    """A parameter covered by only one client keeps that client's full
    update under coverage normalisation (but is diluted under Alg. 3)."""
    params = cnn.init_params(jax.random.PRNGKey(5), CFG)
    small = SubmodelSpec(depth=(1, 1), width=(0.25, 0.25))
    big = full_spec(CFG)
    d_small = pad_cnn(extract_cnn(jax.tree.map(jnp.ones_like, params),
                                  CFG, small), params, CFG, small)
    d_big = pad_cnn(extract_cnn(jax.tree.map(jnp.ones_like, params),
                                CFG, big), params, CFG, big)
    covs = [coverage_cnn(params, CFG, small), coverage_cnn(params, CFG, big)]
    plain = aggregate([d_small, d_big], [1.0, 1.0])
    covnorm = aggregate_coverage([d_small, d_big], covs, [1.0, 1.0])
    # deepest block of stage 2 is only covered by `big`
    leaf_plain = plain["stages"][1]["blocks"][2]["conv1"]["w"]
    leaf_cov = covnorm["stages"][1]["blocks"][2]["conv1"]["w"]
    assert float(leaf_plain.max()) == pytest.approx(0.5)
    assert float(leaf_cov.max()) == pytest.approx(1.0)


def test_latency_monotonic_in_depth_and_width():
    prof = EDGE_FLEET[0]
    small = SubmodelSpec(depth=(1, 1), width=(0.25, 0.25))
    mid = SubmodelSpec(depth=(2, 2), width=(0.5, 0.5))
    big = full_spec(CFG)
    ls = train_step_latency(CFG, small, prof)
    lm = train_step_latency(CFG, mid, prof)
    lb = train_step_latency(CFG, big, prof)
    assert ls < lm < lb


def test_ga_respects_latency_bound():
    table = LatencyTable(CFG, depth_choices=(1, 2, 3))
    pred = AccuracyPredictor(CFG)
    dev = EDGE_FLEET[2]
    lo = train_step_latency(CFG, SubmodelSpec((1, 1), (0.25, 0.25)), dev)
    hi = train_step_latency(CFG, full_spec(CFG), dev)
    bound = (lo + hi) / 2          # feasible but excludes the full model
    spec = search_submodel(CFG, pred, table, device=dev.name,
                           quality=1, latency_bound=bound, seed=3)
    assert table.lookup(spec, dev.name) < bound


def test_predictor_learns_profiles():
    pred = AccuracyPredictor(CFG, lr=1e-2)
    rng = random.Random(0)
    # synthetic ground truth: bigger + cleaner -> more accurate
    samples = []
    for _ in range(64):
        spec = random_spec(CFG, rng)
        q = rng.randint(0, 4)
        acc = 0.2 + 0.1 * sum(spec.depth) / 6 + 0.3 * sum(spec.width) / 2 \
            - 0.05 * q
        samples.append((spec, q, acc))
    pred.add_profiles(samples)
    maes = [pred.train_round(epochs=50) for _ in range(6)]
    assert maes[-1] < 0.08
    big = pred.predict(full_spec(CFG), 0)
    small = pred.predict(SubmodelSpec((1, 1), (0.25, 0.25)), 4)
    assert big > small
