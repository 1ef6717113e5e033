"""Family-agnostic CFL control plane: the ElasticFamily spec-space surface
(mutate/crossover bounds, featurize dims, cost model), latency-bounded
genetic search for the transformer zoo, and the CFLSession entry point."""
import random

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import ARCHS, reduced
from repro.configs.paper_cnn import CNNConfig
from repro.core import (AccuracyPredictor, LatencyTable,
                        TransformerElasticFamily, family_for, featurize,
                        feature_dim, search_submodel, train_step_latency,
                        EDGE_FLEET)

CNN_CFG = CNNConfig(name="cp-test", in_channels=1, image_size=28,
                    stem_channels=8, stages=((16, 3), (32, 2)),
                    groupnorm_groups=4,
                    elastic_widths=(0.25, 0.5, 0.75, 1.0))
ZOO_CFG = reduced(ARCHS["granite-3-8b"], n_layers=4, d_model=64)
MOE_CFG = reduced(ARCHS["granite-moe-1b-a400m"], n_layers=3, d_model=64)

FAMILIES = {
    "cnn": family_for(CNN_CFG),
    "dense": family_for(ZOO_CFG),
    "moe": family_for(MOE_CFG),
}


def _assert_cnn_in_bounds(spec):
    cfg = CNN_CFG
    assert len(spec.depth) == len(cfg.stages)
    for d, (_, bmax) in zip(spec.depth, cfg.stages):
        assert 1 <= d <= bmax
    for w in spec.width:
        assert w in cfg.elastic_widths


def _assert_zoo_in_bounds(fam, spec):
    cfg = fam.cfg
    grid = set(cfg.elastic_widths) | {1.0}
    assert len(spec.layers) == len(cfg.segments)
    for keep, seg in zip(spec.layers, cfg.segments):
        assert len(keep) >= 1
        assert tuple(sorted(set(keep))) == keep          # sorted, unique
        assert all(0 <= i < seg.n_layers for i in keep)
    assert spec.ff_frac in grid
    assert spec.expert_frac in grid
    assert spec.ssm_head_frac in grid


# ---------------------------------------------------------------------------
# mutate / crossover stay in-bounds (hypothesis round-trips, both families)
# ---------------------------------------------------------------------------
@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_cnn_mutate_crossover_in_bounds(seed):
    fam = FAMILIES["cnn"]
    rng = random.Random(seed)
    a, b = fam.random_spec(rng), fam.random_spec(rng)
    _assert_cnn_in_bounds(a)
    _assert_cnn_in_bounds(fam.mutate(a, rng, p=0.7))
    child = fam.crossover(a, b, rng)
    _assert_cnn_in_bounds(child)
    _assert_cnn_in_bounds(fam.mutate(child, rng, p=1.0))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000),
       fam_key=st.sampled_from(["dense", "moe"]))
def test_zoo_mutate_crossover_in_bounds(seed, fam_key):
    fam = FAMILIES[fam_key]
    rng = random.Random(seed)
    a, b = fam.random_spec(rng), fam.random_spec(rng)
    _assert_zoo_in_bounds(fam, a)
    _assert_zoo_in_bounds(fam, fam.mutate(a, rng, p=0.7))
    child = fam.crossover(a, b, rng)
    _assert_zoo_in_bounds(fam, child)
    _assert_zoo_in_bounds(fam, fam.mutate(child, rng, p=1.0))


def test_zoo_inapplicable_dims_stay_whole():
    """A dense parent (no MoE/SSM) never mutates expert/SSD-head genes."""
    fam = FAMILIES["dense"]
    rng = random.Random(0)
    for _ in range(32):
        s = fam.mutate(fam.random_spec(rng), rng, p=1.0)
        assert s.expert_frac == 1.0
        assert s.ssm_head_frac == 1.0


# ---------------------------------------------------------------------------
# featurize: dimension and range checks
# ---------------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000),
       fam_key=st.sampled_from(["cnn", "dense", "moe"]))
def test_featurize_dims(seed, fam_key):
    fam = FAMILIES[fam_key]
    rng = random.Random(seed)
    spec = fam.random_spec(rng)
    f = fam.featurize(spec)
    assert f.shape == (fam.feature_dim,)
    assert np.all(np.isfinite(f))
    assert np.all(f >= 0.0) and np.all(f <= 1.0 + 1e-6)
    # predictor features = structure + quality one-hot
    x = featurize(fam, spec, quality=3)
    assert x.shape == (feature_dim(fam),)
    assert feature_dim(fam) == fam.feature_dim + 5


def test_featurize_full_spec_is_ones_ish():
    for fam in FAMILIES.values():
        f = fam.featurize(fam.full_spec())
        np.testing.assert_allclose(f, np.ones_like(f), atol=1e-6)


# ---------------------------------------------------------------------------
# cost model: monotone in spec size, and the LUT memoises
# ---------------------------------------------------------------------------
def test_cost_model_minimal_below_full():
    for fam in FAMILIES.values():
        lo, hi = fam.minimal_spec(), fam.full_spec()
        assert fam.flops(lo) < fam.flops(hi)
        assert fam.param_bytes(lo) < fam.param_bytes(hi)
        prof = EDGE_FLEET[0]
        assert train_step_latency(fam, lo, prof) < \
            train_step_latency(fam, hi, prof)


def test_latency_table_lazy_fill_for_zoo():
    fam = FAMILIES["dense"]
    table = LatencyTable(fam)
    assert len(table) == 0          # combinatorial gene space: no pre-fill
    spec = fam.random_spec(random.Random(1))
    t1 = table.lookup(spec, EDGE_FLEET[0].name)
    assert len(table) == 1
    assert table.lookup(spec, EDGE_FLEET[0].name) == t1


# ---------------------------------------------------------------------------
# Alg. 1 for the zoo: search respects g(ω, p_k) < l_k
# ---------------------------------------------------------------------------
def test_zoo_search_respects_latency_bound():
    fam = TransformerElasticFamily(ZOO_CFG, seq_len=24)
    table = LatencyTable(fam)
    pred = AccuracyPredictor(fam)
    dev = EDGE_FLEET[2]
    lo = train_step_latency(fam, fam.minimal_spec(), dev)
    hi = train_step_latency(fam, fam.full_spec(), dev)
    bound = (lo + hi) / 2          # feasible but excludes the full model
    spec = search_submodel(fam, pred, table, device=dev.name,
                           quality=1, latency_bound=bound, seed=3)
    assert table.lookup(spec, dev.name) < bound
    assert spec != fam.full_spec()


# ---------------------------------------------------------------------------
# CFLSession: the one entry point, LM scenario end-to-end
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_cfl_session_transformer_rounds():
    from repro.fl import CFLConfig, CFLSession
    fam = TransformerElasticFamily(ZOO_CFG, seq_len=16)
    fl = CFLConfig(n_workers=3, local_epochs=1, batch_size=8, lr=0.05,
                   seed=0)
    sess = CFLSession.from_synthetic(fam, n_workers=3, n_samples=96,
                                     heterogeneity="both", fl_cfg=fl)
    hist = sess.run(2)
    assert len(hist) == 2
    for rec in hist:
        assert set(rec) >= {"accs", "fairness", "timing", "specs",
                            "predictor_mae"}
        assert len(rec["accs"]) == 3
        assert rec["timing"]["round_time"] > 0
    # every searched spec honours its client's latency bound (or is the
    # deterministic minimal fallback)
    minimal = fam.minimal_spec()
    specs = sess.server.sample_submodels()
    for client, spec in zip(sess.clients, specs):
        lat = sess.server.latency.lookup(spec, client.device)
        assert lat < client.latency_bound or spec == minimal
    assert sess.fairness()["mean"] >= 0.0


def test_cfl_session_rejects_unknown_algorithm():
    from repro.fl import CFLSession
    with pytest.raises(ValueError):
        CFLSession(CNN_CFG, [], [], [], algorithm="nope")


def test_cfl_session_il_semantics():
    """IL has no aggregated parent and consumes its budget in one shot."""
    from repro.fl import CFLConfig, CFLSession
    fl = CFLConfig(n_workers=3, local_epochs=1, batch_size=32, lr=0.08,
                   seed=0)
    sess = CFLSession.from_synthetic(
        CNN_CFG, kind="synthmnist", n_workers=3, n_samples=300,
        heterogeneity="none", fl_cfg=fl, algorithm="il")
    hist = sess.run(1)
    assert len(hist) == 1 and len(sess.il_accs) == 3
    with pytest.raises(RuntimeError):
        sess.run(1)                 # single-shot: no silent restart
    with pytest.raises(RuntimeError):
        _ = sess.params             # no aggregated parent to return
