"""Family-agnostic CFL control plane: the ElasticFamily spec-space surface
(mutate/crossover bounds, featurize dims, cost model), latency-bounded
genetic search for the transformer zoo, the workers' GAs in lockstep
against one GA per worker, and the CFLSession entry point."""
import random

import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro import obs
from repro.configs import ARCHS, reduced
from repro.configs.paper_cnn import CNNConfig
from repro.core import (AccuracyPredictor, LatencyTable, SearchConfig,
                        TransformerElasticFamily, family_for, featurize,
                        feature_dim, search_all_workers, search_submodel,
                        train_step_latency, EDGE_FLEET)

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


# ---------------------------------------------------------------------------
# Alg. 1 in lockstep: the same genes as one GA per worker, fewer calls
# ---------------------------------------------------------------------------
def _ga_per_worker(fam, pred, table, device, quality, bound, scfg, seed):
    """The per-worker GA, one predictor round trip per generation: the
    reference the lockstep search must reproduce gene for gene."""
    rng = random.Random(seed)
    pop = [fam.random_spec(rng) for _ in range(scfg.population)]
    best, best_acc = None, -1.0
    for _ in range(scfg.generations):
        feasible = [s for s in pop if table.lookup(s, device) < bound]
        if feasible:
            x = np.stack([featurize(fam, s, quality) for s in feasible])
            accs = np.asarray(pred._net(pred.params, jnp.asarray(x)))
            order = np.argsort(-accs)
            if accs[order[0]] > best_acc:
                best_acc = float(accs[order[0]])
                best = feasible[order[0]]
            elites = [feasible[i] for i in order[:scfg.elite]]
        else:
            elites = []
        nxt = list(elites)
        while len(nxt) < scfg.population:
            if elites and rng.random() < scfg.crossover_prob:
                child = fam.crossover(rng.choice(elites), rng.choice(pop),
                                      rng)
            else:
                child = fam.random_spec(rng)
            nxt.append(fam.mutate(child, rng, scfg.mutate_prob))
        pop = nxt
    return fam.minimal_spec() if best is None else best


def _trained_predictor(fam, seed):
    """A predictor fitted to a few random profiles, so its scores differ
    across specs and qualities."""
    rng = random.Random(seed + 101)
    pred = AccuracyPredictor(fam, seed=seed)
    pred.add_profiles([(fam.random_spec(rng), i % 5, rng.random())
                       for i in range(24)])
    pred.train_round(epochs=2)
    return pred


def _search_family(key):
    if key == "cnn":
        return FAMILIES["cnn"]
    return TransformerElasticFamily(ZOO_CFG, seq_len=24)


def _bounds(fam, table, devices):
    """Per-worker bounds between each device's minimal and full latency;
    the last worker's admits nothing."""
    out = []
    for i, d in enumerate(devices):
        lo = table.lookup(fam.minimal_spec(), d)
        hi = table.lookup(fam.full_spec(), d)
        out.append(lo + (hi - lo) * (0.3 + 0.1 * (i % 5)))
    out[-1] = 0.0
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("fam_key", ["cnn", "zoo"])
def test_lockstep_search_matches_per_worker_ga(fam_key, seed):
    fam = _search_family(fam_key)
    table = LatencyTable(fam)
    pred = _trained_predictor(fam, seed)
    scfg = SearchConfig()
    k = 7
    devices = [EDGE_FLEET[i % len(EDGE_FLEET)].name for i in range(k)]
    qualities = [i % 5 for i in range(k)]
    bounds = _bounds(fam, table, devices)
    before = obs.counters().get("search.predict_calls", 0)
    got = search_all_workers(fam, pred, table, devices=devices,
                             qualities=qualities, latency_bounds=bounds,
                             search_cfg=scfg, seed=seed)
    calls = obs.counters()["search.predict_calls"] - before
    want = [_ga_per_worker(fam, pred, table, d, q, lb, scfg,
                           seed * 977 + i)
            for i, (d, q, lb) in enumerate(zip(devices, qualities, bounds))]
    assert [fam.genes(s) for s in got] == [fam.genes(s) for s in want]
    assert 1 <= calls <= scfg.generations
    assert got[-1] == fam.minimal_spec()       # its bound admits nothing
    assert len({fam.genes(s) for s in got[:-1]}) > 1


def test_lockstep_search_cohort_subset_through_server():
    """Partial participation: ``sample_submodels(client_ids=...)`` runs
    the lockstep search over the cohort, each worker keyed by its
    position in it, as the per-worker GA was."""
    from repro.fl import CFLConfig
    from repro.fl.client import ClientInfo
    from repro.fl.server import CFLServer
    fam = FAMILIES["cnn"]
    table = LatencyTable(fam)
    devices = [EDGE_FLEET[i % len(EDGE_FLEET)].name for i in range(10)]
    bounds = _bounds(fam, table, devices)
    clients = [ClientInfo(cid=i, device=d, quality=i % 5, n_samples=8,
                          latency_bound=b)
               for i, (d, b) in enumerate(zip(devices, bounds))]
    fl = CFLConfig(n_workers=len(clients), seed=4)
    server = CFLServer(fam, None, clients, [{}] * len(clients),
                       [{}] * len(clients), fl)
    server.predictor = _trained_predictor(fam, 4)
    server.round_idx = 3
    ids = [1, 4, 6, 9]
    got = server.sample_submodels(client_ids=ids)
    want = [_ga_per_worker(fam, server.predictor, server.latency,
                           clients[i].device, clients[i].quality,
                           clients[i].latency_bound, fl.search,
                           (fl.seed + 3) * 977 + k)
            for k, i in enumerate(ids)]
    assert [fam.genes(s) for s in got] == [fam.genes(s) for s in want]
    assert got[-1] == fam.minimal_spec()       # client 9 admits nothing


def test_search_submodel_is_one_worker_lockstep():
    fam = FAMILIES["cnn"]
    table = LatencyTable(fam)
    pred = _trained_predictor(fam, 5)
    dev = EDGE_FLEET[1].name
    bound = _bounds(fam, table, [dev, dev])[0]
    got = search_submodel(fam, pred, table, device=dev, quality=2,
                          latency_bound=bound, seed=11)
    want = _ga_per_worker(fam, pred, table, dev, 2, bound, SearchConfig(),
                          11)
    assert fam.genes(got) == fam.genes(want)


@pytest.mark.parametrize("fam_key", ["cnn", "zoo"])
def test_padded_predict_rows_match_net_and_memo(fam_key):
    fam = _search_family(fam_key)
    pred = _trained_predictor(fam, 0)
    rng = random.Random(9)
    specs = [fam.random_spec(rng) for _ in range(37)]
    quals = [rng.randrange(5) for _ in specs]
    x = np.stack([featurize(fam, s, q) for s, q in zip(specs, quals)])
    want = np.asarray(pred._net(pred.params, jnp.asarray(x)))
    got = pred.predict_rows(specs, quals, pad_to=7 * 24)
    assert got.shape == (len(specs),)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    # the genes memo gives featurize's rows, bit for bit, on a second pass
    assert pred._structure
    again = pred._features(specs, quals, len(specs))
    np.testing.assert_array_equal(again, x)
    np.testing.assert_allclose(pred.predict_batch(specs[:5], 3),
                               np.asarray(pred._net(pred.params, jnp.asarray(
                                   np.stack([featurize(fam, s, 3)
                                             for s in specs[:5]])))),
                               rtol=0, atol=1e-6)
