"""Client-selection policies + partial-participation engine rounds:
policy-output validity (hypothesis), engine A/B (identity participation ==
legacy path; partial cohort == manually gathered sub-cohort), the
no-recompile-under-subset-churn invariant, sharded == unsharded partial
rounds, and session-level smokes for both families."""
import json
import os
import random
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs.paper_cnn import CNNConfig
from repro.core import SubmodelSpec, full_spec, minimal_spec
from repro.data import make_dataset
from repro.fl import CFLConfig, CFLSession
from repro.fl.client import ClientInfo
from repro.fl.engine import BatchedRoundEngine, n_stream_steps
from repro.fl.selection import (SELECTION_POLICIES, FairnessSelection,
                                FleetState, FleetTracker, FullParticipation,
                                LatencySelection, Selection, resolve_policy)
from repro.models import cnn

CFG = CNNConfig(name="sel-test", in_channels=1, image_size=28,
                stem_channels=8, stages=((16, 2), (32, 2)),
                groupnorm_groups=4, elastic_widths=(0.5, 1.0))


def _fleet_state(k=8, seed=0, round_idx=3, with_times=True):
    rng = np.random.RandomState(seed)
    clients = [ClientInfo(cid=i, device=f"dev-{i % 3}", quality=i % 3,
                          n_samples=int(rng.randint(20, 200)),
                          latency_bound=1.0) for i in range(k)]
    accs = rng.rand(k)
    accs[rng.rand(k) < 0.3] = np.nan          # some never participated
    counts = rng.randint(0, round_idx + 1, size=k)
    times = rng.rand(k) * 10 if with_times else None
    return FleetState(clients, round_idx, accs, counts, times)


# ---------------------------------------------------------------------------
# every policy returns valid in-range padded cohorts (hypothesis)
# ---------------------------------------------------------------------------
@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 10_000),
       k=st.integers(1, 16),
       name=st.sampled_from(sorted(SELECTION_POLICIES)))
def test_policy_outputs_are_valid_padded_cohorts(seed, k, name):
    state = _fleet_state(k=k, seed=seed, round_idx=seed % 7)
    policy = SELECTION_POLICIES[name]()
    sel = policy.select(state, np.random.RandomState(seed))
    m = policy.cohort_size(k)
    assert sel.idx.shape == sel.valid.shape == sel.weights.shape == (m,)
    assert np.all((sel.idx >= 0) & (sel.idx < k))
    assert set(np.unique(sel.valid)) <= {0.0, 1.0}
    participants = sel.participants
    assert len(participants) >= 1
    assert len(np.unique(participants)) == len(participants)  # no repeats
    assert np.all(sel.weights >= 0)
    assert np.all(sel.weights[sel.valid == 0] == 0)
    # weights sum to the participating mass (unbiased FedAvg weighting)
    mass = sum(state.clients[i].n_samples for i in participants)
    np.testing.assert_allclose(sel.weights.sum(), mass, rtol=1e-5)


def test_full_policy_is_everyone_in_order():
    state = _fleet_state(k=5)
    sel = FullParticipation().select(state, np.random.RandomState(0))
    np.testing.assert_array_equal(sel.participants, np.arange(5))
    np.testing.assert_array_equal(sel.weights, state.n_samples)


def test_latency_policy_drops_predicted_stragglers():
    state = _fleet_state(k=8, with_times=True)
    state.predicted_times = np.arange(8, dtype=np.float64)   # 7 is slowest
    policy = LatencySelection(fraction=0.5, deadline_q=0.75)
    for seed in range(16):
        sel = policy.select(state, np.random.RandomState(seed))
        assert 7 not in sel.participants
    # falls back to uniform (still valid) without predictions
    state.predicted_times = None
    sel = policy.select(state, np.random.RandomState(0))
    assert len(sel.participants) == policy.cohort_size(8)


def test_latency_policy_fill_uses_fastest_stragglers():
    """When fewer clients beat the deadline than the cohort needs, the
    remaining slots take the *fastest* stragglers — not the
    lowest-indexed ones."""
    state = _fleet_state(k=6)
    state.predicted_times = np.asarray([100.0, 5.0, 4.0, 3.0, 2.0, 1.0])
    policy = LatencySelection(fraction=0.5, deadline_q=0.2)
    sel = policy.select(state, np.random.RandomState(0))
    assert set(sel.participants) == {5, 4, 3}     # slowest (incl. 0) out


def test_fairness_policy_prefers_lossy_and_underserved_clients():
    """Client 0: never seen, zero participations; client 7: accurate and
    over-served. Over many draws, 0 must participate far more often."""
    k = 8
    clients = [ClientInfo(cid=i, device="d", quality=i % 2, n_samples=50,
                          latency_bound=1.0) for i in range(k)]
    accs = np.full(k, 0.9)
    accs[0] = np.nan
    counts = np.full(k, 10)
    counts[0] = 0
    state = FleetState(clients, round_idx=20, last_accs=accs,
                       participation_counts=counts)
    policy = FairnessSelection(fraction=0.25)
    hits = np.zeros(k)
    for seed in range(200):
        sel = policy.select(state, np.random.RandomState(seed))
        hits[sel.participants] += 1
    assert hits[0] > 3 * hits[7]


def test_resolve_policy():
    assert isinstance(resolve_policy(None), FullParticipation)
    assert isinstance(resolve_policy("full"), FullParticipation)
    p = FairnessSelection(fraction=0.25)
    assert resolve_policy(p) is p
    with pytest.raises(ValueError):
        resolve_policy("nope")
    with pytest.raises(TypeError):
        resolve_policy(3.14)


def test_n_stream_steps_matches_loader():
    from repro.data.loader import index_batches
    for n in (1, 7, 8, 9, 31, 32, 33, 200):
        for bs in (8, 32):
            for epochs in (1, 2):
                got = n_stream_steps(n, bs, epochs)
                ref = len(list(index_batches(n, bs, seed=0, epochs=epochs)))
                assert got == ref, (n, bs, epochs)


# ---------------------------------------------------------------------------
# engine: identity participation == legacy path; partial == manual subset
# ---------------------------------------------------------------------------
def _cnn_round_fixture(n_clients=4, seed=0):
    params = cnn.init_params(jax.random.PRNGKey(seed), CFG)
    data = make_dataset("synthmnist", n_clients * 70, seed=seed + 1)
    datasets = [{k: v[i * 60:(i + 1) * 60] for k, v in data.items()}
                for i in range(n_clients)]
    tdata = [{k: v[240 + i * 10:240 + (i + 1) * 10] for k, v in data.items()}
             for i in range(n_clients)]
    specs = [full_spec(CFG), minimal_spec(CFG),
             SubmodelSpec((1, 2), (0.5, 1.0)),
             SubmodelSpec((2, 1), (1.0, 0.5))][:n_clients]
    return params, datasets, tdata, specs


def test_engine_identity_participation_matches_legacy():
    """participation=arange(K) runs the gather path yet must reproduce the
    no-participation round exactly (the ISSUE's full == current A/B)."""
    params, datasets, tdata, specs = _cnn_round_fixture()
    kw = dict(batch_size=32, epochs=1, seeds=[1, 2, 3, 4])
    sizes = [60.0] * 4
    eng = BatchedRoundEngine(CFG, lr=0.05, momentum=0.9)
    p_ref, a_ref, n_ref = eng.run_fl_round(params, specs, datasets, tdata,
                                           sizes, **kw)
    ident = Selection(np.arange(4), np.ones(4), np.asarray(sizes))
    p_got, a_got, n_got = eng.run_fl_round(params, specs, datasets, tdata,
                                           None, participation=ident, **kw)
    err = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                       p_ref, p_got)
    assert max(jax.tree.leaves(err)) < 1e-5
    np.testing.assert_allclose(a_ref, a_got, atol=1e-5)
    np.testing.assert_array_equal(n_ref, n_got)


def test_engine_partial_round_matches_manual_subset():
    """A padded partial cohort must equal the same round run directly on
    the gathered sub-lists (padding slots contribute nothing)."""
    params, datasets, tdata, specs = _cnn_round_fixture()
    chosen = [2, 0]
    sub_specs = [specs[i] for i in chosen]
    seeds = [11, 12]
    weights = [60.0, 60.0]
    eng_ref = BatchedRoundEngine(CFG, lr=0.05, momentum=0.9)
    p_ref, a_ref, _ = eng_ref.run_fl_round(
        params, sub_specs, [datasets[i] for i in chosen],
        [tdata[i] for i in chosen], weights, batch_size=32, epochs=1,
        seeds=seeds, coverage_norm=True)
    # padded to M=3: slot 2 is padding (valid 0, weight 0)
    sel = Selection(np.asarray(chosen + [chosen[0]]),
                    np.asarray([1.0, 1.0, 0.0]),
                    np.asarray(weights + [0.0]))
    eng = BatchedRoundEngine(CFG, lr=0.05, momentum=0.9)
    p_got, a_got, n_got = eng.run_fl_round(
        params, sub_specs + [sub_specs[0]], datasets, tdata, None,
        batch_size=32, epochs=1, seeds=seeds + [99], coverage_norm=True,
        participation=sel)
    err = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                       p_ref, p_got)
    assert max(jax.tree.leaves(err)) < 1e-5
    np.testing.assert_allclose(a_ref, a_got[:2], atol=1e-5)
    assert n_got[2] == 0                       # padding slot trained 0 steps


def test_engine_no_recompile_under_subset_churn():
    """Fixed padded size M: per-round subset + spec churn must not add
    compiled programs (the 2-programs/round invariant under partial
    participation)."""
    import importlib
    agg_mod = importlib.import_module("repro.core.aggregate")

    def cache_size(fn):
        get = getattr(fn, "_cache_size", None)
        if not callable(get):
            pytest.skip("jit._cache_size accessor unavailable")
        return get()

    params, datasets, tdata, specs = _cnn_round_fixture()
    eng = BatchedRoundEngine(CFG, lr=0.05, momentum=0.9)
    churn = [([0, 1], [specs[0], specs[1]]),
             ([3, 2], [specs[2], specs[3]]),
             ([1, 3], [specs[3], specs[0]]),
             ([2], [specs[1]])]               # padded round: 1 participant
    agg0 = cache_size(agg_mod.aggregate_apply)
    for r, (chosen, sp) in enumerate(churn):
        pad = 2 - len(chosen)
        sel = Selection(np.asarray(chosen + chosen[:1] * pad),
                        np.asarray([1.0] * len(chosen) + [0.0] * pad),
                        np.asarray([60.0] * len(chosen) + [0.0] * pad))
        sp = sp + sp[:1] * pad
        params, _, _ = eng.run_fl_round(
            params, sp, datasets, tdata, None, batch_size=32, epochs=1,
            seeds=[r * 10 + 1, r * 10 + 2], participation=sel)
    assert cache_size(eng._train_eval) == 1
    assert cache_size(agg_mod.aggregate_apply) - agg0 <= 1


# ---------------------------------------------------------------------------
# sharded == unsharded partial participation (2 fake CPU devices)
# ---------------------------------------------------------------------------
_SHARD_SUB = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, r"%s")
import json
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_platform_name", "cpu")
from repro.configs.paper_cnn import CNNConfig
from repro.core import SubmodelSpec, full_spec, minimal_spec
from repro.data import make_dataset
from repro.fl.engine import BatchedRoundEngine
from repro.fl.selection import Selection
from repro.models import cnn

CFG = CNNConfig(name="sel-shard-sub", in_channels=1, image_size=28,
                stem_channels=8, stages=((16, 2), (32, 2)),
                groupnorm_groups=4, elastic_widths=(0.5, 1.0))
params = cnn.init_params(jax.random.PRNGKey(0), CFG)
data = make_dataset("synthmnist", 280, seed=1)
datasets = [{k: v[i*60:(i+1)*60] for k, v in data.items()} for i in range(4)]
tdata = [{k: v[240+i*10:240+(i+1)*10] for k, v in data.items()}
         for i in range(4)]
specs = [minimal_spec(CFG), SubmodelSpec((1, 2), (0.5, 1.0))]
# M=2 cohort out of a 4-client fleet: client 3 + a padding slot
sel = Selection(np.asarray([3, 3]), np.asarray([1.0, 0.0]),
                np.asarray([60.0, 0.0]))
kw = dict(batch_size=32, epochs=1, seeds=[5, 6], participation=sel)
e1 = BatchedRoundEngine(CFG, lr=0.05, momentum=0.9)
p1, a1, _ = e1.run_fl_round(params, specs, datasets, tdata, None, **kw)
e2 = BatchedRoundEngine(CFG, lr=0.05, momentum=0.9, cohort_shards=2)
sh = e2.cohort_sharding(2)
assert sh is not None and sh.mesh.shape["cohort"] == 2, sh
p2, a2, _ = e2.run_fl_round(params, specs, datasets, tdata, None, **kw)
err = max(jax.tree.leaves(jax.tree.map(
    lambda a, b: float(jnp.max(jnp.abs(a - b))), p1, p2)))
print(json.dumps({"err": err, "accs_match":
                  bool(np.allclose(a1, a2, atol=1e-5))}))
"""


@pytest.mark.slow
def test_partial_participation_sharded_matches_unsharded():
    """The participation mask commutes with cohort_shards: a 2-way sharded
    partial round equals the unsharded one on 2 fake CPU devices."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SHARD_SUB % src],
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["err"] < 1e-5, rec
    assert rec["accs_match"], rec


# ---------------------------------------------------------------------------
# control plane: selection through CFLServer / FedAvgServer / CFLSession
# ---------------------------------------------------------------------------
def test_session_selection_full_matches_default():
    """selection='full' must reproduce the pre-selection session exactly
    (the default path is the legacy full-participation dispatch)."""
    kw = dict(kind="synthmnist", n_workers=4, n_samples=400,
              heterogeneity="quality", seed=3)
    fl = CFLConfig(n_workers=4, local_epochs=1, batch_size=32, lr=0.05,
                   seed=3)
    s1 = CFLSession.from_synthetic(CFG, fl_cfg=fl, **kw)
    s1.run(2)
    s2 = CFLSession.from_synthetic(CFG, fl_cfg=fl, selection="full", **kw)
    s2.run(2)
    err = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                       s1.params, s2.params)
    assert max(jax.tree.leaves(err)) < 1e-5
    for r1, r2 in zip(s1.history, s2.history):
        np.testing.assert_allclose(r1["accs"], r2["accs"], atol=1e-5)
        assert r2["participants"] == list(range(4))


@pytest.mark.parametrize("policy", ["uniform", "fairness", "latency"])
def test_session_partial_policies_run_cnn(policy):
    fl = CFLConfig(n_workers=4, local_epochs=1, batch_size=32, lr=0.05,
                   seed=0)
    sess = CFLSession.from_synthetic(
        CFG, kind="synthmnist", n_workers=4, n_samples=400,
        heterogeneity="quality", fl_cfg=fl)
    hist = sess.run(2, selection=policy)
    for rec in hist:
        assert rec["selection"] == policy
        assert 1 <= len(rec["participants"]) <= 2      # fraction 0.5 of 4
        assert len(rec["accs"]) == len(rec["participants"])
        assert rec["timing"]["round_time"] > 0
    assert np.isfinite(sess.fairness()["mean"])


def test_session_batched_matches_sequential_partial():
    """Partial-participation rounds agree between the batched padded-
    cohort path and the sequential per-client loop (same cohorts, same
    seeds) — the engine integration's exactness contract."""
    kw = dict(kind="synthmnist", n_workers=4, n_samples=400,
              heterogeneity="quality", seed=5)
    base = dict(n_workers=4, local_epochs=1, batch_size=32, lr=0.05, seed=5,
                selection="uniform")
    s_b = CFLSession.from_synthetic(
        CFG, fl_cfg=CFLConfig(batched_rounds=True, **base), **kw)
    s_b.run(2)
    s_s = CFLSession.from_synthetic(
        CFG, fl_cfg=CFLConfig(batched_rounds=False, **base), **kw)
    s_s.run(2)
    for rb, rs in zip(s_b.history, s_s.history):
        assert rb["participants"] == rs["participants"]
        assert rb["specs"] == rs["specs"]
        np.testing.assert_allclose(rb["accs"], rs["accs"], atol=1e-3)
    err = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                       s_b.params, s_s.params)
    # tolerance matches the engine's documented ReLU-kink noise across the
    # two summation orders (see test_engine_handles_uneven_client_steps);
    # exactness at 1e-5 is asserted at the engine level in
    # test_engine_partial_round_matches_manual_subset
    assert max(jax.tree.leaves(err)) < 2e-3


def test_fedavg_partial_participation():
    from repro.fl import FedAvgServer
    from repro.fl.rounds import build_population
    fl = CFLConfig(n_workers=4, local_epochs=1, batch_size=32, lr=0.05,
                   seed=1, selection="uniform")
    clients, cdata, tdata = build_population(
        CFG, kind="synthmnist", n_workers=4, n_samples=400,
        heterogeneity="quality", seed=1)
    params = cnn.init_params(jax.random.PRNGKey(1), CFG)
    srv = FedAvgServer(CFG, params, clients, cdata, tdata, fl)
    for _ in range(2):
        rec = srv.run_round()
        assert rec["selection"] == "uniform"
        assert 1 <= len(rec["participants"]) <= 2
        assert len(rec["accs"]) == len(rec["participants"])
    assert srv.tracker.participation_counts.sum() == 4


def test_il_rejects_partial_selection():
    fl = CFLConfig(n_workers=2, local_epochs=1, batch_size=32, lr=0.05)
    sess = CFLSession.from_synthetic(
        CFG, kind="synthmnist", n_workers=2, n_samples=200,
        heterogeneity="none", fl_cfg=fl, algorithm="il")
    with pytest.raises(ValueError):
        sess.run(1, selection="uniform")
    # config-level selection is rejected at construction, not silently
    # ignored (the IL baseline would otherwise run a different
    # participation regime than the cfl/fedavg sessions it compares to)
    with pytest.raises(ValueError):
        CFLSession.from_synthetic(
            CFG, kind="synthmnist", n_workers=2, n_samples=200,
            heterogeneity="none", fl_cfg=fl, algorithm="il",
            selection="uniform")


@pytest.mark.slow
def test_session_selection_transformer_family():
    """Partial-participation fairness rounds for the transformer zoo, with
    the 2-programs/round invariant asserted under subset churn."""
    import importlib
    from repro.configs import ARCHS, reduced
    from repro.core import TransformerElasticFamily
    agg_mod = importlib.import_module("repro.core.aggregate")

    def cache_size(fn):
        get = getattr(fn, "_cache_size", None)
        if not callable(get):
            pytest.skip("jit._cache_size accessor unavailable")
        return get()

    fam = TransformerElasticFamily(
        reduced(ARCHS["granite-3-8b"], n_layers=4, d_model=64), seq_len=16)
    fl = CFLConfig(n_workers=4, local_epochs=1, batch_size=8, lr=0.05,
                   seed=0)
    sess = CFLSession.from_synthetic(fam, n_workers=4, n_samples=128,
                                     heterogeneity="both", fl_cfg=fl)
    hist = sess.run(3, selection="fairness")
    cohorts = set()
    for rec in hist:
        assert rec["selection"] == "fairness"
        assert 1 <= len(rec["participants"]) <= 2
        cohorts.add(tuple(rec["participants"]))
        assert all(np.isfinite(a) for a in rec["accs"])
    agg0 = cache_size(agg_mod.aggregate_apply)
    assert cache_size(sess.server.engine._train_eval) == 1
    assert agg0 >= 1
