"""Event-driven fleet runtime (fl/runtime.py) + device-resident fleet
state (fl/selection.py FleetArrays): async↔sync equivalence at the sync
operating point (hypothesis), the bounded-program-count invariant under
async churn, fleet-scale jitted selection at K=10^5, buffered/staleness
semantics, and the FleetTracker RNG/caching satellite fixes."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs.paper_cnn import CNNConfig
from repro.core.aggregate import (aggregate_apply, buffer_add, buffer_apply,
                                  cohort_reduce, staleness_scale)
from repro.fl import CFLConfig, CFLSession
from repro.fl.client import ClientInfo
from repro.fl.selection import (FairnessSelection, FleetArrays, FleetTracker,
                                LatencySelection, UniformSelection)

CFG = CNNConfig(name="async-test", in_channels=1, image_size=28,
                stem_channels=8, stages=((16, 2), (32, 2)),
                groupnorm_groups=4, elastic_widths=(0.5, 1.0))


def _param_err(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(jnp.max(jnp.abs(x - y))), a, b)))


def _sessions(seed, selection, *, algorithm="cfl", rounds=2,
              async_buffer=None):
    """One sync and one async session over the same population/seed; the
    async one runs at the sync operating point (buffer = cohort unless
    overridden, zero staleness decay)."""
    kw = dict(kind="synthmnist", n_workers=4, n_samples=400,
              heterogeneity="quality", seed=seed, algorithm=algorithm)
    base = dict(n_workers=4, local_epochs=1, batch_size=32, lr=0.05,
                seed=seed, selection=selection)
    s_sync = CFLSession.from_synthetic(
        CFG, fl_cfg=CFLConfig(mode="sync", **base), **kw)
    s_async = CFLSession.from_synthetic(
        CFG, fl_cfg=CFLConfig(mode="async", async_buffer=async_buffer,
                              staleness_decay=0.0, **base), **kw)
    return s_sync.run(rounds), s_async.run(rounds), s_sync, s_async


# ---------------------------------------------------------------------------
# async at the sync operating point == sync (the acceptance A/B)
# ---------------------------------------------------------------------------
@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 100),
       selection=st.sampled_from(["full", "uniform"]))
def test_async_full_buffer_matches_sync_cnn(seed, selection):
    """mode='async' with buffer = fleet size and staleness_decay=0 fires
    the aggregate exactly at the barrier — params and history must match
    the sync batched path ≤1e-5 (they match bit-for-bit: the runtime
    routes the full fresh group through the same fused program)."""
    h_sync, h_async, s_sync, s_async = _sessions(
        seed, selection, async_buffer=4 if selection == "full" else None)
    assert _param_err(s_sync.params, s_async.params) <= 1e-5
    for a, b in zip(h_sync, h_async):
        assert a["participants"] == b["participants"]
        np.testing.assert_allclose(a["accs"], b["accs"], atol=1e-5)
        assert b["mode"] == "async" and a["mode"] == "sync"
        assert b["staleness"] == 0.0
    # async rows carry the scheduling columns
    for col in ("staleness", "aggregate_lag", "sim_clock"):
        assert all(np.isfinite(r[col]) for r in h_async)


def test_async_full_buffer_matches_sync_fedavg():
    h_sync, h_async, s_sync, s_async = _sessions(
        7, "uniform", algorithm="fedavg")
    assert _param_err(s_sync.params, s_async.params) <= 1e-5
    for a, b in zip(h_sync, h_async):
        assert a["participants"] == b["participants"]
        np.testing.assert_allclose(a["accs"], b["accs"], atol=1e-5)


@pytest.mark.slow
def test_async_full_buffer_matches_sync_transformer():
    """Same A/B for the transformer zoo family."""
    from repro.configs import ARCHS, reduced
    from repro.core import TransformerElasticFamily
    fam = TransformerElasticFamily(
        reduced(ARCHS["granite-3-8b"], n_layers=4, d_model=64), seq_len=16)
    base = dict(n_workers=4, local_epochs=1, batch_size=8, lr=0.05, seed=0,
                selection="uniform")
    kw = dict(n_workers=4, n_samples=128, heterogeneity="both", seed=0)
    s_sync = CFLSession.from_synthetic(
        fam, fl_cfg=CFLConfig(mode="sync", **base), **kw)
    s_async = CFLSession.from_synthetic(
        fam, fl_cfg=CFLConfig(mode="async", staleness_decay=0.0, **base),
        **kw)
    h_sync, h_async = s_sync.run(2), s_async.run(2)
    assert _param_err(s_sync.params, s_async.params) <= 1e-5
    for a, b in zip(h_sync, h_async):
        assert a["participants"] == b["participants"]
        np.testing.assert_allclose(a["accs"], b["accs"], atol=1e-5)


# ---------------------------------------------------------------------------
# true async operation: buffered semantics + staleness accounting
# ---------------------------------------------------------------------------
def test_async_small_buffer_interleaves_and_ages():
    """B=1 on a straggler-skewed fleet: aggregates interleave with
    in-flight cohorts, so some consumed deltas must have aged (staleness
    > 0) and every row stays internally consistent."""
    fl = CFLConfig(n_workers=4, local_epochs=1, batch_size=32, lr=0.05,
                   seed=2, selection="uniform", mode="async",
                   async_buffer=1, staleness_decay=0.5)
    sess = CFLSession.from_synthetic(
        CFG, kind="synthmnist", n_workers=4, n_samples=400,
        heterogeneity="quality", fl_cfg=fl, seed=2)
    hist = sess.run(8)
    assert len(hist) == 8
    clocks = [r["sim_clock"] for r in hist]
    assert clocks == sorted(clocks)            # the clock is monotone
    for r in hist:
        assert r["buffered"] == len(r["participants"])
        assert r["aggregate_lag"] >= 0.0
        assert np.isfinite(r["fairness"]["mean"])
    assert any(r["staleness"] > 0 for r in hist), \
        "B=1 under a 40x-spread fleet must age some deltas"
    # pending bookkeeping drained or tracked, never leaked
    tracker = sess.server.tracker
    assert tracker.pending_mask().sum() == sum(
        int((~g.consumed & (g.sel.valid > 0)).sum())
        for g in sess.server.runtime.groups.values())


def test_async_group_compaction_keeps_event_addresses_stable():
    """Regression: COMPLETE events must survive group compaction. With
    B=1 and uniform selection over a straggler-skewed fleet, earlier
    groups drain and are deleted while later groups still have events in
    flight — every pending event must still resolve to *its* group (no
    IndexError, no starved clients, accuracies recorded for the right
    clients), across many interleavings."""
    fl = CFLConfig(n_workers=4, local_epochs=1, batch_size=32, lr=0.05,
                   seed=5, selection="uniform", mode="async",
                   async_buffer=1, staleness_decay=0.5)
    sess = CFLSession.from_synthetic(
        CFG, kind="synthmnist", n_workers=4, n_samples=400,
        heterogeneity="quality", fl_cfg=fl, seed=5)
    hist = sess.run(16)                 # enough rounds to force compaction
    assert len(hist) == 16
    rt = sess.server.runtime
    assert rt._next_gid > len(rt.groups)    # groups were compacted away
    # no slot was double-consumed or dropped: every applied participant
    # count matches, and live groups are internally consistent
    for g in rt.groups.values():
        assert not np.any(g.consumed & ~g.completed)
    # no starvation: the pending flags match exactly the live groups'
    # unconsumed valid slots (a misaddressed complete would leak one)
    pending = set(np.flatnonzero(sess.server.tracker.pending_mask()))
    inflight = set()
    for g in rt.groups.values():
        inflight.update(int(g.sel.idx[s]) for s in
                        np.flatnonzero(~g.consumed & (g.sel.valid > 0)))
    assert pending == inflight
    # every client got aggregated at least once — starved clients never
    # reappear in participants
    seen = {i for r in hist for i in r["participants"]}
    assert seen == {0, 1, 2, 3}


def test_set_mode_sync_drains_in_flight_deltas():
    """Switching async -> sync flushes the runtime: every in-flight
    delta is aggregated (recorded in history), no client stays flagged
    pending, and the following sync rounds run clean."""
    fl = CFLConfig(n_workers=4, local_epochs=1, batch_size=32, lr=0.05,
                   seed=6, selection="uniform", mode="async",
                   async_buffer=1, staleness_decay=0.5)
    sess = CFLSession.from_synthetic(
        CFG, kind="synthmnist", n_workers=4, n_samples=400,
        heterogeneity="quality", fl_cfg=fl, seed=6)
    sess.run(2)                          # B=1 leaves deltas in flight
    server = sess.server
    assert server.tracker.pending_mask().any()   # something to flush
    n_before = len(server.history)
    server.set_mode("sync")
    assert not server.runtime.groups             # fully drained
    assert not server.tracker.pending_mask().any()
    assert len(server.history) > n_before        # flush steps recorded
    hist = sess.run(1)                           # sync rounds run clean
    assert hist[-1]["mode"] == "sync"
    assert not server.tracker.pending_mask().any()


def test_async_buffer_flush_guard():
    """B larger than the fleet can never fill; the runtime must flush at
    quiescence instead of deadlocking."""
    fl = CFLConfig(n_workers=4, local_epochs=1, batch_size=32, lr=0.05,
                   seed=3, mode="async", async_buffer=64,
                   staleness_decay=0.5)
    sess = CFLSession.from_synthetic(
        CFG, kind="synthmnist", n_workers=4, n_samples=400,
        heterogeneity="quality", fl_cfg=fl, seed=3)
    hist = sess.run(2)
    assert len(hist) == 2
    assert all(len(r["participants"]) == 4 for r in hist)


def test_async_no_recompile_under_churn():
    """The 2-programs/round invariant under async churn: cohort/subset
    churn across buffered rounds adds no train/eval programs, and the
    buffered-aggregation path stays a bounded set of compiled programs
    (reduce / add / apply — compiled once, reused across every
    interleaving)."""
    agg_mod = importlib.import_module("repro.core.aggregate")

    def cache_size(fn):
        get = getattr(fn, "_cache_size", None)
        if not callable(get):
            pytest.skip("jit._cache_size accessor unavailable")
        return get()

    fl = CFLConfig(n_workers=4, local_epochs=1, batch_size=32, lr=0.05,
                   seed=4, selection="uniform", mode="async",
                   async_buffer=1, staleness_decay=0.5)
    sess = CFLSession.from_synthetic(
        CFG, kind="synthmnist", n_workers=4, n_samples=400,
        heterogeneity="quality", fl_cfg=fl, seed=4)
    sess.run(2)
    r0 = cache_size(agg_mod.cohort_reduce)
    a0 = cache_size(agg_mod.buffer_apply)
    t0 = cache_size(sess.server.engine._train_eval)
    assert t0 == 1                      # one fused train+eval program
    sess.run(6)                         # churn: subsets + staleness vary
    assert cache_size(sess.server.engine._train_eval) == 1
    assert cache_size(agg_mod.cohort_reduce) == r0
    assert cache_size(agg_mod.buffer_apply) == a0


# ---------------------------------------------------------------------------
# buffered-aggregation primitives (core/aggregate.py)
# ---------------------------------------------------------------------------
def test_staleness_scale_values():
    assert staleness_scale(0, 0.5) == 1.0
    assert abs(staleness_scale(3, 0.5) - 0.5) < 1e-12   # 1/sqrt(4)
    assert staleness_scale(7, 0.0) == 1.0               # decay off
    assert staleness_scale(1, 1.0) == 0.5


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), coverage_norm=st.booleans(),
       split=st.integers(1, 5))
def test_buffered_partial_sums_match_fused_aggregate(seed, coverage_norm,
                                                     split):
    """Any split of a cohort into completion groups, reduced separately
    and buffer-applied, equals the fused aggregate_apply (scale 1)."""
    rng = np.random.RandomState(seed)
    K = 6
    params = {"w": jnp.asarray(rng.randn(4, 3), jnp.float32)}
    deltas = {"w": jnp.asarray(rng.randn(K, 4, 3), jnp.float32)}
    covs = jax.tree.map(lambda d: (jnp.abs(d) > 0.3).astype(jnp.float32),
                        deltas)
    w = jnp.asarray(rng.rand(K) + 0.5, jnp.float32)
    ref = aggregate_apply(params, deltas, covs, w,
                          coverage_norm=coverage_norm)
    total = None
    for lo, hi in ((0, split), (split, K)):
        if lo == hi:
            continue
        nd = cohort_reduce(jax.tree.map(lambda d: d[lo:hi], deltas),
                           jax.tree.map(lambda c: c[lo:hi], covs),
                           w[lo:hi], coverage_norm=coverage_norm,
                           scale=jnp.float32(1.0))
        total = nd if total is None else buffer_add(total, nd)
    got = buffer_apply(params, *total, coverage_norm=coverage_norm)
    assert _param_err(ref, got) <= 1e-5


def test_staleness_discount_shrinks_contribution():
    """A stale group's delta moves the params less than a fresh one."""
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(4,), jnp.float32)}
    deltas = {"w": jnp.asarray(rng.randn(2, 4), jnp.float32)}
    fresh_d = {"w": deltas["w"][:1]}
    stale_d = {"w": deltas["w"][1:]}
    w1 = jnp.ones((1,), jnp.float32)
    fresh = cohort_reduce(fresh_d, None, w1, scale=jnp.float32(1.0))
    stale = cohort_reduce(stale_d, None, w1,
                          scale=jnp.float32(staleness_scale(3, 0.5)))
    num, den = buffer_add(fresh, stale)
    got = buffer_apply(params, num, den)
    # weighted mean with the stale delta at half weight
    expect = params["w"] - (deltas["w"][0] + 0.5 * deltas["w"][1]) / 1.5
    np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(expect),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# device-resident fleet state at fleet scale
# ---------------------------------------------------------------------------
def _arrays(k, seed=0):
    rng = np.random.RandomState(seed)
    a = FleetArrays(
        n_samples=jnp.asarray(rng.randint(20, 200, k), jnp.float32),
        quality=jnp.asarray(rng.randint(0, 5, k), jnp.int32),
        last_accs=jnp.asarray(
            np.where(rng.rand(k) < 0.3, np.nan, rng.rand(k)), jnp.float32),
        participation_counts=jnp.asarray(rng.randint(0, 9, k), jnp.int32),
        predicted_times=jnp.asarray(rng.rand(k) * 10, jnp.float32),
        staleness=jnp.zeros((k,), jnp.int32),
        pending=jnp.zeros((k,), jnp.float32))
    return a


@pytest.mark.parametrize("policy_cls", [UniformSelection, FairnessSelection,
                                        LatencySelection])
def test_vectorized_selection_at_fleet_scale(policy_cls):
    """The jitted gumbel-top-k selection runs at K=10^5 in one compiled
    program, reused across rounds (the fleet-scale acceptance check)."""
    K = 100_000
    policy = policy_cls(fraction=0.001)
    arrays = _arrays(K)
    sel1 = policy.select_arrays(arrays, 0, jax.random.PRNGKey(0))
    sel2 = policy.select_arrays(arrays, 1, jax.random.PRNGKey(1))
    get = getattr(policy._jit_select, "_cache_size", None)
    if callable(get):
        assert get() == 1               # one program across rounds
    m = policy.cohort_size(K)
    for sel in (sel1, sel2):
        assert sel.idx.shape == (m,)
        assert np.all((sel.idx >= 0) & (sel.idx < K))
        assert len(np.unique(sel.idx)) == m      # without replacement
        assert np.all(sel.weights > 0)
    assert list(sel1.idx) != list(sel2.idx)      # round key varies draws
    # weights renormalise to the participating mass
    mass = np.asarray(arrays.n_samples)[sel1.idx].sum()
    np.testing.assert_allclose(sel1.weights.sum(), mass, rtol=1e-4)


def test_device_path_matches_policy_semantics():
    """Device-path fairness selection prefers lossy/underserved clients,
    like its numpy twin (distributional check, not bitwise)."""
    K = 64
    arrays = _arrays(K, seed=1)
    arrays = FleetArrays(
        arrays.n_samples, arrays.quality,
        jnp.full((K,), 0.95).at[0].set(jnp.nan),     # client 0 never seen
        jnp.full((K,), 20, jnp.int32).at[0].set(0),  # ...and underserved
        arrays.predicted_times, arrays.staleness, arrays.pending)
    policy = FairnessSelection(fraction=0.25)
    hits = 0
    for r in range(64):
        sel = policy.select_arrays(arrays, 40, jax.random.PRNGKey(r))
        hits += int(0 in set(sel.idx.tolist()))
    assert hits > 48        # lossy+underserved client almost always drawn


def test_tracker_auto_routes_large_fleets_to_device_path():
    clients = [ClientInfo(cid=i, device="d", quality=i % 3, n_samples=50,
                          latency_bound=1.0) for i in range(8)]
    tr_small = FleetTracker(clients, "uniform", seed=0)
    assert not tr_small._use_device_path()
    tr_forced = FleetTracker(clients, "uniform", seed=0, device_select=True)
    assert tr_forced._use_device_path()
    sel = tr_forced.select(0)
    assert len(sel.participants) == 4
    assert len(np.unique(sel.participants)) == 4


# ---------------------------------------------------------------------------
# satellite fixes: RNG derivation + predicted_times invalidation
# ---------------------------------------------------------------------------
def _clients(k=8):
    return [ClientInfo(cid=i, device="d", quality=i % 3, n_samples=50 + i,
                       latency_bound=1.0) for i in range(k)]


def test_seedseq_rng_is_deterministic_and_seed_separated():
    """SeedSequence-derived cohorts: reproducible across tracker
    instances, distinct across rounds, and not collision-prone across
    nearby seeds (the old modular mixing folded (seed, round) pairs
    onto each other)."""
    sel_a = FleetTracker(_clients(), "uniform", seed=3).select(5)
    sel_b = FleetTracker(_clients(), "uniform", seed=3).select(5)
    np.testing.assert_array_equal(sel_a.participants, sel_b.participants)
    draws = {tuple(FleetTracker(_clients(), "uniform", seed=s)
                   .select(r).participants)
             for s in range(4) for r in range(4)}
    assert len(draws) > 8           # nearby (seed, round) pairs decorrelate


def test_legacy_rng_flag_reproduces_old_mixing():
    tr = FleetTracker(_clients(), "uniform", seed=3, rng_mode="legacy")
    rng = np.random.RandomState((3 * 9176 + 31 * 5 + 7) % (2 ** 31))
    expect = rng.choice(8, size=4, replace=False)
    np.testing.assert_array_equal(tr.select(5).participants, expect)
    with pytest.raises(ValueError):
        FleetTracker(_clients(), "uniform", seed=0, rng_mode="bogus")


def test_legacy_rng_never_routes_through_device_path():
    """rng_mode='legacy' promises the recorded numpy draws; the device
    path draws differently, so legacy must pin the numpy path even on
    fleets past the auto-routing threshold, and explicitly combining
    legacy with device_select=True is an error, not a silent switch."""
    from repro.fl.selection import DEVICE_SELECT_THRESHOLD
    big = _clients(DEVICE_SELECT_THRESHOLD)
    assert FleetTracker(big, "uniform", seed=0)._use_device_path()
    tr = FleetTracker(big, "uniform", seed=0, rng_mode="legacy")
    assert not tr._use_device_path()
    # and the draws really are the legacy ones
    rng = np.random.RandomState((0 * 9176 + 31 * 2 + 7) % (2 ** 31))
    expect = rng.choice(len(big), size=len(big) // 2, replace=False)
    np.testing.assert_array_equal(tr.select(2).participants, expect)
    bad = FleetTracker(_clients(), "uniform", seed=0, rng_mode="legacy",
                       device_select=True)
    with pytest.raises(ValueError, match="legacy"):
        bad.select(0)


def test_fairness_device_path_rejects_out_of_range_quality():
    """The jitted group-weight table has N_QUALITY_LEVELS rows and jax
    clamps out-of-range gathers silently — the device path must refuse
    qualities past the bound instead of quietly disagreeing with the
    numpy path."""
    K = 16
    arrays = _arrays(K)
    policy = FairnessSelection(fraction=0.5)
    bad = FleetArrays(
        arrays.n_samples,
        arrays.quality.at[3].set(policy.N_QUALITY_LEVELS),
        arrays.last_accs, arrays.participation_counts,
        arrays.predicted_times, arrays.staleness, arrays.pending)
    with pytest.raises(ValueError, match="quality"):
        policy.select_arrays(bad, 0, jax.random.PRNGKey(0))
    # in-range fleets still select fine
    sel = policy.select_arrays(arrays, 0, jax.random.PRNGKey(0))
    assert len(sel.participants) == policy.cohort_size(K)


def test_predicted_times_cache_invalidation():
    calls = []

    def times_fn():
        calls.append(1)
        return [float(i) for i in range(8)]

    tr = FleetTracker(_clients(), "latency", seed=0,
                      predicted_times_fn=times_fn)
    tr.predicted_times()
    tr.predicted_times()
    assert len(calls) == 1              # lazily computed once
    tr.set_policy("latency")            # policy swap drops the cache
    tr.predicted_times()
    assert len(calls) == 2
    tr.set_fleet(_clients(4))           # fleet mutation drops it too
    assert tr._predicted_times is None
    assert tr.arrays.n_clients == 4


def test_fleet_arrays_record_and_staleness_bookkeeping():
    tr = FleetTracker(_clients(), "uniform", seed=0)
    tr.record([1, 3], [0.5, 0.7])
    assert tr.participation_counts[1] == 1
    assert abs(tr.last_accs[3] - 0.7) < 1e-6
    tr.mark_pending([1, 3])
    tr.bump_staleness()
    tr.bump_staleness()
    assert tr.arrays.staleness.max() == 2
    assert set(np.flatnonzero(tr.pending_mask())) == {1, 3}
    tr.clear_pending([1])
    assert set(np.flatnonzero(tr.pending_mask())) == {3}
    assert int(tr.arrays.staleness[1]) == 0
