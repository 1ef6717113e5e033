"""Fault-tolerant fleet runtime (fl/faults.py + fl/runtime.py deadline/
retry path + core/aggregate.py quarantine gate + checkpoint/fleet.py):
deterministic FaultPlan draws, the jitted validity gate, the
empty-aggregation no-op guard, chaos runs under random plans (hypothesis)
with exact fairness-miss accounting and no recompiles, drain() flushing
retry/backoff clients, and bit-exact kill-and-resume in both modes."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.configs.paper_cnn import CNNConfig
from repro.core.aggregate import aggregate_apply, delta_validity
from repro.fl import CFLConfig, CFLSession
from repro.fl.faults import (DROP, INF, NAN, OK, STREAM_SYNC, FaultPlan,
                             GroupFaults, inject_deltas,
                             resolve_fault_plan)

CFG = CNNConfig(name="faults-test", in_channels=1, image_size=28,
                stem_channels=8, stages=((16, 2), (32, 2)),
                groupnorm_groups=4, elastic_widths=(0.5, 1.0))


def _param_err(a, b):
    return max(jax.tree.leaves(jax.tree.map(
        lambda x, y: float(jnp.max(jnp.abs(x - y))), a, b)))


def _session(seed=0, *, algorithm="cfl", faults=None, mode="sync",
             **fl_kw):
    fl = CFLConfig(n_workers=4, local_epochs=1, batch_size=32, lr=0.05,
                   seed=seed, faults=faults, mode=mode, **fl_kw)
    return CFLSession.from_synthetic(
        CFG, kind="synthmnist", n_workers=4, n_samples=400,
        heterogeneity="quality", fl_cfg=fl, seed=seed,
        algorithm=algorithm)


def _missing(sess):
    """Every fairness miss the run recorded, from the history rows plus
    the runtime's not-yet-reported residual counters."""
    hist = sum(r.get("dropped", 0) + r.get("quarantined", 0)
               for r in sess.history)
    rt = sess.server._runtime
    return hist + (0 if rt is None else rt._dropped_since_agg)


# ---------------------------------------------------------------------------
# the FaultPlan harness itself (no training)
# ---------------------------------------------------------------------------
def test_fault_plan_draws_are_deterministic_and_keyed():
    plan = FaultPlan(seed=3, drop_rate=0.3, straggle_rate=0.2,
                     corrupt_rate=0.2)
    a = plan.draw(0, 17, 64)
    b = plan.draw(0, 17, 64)
    np.testing.assert_array_equal(a.kinds, b.kinds)   # replay-stable
    c = plan.draw(0, 18, 64)
    d = plan.draw(1, 17, 64)
    assert not np.array_equal(a.kinds, c.kinds)       # fresh per gid
    assert not np.array_equal(a.kinds, d.kinds)       # stream-separated
    assert set(np.unique(a.kinds)) <= set(range(6))


def test_fault_plan_validates_rates():
    with pytest.raises(ValueError, match="sum"):
        FaultPlan(drop_rate=0.6, corrupt_rate=0.6)
    with pytest.raises(ValueError, match="drop_rate"):
        FaultPlan(drop_rate=-0.1)
    assert not FaultPlan().any_rates()
    assert FaultPlan(shard_kill_rate=0.5).any_rates()


def test_shard_kill_drops_a_contiguous_shard():
    plan = FaultPlan(seed=0, shard_kill_rate=1.0)
    gf = plan.draw(0, 5, 8, n_shards=2)
    assert gf.killed_shard in (0, 1)
    per = 8 // 2
    lo = gf.killed_shard * per
    assert np.all(gf.kinds[lo:lo + per] == DROP)
    # one shard means no host to kill
    assert plan.draw(0, 5, 8, n_shards=1).killed_shard == -1


def test_resolve_fault_plan_surfaces():
    assert resolve_fault_plan(None) is None
    assert resolve_fault_plan(False) is None
    p = FaultPlan(drop_rate=0.1)
    assert resolve_fault_plan(p) is p
    assert resolve_fault_plan({"drop_rate": 0.2}).drop_rate == 0.2
    assert resolve_fault_plan(0.3).drop_rate == 0.3
    s = resolve_fault_plan("drop=0.2, straggle=0.1, corrupt=0.05, seed=3")
    assert (s.drop_rate, s.straggle_rate, s.corrupt_rate, s.seed) == \
        (0.2, 0.1, 0.05, 3)
    with pytest.raises(ValueError, match="key=value"):
        resolve_fault_plan("drop")
    with pytest.raises(TypeError):
        resolve_fault_plan(object())


def test_inject_deltas_applies_codes_and_scales():
    d = {"w": jnp.ones((3, 2, 2)), "b": jnp.ones((3, 4))}
    gf = GroupFaults(kinds=np.asarray([NAN, OK, 5]))   # 5 = OUTLIER
    codes, scales = gf.codes_scales(1e6)
    out = inject_deltas(d, codes, scales)
    for leaf in (out["w"], out["b"]):
        assert bool(jnp.isnan(leaf[0]).all())
        assert bool((leaf[1] == 1.0).all())
        assert bool((leaf[2] == 1e6).all())


# ---------------------------------------------------------------------------
# quarantine gate + empty-aggregation guard (core/aggregate.py)
# ---------------------------------------------------------------------------
def test_delta_validity_flags_nonfinite_and_outliers():
    rng = np.random.RandomState(0)
    d = {"w": jnp.asarray(rng.randn(5, 8), jnp.float32)}
    d["w"] = d["w"].at[1].set(jnp.nan).at[2, 0].set(jnp.inf) \
                   .at[3].multiply(1e6)
    part = jnp.ones((5,), jnp.float32)
    ok, norms = delta_validity(d, part, jnp.float32(6.0))
    assert list(np.asarray(ok)) == [1.0, 0.0, 0.0, 0.0, 1.0]
    assert np.isfinite(np.asarray(norms)[[0, 4]]).all()
    # clip_factor <= 0 keeps the finite check, drops the norm test
    ok2, _ = delta_validity(d, part, jnp.float32(0.0))
    assert list(np.asarray(ok2)) == [1.0, 0.0, 0.0, 1.0, 1.0]
    # the norm reference is participation-scoped: with the clean rows
    # out of the cohort, the lone finite delta has no peer median to be
    # an outlier against, so only the non-finite rows stay flagged
    ok3, _ = delta_validity(d, part.at[0].set(0.0).at[4].set(0.0),
                            jnp.float32(6.0))
    assert list(np.asarray(ok3)[1:4]) == [0.0, 0.0, 1.0]


def test_sanitize_is_bit_identical_for_clean_cohorts():
    rng = np.random.RandomState(1)
    params = {"w": jnp.asarray(rng.randn(6), jnp.float32)}
    deltas = {"w": jnp.asarray(rng.randn(3, 6), jnp.float32)}
    w = jnp.ones((3,), jnp.float32)
    a = aggregate_apply(params, deltas, None, w)
    b = aggregate_apply(params, deltas, None, w, sanitize=True)
    assert _param_err(a, b) == 0.0


def test_all_quarantined_aggregate_is_a_noop_not_nan():
    """The empty-aggregation guard: zero participating mass (every delta
    quarantined) must leave the params untouched, never divide 0/0."""
    rng = np.random.RandomState(2)
    params = {"w": jnp.asarray(rng.randn(6), jnp.float32)}
    deltas = {"w": jnp.full((3, 6), jnp.nan, jnp.float32)}
    w = jnp.ones((3,), jnp.float32)
    part = jnp.zeros((3,), jnp.float32)
    out = aggregate_apply(params, deltas, None, w, participation=part,
                          sanitize=True)
    assert _param_err(params, out) == 0.0


def test_all_corrupt_round_is_noop_server_step():
    """Runtime-level twin: a sync round where every delta is corrupt
    quarantines the whole cohort — the step applies nothing, params stay
    finite and unchanged, and the history row says so. (The plan seed is
    searched so round 0 draws only NaN/Inf modes: an all-outlier cohort
    is its own norm reference and rightly passes the relative gate.)"""
    plan = next(
        FaultPlan(seed=s, corrupt_rate=1.0) for s in range(500)
        if set(FaultPlan(seed=s, corrupt_rate=1.0)
               .draw(STREAM_SYNC, 0, 4).kinds) <= {NAN, INF})
    sess = _session(seed=1, algorithm="fedavg", faults=plan)
    before = jax.tree.map(jnp.copy, sess.server.params)
    rec = sess.run(1)[-1]
    assert rec["quarantined"] == 4 and rec["dropped"] == 0
    assert _param_err(before, sess.server.params) == 0.0
    assert all(bool(jnp.isfinite(x).all())
               for x in jax.tree.leaves(sess.server.params))
    # quarantined clients completed (accs recorded), but missed the step
    assert len(rec["accs"]) == 4
    assert int(sess.server.tracker.miss_counts().sum()) == 4


# ---------------------------------------------------------------------------
# chaos: random FaultPlans complete, account every miss, never recompile
# ---------------------------------------------------------------------------
@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 50),
       drop=st.sampled_from([0.0, 0.2, 0.4]),
       straggle=st.sampled_from([0.0, 0.25]),
       corrupt=st.sampled_from([0.0, 0.05, 0.3]))
def test_sync_chaos_runs_complete_and_account_misses(seed, drop, straggle,
                                                     corrupt):
    plan = FaultPlan(seed=seed, drop_rate=drop, straggle_rate=straggle,
                     corrupt_rate=corrupt)
    sess = _session(seed=seed, algorithm="fedavg", faults=plan)
    hist = sess.run(3)
    assert len(hist) == 3
    for r in hist:
        for col in ("dropped", "retried", "quarantined",
                    "quorum_waited_ms"):
            assert col in r
        assert np.isfinite(r["fairness"]["mean"]) or not r["accs"]
    # every shed/quarantined engagement is a fairness-debt miss, exactly
    assert int(sess.server.tracker.miss_counts().sum()) == _missing(sess)
    assert all(bool(jnp.isfinite(x).all())
               for x in jax.tree.leaves(sess.server.params))
    # fault churn is runtime data: still one fused train+eval program
    get = getattr(sess.server.engine._train_eval, "_cache_size", None)
    if callable(get):
        assert get() == 1


def test_async_chaos_with_retries_completes_and_drains():
    """Async chaos: drops force deadline misses and retry/backoff; the
    run still applies every round, accounts every miss, and a drain()
    flushes backoff clients instead of deadlocking on their timers."""
    sess = _session(seed=7, algorithm="fedavg", mode="async",
                    async_buffer=2,
                    faults="drop=0.25,straggle=0.2,corrupt=0.15,seed=7")
    hist = sess.run(5)
    assert len(hist) == 5
    assert any(r["dropped"] > 0 for r in hist)      # the plan really bites
    clocks = [r["sim_clock"] for r in hist]
    assert clocks == sorted(clocks)
    rt = sess.server.runtime
    n_hist = len(sess.server.history)
    rt.drain()
    assert not rt.groups                            # nothing in flight
    assert not rt._in_backoff                       # backoff ladder flushed
    assert not sess.server.tracker.pending_mask().any()
    assert len(sess.server.history) >= n_hist       # flushes are recorded
    assert int(sess.server.tracker.miss_counts().sum()) == _missing(sess)
    # a drained runtime dispatches fresh work cleanly
    sess.run(1)
    assert len(sess.server.history) >= n_hist + 1


def test_fairness_selection_prefers_missed_clients():
    """Participation debt includes recorded misses: a client that keeps
    failing outranks one that keeps completing."""
    from repro.fl.client import ClientInfo
    from repro.fl.selection import FleetTracker
    clients = [ClientInfo(cid=i, device="d", quality=0, n_samples=50,
                          latency_bound=1.0) for i in range(8)]
    tr = FleetTracker(clients, "fairness", seed=0)
    for _ in range(6):
        tr.record([i for i in range(8) if i != 3],
                  [0.9] * 7)                        # 3 never completes
        tr.record_miss([3])
    hits = sum(3 in set(tr.select(r).participants) for r in range(12))
    assert hits >= 10


# ---------------------------------------------------------------------------
# kill-and-resume: bit-exact in both modes, degraded on reshard
# ---------------------------------------------------------------------------
def _ab_resume(mode, algorithm, tmp_path, **fl_kw):
    def build():
        return _session(seed=3, mode=mode, algorithm=algorithm,
                        faults="drop=0.2,corrupt=0.15,seed=5", **fl_kw)
    a = build()
    a.run(4)                                     # uninterrupted reference
    b = build()
    b.run(2)
    path = b.save_checkpoint(str(tmp_path / f"{mode}.ckpt"))
    c = build()                                  # "new process"
    info = c.restore_checkpoint(path)
    assert info["resharded"] is False
    c.run(2)
    return a, c


# cfl on the sync leg exercises the predictor snapshot; fedavg on the
# async leg exercises the runtime in-flight/retry snapshot
@pytest.mark.parametrize("mode,algorithm,kw", [
    ("sync", "cfl", {}),
    ("async", "fedavg", {"async_buffer": 2})])
def test_kill_and_resume_is_bit_exact(mode, algorithm, kw, tmp_path):
    a, c = _ab_resume(mode, algorithm, tmp_path, **kw)
    assert _param_err(a.params, c.params) == 0.0
    assert len(a.history) == len(c.history)
    for ra, rc in zip(a.history[2:], c.history[2:]):
        assert ra["participants"] == rc["participants"]
        assert ra["sim_clock"] == rc["sim_clock"]
        assert (ra["dropped"], ra["quarantined"]) == \
            (rc["dropped"], rc["quarantined"])
    np.testing.assert_array_equal(a.server.tracker.miss_counts(),
                                  c.server.tracker.miss_counts())


def test_restore_onto_new_topology_rewinds_in_flight(tmp_path):
    """Shard-count change between save and restore takes the degraded
    path: durable state survives, in-flight work is dropped and
    re-dispatched, and the run continues (not bit-exact, but alive)."""
    b = _session(seed=3, mode="async", async_buffer=1,
                 algorithm="fedavg", faults="drop=0.2,seed=5")
    b.run(2)                       # B=1 leaves cohorts in flight
    assert b.server.runtime.groups
    path = b.save_checkpoint(str(tmp_path / "a.ckpt"))
    fl = CFLConfig(n_workers=4, local_epochs=1, batch_size=32, lr=0.05,
                   seed=3, mode="async", async_buffer=1,
                   faults="drop=0.2,seed=5", cohort_shards=2)
    c = CFLSession.from_synthetic(
        CFG, kind="synthmnist", n_workers=4, n_samples=400,
        heterogeneity="quality", fl_cfg=fl, seed=3, algorithm="fedavg")
    info = c.restore_checkpoint(path)
    assert info["resharded"] is True
    assert info["dropped_in_flight"]             # something was in flight
    assert not c.server.tracker.pending_mask().any()
    assert not c.server.runtime.groups
    assert c.server.round_idx == b.server.round_idx
    c.run(1)                                     # training continues
    assert len(c.history) == len(b.history) + 1


def test_checkpoint_every_autosaves_each_round(tmp_path):
    sess = _session(seed=0, algorithm="fedavg",
                    checkpoint_every=1, checkpoint_dir=str(tmp_path))
    sess.run(2)
    ckpts = sorted(glob.glob(os.path.join(str(tmp_path), "*.ckpt")))
    assert [os.path.basename(p) for p in ckpts] == \
        ["round_000001.ckpt", "round_000002.ckpt"]
    # the companion metadata names the round and mode
    import json
    with open(ckpts[-1] + ".meta.json") as f:
        meta = json.load(f)
    assert meta["round_idx"] == 2 and meta["mode"] == "sync"


def test_restore_rejects_wrong_fleet_and_format(tmp_path):
    from repro.checkpoint import load_state, restore_server, save_state
    b = _session(seed=0, algorithm="fedavg")
    b.run(1)
    path = b.save_checkpoint(str(tmp_path / "x.ckpt"))
    snap = load_state(path)
    snap["n_clients"] = 7
    with pytest.raises(ValueError, match="fleet"):
        restore_server(_session(seed=0, algorithm="fedavg").server, snap)
    snap = load_state(path)
    snap["format_version"] = 99
    with pytest.raises(ValueError, match="format"):
        restore_server(_session(seed=0, algorithm="fedavg").server, snap)
    snap = load_state(path)
    snap["family"] = "SomeOtherConfig(name='x')"
    with pytest.raises(ValueError, match="architecture"):
        restore_server(_session(seed=0, algorithm="fedavg").server, snap)
