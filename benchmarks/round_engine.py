"""Round-engine bench: batched parent-space cohort engine vs the
sequential extract→jit-per-spec→pad loop, per elastic family (the paper
CNN and a transformer zoo config) at heterogeneous cohort sizes.

Regime: per-round **spec churn**. At fleet scale each round's cohort is a
fresh sample of devices (millions of users), so the server sees a new mix
of submodel configs every round — the sequential loop then pays one XLA
compile per distinct (depth × width) config per round (train *and* eval
programs), while the batched engine runs the same two compiled programs
(fused train+eval, fused aggregate+apply) no matter what the specs are.
The bench reproduces that by sampling feasible random specs per round with
a fresh seed (the tiny fixed fleet would otherwise let the GA converge and
hide the recompile cost that motivates the engine).

Each (family × mode × cohort size) leg runs in this process after
``jax.clear_caches()``, so its compiles are cold, as they are for a fresh
server process; no leg starts a child process (an accelerator belongs to
the one process that first touched JAX). Wall-clock per
round covers local training + eval + aggregation, including any compiles
it triggers; submodel search / predictor updates are identical in both
modes and excluded. Rows carry JSON derived fields (benchmarks.common)
and the full sweep is recorded at the repo root as
``BENCH_round_engine.json`` (both families + batched-vs-seq speedups),
so the perf trajectory survives across PRs.

Rows carry a ``kernel_path`` column ('dense-masked' | 'tile-skipping') so
BENCH JSONs distinguish the engine's masked-compute paths; the
tile-skipping leg (CFLConfig.elastic_kernels) runs via ``--single <fam>
kernels <n>`` — it is interpret-mode Pallas on CPU hosts, so it is not in
the default sweep.

Rows also carry a ``selection`` column (the client-selection policy;
'full' for the engine sweep, so pre-existing BENCH_round_engine.json rows
stay comparable). ``--selection`` runs the partial-participation leg —
one CFLSession per policy (full/uniform/fairness/latency) on the same
heterogeneous CNN fleet, recording per-policy accuracy fairness
(``sess.fairness()``) and simulated round time / straggler gap — and
writes ``BENCH_round_engine_selection.json``.

``--async`` runs the event-driven-runtime leg (``fl/runtime.py``): a
buffered-async buffer sweep (B in {1, 2, cohort}, FedBuff staleness
discounting) against the sync barrier on the same straggler-skewed
fleet, recording simulated rounds/sec, aggregate-lag and fleet fairness
per buffer size — written to ``BENCH_round_engine_async.json``.

``--overlap`` runs the double-buffered-round leg (``CFLConfig.overlap``,
the fl/engine.py prefetch ring): eager vs overlapped host wall-clock
steps/sec on the skewed fleet, asserting bit-exact params, a non-zero
prefetch hit rate, zero added programs and no throughput regression —
written to ``BENCH_round_engine_overlap.json``.

  PYTHONPATH=src python -m benchmarks.round_engine            # full sweep
  PYTHONPATH=src python -m benchmarks.round_engine --single cnn seq 32
  PYTHONPATH=src python -m benchmarks.round_engine --single cnn kernels 8
  PYTHONPATH=src python -m benchmarks.round_engine --selection
  PYTHONPATH=src python -m benchmarks.round_engine --async
  PYTHONPATH=src python -m benchmarks.round_engine --overlap
"""
from __future__ import annotations

import argparse
import json
import os
import random
import time
from typing import List

import numpy as np

from benchmarks.common import Row, json_row, parse_json_rows
from repro.configs.paper_cnn import CNNConfig

ROUNDS = 3
# smaller than BENCH_CNN (16px) but with the full 4-level width grid, so
# the spec space is rich enough to exercise per-config recompiles
ENGINE_CNN = CNNConfig(name="engine-bench", in_channels=1, image_size=16,
                       stem_channels=8, stages=((16, 2), (32, 2)),
                       groupnorm_groups=4,
                       elastic_widths=(0.25, 0.5, 0.75, 1.0))

# cohort sizes per family: the transformer seq leg compiles one LM train
# program per distinct spec per round, so its sweep stays at the sizes the
# acceptance targets (beating per-spec compilation at >= 8 clients)
SWEEP = {"cnn": (8, 32, 128), "transformer": (8, 32)}


def _engine_transformer_cfg():
    from repro.configs import ARCHS, reduced
    return reduced(ARCHS["granite-3-8b"], n_layers=4, d_model=64)


def _measure_leg_cnn(mode: str, n_workers: int, seed: int = 0):
    """One server, ROUNDS rounds of fresh-spec churn on the CNN parent.

    'Programs' = compiled entry points: for the batched engine the fused
    train+eval jit and the fused aggregate_apply jit (cache-size deltas);
    for the sequential loop the per-submodel-config train-step and eval
    caches — 'one compile per distinct submodel config'.

    mode 'kernels' = the batched engine on the tile-skipping kernel path
    (CFLConfig.elastic_kernels; interpret-mode Pallas on CPU hosts, so it
    is not part of the default sweep — run it via --single)."""
    import importlib

    import jax
    # repro.core re-exports the `aggregate` *function*, shadowing the module
    agg_mod = importlib.import_module("repro.core.aggregate")
    from repro.core.search import random_spec
    from repro.fl import CFLConfig
    from repro.fl.rounds import build_population
    from repro.fl.server import CFLServer
    from repro.models import cnn

    batched = mode in ("batched", "kernels")
    fl = CFLConfig(n_workers=n_workers, local_epochs=1, batch_size=32,
                   batched_rounds=batched, seed=seed,
                   elastic_kernels=(mode == "kernels"))
    clients, cdata, tdata = build_population(
        ENGINE_CNN, kind="synthmnist", n_workers=n_workers,
        n_samples=n_workers * 60, heterogeneity="both", seed=seed,
        latency_bound_frac=fl.latency_bound_frac)
    params = cnn.init_params(jax.random.PRNGKey(seed), ENGINE_CNN)
    server = CFLServer(ENGINE_CNN, params, clients, cdata, tdata, fl)

    def jit_cache_size(fn):
        # _cache_size is private jax API; if a jax release renames it the
        # compile counter (and the <=2-programs acceptance assert) would
        # pass vacuously at 0 — fail the leg loudly instead
        get = getattr(fn, "_cache_size", None)
        if not callable(get):
            raise RuntimeError(
                "jit._cache_size accessor unavailable on this jax version "
                "- compile counting would be vacuous")
        return get()

    def n_programs():
        if batched:
            return (jit_cache_size(server.engine._train_eval) +
                    jit_cache_size(agg_mod.aggregate_apply))
        # sequential rounds now run on SequentialFamilyTrainer: one
        # compiled train-step + eval program per distinct submodel config
        return server._seq.n_programs()

    rounds = 2 if n_workers >= 128 else ROUNDS
    walls, compiles, nspecs = [], [], []
    for r in range(rounds):
        # fresh cohort spec mix every round (feasibility-filtered randoms)
        specs = []
        for k, c in enumerate(clients):
            rng = random.Random(seed * 7919 + r * 131 + k)
            cand = [random_spec(ENGINE_CNN, rng) for _ in range(32)]
            feas = [s for s in cand
                    if server.latency.lookup(s, c.device) < c.latency_bound]
            specs.append(feas[0] if feas else cand[0])
        nspecs.append(len({s.genes() for s in specs}))
        c0, t0 = n_programs(), time.perf_counter()
        if batched:
            server._train_round_batched(specs)
        else:
            server._train_round_sequential(specs)
        walls.append(time.perf_counter() - t0)
        compiles.append(n_programs() - c0)
        server.round_idx += 1
    kp = server.engine.kernel_path if batched else "dense-masked"
    return walls, compiles, nspecs, kp


def _measure_leg_transformer(mode: str, n_workers: int, seed: int = 0):
    """Same churn regime on a transformer zoo parent: the batched leg runs
    the family-agnostic BatchedRoundEngine, the sequential leg the
    extract→jit-per-spec→pad SequentialFamilyTrainer."""
    import importlib

    import jax
    agg_mod = importlib.import_module("repro.core.aggregate")
    from repro.core import family_for
    from repro.data import make_lm_dataset
    from repro.fl.engine import BatchedRoundEngine, SequentialFamilyTrainer
    from repro.models import transformer as T

    cfg = _engine_transformer_cfg()
    fam = family_for(cfg)
    batched = mode in ("batched", "kernels")
    datasets = [make_lm_dataset(48, 24, cfg.vocab_size, seed=seed * 31 + k)
                for k in range(n_workers)]
    tdata = [make_lm_dataset(16, 24, cfg.vocab_size, seed=977 + k)
             for k in range(n_workers)]
    sizes = [float(len(d["y"])) for d in datasets]
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    if batched:
        runner = BatchedRoundEngine(cfg, lr=0.05, momentum=0.9,
                                    elastic_kernels=(mode == "kernels"))
    else:
        runner = SequentialFamilyTrainer(cfg, lr=0.05, momentum=0.9,
                                         cache_size=4 * n_workers)

    def jit_cache_size(fn):
        # see _measure_leg_cnn: vacuous 0 would fake the acceptance assert
        get = getattr(fn, "_cache_size", None)
        if not callable(get):
            raise RuntimeError(
                "jit._cache_size accessor unavailable on this jax version "
                "- compile counting would be vacuous")
        return get()

    def n_programs():
        if batched:
            return (jit_cache_size(runner._train_eval) +
                    jit_cache_size(agg_mod.aggregate_apply))
        return runner.n_programs()

    walls, compiles, nspecs = [], [], []
    for r in range(ROUNDS):
        specs = [fam.random_spec(random.Random(seed * 7919 + r * 131 + k))
                 for k in range(n_workers)]
        nspecs.append(len({fam.genes(s) for s in specs}))
        seeds = [seed * 7 + r * 131 + k for k in range(n_workers)]
        c0, t0 = n_programs(), time.perf_counter()
        params, _, _ = runner.run_fl_round(
            params, specs, datasets, tdata, sizes, batch_size=16, epochs=1,
            seeds=seeds)
        walls.append(time.perf_counter() - t0)
        compiles.append(n_programs() - c0)
    kp = runner.kernel_path if batched else "dense-masked"
    return walls, compiles, nspecs, kp


MEASURE = {"cnn": _measure_leg_cnn, "transformer": _measure_leg_transformer}


def _run_leg(family: str, mode: str, n_workers: int):
    """One leg in this process with every jit cache cleared first, so the
    leg pays its compiles cold."""
    import jax
    jax.clear_caches()
    return MEASURE[family](mode, n_workers)


def run(seed: int = 0) -> List[Row]:
    rows: List[Row] = []
    summary = {}
    for family, sweep in SWEEP.items():
        for n_workers in sweep:
            for mode in ("seq", "batched"):
                walls, compiles, nspecs, kernel_path = _run_leg(
                    family, mode, n_workers)
                per_round = float(np.mean(walls))
                summary[(family, n_workers, mode)] = (per_round, compiles)
                rows.append(json_row(
                    f"round_engine_{family}_{mode}_{n_workers}c",
                    per_round * 1e6,
                    family=family, mode=mode, n_workers=n_workers,
                    kernel_path=kernel_path, selection="full",
                    compiles_per_round=float(np.mean(compiles)),
                    max_round_compiles=float(max(compiles)),
                    distinct_specs=float(max(nspecs))))
        for n_workers in sweep:
            sw, sc = summary[(family, n_workers, "seq")]
            bw, bc = summary[(family, n_workers, "batched")]
            rows.append(json_row(
                f"round_engine_speedup_{family}_{n_workers}c", 0.0,
                family=family, n_workers=n_workers, x=sw / bw,
                selection="full",
                compiles_seq=float(np.mean(sc)),
                compiles_batched=float(np.mean(bc))))
    return rows


# ---------------------------------------------------------------------------
# partial-participation leg: per-policy fairness / round-time deltas
# ---------------------------------------------------------------------------
SELECTION_ROUNDS = 4


def run_selection(seed: int = 0, n_workers: int = 8,
                  rounds: int = SELECTION_ROUNDS) -> List[Row]:
    """One CFLSession per selection policy on the same heterogeneous CNN
    fleet: cohort fairness from ``sess.fairness()`` plus **fleet-wide**
    fairness over every client's accuracy at its last participation
    (``FleetTracker.last_accs``) — under partial participation the cohort
    statistic only covers whoever the policy picked last round (the
    latency policy's cohort excludes exactly the straggler clients), so
    cross-policy comparisons must use the fleet columns. Also records the
    simulated round-time story (the latency policy should shrink the
    straggler barrier; the fairness policy should lift the worst
    clients)."""
    import numpy as _np

    from repro.core.fairness import accuracy_fairness
    from repro.fl import CFLConfig, CFLSession

    rows: List[Row] = []
    for policy in ("full", "uniform", "fairness", "latency"):
        fl = CFLConfig(n_workers=n_workers, local_epochs=1, batch_size=32,
                       seed=seed, selection=policy)
        sess = CFLSession.from_synthetic(
            ENGINE_CNN, kind="synthmnist", n_workers=n_workers,
            n_samples=n_workers * 60, heterogeneity="both", seed=seed,
            fl_cfg=fl)
        t0 = time.perf_counter()
        hist = sess.run(rounds)
        wall = (time.perf_counter() - t0) / rounds
        cohort_fair = sess.fairness()
        last = sess.server.tracker.last_accs
        seen = last[~_np.isnan(last)]
        fleet_fair = accuracy_fairness(list(seen))
        timing = hist[-1]["timing"]
        rows.append(json_row(
            f"round_engine_selection_{policy}_{n_workers}c", wall * 1e6,
            family="cnn", mode="batched", n_workers=n_workers,
            selection=policy,
            cohort=float(len(hist[-1]["participants"])),
            cohort_acc_mean=cohort_fair["mean"],
            cohort_acc_min=cohort_fair["min"],
            cohort_jain=cohort_fair["jain_index"],
            fleet_acc_mean=fleet_fair["mean"],
            fleet_acc_min=fleet_fair["min"],
            fleet_jain=fleet_fair["jain_index"],
            fleet_seen_frac=float(len(seen)) / n_workers,
            sim_round_time=timing["round_time"],
            straggler_gap=timing["straggler_gap"]))
        print(f"  {policy:>8}: cohort {len(hist[-1]['participants'])}"
              f"/{n_workers}  fleet acc {fleet_fair['mean']:.3f} (min "
              f"{fleet_fair['min']:.3f}, jain {fleet_fair['jain_index']:.3f}"
              f", seen {len(seen)}/{n_workers})  sim round "
              f"{timing['round_time']:.2f}s  straggler gap "
              f"{timing['straggler_gap']:.2f}s  wall/round {wall:.2f}s")
    return rows


# ---------------------------------------------------------------------------
# event-driven runtime leg: buffered-async vs sync round throughput
# ---------------------------------------------------------------------------
ASYNC_ROUNDS = 6


def run_async(seed: int = 0, n_workers: int = 8,
              rounds: int = ASYNC_ROUNDS) -> List[Row]:
    """Buffered-async (``mode='async'``, fl/runtime.py) vs the sync
    barrier on the same straggler-skewed CNN fleet (EDGE_FLEET device
    spread is ~40x, so the barrier is straggler-dominated exactly as in
    the paper's fairness story). One CFLSession per leg, uniform half-
    fleet cohorts; the sync leg sets the baseline, then the buffer sweep
    B in {1, 2, cohort} applies a server step every B arrivals with
    FedBuff staleness discounting. Throughput is **simulated** rounds/sec
    (server steps per sim-clock second — the two-term latency model's
    clock, not host wall time): small buffers stop paying the straggler
    barrier per step, so async throughput must beat sync on this fleet
    (asserted). Quality columns (fleet min-acc / Jain over every client's
    last-participation accuracy) record what the staleness discount costs
    — the fairness-vs-efficiency trade the paper optimises."""
    import numpy as _np

    from repro.core.fairness import accuracy_fairness
    from repro.fl import CFLConfig, CFLSession

    rows: List[Row] = []
    cohort = max(1, n_workers // 2)
    legs = [("sync", None)] + [("async", b)
                               for b in sorted({1, 2, cohort})]
    sync_rps = None
    for mode, buf in legs:
        fl = CFLConfig(n_workers=n_workers, local_epochs=1, batch_size=32,
                       seed=seed, selection="uniform", mode=mode,
                       async_buffer=buf,
                       staleness_decay=0.5 if mode == "async" else 0.0)
        sess = CFLSession.from_synthetic(
            ENGINE_CNN, kind="synthmnist", n_workers=n_workers,
            n_samples=n_workers * 60, heterogeneity="both", seed=seed,
            fl_cfg=fl)
        t0 = time.perf_counter()
        hist = sess.run(rounds)
        wall = (time.perf_counter() - t0) / rounds
        sim_clock = float(hist[-1]["sim_clock"])
        rps = rounds / max(sim_clock, 1e-9)
        if mode == "sync":
            sync_rps = rps
        last = sess.server.tracker.last_accs
        seen = last[~_np.isnan(last)]
        fleet_fair = accuracy_fairness(list(seen))
        lag = float(_np.mean([r["aggregate_lag"] for r in hist]))
        stale = float(_np.mean([r["staleness"] for r in hist]))
        tag = mode if buf is None else f"{mode}_b{buf}"
        rows.append(json_row(
            f"round_engine_async_{tag}_{n_workers}c", wall * 1e6,
            family="cnn", mode=mode, n_workers=n_workers,
            selection="uniform",
            buffer=float(buf) if buf is not None else float(cohort),
            staleness_decay=fl.staleness_decay,
            sim_rounds_per_sec=rps,
            sim_rps_vs_sync=rps / sync_rps,
            sim_clock=sim_clock,
            aggregate_lag=lag,
            staleness=stale,
            fleet_acc_mean=fleet_fair["mean"],
            fleet_acc_min=fleet_fair["min"],
            fleet_jain=fleet_fair["jain_index"],
            fleet_seen_frac=float(len(seen)) / n_workers))
        print(f"  {tag:>10}: {rounds} steps in sim {sim_clock:8.2f}s "
              f"({rps:7.4f} steps/s, {rps / sync_rps:5.2f}x sync)  "
              f"lag {lag:6.2f}s  staleness {stale:.2f}  fleet acc "
              f"{fleet_fair['mean']:.3f} (min {fleet_fair['min']:.3f}, "
              f"jain {fleet_fair['jain_index']:.3f})  wall/step {wall:.2f}s")
    by = parse_json_rows(rows)
    # acceptance: buffered-async must out-run the sync barrier on the
    # straggler-skewed fleet (B=1 stops paying max(times) per step)
    best = max(r["sim_rps_vs_sync"] for r in by.values()
               if r["mode"] == "async")
    assert best >= 1.0, f"async never beat sync: best {best:.2f}x"
    return rows


# ---------------------------------------------------------------------------
# double-buffered round leg: overlapped host pipeline vs eager packing
# ---------------------------------------------------------------------------
OVERLAP_ROUNDS = 6


def run_overlap(seed: int = 0, n_workers: int = 8,
                rounds: int = OVERLAP_ROUNDS, reps: int = 3) -> List[Row]:
    """Eager vs double-buffered (``overlap=True``) host wall-clock on the
    same straggler-skewed CNN fleet, uniform half-fleet cohorts (the
    stateless policy the prefetch ring can always speculate on). Both
    legs run one compile-warmup round, then ``reps`` timed blocks of
    ``rounds`` rounds each; steps/sec comes from the best block (min
    wall), which is the standard way to read a host-pipelining change
    through scheduler noise. Acceptance: overlapped >= eager steps/sec
    (the ring can only hide the pack/H2D gap, never add device work —
    asserted together with bit-exact params and the zero-added-programs
    invariant, so the perf row can't silently buy throughput with
    drift)."""
    import jax

    from repro.fl import CFLConfig, CFLSession

    def _leg(overlap):
        fl = CFLConfig(n_workers=n_workers, local_epochs=1, batch_size=32,
                       seed=seed, selection="uniform", overlap=overlap)
        sess = CFLSession.from_synthetic(
            ENGINE_CNN, kind="synthmnist", n_workers=n_workers,
            n_samples=n_workers * 60, heterogeneity="both", seed=seed,
            fl_cfg=fl)
        sess.run(1)                       # compile + first-touch warmup
        walls = []
        for _ in range(reps):
            t0 = time.perf_counter()
            sess.run(rounds)
            jax.block_until_ready(sess.server.params)
            walls.append(time.perf_counter() - t0)
        return sess, walls

    rows: List[Row] = []
    eager_sess, eager_walls = _leg(False)
    over_sess, over_walls = _leg(True)
    err = max(float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
              for x, y in zip(jax.tree.leaves(eager_sess.server.params),
                              jax.tree.leaves(over_sess.server.params)))
    stats = over_sess.server.engine.prefetch_stats()
    n_prog_eager = eager_sess.server.engine._train_eval._cache_size()
    n_prog_over = over_sess.server.engine._train_eval._cache_size()
    for tag, sess, walls in (("eager", eager_sess, eager_walls),
                             ("overlap", over_sess, over_walls)):
        best = min(walls)
        sps = rounds / best
        rows.append(json_row(
            f"round_engine_overlap_{tag}_{n_workers}c",
            best / rounds * 1e6,
            family="cnn", mode="batched", n_workers=n_workers,
            selection="uniform", overlap=float(tag == "overlap"),
            steps_per_sec=sps, reps=float(reps),
            rounds_per_rep=float(rounds),
            n_programs=float(sess.server.engine._train_eval._cache_size()),
            prefetch_staged=float(stats["staged"]),
            prefetch_hits=float(stats["hits"]),
            prefetch_misses=float(stats["misses"]),
            param_err_vs_eager=err))
        print(f"  {tag:>8}: best {best / rounds:.3f}s/round "
              f"({sps:.3f} steps/s) over {reps}x{rounds} rounds")
    by = parse_json_rows(rows)
    eager_sps = by[f"round_engine_overlap_eager_{n_workers}c"][
        "steps_per_sec"]
    over_sps = by[f"round_engine_overlap_overlap_{n_workers}c"][
        "steps_per_sec"]
    rows.append(json_row(
        f"round_engine_overlap_speedup_{n_workers}c", 0.0,
        family="cnn", n_workers=n_workers, selection="uniform",
        x=over_sps / eager_sps))
    print(f"  overlap speedup: {over_sps / eager_sps:.3f}x  "
          f"(hits {stats['hits']}/{stats['staged']} staged, "
          f"param err {err})")
    # acceptance: same numerics, same programs, no throughput regression
    assert err == 0.0, f"overlap changed numerics: {err}"
    assert stats["hits"] > 0, f"ring never hit: {stats}"
    assert n_prog_over == n_prog_eager, (n_prog_over, n_prog_eager)
    assert over_sps >= eager_sps, \
        f"overlapped slower than eager: {over_sps:.3f} < {eager_sps:.3f}"
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--single", nargs=3, metavar=("FAMILY", "MODE", "N"))
    ap.add_argument("--selection", action="store_true",
                    help="partial-participation leg: per-policy fairness/"
                         "round-time rows (full/uniform/fairness/latency)")
    ap.add_argument("--async", dest="async_leg", action="store_true",
                    help="event-driven runtime leg: buffered-async buffer "
                         "sweep vs the sync barrier (simulated rounds/sec"
                         ", aggregate-lag, fleet fairness)")
    ap.add_argument("--overlap", dest="overlap_leg", action="store_true",
                    help="double-buffered round leg: overlapped host "
                         "pipeline vs eager packing (host steps/sec, "
                         "prefetch hit rate, bit-exactness)")
    args = ap.parse_args()
    if args.overlap_leg:
        from benchmarks.common import emit
        rows = run_overlap()
        emit(rows)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out_path = os.path.join(root, "BENCH_round_engine_overlap.json")
        with open(out_path, "w") as f:
            json.dump([dict(json.loads(derived), name=name, us=us)
                       for name, us, derived in rows], f, indent=1)
            f.write("\n")
        print(f"wrote {out_path}")
        return
    if args.async_leg:
        from benchmarks.common import emit
        rows = run_async()
        emit(rows)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out_path = os.path.join(root, "BENCH_round_engine_async.json")
        with open(out_path, "w") as f:
            json.dump([dict(json.loads(derived), name=name, us=us)
                       for name, us, derived in rows], f, indent=1)
            f.write("\n")
        print(f"wrote {out_path}")
        return
    if args.selection:
        from benchmarks.common import emit
        rows = run_selection()
        emit(rows)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out_path = os.path.join(root, "BENCH_round_engine_selection.json")
        with open(out_path, "w") as f:
            json.dump([dict(json.loads(derived), name=name, us=us)
                       for name, us, derived in rows], f, indent=1)
            f.write("\n")
        print(f"wrote {out_path}")
        return
    if args.single:
        family, mode, n = args.single[0], args.single[1], int(args.single[2])
        if family not in MEASURE:
            ap.error(f"FAMILY must be one of {sorted(MEASURE)}, got "
                     f"{family!r}")
        if mode not in ("seq", "batched", "kernels"):
            ap.error(f"MODE must be 'seq', 'batched' or 'kernels', got "
                     f"{mode!r}")
        walls, compiles, nspecs, kernel_path = MEASURE[family](mode, n)
        print("LEG," + json.dumps({"walls": walls,
                                   "compiles": [float(c) for c in compiles],
                                   "nspecs": [float(s) for s in nspecs],
                                   "kernel_path": kernel_path}))
        return

    rows = run()
    from benchmarks.common import emit
    emit(rows)
    # record the perf trajectory at the repo root: one JSON row per leg
    # (both families, batched + sequential, plus the speedup rows)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out_path = os.path.join(root, "BENCH_round_engine.json")
    with open(out_path, "w") as f:
        json.dump([dict(json.loads(derived), name=name, us=us)
                   for name, us, derived in rows], f, indent=1)
        f.write("\n")
    print(f"wrote {out_path}")
    by = parse_json_rows(rows)
    # acceptance: the batched engine compiles <= 2 programs per round in
    # every round regardless of spec diversity (both families); >= 2x
    # faster at 32 heterogeneous CNN clients; and beats per-spec
    # compilation for the transformer family at >= 8 clients
    for family, sweep in SWEEP.items():
        for n_workers in sweep:
            d = by[f"round_engine_{family}_batched_{n_workers}c"]
            assert d["max_round_compiles"] <= 2, d
    cnn_x = by["round_engine_speedup_cnn_32c"]["x"]
    print(f"cnn per-round speedup at 32 clients: {cnn_x:.2f}x")
    assert cnn_x >= 2.0, cnn_x
    for n_workers in SWEEP["transformer"]:
        tx = by[f"round_engine_speedup_transformer_{n_workers}c"]["x"]
        print(f"transformer per-round speedup at {n_workers} clients: "
              f"{tx:.2f}x")
        assert tx > 1.0, tx


if __name__ == "__main__":
    main()
