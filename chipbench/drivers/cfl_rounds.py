"""Driver for CFL-round traffic: whole rounds of ``CFLSession.run``.

Set-up builds the population and the parent weights from the seed, one
``CFLSession`` over them, and runs the mix's first rounds through the
window's own call (``session.run(1)``), keeping the parent after each.
Those rounds compile the round's programs and warm the Alg. 2
predictor's shapes, whose buffer grows by one row per client and round.
The window then goes on with the same session for ``--seconds`` (or, in a
traced run, for the mix's ``trace_rounds``).

``correct`` compares the set-up rounds with the plain reference
(``reference/cnn_ref.py``) run on the same population, weights, specs
and batch order, once the window has closed and the session is freed.
"""
from __future__ import annotations

import gc
import os
import shutil
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import device, flops, population, seeds, stats
from chipbench.harness.compile_meter import CompileMeter
from chipbench.harness.result import Check, Run, log
from chipbench.harness.spans import Spans
from chipbench.reference import cnn_ref


def build_session(cell, seed: int, pop: Dict, params, n_chips: int):
    """The system under test over the population: one CFLSession."""
    from repro.configs.paper_cnn import CNNConfig
    from repro.core.latency import EDGE_FLEET, train_step_latency
    from repro.core.elastic import family_for
    from repro.fl import CFLConfig, CFLSession
    from repro.fl.client import ClientInfo

    from chipbench.harness.bench import apply_precision
    apply_precision(cell.config)
    m, mix = cell.config["model"], cell.traffic
    cfg = CNNConfig(name=cell.config["name"],
                    in_channels=m["in_channels"],
                    image_size=m["image_size"], n_classes=m["n_classes"],
                    stem_channels=m["stem_channels"],
                    stages=tuple(tuple(s) for s in m["stages"]),
                    groupnorm_groups=m["groupnorm_groups"],
                    gate_hidden=m["gate_hidden"],
                    elastic_widths=tuple(m["elastic_widths"]))
    family = family_for(cfg)
    fleet = {p.name: p for p in EDGE_FLEET}
    devices = [mix["devices"][i % len(mix["devices"])]
               for i in range(len(pop["train"]))]
    full = family.full_spec()
    lat = {d: train_step_latency(family, full, fleet[d],
                                 mix["train"]["batch_size"])
           for d in set(devices)}
    med = float(np.median([lat[d] for d in devices]))
    frac = float(mix["train"]["latency_bound_frac"])
    clients = [ClientInfo(cid=i, device=d, quality=int(pop["quality"][i]),
                          n_samples=len(pop["train"][i]["y"]),
                          latency_bound=min(lat[d], med) * frac)
               for i, d in enumerate(devices)]
    t = mix["train"]
    fl = CFLConfig(n_workers=len(clients), local_epochs=t["local_epochs"],
                   batch_size=t["batch_size"], lr=t["lr"],
                   momentum=t["momentum"],
                   latency_bound_frac=frac,
                   cohort_shards=n_chips,
                   seed=seeds.small(seed, 3))
    return CFLSession(family, clients, pop["train"], pop["test"], fl,
                      params=params)


def warm_predictor(sess, rounds_ahead: int, n_clients: int) -> None:
    """Compile the Alg. 2 predictor's programs for the buffer sizes the
    next ``rounds_ahead`` rounds reach and for every GA batch size, so
    the window finds them compiled. Its outputs are discarded."""
    pred = sess.server.predictor
    step = getattr(pred, "_train_step", None)
    net = getattr(pred, "_net", None)
    if step is None or net is None or not pred.buffer_x:
        return
    d = len(pred.buffer_x[0])
    have = len(pred.buffer_x)
    for r in range(1, rounds_ahead + 1):
        n = have + r * n_clients
        x = jnp.zeros((n, d), jnp.float32)
        y = jnp.zeros((n,), jnp.float32)
        jax.block_until_ready(step(pred.params, pred.opt_state, x, y))
        float(jnp.mean(jnp.abs(net(pred.params, x) - y)))
    for b in range(1, sess.fl.search.population + 1):
        jax.block_until_ready(net(pred.params,
                                  jnp.zeros((b, d), jnp.float32)))


def host_params(tree) -> List[np.ndarray]:
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def run(cell, run: Run, ctx: Dict) -> None:
    mix, model = cell.traffic, cell.config["model"]
    t = mix["train"]
    meter = CompileMeter()
    spans = Spans()
    run.spans = spans
    pop = population.make(run.seed, dict(
        mix["population"], image_size=model["image_size"],
        channels=model["in_channels"], n_classes=model["n_classes"]))
    init = jax.jit(lambda k: cnn_ref.init_params(k, model))
    params = init(seeds.jax_key(run.seed, 4))
    theta0 = host_params(params)
    sess = build_session(cell, run.seed, pop, params, cell.chips)
    spans.wrap(sess.server, "sample_submodels", "control.sample_submodels")
    spans.wrap(sess.server, "post_aggregate", "control.post_aggregate")
    n_train = [len(d["y"]) for d in pop["train"]]
    n_test = [len(d["y"]) for d in pop["test"]]
    bs, ep = t["batch_size"], t["local_epochs"]
    trained = [ep * (n // bs) * bs for n in n_train]

    def one_round():
        with spans.span("round"):
            sess.run(1)
        row = sess.history[-1]
        return row

    # -- set-up: the checked rounds, through the window's own call --------
    checked = []
    for _ in range(int(mix["checked_rounds"])):
        row = one_round()
        checked.append({"params": host_params(sess.params),
                        "genes": [tuple(g) for g in row["specs"]],
                        "accs": list(row["accs"])})
    warm_predictor(sess, int(mix["warm_rounds_ahead"]), len(n_train))
    jax.block_until_ready(sess.params)

    # -- the window ---------------------------------------------------------
    rounds_done, samples, req_flops = 0, 0, 0.0
    n0 = meter.snapshot()
    traced = run.traced
    if traced:
        shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
        jax.profiler.start_trace(ctx["trace_dir"])
    t0 = time.perf_counter()
    run.setup_s = time.time() - ctx["process_start"]
    with spans.span("window"):
        while True:
            r0 = time.perf_counter()
            c0 = meter.n
            row = one_round()
            rounds_done += 1
            samples += sum(trained)
            req_flops += flops.cnn_round_flops(model, row["specs"],
                                               trained, n_test)
            run.steps.append({"round": row["round"],
                              "wall_s": time.perf_counter() - r0,
                              "samples": sum(trained),
                              "compiles": meter.n - c0})
            log(f"round {row['round']} wall_s "
                f"{run.steps[-1]['wall_s']:.4f} samples {sum(trained)} "
                f"compiles {meter.n - c0}")
            if traced:
                if rounds_done >= int(mix["trace_rounds"]):
                    break
            elif time.perf_counter() - t0 >= run.seconds:
                break
        jax.block_until_ready(sess.params)
    t1 = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    run.window = (t0, t1)
    run.window_s = t1 - t0
    run.counters.update({
        "compiles_in_window": meter.n - n0[0],
        "rounds": rounds_done, "samples": samples,
        "required_flops": req_flops})
    run.end_to_end["train_samples_per_s"] = samples / run.window_s
    run.end_to_end["setup_s"] = run.setup_s
    run.attempted = rounds_done
    run.memory_peak_bytes = device.memory_peak_bytes(ctx["devices"])
    if traced:
        from chipbench.harness import trace
        run.trace = trace.reduce(ctx["trace_dir"], cell.chips)
        shutil.rmtree(ctx["trace_dir"], ignore_errors=True)

    # -- correctness: free the program, then follow it with the reference --
    fl_seed = sess.fl.seed
    del sess, params
    gc.collect()
    run.checks = compare(cell, run.seed, pop, fl_seed, theta0, checked)


def reference_rounds(cell, seed: int, pop: Dict, fl_seed: int,
                     genes: List, dtype=jnp.float32, precision="highest"):
    model, t = cell.config["model"], cell.traffic["train"]
    init = jax.jit(lambda k: cnn_ref.init_params(k, model))
    p0 = init(seeds.jax_key(seed, 4))
    data = {k: pop[k] for k in ("x", "y", "ex", "ey")}
    return cnn_ref.run_rounds(
        p0, model, data, genes, fl_seed=fl_seed, lr=t["lr"],
        momentum=t["momentum"], clip=t["grad_clip"], batch=t["batch_size"],
        epochs=t["local_epochs"], dtype=dtype, precision=precision)


def readings(theta0, prog: List, ref: List) -> Dict:
    """The numbers compared, from per-round (params leaves, accs) pairs:

    first_update_gap: the gap between the global norms (over the leaves
      the reference moves) of the program's and the reference's first
      round update, over the reference's;
    first_update_gap_median_leaf: the median, over the leaves the
      reference moves, of each leaf's gap in the first round update (the
      number that tells a bfloat16 round from the float32 round's own
      spread);
    change_gap: the global gap of the parent's change after the last
      checked round.

    Also, for the record: the other gaps by the worst and the median
    leaf, and the largest gap of a round's mean client accuracy."""
    def change(r):
        return [a - b for a, b in zip(r[0], theta0)]

    keep = stats.keep_leaves(change(ref[0]))
    out = {}
    for tag, i in (("first_update", 0), ("change", -1)):
        p, r = change(prog[i]), change(ref[i])
        per_leaf = stats.leaf_gaps(p, r, keep)
        out[f"{tag}_gap"] = stats.global_gap(p, r, keep)
        out[f"{tag}_gap_worst_leaf"] = float(per_leaf.max())
        out[f"{tag}_gap_median_leaf"] = float(np.median(per_leaf[keep]))
    out["mean_acc_gap"] = max(abs(float(np.mean(p[1])) -
                                  float(np.mean(r[1])))
                              for p, r in zip(prog, ref))
    out["leaves_kept"], out["leaves"] = int(keep.sum()), int(len(keep))
    return out


COMPARED = ("first_update_gap", "first_update_gap_median_leaf",
            "change_gap")


def compare(cell, seed: int, pop: Dict, fl_seed: int, theta0,
            checked: List) -> List[Check]:
    t = time.perf_counter()
    ref = reference_rounds(cell, seed, pop, fl_seed,
                           [c["genes"] for c in checked])
    prog = [(c["params"], np.asarray(c["accs"])) for c in checked]
    got = readings(theta0, prog, [(host_params(p), a) for p, a in ref])
    log(f"reference: {time.perf_counter() - t:.3f} s; readings "
        + " ".join(f"{k} {v!r}" for k, v in got.items()))
    return [Check(name, got[name], float(cell.limits[name]))
            for name in COMPARED]
