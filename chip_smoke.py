"""Chip smoke test: the main path once, on a TPU, through the normal entry
points.

Default (one chip), all in this one process:

* train — ``CFLSession.from_synthetic`` on the paper's elastic CNN at its
  published widths (32x32x3 synthetic CIFAR, 8 heterogeneous clients),
  three CFL rounds on the batched engine; finite accuracies and at most
  two compiled programs per round;
* kernels — the compiled conv kernel (fwd and grads) against the masked
  XLA conv at the paper CNN's shapes, full and half channel widths
  (≤1e-5 relative); the same session with ``elastic_kernels="tpu"``
  against the dense-masked one: every client's logits on its first batch
  (≤1e-5 absolute) and its params after the first round's first local
  step (≤1e-4 absolute, see ``STEP_TOL``); then that session's whole
  first round on the kernel path;
* serve — ``repro.launch.serve.serve`` on granite-3-8b at its published
  widths, depth cut to 2 layers: 4 tenants with distinct submodel specs
  decoded by one program each for prefill, cache write and decode step,
  after the fused-prefill parity check.

``--chips 4`` runs only the cohort-mesh phase: one CFL round of the same
CNN session with the stacked client axis sharded over 4 chips, against
the same round unsharded: first local step ≤1e-4, the hierarchical
aggregate against the flat one on the same updates ≤1e-5, and the whole
round's difference reported.

Exits non-zero, without the final line, when JAX finds no TPU or any
phase fails. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

  python chip_smoke.py               # one chip
  python chip_smoke.py --chips 4     # cohort mesh over four chips

``JAX_COMPILATION_CACHE_DIR`` places the persistent compilation cache;
unset, it is ``.jax_cache`` in the checkout.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time

import jax

ROOT = os.path.dirname(os.path.abspath(__file__))
TOL = 1e-5          # the documented dense-vs-kernel and prefill parity bound
# Bound on every client's update after one local step when two float32
# programs compute it. ReLU's derivative jumps at 0, so a pre-activation
# within rounding of 0 can take the other branch in the other program and
# move that unit's whole gradient contribution: a one-ulp nudge of the
# initial params alone moves the dense path's first step of the paper CNN
# at published widths by up to 1.066e-5 on a TPU v5e (5 of 8 clients
# above 1e-6) and 1.245e-5 on the CPU. 1e-4 is 8x that; a miswired mask
# or tile moves a step by its own size, ~1e-2.
STEP_TOL = 1e-4


def compile_summary(start, end) -> str:
    """Compilations (persistent-cache loads included), their seconds and
    the persistent-cache hits between two ``repro.obs`` counter
    snapshots."""
    def d(k):
        return end.get(k, 0) - start.get(k, 0)
    return (f"compiles={d('compile.count')} "
            f"compile_s={d('compile.seconds'):.3f} "
            f"cache_hits={d('compile.cache_hits')}")


@contextlib.contextmanager
def phase(name: str):
    from repro import obs
    start, t0 = obs.counters(), time.perf_counter()
    print(f"[{name}] start", flush=True)
    with obs.span("smoke." + name):
        yield
    print(f"[{name}] done: wall_s={time.perf_counter() - t0:.3f} "
          f"{compile_summary(start, obs.counters())}", flush=True)


def engine_programs(engine) -> int:
    """Compiled programs a round can use: the fused train+eval program and
    the fused aggregate+apply program."""
    import importlib
    agg = importlib.import_module("repro.core.aggregate")
    return (engine._train_eval._cache_size() +
            agg.aggregate_apply._cache_size())


def max_param_diff(a, b) -> float:
    import jax.numpy as jnp
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def client_diffs(a, b) -> list:
    """Largest absolute difference of each client's slice of two stacked
    client pytrees."""
    import jax.numpy as jnp
    per = [jnp.max(jnp.abs(x - y), axis=tuple(range(1, x.ndim)))
           for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
    return [float(v) for v in jnp.max(jnp.stack(per), axis=0)]


def cnn_session(*, elastic_kernels=False, cohort_shards: int = 1,
                cfg=None, n_samples: int = 4000):
    from repro.configs import PAPER_CNN
    from repro.fl import CFLConfig, CFLSession
    fl = CFLConfig(n_workers=8, batch_size=32, seed=0,
                   elastic_kernels=elastic_kernels,
                   cohort_shards=cohort_shards)
    return CFLSession.from_synthetic(
        cfg or PAPER_CNN, kind="synthcifar", n_workers=8,
        n_samples=n_samples, heterogeneity="both", fl_cfg=fl)


def phase_train(rounds: int = 3, **session_kw):
    """Three CFL rounds on the default batched engine."""
    sess = cnn_session(**session_kw)
    engine = sess.server.engine
    for r in range(rounds):
        before = engine_programs(engine)
        sess.run(1)
        row = sess.history[-1]
        new = engine_programs(engine) - before
        print("round", json.dumps({
            "round": row["round"], "accs": row["accs"],
            "fairness": row["fairness"], "specs": row["specs"],
            "participants": row.get("participants"),
            "timing": row["timing"], "programs_compiled": new},
            default=str), flush=True)
        if not all(math.isfinite(a) for a in row["accs"]):
            raise AssertionError(f"round {r}: non-finite accuracy")
        if new > 2:
            raise AssertionError(f"round {r}: {new} programs compiled (> 2)")


def first_step_deltas(sess, specs, theta0, batch_size: int = 32):
    """Every client's update after one local step of ``specs`` from
    ``theta0``, through the session engine's own fused training program
    (each client's first ``batch_size`` samples, one epoch): the round's
    math before SGD amplifies any rounding difference."""
    engine = sess.server.engine
    batches = [{"x": d["x"][:batch_size], "y": d["y"][:batch_size]}
               for d in sess.client_data]
    return engine.train_cohort(
        engine.broadcast_params(theta0, len(specs)), specs, batches,
        batch_size=batch_size, epochs=1,
        seeds=list(range(len(specs)))).deltas


def first_batch_logits(sess, specs, theta0, batch_size: int = 32):
    """Every client's submodel logits on its first ``batch_size`` samples
    at ``theta0``, through the engine's own op table: the forward alone,
    which is smooth in its inputs."""
    import jax.numpy as jnp
    engine = sess.server.engine
    xs = jnp.stack([d["x"][:batch_size] for d in sess.client_data])
    fwd = engine.family.cohort_masks(specs).fwd
    return jax.jit(jax.vmap(lambda f, x: engine.family.masked_logits(
        theta0, f, x, kernels=engine._elastic_kernels)))(fwd, xs)


def compare_steps(name: str, a, b):
    """Compare two cohorts' first-step updates client by client against
    ``STEP_TOL``; print every client's difference."""
    per = client_diffs(a, b)
    print(f"{name}, per client: " + " ".join(f"{d:.3e}" for d in per),
          flush=True)
    return compare(f"{name}, max abs diff", max(per), STEP_TOL)


def compare(name: str, err: float, bound: float):
    """Print one comparison; return its failure message, or None."""
    print(f"{name}: {err:.3e} (bound {bound:g})", flush=True)
    return None if err <= bound else f"{name}: {err:.3e} > {bound:g}"


def raise_failures(*failures):
    failures = [f for f in failures if f]
    if failures:
        raise AssertionError("; ".join(failures))


# paper CNN at batch 32: (spatial size, cin, cout, stride) of the stem,
# each stage's stride-2 down conv and a stage-3 block conv
CNN_CONVS = ((32, 3, 32, 1), (32, 32, 32, 2), (16, 32, 64, 2),
             (8, 64, 128, 2), (4, 128, 128, 1))


def conv_kernel_error(backend: str) -> float:
    """The tile-skipping conv (fwd and all three grads) against the masked
    XLA reference at the paper CNN's shapes, at full channel widths and at
    half of them (the skipped-tile path a submodel takes): the largest
    error relative to the reference's largest value."""
    import jax.numpy as jnp
    from repro.kernels import elastic_conv2d
    from repro.kernels.backend import default_interpret
    from repro.kernels.ref import elastic_conv2d_ref

    interpret = default_interpret(backend != "tpu")
    worst = 0.0
    for hw, cin, cout, stride in CNN_CONVS:
        kx, kw, kb, kc = jax.random.split(jax.random.PRNGKey(hw * cin), 4)
        x = jax.random.normal(kx, (32, hw, hw, cin))
        w = jax.random.normal(kw, (3, 3, cin, cout)) / math.sqrt(9 * cin)
        b = jax.random.normal(kb, (cout,)) * 0.1
        # a cotangent on a 2^-8 grid: the bias grad, a sum of up to 32768
        # of its values, is exact in float32 in any order, so the bound
        # measures the kernel and not the reference's summation order
        ct = jax.random.randint(kc, (32, hw // stride, hw // stride, cout),
                                -256, 257) / 256
        for div in (1, 2):
            act = (jnp.int32(max(1, cin // div)), jnp.int32(cout // div))

            def kern(x, w, b, ca, oa):
                return elastic_conv2d(x, w, b, stride=stride, cin_active=ca,
                                      cout_active=oa, interpret=interpret)

            def ref(x, w, b, ca, oa):
                return elastic_conv2d_ref(x, w, b, stride=stride,
                                          cin_active=ca, cout_active=oa)

            def fwd_and_grads(f, ca, oa):
                y, vjp = jax.vjp(lambda *a: f(*a, ca, oa), x, w, b)
                return (y,) + vjp(ct)
            got = jax.jit(lambda *a: fwd_and_grads(kern, *a))(*act)
            want = jax.jit(lambda *a: fwd_and_grads(ref, *a))(*act)
            for g, r in zip(got, want):
                worst = max(worst, float(jnp.max(jnp.abs(g - r)) /
                                         jnp.max(jnp.abs(r))))
    return worst


def phase_kernels(backend: str = "tpu", **session_kw):
    """The compiled conv kernel against the masked XLA conv, then the
    session on the tile-skipping path against the dense-masked one: every
    client's logits on its first batch and its params after the first
    round's first local step, then the whole first round. Both legs at ``highest`` matmul precision: default
    TPU precision rounds float32 matmul inputs to bf16, differently in
    each leg."""
    with jax.default_matmul_precision("highest"):
        conv_err = conv_kernel_error(backend)
        dense = cnn_session(**session_kw)
        kern = cnn_session(elastic_kernels=backend, **session_kw)
        if kern.server.engine.kernel_path != "tile-skipping":
            raise AssertionError("elastic_kernels did not select the kernels")
        specs = dense.server.sample_submodels()      # round 0's specs
        theta0 = dense.server.params
        logit_err = max_param_diff(first_batch_logits(dense, specs, theta0),
                                   first_batch_logits(kern, specs, theta0))
        step_dense = first_step_deltas(dense, specs, theta0)
        step_kern = first_step_deltas(kern, specs, theta0)
        before = engine_programs(kern.server.engine)
        kern.run(1)
        new = engine_programs(kern.server.engine) - before
    row = kern.history[-1]
    print("kernel-path round", json.dumps({
        "accs": row["accs"], "specs": row["specs"],
        "programs_compiled": new}, default=str), flush=True)
    genes = [kern.server.family.genes(s) for s in specs]
    raise_failures(
        compare(f"conv kernel ({backend}) vs masked XLA conv, fwd+grads, "
                f"max rel err", conv_err, TOL),
        compare(f"kernel path ({backend}) vs dense-masked, logits on each "
                f"client's first batch, max abs diff", logit_err, TOL),
        compare_steps(f"kernel path ({backend}) vs dense-masked, params "
                      f"after one local step", step_kern, step_dense),
        None if all(math.isfinite(a) for a in row["accs"])
        else "kernel-path round: non-finite accuracy",
        None if [tuple(g) for g in row["specs"]] == genes
        else "kernel-path round trained other specs than round 0's",
        None if new <= 2 else f"kernel-path round: {new} programs (> 2)")


def phase_serve(arch: str = "granite-3-8b", *, n_layers: int = 2,
                use_reduced: bool = False, **serve_kw):
    """Multi-tenant elastic decode through the serving CLI's entry point:
    4 slots, 64-token prompts, 16 generated tokens each."""
    from repro.launch.serve import serve
    completions, stats = serve(arch, batch=4, prompt_len=64, gen=16,
                               use_reduced=use_reduced, n_layers=n_layers,
                               elastic=True, check_prefill=True, **serve_kw)
    tenants = {c.spec.genes() for c in completions}
    print(f"tenants={len(completions)} distinct_specs={len(tenants)} "
          f"programs={stats['programs']}", flush=True)
    if stats["programs"] != {"prefill": 1, "write": 1, "step": 1}:
        raise AssertionError(f"program budget broken: {stats['programs']}")
    if len(tenants) < 2:
        raise AssertionError("tenants did not get distinct specs")
    for c in completions:
        if len(c.tokens) != 16:
            raise AssertionError(f"req{c.uid}: {len(c.tokens)} tokens")


def aggregate_error(sharded, theta0, deltas) -> float:
    """The same stacked client updates through the flat aggregate and
    through the hierarchical one on ``sharded``'s cohort mesh (per-shard
    partial sums, one collective): largest absolute difference of the new
    params."""
    import jax.numpy as jnp
    from repro.core.aggregate import (aggregate_apply,
                                      aggregate_apply_hierarchical)
    from repro.sharding.cohort import shard_cohort
    weights = jnp.asarray([len(d["y"]) for d in sharded.client_data],
                          jnp.float32)
    sh = sharded.server.engine.cohort_sharding(len(sharded.client_data))
    flat = aggregate_apply(theta0, deltas, None, weights)
    hier = aggregate_apply_hierarchical(
        theta0, shard_cohort(deltas, sh), None, weights, mesh=sh.mesh)
    return max_param_diff(flat, hier)


def phase_cohort_mesh(n_shards: int = 4, **session_kw):
    """One round with the client axis sharded over ``n_shards`` devices
    against the same round on one device: every client's params after the
    first local step, and the hierarchical aggregate against the flat one
    on the same updates. The whole round then runs in both and is reported
    but not bounded: its local steps grow any first-step difference (a
    one-ulp nudge of the initial params moves the dense round by 2.5e-3 on
    the CPU). All at ``highest`` precision."""
    with jax.default_matmul_precision("highest"):
        single = cnn_session(**session_kw)
        sharded = cnn_session(cohort_shards=n_shards, **session_kw)
        specs = single.server.sample_submodels()     # round 0's specs
        theta0 = single.server.params
        step_single = first_step_deltas(single, specs, theta0)
        step_sharded = first_step_deltas(sharded, specs, theta0)
        agg_err = aggregate_error(sharded, theta0, step_single)
        single.run(1)
        sharded.run(1)
    engine = sharded.server.engine
    x, _ = engine._cohort_data(sharded.client_data)
    devices = x.sharding.device_set
    print(f"cohort mesh: stacked clients {tuple(x.shape)} on "
          f"{len(devices)} devices {sorted(d.id for d in devices)}",
          flush=True)
    accs = sharded.history[-1]["accs"]
    acc_err = max(abs(a - b) for a, b in zip(single.history[-1]["accs"],
                                             accs))
    print(f"sharded vs unsharded, round 0 (not bounded): params max abs "
          f"diff {max_param_diff(single.params, sharded.params):.3e}, "
          f"accuracies max abs diff {acc_err:.3e}", flush=True)
    raise_failures(
        None if len(devices) == n_shards
        else f"client axis spans {len(devices)} devices, not {n_shards}",
        compare_steps("sharded vs unsharded, params after one local step",
                      step_sharded, step_single),
        compare("hierarchical vs flat aggregate of the same updates, max "
                "abs diff", agg_err, TOL),
        None if all(math.isfinite(a) for a in accs)
        else "sharded round: non-finite accuracy")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cohort-mesh phase on four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"jax {jax.__version__}; devices: {device}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU found; this script runs on the chip only",
              file=sys.stderr)
        return 2
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {device['count']}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro import obs
    print(f"compilation cache: {enable_compile_cache()}", flush=True)
    start = obs.counters()

    if args.chips == 4:
        with phase("cohort-mesh"):
            phase_cohort_mesh(4)
    else:
        with phase("train"):
            phase_train()
        with phase("kernels"):
            phase_kernels("tpu")
        with phase("serve"):
            phase_serve()
    print(f"total: {compile_summary(start, obs.counters())}", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
