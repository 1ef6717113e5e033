"""Readings that the CFL cell's limits are set from (run on the chip).

For each seed, in one process: the system's checked rounds at the cell's
own size against the plain reference (sound readings); the controls, the
reference itself computed one step lower (float32 at ``high``, and
bfloat16), put in the system's place; and the
system with a fault planted: half of every batch left out, the mean
taken over the rest. A step that returns its state unchanged reads 1 by
the comparison's measure and needs no run.

    python3 chipbench/controls/cfl_controls.py --workload paper-cnn.fl-full32 \
        --seeds 11 12 13 --control-seeds 11 12 13

Prints one JSON line per reading, with what the cell's own checks
(``limits/<cell>.json``) make of it: ``correct`` false for a control or
a fault that one of the compared numbers catches.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@contextlib.contextmanager
def half_batch(family):
    """Plant the fault: every local step's loss weighs only the first half
    of its batch."""
    inner = family.masked_loss

    import jax.numpy as jnp

    def faulty(params, fwd, x, y, w, **kw):
        half = (jnp.arange(w.shape[0]) < w.shape[0] // 2).astype(w.dtype)
        return inner(params, fwd, x, y, w * half, **kw)
    family.masked_loss = faulty
    try:
        yield
    finally:
        family.masked_loss = inner


def program_rounds(cell, seed: int, pop, fault: bool = False):
    """The checked rounds as the benchmark's set-up runs them."""
    import jax
    from chipbench.drivers import cfl_rounds as D
    from chipbench.harness import seeds
    from chipbench.reference import cnn_ref
    model = cell.config["model"]
    params = jax.jit(lambda k: cnn_ref.init_params(k, model))(
        seeds.jax_key(seed, 4))
    theta0 = D.host_params(params)
    sess = D.build_session(cell, seed, pop, params, cell.chips)
    ctx = half_batch(sess.family) if fault else contextlib.nullcontext()
    out = []
    with ctx:
        for _ in range(int(cell.traffic["checked_rounds"])):
            sess.run(1)
            row = sess.history[-1]
            out.append((D.host_params(sess.params), row["accs"],
                        [tuple(g) for g in row["specs"]]))
    return theta0, out, sess.fl.seed


CONTROLS = {"control_high": ("float32", "high"),
            "control_bf16": ("bfloat16", "default")}


def judged(cell, readings):
    """The harness's own checks over the compared numbers."""
    from chipbench.drivers import cfl_rounds as D
    from chipbench.harness.result import Check
    return [Check(name, readings[name], float(cell.limits[name]))
            for name in D.COMPARED]


def readings_for(cell, seed: int, kind: str):
    import jax.numpy as jnp
    import numpy as np
    from chipbench.drivers import cfl_rounds as D
    from chipbench.harness import population
    model = cell.config["model"]
    pop = population.make(seed, dict(
        cell.traffic["population"], image_size=model["image_size"],
        channels=model["in_channels"], n_classes=model["n_classes"]))
    theta0, prog, fl_seed = program_rounds(cell, seed, pop,
                                           fault=(kind == "half_batch"))
    genes = [g for _, _, g in prog]
    ref = D.reference_rounds(cell, seed, pop, fl_seed, genes)
    ref = [(D.host_params(p), a) for p, a in ref]
    if kind in CONTROLS:
        dtype, prec = CONTROLS[kind]
        ctl = D.reference_rounds(cell, seed, pop, fl_seed, genes,
                                 dtype=getattr(jnp, dtype), precision=prec)
        prog = [(D.host_params(p), a) for p, a in ctl]
    else:
        prog = [(p, np.asarray(a)) for p, a, _ in prog]
    return D.readings(theta0, prog, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="paper-cnn.fl-full32")
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--precision", default=None,
                    help="override the configuration's matmul precision")
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench.harness import bench
    from chipbench.run import enable_compile_cache
    enable_compile_cache()
    cell = bench.load_cell(args.workload)
    if args.precision:
        cell.config["matmul_precision"] = args.precision
    plan = ([("sound", s) for s in args.seeds]
            + [(k, s) for s in args.control_seeds for k in CONTROLS]
            + [("half_batch", s) for s in args.fault_seeds])
    for kind, seed in plan:
        t = time.perf_counter()
        r = readings_for(cell, seed, kind)
        checks = judged(cell, r)
        print(json.dumps(dict(
            r, kind=kind, seed=seed, seconds=time.perf_counter() - t,
            correct=all(c.ok for c in checks),
            failed_checks=[c.name for c in checks if not c.ok])),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
