"""Readings that a serving cell's limit is set from (run on the chip).

For each seed, in one process: a short window of the cell's own traffic
through the system, then the plain reference over the benchmark's sample
of finished requests: the widest gap of the served tokens (sound), and
the widest gap of the tokens that the reference computed one step lower
puts first at the same positions (the controls: float32 at ``high``,
and bfloat16). With ``--faults`` the window
is also run with the system broken underneath: the decode step returning
its cache unchanged, and each sampled token altered where it is produced.

    python3 chipbench/controls/serve_controls.py \
        --workload granite-3-8b-l2.chat --seeds 11 12 13 --seconds 8

Prints one JSON line per reading.
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
def fault(name: str):
    """Plant one fault in the system's serving path."""
    from repro.models import transformer as T
    from repro.serving import server as S
    if name == "state_unchanged":
        inner = T.decode_step

        def stale(params, cfg, caches, *a, **k):
            logits, _ = inner(params, cfg, caches, *a, **k)
            return logits, caches
        T.decode_step = stale
        try:
            yield
        finally:
            T.decode_step = inner
    elif name == "token_altered":
        inner = S.EdgeServer._sample

        def altered(self, logits):
            return (inner(self, logits) + 1) % len(logits)
        S.EdgeServer._sample = altered
        try:
            yield
        finally:
            S.EdgeServer._sample = inner
    else:
        yield


CONTROLS = {"control_high": ("float32", "high"),
            "control_bf16": ("bfloat16", "default")}


def one(cell, seed: int, seconds: float, kind: str, ctx, controls=()):
    import jax.numpy as jnp
    from chipbench.drivers import serve as D
    from chipbench.harness.result import Run
    run = Run(cell=cell.name, seed=seed, seconds=seconds, traced=False)
    with fault(kind):
        D.run(cell, run, ctx)
    ledger = run.extra["ledger"]
    sample = D.check_sample(ledger, seed,
                            int(cell.traffic["check_requests"]))
    out = {"kind": kind, "seed": seed, "check": [c.value for c in run.checks],
           "end_to_end": run.end_to_end,
           "precision": cell.config.get("matmul_precision")}
    for name in controls:
        dtype, prec = CONTROLS[name]
        widest, widest_ctl, n = D.reference_gaps(
            cell, seed, sample, run.extra["specs"], run.extra["spec_of"],
            ledger, control=(getattr(jnp, dtype), prec))
        out.update({"served_gap": widest, name: widest_ctl, "tokens": n,
                    "requests": len(sample)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--faults", type=int, nargs="*", default=[],
                    help="seeds to run each fault on")
    ap.add_argument("--precision", default=None,
                    help="override the configuration's matmul precision")
    ap.add_argument("--check-requests", type=int, default=None)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench.harness import bench, device
    from chipbench.run import TRACE_DIR, enable_compile_cache
    enable_compile_cache()
    cell = bench.load_cell(args.workload)
    if args.precision:
        cell.config["matmul_precision"] = args.precision
    if args.check_requests:
        cell.traffic["check_requests"] = args.check_requests
    devs = device.require(cell.chips)
    ctx = {"devices": devs, "process_start": time.time(),
           "trace_dir": TRACE_DIR}
    plan = [("sound", s) for s in args.seeds] + \
        [(k, s) for s in args.faults
         for k in ("state_unchanged", "token_altered")]
    for kind, seed in plan:
        t = time.perf_counter()
        r = one(cell, seed, args.seconds, kind, ctx,
                controls=tuple(CONTROLS) if kind == "sound" else ())
        r["seconds"] = time.perf_counter() - t
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
