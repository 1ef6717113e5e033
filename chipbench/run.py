"""Run one benchmark cell and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell's configuration, traffic mix,
driver, limits and per-layer metric readers are found by the names in
``BENCHMARK.json`` (see ``harness/bench.py``). Inputs and weights are made
from ``--seed``; set-up warms every shape the window uses, then the
window runs for ``--seconds``. With ``--trace 1`` a profiled window is
reduced to the cell's per-layer metrics. The last line of stdout is one
JSON object; the numbers compared for ``correct`` are the last lines of
stderr and the last key of that object.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def _process_start() -> float:
    """Wall-clock time at which this process started (Linux /proc)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        boot = time.time() - uptime
        return boot + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


PROCESS_START = _process_start()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    args = parse(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench.harness import bench, device
    from chipbench.harness.result import Run, emit, log

    cell = bench.load_cell(args.workload)
    enable_compile_cache()
    try:
        devs = device.require(cell.chips)
    except device.NoAccelerator as e:
        log(f"chipbench: {e}")
        return 3
    run = Run(cell=cell.name, seed=args.seed, seconds=args.seconds,
              traced=bool(args.trace), device=device.describe(devs),
              peaks=device.peaks_for(devs[0].device_kind))
    ctx = {"devices": devs, "process_start": PROCESS_START,
           "trace_dir": TRACE_DIR}
    cell.driver().run(cell, run, ctx)
    metrics = {}
    if args.trace:
        for m in cell.per_layer:
            value = bench.metric_reader(m["name"], cell.base)(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(run.end_to_end[m["name"]]),
                                  "unit": m["unit"]}
    breakdown = run.trace.breakdown() if (args.trace and run.trace) \
        else None
    emit(run, metrics, breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
