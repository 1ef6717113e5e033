"""Find the highest open-loop rate a serving cell sustains (run on the
chip once, when the cell's rate is chosen).

One server from the cell's set-up serves the cell's traffic at each rate
in turn, for ``--seconds`` of arrivals each, drained between rates. A
rate is sustained while the backlog does not grow: the last tenth of the
requests waits for admission no longer than the first tenth.

    python3 chipbench/controls/chat_sweep.py --workload granite-3-8b-l2.chat \
        --seed 5 --seconds 12 --rates 6 8 10 12 14

Prints one JSON line per rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="granite-3-8b-l2.chat")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    import numpy as np
    from chipbench.drivers import serve as D
    from chipbench.harness import bench, device
    from chipbench.harness.spans import Spans
    from chipbench.harness.stats import percentile
    from chipbench.run import enable_compile_cache
    enable_compile_cache()
    cell = bench.load_cell(args.workload)
    device.require(cell.chips)
    spans = Spans()
    server, specs, prog_specs = D.build(cell, args.seed, spans)
    spec_of = D.spec_index(specs)
    vocab = cell.arch().vocab_size(cell)
    for i, rate in enumerate(args.rates):
        mix = dict(cell.traffic, rate=rate)
        ledger = D.Ledger()
        t0 = time.perf_counter()
        D._open_loop(server, ledger, spans, mix, args.seed + i, args.seconds,
                     vocab, prog_specs, spec_of, t0)
        reqs = sorted(ledger.req.values(), key=lambda r: r["due"])
        wait = [(r["admit"] - r["due"]) * 1e3 for r in reqs
                if r["admit"] is not None]
        tenth = max(1, len(wait) // 10)
        ttft = [(r["deliveries"][0] - r["due"]) * 1e3 for r in reqs
                if r["deliveries"]]
        gaps = [(b - a) * 1e3 for r in reqs
                for a, b in zip(r["deliveries"], r["deliveries"][1:])]
        print(json.dumps({
            "rate": rate, "requests": len(reqs),
            "finished": len(ledger.done),
            "wait_first_tenth_ms": float(np.mean(wait[:tenth])),
            "wait_last_tenth_ms": float(np.mean(wait[-tenth:])),
            "ttft_p50_ms": percentile(ttft, 50),
            "ttft_p95_ms": percentile(ttft, 95),
            "itl_p50_ms": percentile(gaps, 50),
            "itl_p95_ms": percentile(gaps, 95),
            "span_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
