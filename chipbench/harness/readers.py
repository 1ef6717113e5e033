"""Small reductions shared by the per-layer metric readers."""
from __future__ import annotations

from typing import Dict, List, Optional


def idle_share(run) -> Optional[float]:
    """Percent of the traced window in which no operation ran on the
    device (averaged over the chips)."""
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def per_run_ms(run, pattern: str) -> Optional[float]:
    """Mean device milliseconds per run of the programs named
    ``pattern``, from the trace."""
    tr = run.trace
    if tr is None:
        return None
    runs = tr.module_runs(pattern)
    if not runs:
        return None
    return tr.module_ns(pattern) / len(runs) / 1e6


def per_round_ms(run, pattern: str) -> Optional[float]:
    tr = run.trace
    rounds = run.counters.get("rounds", 0)
    if tr is None or not rounds or not tr.module_runs(pattern):
        return None
    return tr.module_ns(pattern) / rounds / 1e6


def window_steps(run) -> List[Dict]:
    lo, hi = run.window
    return [s for s in run.steps if lo <= s["t0"] < hi]


def step_work(run, step: Dict):
    """(required FLOPs, required bytes) of one step's batched decode, by
    the counts of the cell's model file (``archs/<arch>.py``)."""
    ex = run.extra
    cell, specs, spec_of = ex["cell"], ex["specs"], ex["spec_of"]
    arch, req = cell.arch(), ex["ledger"].req
    ss = [specs[spec_of(req[u]["draw"])] for u, _ in step["decoded"]]
    pos = [p for _, p in step["decoded"]]
    f = sum(arch.token_flops(cell, s, p) for s, p in zip(ss, pos))
    return f, arch.decode_step_bytes(cell, ss, pos)


def prefill_work(run, step: Dict) -> float:
    """Required FLOPs of the prompts one step admitted."""
    ex = run.extra
    cell, specs, spec_of = ex["cell"], ex["specs"], ex["spec_of"]
    arch, req = cell.arch(), ex["ledger"].req
    return sum(arch.prompt_flops(cell, specs[spec_of(req[u]["draw"])],
                                 req[u]["draw"].prompt_len)
               for u in step["admitted"])
