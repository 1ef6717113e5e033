"""What one run hands to the metric readers, and the result line."""
from __future__ import annotations

import dataclasses
import json
import sys
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct iff value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Run:
    cell: str
    seed: int
    seconds: float
    traced: bool
    device: Dict = dataclasses.field(default_factory=dict)
    peaks: Dict = dataclasses.field(default_factory=dict)
    setup_s: float = 0.0
    window_s: float = 0.0
    window: Tuple[float, float] = (0.0, 0.0)   # perf_counter bounds
    end_to_end: Dict[str, float] = dataclasses.field(default_factory=dict)
    # raw readings the per-layer readers reduce
    spans: object = None
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    steps: List[Dict] = dataclasses.field(default_factory=list)
    trace: Optional[object] = None              # harness.trace.Reduction
    attempted: int = 0
    failed: int = 0
    checks: List[Check] = dataclasses.field(default_factory=list)
    memory_peak_bytes: int = 0
    extra: Dict = dataclasses.field(default_factory=dict)  # driver-specific

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def emit(run: Run, metrics: Dict[str, Dict], breakdown=None) -> None:
    """Checks as the last lines of stderr, then the result as the last
    line of stdout, with the checks under the last key."""
    for c in run.checks:
        log(f"check {c.name} = {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAIL'}")
    device = dict(run.device, memory_peak_bytes=int(run.memory_peak_bytes))
    if run.traced and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    out = {"correct": run.correct, "attempted": int(run.attempted),
           "failed": int(run.failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in run.checks}
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
