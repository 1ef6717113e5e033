"""Reduce a profiler trace to what the per-layer readers need.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
Its device planes (``/device:TPU:<n>``) hold one line of XLA programs
(``XLA Modules``: one event per program run, named after the jitted
function) and one line of XLA operations (``XLA Ops``). The host plane
holds the harness's own spans (``bench.<name>``) on the same clock.

* busy: the union of the operation intervals inside the window, per chip,
  averaged over chips;
* program time: the summed device time of each program, by name;
* idle gaps: the parts of the window with no operation running on chip 0,
  each labelled with the innermost harness span open at its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench.harness.spans import PREFIX

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")
_OP_NUMBER = re.compile(r"\.\d+$")
# operations that contain others on the ops line (a loop, a call): left
# out of the per-kind breakdown so their children are not counted twice
CONTAINERS = ("while", "conditional", "call")

Interval = Tuple[int, int]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def covered(merged: Sequence[Interval], lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi] covered by the merged intervals."""
    return sum(b - a for a, b in clip(merged, lo, hi))


def module_name(event_name: str) -> str:
    """'jit__step(12)' -> 'jit__step'."""
    return _MODULE_SUFFIX.sub("", event_name)


def op_kind(event_name: str) -> str:
    """'%fusion.93 = f32[16,49408]{...} fusion(...)' -> 'fusion'."""
    return _OP_NUMBER.sub("", event_name.split(" = ")[0].lstrip("%"))


@dataclasses.dataclass
class Reduction:
    window: Interval                         # ns, trace clock
    busy: Dict[int, List[Interval]]          # chip -> merged op intervals
    modules: List[Tuple[int, str, int, int]]  # (chip, name, start, end)
    ops: Dict[str, int]                      # op kind -> ns, chip 0
    spans: List[Tuple[str, int, int]]        # harness spans (name, s, e)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        if not self.busy:
            return 0.0
        return sum(covered(v, *self.window) for v in self.busy.values()) \
            / len(self.busy) / 1e9

    def module_runs(self, pattern: str, chip: int = 0
                    ) -> List[Tuple[int, int]]:
        """(start, end) of each run of the programs whose name contains
        ``pattern``, inside the window."""
        lo, hi = self.window
        return [(a, b) for c, n, a, b in self.modules
                if c == chip and pattern in n and a < hi and b > lo]

    def module_ns(self, pattern: str, chip: int = 0) -> int:
        """Device time of those program runs: the operation time inside
        each run's interval (a program's own span also holds waits)."""
        merged = self.busy.get(chip, [])
        return sum(covered(merged, a, b)
                   for a, b in self.module_runs(pattern, chip))

    def span_list(self, name: str) -> List[Tuple[int, int]]:
        lo, hi = self.window
        return [(a, b) for n, a, b in self.spans
                if n == name and a < hi and b > lo]

    def busy_in(self, a: int, b: int, chip: int = 0) -> int:
        return covered(self.busy.get(chip, []), a, b)

    def idle_gaps(self, chip: int = 0) -> List[Tuple[str, int]]:
        lo, hi = self.window
        merged = clip(self.busy.get(chip, []), lo, hi)
        gaps, t = [], lo
        for a, b in merged:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < hi:
            gaps.append((t, hi))
        out = []
        for a, b in gaps:
            mid = (a + b) // 2
            open_ = [(s, e, n) for n, s, e in self.spans if s <= mid <= e]
            label = min(open_, key=lambda x: x[1] - x[0])[2] if open_ \
                else "outside harness spans"
            out.append((label, b - a))
        return out

    def breakdown(self, top: int = 10) -> Dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        by_label: Dict[str, int] = defaultdict(int)
        for label, ns in self.idle_gaps():
            by_label[label] += ns
        gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
                "idle_gaps": [[n, ns / 1e9] for n, ns in gaps]}


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_file(path: str, window_span: str = "window",
                n_chips: Optional[int] = None) -> Reduction:
    """Read one xplane file; the window runs from the first
    ``bench.<window_span>`` span's start to the last one's end.

    The profiler puts host and device events on one clock only to about a
    millisecond (a TPU v5e trace shows each program starting 1.2 ms before
    the host span that launched it), so readers take sums over many steps
    rather than matching one step's host span to its device events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    busy_raw: Dict[int, List[Interval]] = defaultdict(list)
    modules: List[Tuple[int, str, int, int]] = []
    ops: Dict[str, int] = defaultdict(int)
    spans: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            if n_chips is not None and chip >= n_chips:
                continue
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        a = int(ev.start_ns)
                        busy_raw[chip].append((a, a + int(ev.duration_ns)))
                        kind = op_kind(ev.name)
                        if chip == 0 and kind not in CONTAINERS:
                            ops[kind] += int(ev.duration_ns)
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        a = int(ev.start_ns)
                        modules.append((chip, module_name(ev.name), a,
                                        a + int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        a = int(ev.start_ns)
                        spans.append((ev.name[len(PREFIX):], a,
                                      a + int(ev.duration_ns)))
    wins = sorted((a, b) for n, a, b in spans if n == window_span)
    if not wins:
        raise ValueError(f"no bench.{window_span} span in {path}")
    return Reduction(window=(wins[0][0], max(b for _, b in wins)),
                     busy={c: union(v) for c, v in busy_raw.items()},
                     modules=modules, ops=dict(ops), spans=spans)


def reduce(trace_dir: str, n_chips: int) -> Reduction:
    """The trace that a traced run wrote to ``trace_dir``."""
    return reduce_file(find_xplane(trace_dir), n_chips=n_chips)
