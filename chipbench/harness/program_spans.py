"""The program's own spans and counters (``repro.obs``), read after a run.

``repro.obs`` records while a JAX profiler trace is being captured, so
after a traced run the process holds the program's spans of the window:
``(name, start_ns, end_ns, parent, attrs)`` on the host's
``perf_counter_ns`` clock, ``parent`` an index into the same list, and
each counter increment with its innermost open span. A program without
``repro.obs`` gives nothing, and every reader of it returns None.

The idle-gap readers need the spans on the profiler trace's clock:
:func:`trace_clock` maps them there by the harness's own ``window`` span,
which both clocks recorded.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from chipbench.harness import trace
from chipbench.harness.result import log


def _obs():
    try:
        from repro import obs
    except ImportError:
        return None
    return obs


def records(run) -> Optional[List]:
    """Every span the program recorded in this process, or None."""
    obs = _obs()
    if obs is None or not run.traced:
        return None
    recs = obs.records()
    return recs or None


def increments(run) -> List:
    obs = _obs()
    return obs.increments() if obs is not None else []


def duration(r) -> int:
    return 0 if r.end_ns is None else r.end_ns - r.start_ns


def window_roots(run, recs: Sequence, name: str) -> List[int]:
    """Indices of the top-level spans called ``name`` that start inside
    the run's window."""
    lo, hi = (t * 1e9 for t in run.window)
    return [i for i, r in enumerate(recs)
            if r.name == name and r.end_ns is not None
            and lo <= r.start_ns <= hi]


def root_of(recs: Sequence, roots: Sequence[int]) -> Dict[int, int]:
    """For each record strictly below one of ``roots``, that root."""
    roots = set(roots)
    out: Dict[int, int] = {}
    for i, r in enumerate(recs):       # a parent comes before its children
        p = r.parent
        if p in roots:
            out[i] = p
        elif p in out:
            out[i] = out[p]
    return out


def below(recs: Sequence, roots: Sequence[int], name: str) -> List[int]:
    """Indices of the records called ``name`` below one of ``roots``."""
    under = root_of(recs, roots)
    return [i for i in under if recs[i].name == name]


def count_below(run, recs: Sequence, roots: Sequence[int],
                name: str) -> float:
    """Increments of counter ``name`` made inside one of ``roots``."""
    under = root_of(recs, roots)
    roots = set(roots)
    return sum(x.n for x in increments(run)
               if x.name == name and (x.parent in under
                                      or x.parent in roots))


def trace_clock(run) -> Optional[Callable[[int], float]]:
    """perf_counter_ns -> the trace's clock, from the start and end of the
    harness's ``window`` span as each clock saw it."""
    tr, spans = run.trace, run.spans
    if tr is None or spans is None:
        return None
    host = [(a, b) for n, a, b in getattr(spans, "records", [])
            if n == "window"]
    dev = [(a, b) for n, a, b in tr.spans if n == "window"]
    if len(host) != 1 or len(dev) != 1:
        return None
    (ha, hb), (da, db) = host[0], dev[0]
    ha, hb = ha * 1e9, hb * 1e9
    if hb <= ha:
        return None
    scale = (db - da) / (hb - ha)
    return lambda t: da + (t - ha) * scale


def _nested(recs: Sequence, i: int) -> bool:
    """Whether a record lies inside its parent (a queue wait, recorded
    after the fact, starts before the step that admits it)."""
    r = recs[i]
    if r.end_ns is None:
        return False
    p = r.parent
    return p < 0 or recs[p].start_ns <= r.start_ns


def idle_gaps(tr, chip: int = 0) -> List[Tuple[int, int]]:
    lo, hi = tr.window
    gaps, t = [], lo
    for a, b in trace.clip(tr.busy.get(chip, []), lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def segments(recs: Sequence, to_trace: Callable[[int], float],
             lo: float, hi: float) -> List[Tuple[float, float, int]]:
    """[lo, hi] cut into (start, end, span) pieces on the trace's clock,
    each labelled by the innermost nested span open there (-1: none)."""
    events = []
    for i, r in enumerate(recs):
        if _nested(recs, i):
            events.append((to_trace(r.start_ns), 1, i))
            events.append((to_trace(r.end_ns), 0, i))
    events.sort()
    out, stack, t = [], [], lo
    for when, opening, i in events:
        when = min(max(when, lo), hi)
        if when > t:
            out.append((t, when, stack[-1] if stack else -1))
            t = when
        if opening:
            stack.append(i)
        elif i in stack:
            stack.remove(i)
    if t < hi:
        out.append((t, hi, -1))
    return out


def _nearest(sorted_ts: List[float], t: float, reach: float
             ) -> Optional[float]:
    k = bisect.bisect_left(sorted_ts, t)
    near = [sorted_ts[j] for j in (k - 1, k) if 0 <= j < len(sorted_ts)]
    best = min(near, key=lambda u: abs(u - t), default=None)
    return best if best is not None and abs(best - t) <= reach else None


def device_lag(modules: Sequence,
               pairs: Sequence[Tuple[str, Sequence[float], Sequence[float]]],
               reach: float = 5e6) -> Optional[Tuple[float, float, float]]:
    """How far the trace puts chip 0's clock behind the host's, in ns: on
    a TPU v5e each program shows before the host span that launched it.
    For each ``(program, launches, waits)`` a run of ``program`` starts
    after the host span launching it starts and ends before the host span
    waiting on it ends, so each launch start (paired with the nearest run
    start within ``reach``) bounds the lag from below and each wait end
    (paired with the nearest run end) from above. Returns (estimate,
    lower, upper): the middle of the tightest bounds, or the upper one
    where they cross."""
    lower, upper = [], []
    for program, launches, waits in pairs:
        runs = [(a, b) for c, n, a, b in modules
                if c == 0 and program in n]
        starts, ends = sorted(a for a, _ in runs), sorted(b for _, b in runs)
        lower += [t - m for t in launches
                  if (m := _nearest(starts, t, reach)) is not None]
        upper += [t - m for t in waits
                  if (m := _nearest(ends, t, reach)) is not None]
    if not lower or not upper:
        return None
    lo, hi = max(lower), min(upper)
    return ((lo + hi) / 2 if lo <= hi else hi), lo, hi


def idle_unattributed_share(run, root: str,
                            programs: Sequence[Tuple[str, str, str]]
                            ) -> Optional[float]:
    """Percent of chip 0's idle time in the window during which the host
    was in no program span below a ``root`` span, with the device's clock
    moved onto the host's by :func:`device_lag` of each ``(program,
    launch span, wait span)``. Each idle gap is split over the innermost
    spans open during it (a gap of a few milliseconds crosses several, so
    placing it by its midpoint would swing with the lag's error). Logs the
    lag and the idle time by innermost span."""
    recs, to_trace = records(run), trace_clock(run)
    tr = run.trace
    if recs is None or to_trace is None or not window_roots(run, recs,
                                                            root):
        return None
    lag = device_lag(tr.modules, [
        (program, [to_trace(r.start_ns) for r in recs if r.name == launch],
         [to_trace(r.end_ns) for r in recs
          if r.name == wait and r.end_ns is not None])
        for program, launch, wait in programs])
    shift = lag[0] if lag is not None else 0.0
    lo, hi = tr.window
    pieces = segments(recs, to_trace, lo + shift, hi + shift)
    under = root_of(recs, [i for i, r in enumerate(recs) if r.name == root])
    idle, lost, k = 0, 0, 0
    by_label: Dict[str, float] = defaultdict(float)
    for a, b in idle_gaps(tr):
        a, b = a + shift, b + shift
        idle += b - a
        while k < len(pieces) and pieces[k][1] <= a:
            k += 1
        j = k
        while j < len(pieces) and pieces[j][0] < b:
            p0, p1, i = pieces[j]
            ns = min(b, p1) - max(a, p0)
            by_label[recs[i].name if i >= 0 else "outside program spans"] \
                += ns
            if i not in under:
                lost += ns
            j += 1
    if idle == 0:
        return 0.0
    top = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
    log("device clock lag " + ("not found" if lag is None else
                               "%.3f ms (bounds %.3f to %.3f)"
                               % tuple(x / 1e6 for x in lag))
        + "; idle time by the innermost program span open: " + ", ".join(
            f"{n} {ns / 1e9:.6f} s" for n, ns in top))
    return 100.0 * lost / idle


def log_slow_steps(recs: Sequence, steps: Sequence[int], t0_ns: float,
                   n: int = 5) -> None:
    """The ``n`` longest steps, each with the time of its child spans (an
    admission's own children beside it) and of any compile inside it."""
    kids: Dict[int, List[int]] = defaultdict(list)
    for i, r in enumerate(recs):
        if r.parent >= 0:
            kids[r.parent].append(i)
    under = root_of(recs, steps)
    compiles: Dict[int, List[int]] = defaultdict(list)
    for i, root in under.items():
        if recs[i].name == "compile":
            compiles[root].append(i)

    def summed(ids) -> str:
        tot: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
        for i in ids:
            tot[recs[i].name][0] += 1
            tot[recs[i].name][1] += duration(recs[i]) / 1e6
        return ", ".join(f"{name} {c}x {ms:.3f} ms"
                         for name, (c, ms) in tot.items())

    for i in sorted(steps, key=lambda i: -duration(recs[i]))[:n]:
        s = recs[i]
        admits = [k for k in kids[i] if recs[k].name == "serve.admit"]
        inner = [g for k in admits for g in kids[k]]
        log(f"slow serve.step at {(s.start_ns - t0_ns) / 1e9:.3f} s: "
            f"{duration(s) / 1e6:.3f} ms {s.attrs}: "
            f"{summed(kids[i])}; admissions: {summed(inner) or 'none'}; "
            f"compiles: {summed(compiles[i]) or 'none'}")
