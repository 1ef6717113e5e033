"""Program-side spans and counters at the layer boundaries of the hot paths.

Spans record while ``enable()`` is on, or while a JAX profiler trace is
being captured (``jax.profiler.trace`` / ``start_trace``). A recorded
span is two things:

* a ``jax.profiler.TraceAnnotation("repro.<name>", **attrs)``, so it lands
  in the trace's ``.xplane.pb`` beside the device's programs and on the
  device trace's clock;
* a :class:`Record` ``(name, start_ns, end_ns, parent, attrs)`` in memory,
  timed by ``time.perf_counter_ns``, where ``parent`` is the index in
  :func:`records` of the innermost span open on the same thread (-1 at
  the top): the span that caused it.

Off, :func:`span` tests a flag, asks the profiler whether it is capturing
and returns one shared null context: it keeps nothing per call.

Counters (:func:`count`) are plain numbers and always on. While spans
record, each increment is also logged with its time and its innermost
open span (:func:`increments`), so a reader can count inside a window.

Compiles: two ``jax.monitoring`` listeners, registered when this module
is imported, count JAX's compile events as ``compile.count`` and
``compile.seconds`` (persistent-cache loads included) and its cache hits
as ``compile.cache_hits``. While spans record, each compile is also a
``compile`` span under the innermost open span, so a trace says which
step compiled.

Host code only: a span inside a jitted function would time its tracing,
not its execution.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax

PREFIX = "repro."
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Record(NamedTuple):
    name: str
    start_ns: int
    end_ns: Optional[int]       # None while the span is still open
    parent: int                 # index in records(), -1 at the top
    attrs: Dict


class Increment(NamedTuple):
    name: str
    t_ns: int
    n: float
    parent: int                 # innermost open span, as for Record


_enabled = False
_capturing = jax.profiler.TraceAnnotation.is_enabled
_NULL = contextlib.nullcontext()
_lock = threading.Lock()
_local = threading.local()
_records: List[Record] = []
_increments: List[Increment] = []
_counters: Dict[str, float] = {}


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def active() -> bool:
    """Whether spans record now."""
    return _enabled or _capturing()


def reset() -> None:
    """Forget every record, increment and counter."""
    global _records, _increments
    with _lock:
        _records, _increments = [], []
        _counters.clear()


def records() -> List[Record]:
    return list(_records)


def increments() -> List[Increment]:
    return list(_increments)


def counters() -> Dict[str, float]:
    return dict(_counters)


def _open_parent(recs: List[Record]) -> int:
    stack = getattr(_local, "stack", None)
    if stack and stack[-1][0] is recs:
        return stack[-1][1]
    return -1


def _append(recs: List, item) -> int:
    with _lock:
        recs.append(item)
        return len(recs) - 1


class _Span:
    __slots__ = ("name", "attrs", "_annotation", "_recs", "_index")

    def __init__(self, name: str, attrs: Dict):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        self._annotation = jax.profiler.TraceAnnotation(PREFIX + self.name,
                                                        **self.attrs)
        self._annotation.__enter__()
        recs = _records
        self._recs = recs
        self._index = _append(recs, Record(
            self.name, time.perf_counter_ns(), None, _open_parent(recs),
            self.attrs))
        if not hasattr(_local, "stack"):
            _local.stack = []
        _local.stack.append((recs, self._index))
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        _local.stack.pop()
        self._recs[self._index] = self._recs[self._index]._replace(
            end_ns=end)
        self._annotation.__exit__(*exc)
        return False


def span(name: str, **attrs):
    """A context manager timing one layer of the host's work."""
    if not (_enabled or _capturing()):
        return _NULL
    return _Span(name, attrs)


def record(name: str, start_ns: int, end_ns: int, **attrs) -> None:
    """A span that does not nest as a ``with`` block (a request's wait in
    a queue), under the innermost open span. Kept in memory only: the
    profiler takes no span with given times."""
    if _enabled or _capturing():
        recs = _records
        _append(recs, Record(name, int(start_ns), int(end_ns),
                             _open_parent(recs), attrs))


def count(name: str, n: float = 1) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0) + n
    if _enabled or _capturing():
        _append(_increments, Increment(name, time.perf_counter_ns(), n,
                                       _open_parent(_records)))


def _on_duration(event: str, secs: float, **_) -> None:
    if event != COMPILE_EVENT:
        return
    count("compile.count")
    count("compile.seconds", secs)
    end = time.perf_counter_ns()
    record("compile", end - int(secs * 1e9), end)


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:
        count("compile.cache_hits")


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
