"""The readers of the program's own spans and counters (``repro.obs``):
on hand-built records and a hand-built trace reduction, on a real
profiler trace, and in a small traced serving run on the CPU."""
import os
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from chipbench.harness import bench, program_spans as ps, trace
from chipbench.harness.result import Run
from chipbench.harness.spans import Spans
from repro import obs
from repro.obs import Increment, Record

NEW = ("pack_h2d_ms_per_round", "search_predict_calls_per_round",
       "idle_unattributed_share.train", "idle_unattributed_share.chat",
       "step_host_self_ms.chat", "admit_wait_p95_ms.chat")


def read(name, run):
    return bench.metric_reader(name)(run)


def fake_obs(monkeypatch, recs, incs=()):
    stub = SimpleNamespace(records=lambda: list(recs),
                           increments=lambda: list(incs))
    monkeypatch.setattr(ps, "_obs", lambda: stub)


def fake_run(busy, window=(1000, 2000)):
    """A traced run whose window is 1..2 s on the host's perf clock and
    1000..2000 ns on the trace's (every host ns is 1e-6 trace ns)."""
    spans = Spans()
    spans.records.append(("window", 1.0, 2.0))
    red = trace.Reduction(window=window, busy={0: busy}, modules=[],
                          ops={}, spans=[("window", *window)])
    return Run(cell="c", seed=1, seconds=1.0, traced=True, spans=spans,
               trace=red, window=(1.0, 2.0))


S = 10 ** 9     # one host second in ns


def test_trace_clock_maps_the_window(monkeypatch):
    to_trace = ps.trace_clock(fake_run([]))
    assert to_trace(1 * S) == 1000 and to_trace(2 * S) == 2000
    assert to_trace(1.5 * S) == pytest.approx(1500)


CFL = [
    Record("cfl.round", int(1.1 * S), int(1.5 * S), -1, {"round": 3}),
    Record("cfl.search", int(1.1 * S), int(1.3 * S), 0, {}),
    Record("cfl.search.worker", int(1.1 * S), int(1.3 * S), 1, {}),
    Record("engine.pack", int(1.3 * S), int(1.35 * S), 0, {}),
    Record("engine.masks", int(1.3 * S), int(1.32 * S), 3, {}),
    Record("cfl.round", int(1.6 * S), int(1.9 * S), -1, {"round": 4}),
    Record("engine.pack", int(1.6 * S), int(1.63 * S), 5, {}),
    # before the window: not counted
    Record("cfl.round", int(0.5 * S), int(0.9 * S), -1, {"round": 2}),
    Record("engine.pack", int(0.5 * S), int(0.6 * S), 7, {}),
]
INCS = [Increment("search.predict_calls", int(1.2 * S), 1, 2),
        Increment("search.predict_calls", int(1.2 * S), 1, 2),
        Increment("search.predict_calls", int(1.6 * S), 1, 5),
        Increment("search.predict_calls", int(0.6 * S), 1, 7)]


def test_round_readers(monkeypatch):
    fake_obs(monkeypatch, CFL, INCS)
    run = fake_run([])
    assert read("pack_h2d_ms_per_round", run) == pytest.approx(40.0)
    assert read("search_predict_calls_per_round", run) == 1.5


def test_idle_gaps_labelled_by_program_spans(monkeypatch, capsys):
    fake_obs(monkeypatch, CFL, INCS)
    # idle on the trace's clock: 1000-1100 (outside any round), 1200-1250
    # (in a search worker), 1360-1400 (in a round, below no child),
    # 1900-2000 (outside)
    run = fake_run([(1100, 1200), (1250, 1360), (1400, 1900)])
    share = read("idle_unattributed_share.train", run)
    assert share == pytest.approx(100 * (100 + 40 + 100) / 290)
    err = capsys.readouterr().err
    assert "cfl.search.worker 0.000000" in err
    assert "outside program spans" in err and "cfl.round" in err


STEPS = [
    Record("serve.step", int(1.1 * S), int(1.2 * S), -1,
           {"admitted": 1, "active": 2}),
    Record("serve.queue", int(1.0 * S), int(1.11 * S), 0, {"uid": 7}),
    Record("serve.admit", int(1.11 * S), int(1.15 * S), 0, {"uid": 7}),
    Record("serve.first_token_wait", int(1.13 * S), int(1.14 * S), 2, {}),
    Record("compile", int(1.11 * S), int(1.12 * S), 2, {}),
    Record("serve.logits_wait", int(1.16 * S), int(1.19 * S), 0, {}),
    Record("serve.step", int(1.3 * S), int(1.32 * S), -1,
           {"admitted": 0, "active": 2}),
    Record("serve.logits_wait", int(1.3 * S), int(1.31 * S), 6, {}),
]


def test_step_readers(monkeypatch, capsys):
    fake_obs(monkeypatch, STEPS)
    run = fake_run([])
    # (100 - 10 - 30) and (20 - 10) ms of host work
    assert read("step_host_self_ms.chat", run) == pytest.approx(35.0)
    assert read("admit_wait_p95_ms.chat", run) == pytest.approx(110.0)
    err = capsys.readouterr().err
    assert "slow serve.step at 0.100 s: 100.000 ms" in err
    assert "serve.first_token_wait 1x 10.000 ms" in err
    assert "compiles: compile 1x 10.000 ms" in err
    # idle at 1050-1060, in the queue wait only (which starts before its
    # step, so labels nothing), and at 1160-1170, in the logits wait
    run = fake_run([(1000, 1050), (1060, 1160), (1170, 2000)])
    assert read("idle_unattributed_share.chat", run) == pytest.approx(50)


def test_device_lag_on_a_chip_trace():
    """On the recorded TPU v5e trace each program shows before the host
    span that ran it; the runtime's own host events (its enqueue and its
    read of the done flag, 1.46 and 1.78 ms after the program) bracket
    the lag, and the estimate from the spans alone falls between."""
    red = trace.reduce_file(
        os.path.join(os.path.dirname(__file__), "fixtures",
                     "iter.xplane.pb"), window_span="iter")
    spans = red.span_list("iter")
    est, lo, hi = ps.device_lag(
        red.modules, [("jit_f", [a for a, _ in spans], [b for _, b in spans])])
    assert lo <= est <= hi
    assert 1.46e6 <= est <= 1.78e6


def test_device_lag_moves_the_idle_gaps(monkeypatch):
    """A decode program that the trace shows about 40 ns early: its launch
    and wait spans bound the lag, and the idle time is read where the
    host was at the time."""
    recs = [
        Record("serve.step", int(1.1 * S), int(1.3 * S), -1, {}),
        Record("serve.decode_dispatch", int(1.112 * S), int(1.12 * S), 0,
               {}),
        Record("serve.logits_wait", int(1.12 * S), int(1.247 * S), 0, {}),
        Record("serve.sample", int(1.247 * S), int(1.3 * S), 0, {}),
    ]
    fake_obs(monkeypatch, recs)
    run = fake_run([(1075, 1260), (1290, 2000)])
    run.trace.modules = [(0, "jit__step", 1075, 1205)]
    assert ps.device_lag(run.trace.modules,
                         [("jit__step", [1112], [1247])]) == (39.5, 37, 42)
    # idle 1000-1075 and 1260-1290, moved by 39.5: 60.5 outside the step,
    # 12 in the step's own time, 2.5 in the dispatch, 0.5 sampling, 29.5
    # outside (unmoved, the whole second gap would read as sampling)
    assert read("idle_unattributed_share.chat", run) == \
        pytest.approx(100 * 102 / 105)


def test_a_program_without_obs_reads_nothing(monkeypatch):
    monkeypatch.setattr(ps, "_obs", lambda: None)
    run = fake_run([(1100, 1200)])
    assert all(read(name, run) is None for name in NEW)


def test_program_spans_stay_out_of_the_harness_spans(tmp_path):
    """On a real profiler trace: the program's repro. spans label no
    harness span, so the accepted readers' inputs do not change."""
    spans = Spans()
    obs.reset()
    with jax.profiler.trace(str(tmp_path)):
        with spans.span("window"):
            with spans.span("step"):
                with obs.span("serve.step", admitted=0, active=1):
                    jnp.ones(4).block_until_ready()
    red = trace.reduce(str(tmp_path), n_chips=1)
    assert {n for n, _, _ in red.spans} == {"window", "step"}
    assert len(red.span_list("step")) == 1
    assert red.span_list("serve.step") == []
    assert [r.name for r in obs.records() if r.name != "compile"] == \
        ["serve.step"]
    obs.reset()


def test_small_traced_serving_run(tmp_path):
    """A whole traced run of the chat cell cut to CPU size: every new
    serving reader finds its spans."""
    from chipbench.tests import small
    cell = small.serve_cell()
    cell.traffic["trace_seconds"] = 2
    obs.reset()
    run = Run(cell=cell.name, seed=5, seconds=2.0, traced=True)
    ctx = {"devices": jax.devices()[:1], "process_start": time.time(),
           "trace_dir": str(tmp_path / "trace")}
    cell.arch().program_config = small.small_system_config
    cell.driver().run(cell, run, ctx)
    for name in ("step_host_self_ms.chat", "admit_wait_p95_ms.chat",
                 "idle_unattributed_share.chat"):
        value = read(name, run)
        assert value is not None and value >= 0, name
    obs.reset()
