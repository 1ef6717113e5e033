"""The reduction from a profiler trace to device busy time, program time
and labelled idle gaps, on a small trace recorded on a TPU v5e."""
import os

import pytest

from chipbench.harness import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "iter.xplane.pb")


def test_union_clip_covered():
    merged = trace.union([(5, 7), (0, 2), (1, 3), (6, 9), (12, 13)])
    assert merged == [(0, 3), (5, 9), (12, 13)]
    assert trace.clip(merged, 2, 12) == [(2, 3), (5, 9)]
    assert trace.covered(merged, 0, 20) == 3 + 4 + 1
    assert trace.covered(merged, 8, 12) == 1


def test_module_name_drops_program_id():
    assert trace.module_name("jit__step(1249401150305475086)") == \
        "jit__step"
    assert trace.module_name("jit_f") == "jit_f"


@pytest.fixture(scope="module")
def red():
    # three runs of one jitted program, each inside a bench.iter span,
    # 10 ms of host sleep between them; the window spans the three
    return trace.reduce_file(FIXTURE, window_span="iter")


def test_window_and_busy(red):
    assert red.window_s > 0.02          # two 10 ms sleeps inside
    assert 0 < red.busy_s < red.window_s
    # the trace's clock puts each program 1.2 ms before its host span:
    # the first run falls before the window, the other two inside it
    assert len(red.module_runs("jit_f")) == 2
    assert red.module_ns("jit_f") == pytest.approx(red.busy_s * 1e9)


def test_every_span_and_program_seen(red):
    assert len([s for s in red.spans if s[0] == "iter"]) == 3
    assert len([m for m in red.modules if m[1] == "jit_f"]) == 3
    assert sum(red.ops.values()) > 0


def test_idle_gaps_labelled_and_sum(red):
    gaps = red.idle_gaps()
    assert {label for label, _ in gaps} <= {"iter", "outside harness spans"}
    idle = sum(ns for _, ns in gaps)
    assert idle / 1e9 == pytest.approx(red.window_s - red.busy_s)
    b = red.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    # the 10 ms sleeps between the spans are the longest idle time
    assert b["idle_gaps"][0][0] == "outside harness spans"
    assert b["idle_gaps"][0][1] > 0.015


def test_op_kinds():
    assert trace.op_kind("%fusion.93 = f32[16,49408]{1,0} fusion(f32[4])") \
        == "fusion"
    assert trace.op_kind("%copy-start = (f32[2]) copy-start(f32[2])") == \
        "copy-start"
