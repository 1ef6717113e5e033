"""The per-layer readers' arithmetic on fixed fixtures."""
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench.harness import bench, readers, trace
from chipbench.harness.requests import Draw


def _serving_run():
    """Four requests of two submodels at granite's published widths."""
    cell = bench.load_cell("granite-3-8b-l2.docs-offline")
    specs = [{"layers": (0, 1), "ff_frac": 0.5, "head_frac": 0.75},
             {"layers": (1,), "ff_frac": 0.25, "head_frac": 1.0}]
    req = {u: {"draw": Draw(uid=u, due=0.0, prompt_len=pl, output_len=8,
                            tenant=t, prompt=np.zeros(pl, np.int32))}
           for u, pl, t in [(0, 100, 0), (1, 1024, 1), (2, 17, 2),
                            (3, 600, 5)]}
    return SimpleNamespace(extra={
        "cell": cell, "specs": specs,
        "spec_of": lambda d: d.tenant % 2,
        "ledger": SimpleNamespace(req=req)})


# (step, step_work, prefill_work) as the harness computed them before the
# counts moved into the model file
STEPS = [
    ({"decoded": [(0, 100), (1, 1030), (2, 17), (3, 640)],
      "admitted": [0, 2, 3]},
     (2863587328.0, 1752170496.0), 441827917824.0),
    ({"decoded": [(1, 1100)], "admitted": [1]},
     (583229440.0, 1139474432.0), 587370332160.0),
]


@pytest.mark.parametrize("step,work,prefill", STEPS)
def test_step_and_prefill_work_unchanged(step, work, prefill):
    run = _serving_run()
    assert readers.step_work(run, step) == work
    assert readers.prefill_work(run, step) == prefill


def _traced(ops, count, modules=(), busy=()):
    red = trace.Reduction(window=(0, 10 ** 9), busy={0: list(busy)},
                          modules=list(modules), ops=ops, spans=[])
    return SimpleNamespace(trace=red, counters={"rounds": 2,
                                                "required_flops": 4e12},
                           device={"count": count},
                           peaks={"flops_bf16": 197e12})


def test_train_eval_roofline_per_chip():
    prog = [(0, "jit__client_train_eval", 0, 400_000_000)]
    busy = [(0, 400_000_000)]
    read = bench.metric_reader("train_eval_roofline")
    one = read(_traced({}, 1, prog, busy))
    four = read(_traced({}, 4, prog, busy))
    assert one == pytest.approx(100 * 4e12 / (197e12 * 0.4))
    assert four == pytest.approx(one / 4)


def test_aggregate_reader_reads_only_the_aggregate_program():
    """A program of another inner function named ``run`` (the cohort
    selection's ``jit_run``) is not the aggregate's, and other chips'
    programs are not chip 0's."""
    prog = [(0, "jit_aggregate_apply", 100, 300), (0, "jit_run", 400, 900),
            (1, "jit_aggregate_apply", 100, 300)]
    run = _traced({}, 1, prog, [(0, 1000)])
    read = bench.metric_reader("aggregate_device_ms_per_round")
    assert read(run) == pytest.approx(200 / 2 / 1e6)
