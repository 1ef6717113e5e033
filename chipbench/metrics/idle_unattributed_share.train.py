"""Percent of chip 0's idle time in the traced window during which the
host was in no program span below ``cfl.round``: the program's spans
mapped onto the trace's clock, the device's clock moved onto the host's
by the fused program's launch (``engine.dispatch``) and wait
(``engine.wait``). Logs how the idle time splits over the program's
spans."""
from chipbench.harness import program_spans as ps


def compute(run):
    return ps.idle_unattributed_share(
        run, "cfl.round", [("train_eval", "engine.dispatch", "engine.wait")])
