"""Offline serving's share of the chip's peak: the required FLOPs of the
window's real prompt tokens (padding left out) and decoded tokens, at
each tenant's submodel, over window x bf16 peak."""
from chipbench.harness.readers import prefill_work, step_work, window_steps


def compute(run):
    steps = window_steps(run)
    if not steps or run.window_s <= 0:
        return None
    total = sum(step_work(run, s)[0] + prefill_work(run, s) for s in steps)
    return 100.0 * total / (run.window_s * run.peaks["flops_bf16"])
