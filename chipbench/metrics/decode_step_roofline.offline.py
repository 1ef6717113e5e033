"""The batched decode step's share of its roofline: per traced step the
larger of required FLOPs over the bf16 peak and required bytes over HBM
bandwidth, summed, over the step program's device time. Required: the
active slots' submodel FLOPs per token; the weights of every unit some
active slot keeps, once, in float32, and the active slots' keys and
values."""
from chipbench.harness.readers import step_work, window_steps


def compute(run):
    tr = run.trace
    if tr is None:
        return None
    ns = tr.module_ns("jit__step")
    steps = [s for s in window_steps(run) if s["decoded"]]
    if ns <= 0 or not steps:
        return None
    pk = run.peaks
    need = 0.0
    for s in steps:
        f, b = step_work(run, s)
        need += max(f / pk["flops_bf16"], b / pk["hbm_bytes_per_s"])
    return 100.0 * need / (ns / 1e9)
