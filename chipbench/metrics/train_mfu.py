"""Whole rounds' share of the chip's peak: required FLOPs in the traced
window over window x chips x bf16 peak."""


def compute(run):
    flops = run.counters.get("required_flops", 0.0)
    if flops <= 0 or run.window_s <= 0:
        return None
    return 100.0 * flops / (run.window_s * run.device["count"]
                            * run.peaks["flops_bf16"])
