"""Prefill program device time over the traced window, in percent."""


def compute(run):
    tr = run.trace
    if tr is None or not tr.module_runs("jit__prefill"):
        return None
    return 100.0 * tr.module_ns("jit__prefill") / 1e9 / tr.window_s
