"""Mean host milliseconds per ``EdgeServer.step()``: the steps' wall time
on the trace's clock minus the device's busy time in the window, over the
number of steps. Between steps the chat loop only waits for arrivals, so
the device's work all falls inside steps; summing over the window keeps
the trace's millisecond host/device clock offset out of the reading."""


def compute(run):
    tr = run.trace
    if tr is None:
        return None
    steps = tr.span_list("step")
    if not steps:
        return None
    wall = sum(b - a for a, b in steps)
    return (wall - tr.busy_s * 1e9) / len(steps) / 1e6
