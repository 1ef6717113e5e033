"""Mean host milliseconds of an ``EdgeServer.step()`` in the window that
are not spent waiting on the device: each ``serve.step`` span less the
``*_wait`` spans inside it (the decode logits and each admission's first
token). Logs the five slowest steps with the time of their parts."""
from chipbench.harness import program_spans as ps


def compute(run):
    recs = ps.records(run)
    if recs is None:
        return None
    steps = ps.window_roots(run, recs, "serve.step")
    if not steps:
        return None
    self_ns = {i: ps.duration(recs[i]) for i in steps}
    for i, root in ps.root_of(recs, steps).items():
        if recs[i].name.endswith("_wait"):
            self_ns[root] -= ps.duration(recs[i])
    ps.log_slow_steps(recs, steps, run.window[0] * 1e9)
    return sum(self_ns.values()) / len(steps) / 1e6
