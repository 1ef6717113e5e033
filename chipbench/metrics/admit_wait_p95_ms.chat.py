"""95th percentile of a request's own wait in the server's queue, from
``EdgeServer.submit`` to the start of its admission (behind the
admissions before it in the same step), over the requests admitted in
the window: the program's ``serve.queue`` records."""
from chipbench.harness import program_spans as ps
from chipbench.harness.stats import percentile


def compute(run):
    recs = ps.records(run)
    if recs is None:
        return None
    lo, hi = (t * 1e9 for t in run.window)
    waits = [ps.duration(r) / 1e6 for r in recs
             if r.name == "serve.queue" and lo <= r.end_ns <= hi]
    return percentile(waits, 95) if waits else None
