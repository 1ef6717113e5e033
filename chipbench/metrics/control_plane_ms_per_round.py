"""Host milliseconds per window round inside Alg. 1's submodel search and
Alg. 2's predictor update (spans around ``CFLServer.sample_submodels``
and ``post_aggregate``)."""


def compute(run):
    rounds = run.counters.get("rounds", 0)
    if not rounds or run.spans is None:
        return None
    lo, hi = run.window
    secs = sum(run.spans.total(n, lo, hi) for n in
               ("control.sample_submodels", "control.post_aggregate"))
    return secs / rounds * 1e3
