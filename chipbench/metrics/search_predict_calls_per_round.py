"""Alg. 2 predictor calls per window round made by Alg. 1's search: the
program's ``search.predict_calls`` counter, incremented once per
``AccuracyPredictor.predict_batch`` (one device round trip each), inside
its ``cfl.round`` spans."""
from chipbench.harness import program_spans as ps


def compute(run):
    recs = ps.records(run)
    if recs is None:
        return None
    rounds = ps.window_roots(run, recs, "cfl.round")
    if not rounds:
        return None
    return ps.count_below(run, recs, rounds,
                          "search.predict_calls") / len(rounds)
