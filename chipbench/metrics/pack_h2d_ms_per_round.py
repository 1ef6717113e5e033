"""Host milliseconds per window round spent building the round program's
inputs: the program's ``engine.pack`` spans (cohort masks, batch-stream
packing and its host-to-device copy, resident-data lookups) inside its
``cfl.round`` spans, over the window's rounds."""
from chipbench.harness import program_spans as ps


def compute(run):
    recs = ps.records(run)
    if recs is None:
        return None
    rounds = ps.window_roots(run, recs, "cfl.round")
    if not rounds:
        return None
    packs = ps.below(recs, rounds, "engine.pack")
    return sum(ps.duration(recs[i]) for i in packs) / len(rounds) / 1e6
