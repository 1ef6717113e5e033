"""Device milliseconds per round of the fused aggregate + apply program
(``core/aggregate.py``), from the trace."""
from chipbench.harness.readers import per_round_ms


def compute(run):
    return per_round_ms(run, "aggregate_apply")
