"""Device milliseconds per round of the fused local train + eval program
(``fl/engine.py``), from the trace."""
from chipbench.harness.readers import per_round_ms


def compute(run):
    return per_round_ms(run, "_client_train_eval")
