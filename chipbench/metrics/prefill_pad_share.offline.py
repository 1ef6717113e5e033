"""Padded prompt positions over all prefilled positions in the window:
every prompt is front-padded to the server's fixed ``prompt_len``."""
from chipbench.harness.readers import window_steps


def compute(run):
    ledger = run.extra.get("ledger")
    if ledger is None:
        return None
    window = run.counters["prompt_len"]
    real = [min(ledger.req[u]["draw"].prompt_len, window)
            for s in window_steps(run) for u in s["admitted"]]
    if not real:
        return None
    return 100.0 * (1.0 - sum(real) / (window * len(real)))
