"""95th percentile of the wait from a request's due time to the step
that admits it, over the requests admitted in the traced window."""
from chipbench.harness.stats import percentile


def compute(run):
    ledger = run.extra.get("ledger")
    if ledger is None:
        return None
    waits = [(r["admit"] - r["due"]) * 1e3 for r in ledger.req.values()
             if r["admit"] is not None]
    return percentile(waits, 95) if waits else None
