"""Mean share of the server's slots decoding in each window step."""
from chipbench.harness.readers import window_steps


def compute(run):
    steps = window_steps(run)
    slots = run.counters.get("slots")
    if not steps or not slots:
        return None
    return 100.0 * sum(len(s["decoded"]) for s in steps) / \
        (len(steps) * slots)
