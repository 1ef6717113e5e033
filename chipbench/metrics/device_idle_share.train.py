"""Percent of the traced window of CFL rounds with no operation on the
device."""
from chipbench.harness.readers import idle_share


def compute(run):
    return idle_share(run)
