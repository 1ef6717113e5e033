"""Device milliseconds per run of the batched decode-step program, from
the trace."""
from chipbench.harness.readers import per_run_ms


def compute(run):
    return per_run_ms(run, "jit__step")
