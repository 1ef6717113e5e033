"""Percent of chip 0's idle time in the traced window during which the
host was in no program span below ``serve.step`` (the open loop's waits
for arrivals between steps included): the program's spans mapped onto
the trace's clock, the device's clock moved onto the host's by the
launch and wait spans of the decode step and of the prefill. Logs how
the idle time splits over the program's spans."""
from chipbench.harness import program_spans as ps


def compute(run):
    return ps.idle_unattributed_share(
        run, "serve.step",
        [("jit__step", "serve.decode_dispatch", "serve.logits_wait"),
         ("jit__prefill", "serve.prefill", "serve.first_token_wait")])
