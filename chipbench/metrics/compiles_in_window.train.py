"""XLA compilations (persistent-cache loads included) inside the traced
window of CFL rounds, from JAX's compile events."""


def compute(run):
    return run.counters.get("compiles_in_window")
