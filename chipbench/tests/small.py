"""Cells cut to sizes a CPU test run can hold. Every shape the drivers
and the references depend on is kept; sizes are cut."""
import dataclasses
import time

import jax

from chipbench.harness import bench
from chipbench.harness.result import Run


def cnn_cell():
    cell = bench.load_cell("paper-cnn.fl-full32")
    # two clients of the cell's own size: 22 local steps a round, so the
    # global-norm clipping (5.0) does not bind on every step
    cell.traffic["population"].update(clients=2, test_per_client=32)
    cell.traffic["warm_rounds_ahead"] = 1
    return cell


SMALL_LM = dict(hidden_size=64, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                vocab_size=500)


def serve_cell(name="granite-3-8b-l2.chat"):
    cell = bench.load_cell(name)
    cell.config["model"].update(SMALL_LM)
    cell.traffic.update(
        prompt_len=64, max_new_tokens=16, slots=4, check_requests=4,
        prompt={"kind": "lognormal", "median": 20, "sigma": 1.0, "min": 4,
                "max": 64},
        output={"kind": "uniform", "min": 4, "max": 16}, rate=5.0,
        drain_s=20, stratum=16)
    return cell


def small_system_config(cell):
    """The system's granite config at the cut cell's widths."""
    from repro.configs.archs import ARCHS
    from repro.configs.base import depth_cut
    c = cell.config["model"]
    return dataclasses.replace(
        depth_cut(ARCHS["granite-3-8b"], c["num_hidden_layers"]),
        d_model=c["hidden_size"], d_ff=c["intermediate_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        vocab_size=c["vocab_size"])


def drive(cell, seed=77, seconds=2.0):
    """A whole run past the chip check: set-up, window, correctness."""
    run = Run(cell=cell.name, seed=seed, seconds=seconds, traced=False)
    ctx = {"devices": jax.devices()[:1], "process_start": time.time(),
           "trace_dir": None}
    if cell.traffic["driver"] == "serve":
        cell.arch().program_config = small_system_config
    cell.driver().run(cell, run, ctx)
    return run
