"""The controls, at a size a CPU test run can hold: the plain reference
computed in bfloat16, in the system's place, reads above what the system
reads, while the system itself stays under every limit."""
import jax.numpy as jnp
import numpy as np

from chipbench.controls import cfl_controls
from chipbench.drivers import serve as serve_driver
from chipbench.tests import small


def test_cfl_control_reads_above_the_system():
    """On the chip the bfloat16 control read 0.0152 to 0.0158 on
    `first_update_gap_median_leaf` against the system's 0.0007 to 0.0036
    and the limit 0.009 (see PERF.md). At CPU size too the harness's own
    checks pass the system and fail the control."""
    cell = small.cnn_cell()
    sound = cfl_controls.readings_for(cell, 77, "sound")
    control = cfl_controls.readings_for(cell, 77, "control_bf16")
    assert all(c.ok for c in cfl_controls.judged(cell, sound)), sound
    failed = [c.name for c in cfl_controls.judged(cell, control)
              if not c.ok]
    assert "first_update_gap_median_leaf" in failed, (control, sound)
    assert control["first_update_gap"] > sound["first_update_gap"], \
        (control, sound)


def test_serve_control_fails_and_system_passes():
    cell = small.serve_cell()
    cell.config["model"].update(hidden_size=256, intermediate_size=512,
                                num_attention_heads=8, head_dim=32,
                                vocab_size=4096)
    cell.traffic.update(check_requests=16)
    run = small.drive(cell, seconds=3.0)
    ledger = run.extra["ledger"]
    sample = serve_driver.check_sample(ledger, 77, 16)
    widest, widest_ctl, n = serve_driver.reference_gaps(
        cell, 77, sample, run.extra["specs"], run.extra["spec_of"], ledger,
        control=(jnp.bfloat16, "default"))
    limit = cell.limits["served_gap"]
    assert n > 100 and np.isfinite(widest)
    # at this size bfloat16 moves logits by less than the cell's limit,
    # which was set on the chip at published widths; here the control
    # has to flip a served token where the system flips none
    assert widest == 0.0 < widest_ctl and widest <= limit, \
        (widest, limit, widest_ctl)
