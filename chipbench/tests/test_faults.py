"""A run with the timed path broken underneath comes out not correct,
once for each fault the cell can have; the same run unbroken is correct.
The cells are cut to CPU size (``small.py``); the limits are the cells'
own (``limits/``)."""
import contextlib

import pytest

from chipbench.controls import cfl_controls, serve_controls
from chipbench.tests import small


@contextlib.contextmanager
def state_unchanged_round():
    """The round's aggregate+apply returns the parent unchanged."""
    from repro.core import aggregate as _  # noqa: F401  (module import)
    import importlib
    agg = importlib.import_module("repro.core.aggregate")
    inner = agg.aggregate_apply

    def stale(params, *a, **k):
        return params
    agg.aggregate_apply = stale
    try:
        yield
    finally:
        agg.aggregate_apply = inner


@contextlib.contextmanager
def half_batch_everywhere():
    from repro.core.elastic import CNNElasticFamily
    inner = CNNElasticFamily.masked_loss

    def faulty(self, params, fwd, x, y, w, **kw):
        import jax.numpy as jnp
        half = (jnp.arange(w.shape[0]) < w.shape[0] // 2).astype(w.dtype)
        return inner(self, params, fwd, x, y, w * half, **kw)
    CNNElasticFamily.masked_loss = faulty
    try:
        yield
    finally:
        CNNElasticFamily.masked_loss = inner


CFL_FAULTS = {"sound": contextlib.nullcontext,
              "state_unchanged": state_unchanged_round,
              "half_batch": half_batch_everywhere}


@pytest.mark.parametrize("fault", sorted(CFL_FAULTS))
def test_cfl_fault_fails_correct(fault):
    with CFL_FAULTS[fault]():
        run = small.drive(small.cnn_cell())
    assert run.correct == (fault == "sound"), \
        [(c.name, c.value, c.limit) for c in run.checks]


@pytest.mark.parametrize("fault", ["sound", "state_unchanged",
                                   "token_altered"])
def test_serve_fault_fails_correct(fault):
    with serve_controls.fault(fault):
        run = small.drive(small.serve_cell())
    assert run.correct == (fault == "sound"), \
        [(c.name, c.value, c.limit) for c in run.checks]


def test_cfl_half_batch_context_restores():
    from repro.core.elastic import family_for
    from repro.configs import PAPER_CNN
    fam = family_for(PAPER_CNN)
    inner = fam.masked_loss
    with cfl_controls.half_batch(fam):
        assert fam.masked_loss is not inner
    assert fam.masked_loss == inner
