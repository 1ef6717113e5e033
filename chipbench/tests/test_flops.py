"""The benchmark's FLOP and byte arithmetic against the system's own
analytic counts (``ElasticFamily.flops``, ``ModelConfig.param_count``):
the CNN's in ``harness/flops.py``, granite's in its model file
(``archs/gqa_decoder.py``)."""
import json
import os

import pytest

from chipbench.harness import bench, flops

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_cnn_full_width_equals_family_flops():
    from repro.configs import PAPER_CNN
    from repro.core.elastic import family_for
    fam = family_for(PAPER_CNN)
    model = _config("paper-cnn")["model"]
    full = fam.full_spec()
    assert flops.cnn_forward_flops(model, fam.genes(full)) == \
        fam.flops(full) == 96143872.0


@pytest.mark.parametrize("depth,width", [((1, 2, 3), (0.25, 0.5, 0.75)),
                                         ((3, 1, 2), (1.0, 0.25, 0.5)),
                                         ((1, 1, 1), (0.25, 0.25, 0.25))])
def test_cnn_submodel_never_above_family_count(depth, width):
    """The system counts a narrowed stage's convs at the parent's input
    width; the benchmark counts what the extracted submodel computes."""
    from repro.configs import PAPER_CNN
    from repro.core.elastic import family_for
    from repro.core.submodel import SubmodelSpec
    fam = family_for(PAPER_CNN)
    spec = SubmodelSpec(depth, width)
    ours = flops.cnn_forward_flops(_config("paper-cnn")["model"],
                                   fam.genes(spec))
    assert 0 < ours <= fam.flops(spec)


def test_cnn_round_counts_train_thrice_eval_once():
    model = _config("paper-cnn")["model"]
    g = (3, 3, 3, 100, 100, 100)
    f = flops.cnn_forward_flops(model, g)
    assert flops.cnn_round_flops(model, [g, g], [10, 20], [5, 0]) == \
        f * (3 * 10 + 5) + f * (3 * 20)


def _granite():
    from repro.configs.archs import ARCHS
    from repro.configs.base import depth_cut
    return depth_cut(ARCHS["granite-3-8b"], 2)


@pytest.fixture(scope="module")
def cell():
    return bench.load_cell("granite-3-8b-l2.chat")


FULL = {"layers": (0, 1), "ff_frac": 1.0, "head_frac": 1.0}


def test_token_flops_full_spec_is_twice_the_matmul_params(cell):
    pcfg = _granite()
    norms = 2 * pcfg.d_model * pcfg.n_layers + pcfg.d_model
    assert cell.arch().token_flops(cell, FULL, 0) == \
        2.0 * (pcfg.param_count() - norms)


def test_token_flops_attention_term_and_submodels(cell):
    arch = cell.arch()
    per_pos = (arch.token_flops(cell, FULL, 1)
               - arch.token_flops(cell, FULL, 0))
    assert per_pos == 2 * 2 * 32 * 128 * 2          # 2 layers, 32 heads
    small = {"layers": (1,), "ff_frac": 0.25, "head_frac": 0.25}
    assert arch.token_flops(cell, small, 100) < \
        arch.token_flops(cell, FULL, 100)
    assert arch.prompt_flops(cell, FULL, 3) == pytest.approx(
        sum(arch.token_flops(cell, FULL, k) for k in (1, 2, 3)))


def test_decode_bytes_full_spec_reads_every_weight_once(cell):
    arch, pcfg = cell.arch(), _granite()
    assert arch.decode_step_bytes(cell, [FULL], [0]) == \
        4.0 * pcfg.param_count()
    # a second slot of the same submodel adds only its keys and values
    two = arch.decode_step_bytes(cell, [FULL, FULL], [0, 10])
    assert two - arch.decode_step_bytes(cell, [FULL], [0]) == \
        2 * 2 * 8 * 128 * 10 * 4
