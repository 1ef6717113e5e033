"""The model files (``archs/<arch>.py``) that serving configurations name:
found by the configuration's ``"arch"`` key, never by a fallback."""
import pytest

from chipbench.harness import bench

BIG = 2 ** 40 + 7
INTERFACE = ("program_config", "family", "init_params", "tenant_specs",
             "program_spec", "vocab_size", "reference_gaps", "token_flops",
             "prompt_flops", "decode_step_bytes")


@pytest.mark.parametrize("workload", ["granite-3-8b-l2.chat",
                                      "granite-3-8b-l2.docs-offline"])
def test_serving_cells_name_a_model_file(workload):
    cell = bench.load_cell(workload)
    arch = cell.arch()
    assert cell.config["arch"] == "gqa_decoder"
    assert all(callable(getattr(arch, f)) for f in INTERFACE)
    assert cell.arch() is arch          # loaded once per cell


def test_a_serving_configuration_without_arch_fails():
    cell = bench.load_cell("granite-3-8b-l2.chat")
    del cell.config["arch"]
    with pytest.raises(KeyError, match="names no"):
        cell.arch()


def test_tenant_specs_seeded():
    cell = bench.load_cell("granite-3-8b-l2.chat")
    cell.traffic["specs"] = 3
    cell.config["elastic_widths"] = [0.25, 0.5, 0.75, 1.0]
    arch = cell.arch()
    s1 = arch.tenant_specs(cell, BIG)
    assert s1 == arch.tenant_specs(cell, BIG)
    assert len(s1) == 3
    assert all(1 <= len(s["layers"]) <= 2 for s in s1)
