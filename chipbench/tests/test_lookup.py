"""A new configuration, traffic mix, cell and per-layer metric are found
by the names in BENCHMARK.json, with no edit to the harness."""
import json
import os
import shutil

from chipbench.harness import bench
from chipbench.harness.result import Run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_new_files_found_by_name(tmp_path):
    base = tmp_path / "chipbench"
    for d in ("configs", "traffic", "limits", "metrics", "drivers"):
        (base / d).mkdir(parents=True)
    shutil.copy(os.path.join(HERE, "configs", "paper-cnn.json"),
                base / "configs" / "paper-cnn-wide.json")
    (base / "traffic" / "fl-new.json").write_text(json.dumps(
        {"driver": "echo", "answer": 42}))
    (base / "limits" / "paper-cnn-wide.fl-new.json").write_text("{}")
    (base / "drivers" / "echo.py").write_text(
        "def run(cell, run, ctx):\n"
        "    run.counters['answer'] = cell.traffic['answer']\n")
    (base / "metrics" / "answer.new.py").write_text(
        "def compute(run):\n    return run.counters.get('answer')\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "paper-cnn-wide.fl-new",
                       "config": "paper-cnn-wide", "traffic": "fl-new",
                       "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "x_per_s", "unit": "1/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "answer.new", "unit": "count",
                       "moves": "x_per_s"},
                      {"name": "elsewhere", "unit": "count",
                       "moves": "x_per_s", "workloads": ["other"]}]}))
    cell = bench.load_cell("paper-cnn-wide.fl-new", root=str(tmp_path),
                           base=str(base))
    assert cell.config["model"]["stem_channels"] == 32
    assert [m["name"] for m in cell.per_layer] == ["answer.new"]
    run = Run(cell=cell.name, seed=1, seconds=1.0, traced=True)
    cell.driver().run(cell, run, {})
    assert bench.metric_reader("answer.new", str(base))(run) == 42


def test_every_benchmark_entry_has_its_files():
    root = os.path.dirname(HERE)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    for w in b["workloads"]:
        cell = bench.load_cell(w["name"])
        assert os.path.exists(os.path.join(
            HERE, "drivers", cell.traffic["driver"] + ".py"))
        for m in cell.per_layer:
            assert callable(bench.metric_reader(m["name"]))
        assert cell.limits
    for c in b["configs"]:
        assert os.path.exists(os.path.join(root, c["file"]))


TINY_ARCH = '''"""A second serving architecture, for the test: a GQA
decoder at CPU widths, its own system config, the rest from
gqa_decoder.py."""
import dataclasses
import os

from chipbench.harness.bench import load_module

_gqa = load_module(os.path.join(os.path.dirname(__file__), "gqa_decoder.py"),
                   "chipbench_arch_tiny_gqa_base")
init_params, tenant_specs, program_spec, vocab_size = (
    _gqa.init_params, _gqa.tenant_specs, _gqa.program_spec, _gqa.vocab_size)
reference_gaps, token_flops, prompt_flops, decode_step_bytes = (
    _gqa.reference_gaps, _gqa.token_flops, _gqa.prompt_flops,
    _gqa.decode_step_bytes)


def program_config(cell):
    from repro.configs.archs import ARCHS
    from repro.configs.base import depth_cut
    c = cell.config["model"]
    return dataclasses.replace(
        depth_cut(ARCHS[cell.config["system_arch"]], c["num_hidden_layers"]),
        d_model=c["hidden_size"], d_ff=c["intermediate_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        vocab_size=c["vocab_size"])


def family(cell):
    from repro.core.elastic import TransformerElasticFamily
    return TransformerElasticFamily(program_config(cell))
'''

WORK_METRIC = '''from chipbench.harness.readers import step_work, window_steps


def compute(run):
    steps = [s for s in window_steps(run) if s["decoded"]]
    if not steps:
        return None
    return sum(step_work(run, s)[0] for s in steps) / len(steps)
'''


def test_new_serving_architecture_joins_with_new_files_only(tmp_path):
    """A copy of the benchmark's tree gains a serving configuration that
    names its own ``archs/<name>.py``, a traffic mix for the existing
    serve driver, the cell's limits and a metric that reads the new
    model file's counts. The unchanged driver runs the cell through the
    new files, correct, and no file that was there changes."""
    from chipbench.tests import small
    base = tmp_path / "chipbench"
    shutil.copytree(HERE, base,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in base.rglob("*") if p.is_file()}
    cfg = json.loads((base / "configs" / "granite-3-8b-l2.json").read_text())
    cfg.update(name="tiny-gqa", arch="tiny_gqa")
    cfg["model"].update(small.SMALL_LM)
    (base / "configs" / "tiny-gqa.json").write_text(json.dumps(cfg))
    (base / "archs" / "tiny_gqa.py").write_text(TINY_ARCH)
    mix = small.serve_cell().traffic
    (base / "traffic" / "chat-tiny.json").write_text(json.dumps(mix))
    (base / "limits" / "tiny-gqa.chat-tiny.json").write_text(
        json.dumps({"served_gap": 0.0065}))
    (base / "metrics" / "flops_per_step.tiny.py").write_text(WORK_METRIC)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "workloads": [{"name": "tiny-gqa.chat-tiny", "config": "tiny-gqa",
                       "traffic": "chat-tiny", "chips": 1, "why": "test"}],
        "end_to_end": [{"name": "ttft_p95_ms", "unit": "ms"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "flops_per_step.tiny", "unit": "FLOP",
                       "moves": "ttft_p95_ms"}]}))
    cell = bench.load_cell("tiny-gqa.chat-tiny", root=str(tmp_path),
                           base=str(base))
    assert cell.arch().__file__ == str(base / "archs" / "tiny_gqa.py")
    run = Run(cell=cell.name, seed=2 ** 35 + 3, seconds=2.0, traced=False)
    import jax
    import time
    cell.driver().run(cell, run, {"devices": jax.devices()[:1],
                                  "process_start": time.time(),
                                  "trace_dir": None})
    assert run.correct, [(c.name, c.value, c.limit) for c in run.checks]
    assert run.end_to_end["ttft_p95_ms"] > 0
    assert bench.metric_reader("flops_per_step.tiny", str(base))(run) > 0
    assert all(p.read_bytes() == b for p, b in before.items())
