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
