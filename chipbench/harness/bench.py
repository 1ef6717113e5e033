"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, per-layer
metric or cell lives in a file of its own, found by name:

* ``configs/<config>.json``  — the configuration as it is run
* ``traffic/<mix>.json``     — the mix's parameters and its ``driver``
* ``drivers/<driver>.py``    — the code that runs one kind of traffic
* ``metrics/<metric>.py``    — ``compute(run)`` for one per-layer metric
* ``limits/<cell>.json``     — the limits the cell's correctness check uses
* ``archs/<arch>.py``        — the model half of a serving configuration,
  named by its ``"arch"`` key: the system's config and family, weights
  and tenants' submodels from the seed, the plain reference's check and
  the work counts (``archs/gqa_decoder.py`` lists the functions)
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path (metric and driver files are named after
    their entry in BENCHMARK.json, dots included)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    base: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    _arch: object = dataclasses.field(default=None, repr=False,
                                      compare=False)

    def driver(self):
        kind = self.traffic["driver"]
        return load_module(os.path.join(self.base, "drivers", f"{kind}.py"),
                           f"chipbench_driver_{kind}")

    def arch(self):
        """The model file ``archs/<arch>.py`` that the configuration's
        ``"arch"`` key names, loaded once per cell (every caller of one
        cell then shares it, and a test may replace its functions)."""
        if self._arch is None:
            name = self.config.get("arch")
            if name is None:
                raise KeyError(
                    f"configuration {self.config.get('name')!r} names no "
                    f"\"arch\": a serving configuration names its model "
                    f"file archs/<arch>.py")
            self._arch = load_module(
                os.path.join(self.base, "archs", f"{name}.py"),
                f"chipbench_arch_{name}")
        return self._arch


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT, base: str = HERE) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, its files read from
    ``base`` (this directory)."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(work)}")
    w = work[name]
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name) and m["moves"] in e2e_names]
    return Cell(
        name=name, base=base, chips=int(w["chips"]),
        config=_load_json(os.path.join(base, "configs",
                                       f"{w['config']}.json")),
        traffic=_load_json(os.path.join(base, "traffic",
                                        f"{w['traffic']}.json")),
        limits=_load_json(os.path.join(base, "limits", f"{name}.json")),
        end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str, base: str = HERE):
    """The ``compute(run)`` function of ``metrics/<name>.py``."""
    mod = load_module(os.path.join(base, "metrics", f"{name}.py"),
                      "chipbench_metric_" + name.replace(".", "_"))
    return mod.compute


def apply_precision(config: Dict) -> None:
    """Run the system at the matmul precision the configuration states
    (``default`` leaves JAX's own default: bfloat16 operands on a TPU)."""
    prec = config.get("matmul_precision", "default")
    if prec != "default":
        import jax
        jax.config.update("jax_default_matmul_precision", prec)
