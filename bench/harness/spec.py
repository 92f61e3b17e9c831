"""A cell's parts, found by name: the workload's entry in BENCHMARK.json,
its configuration (``configs/<name>.json``), its traffic
(``traffic/<name>.json``), its limits (``limits/<cell>.json``) and the
readers of its per-layer metrics (``metrics/<name>.py``)."""

from __future__ import annotations

import functools
import importlib.util
import json
import os
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    def metrics(self, trace: bool) -> list:
        """The metric entries this cell reports in a run: its end-to-end
        metrics with ``trace`` 0, its per-layer metrics with 1."""
        pool = self.per_layer if trace else self.end_to_end
        return [m for m in pool if self.name in m.get("workloads",
                                                      [self.name])]


def workload_names() -> list:
    return [w["name"] for w in
            _load(os.path.join(ROOT, "BENCHMARK.json"))["workloads"]]


def load_cell(name: str, spec_path: str | None = None) -> Cell:
    spec = _load(spec_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    config = _load(os.path.join(BENCH, "configs", f"{w['config']}.json"))
    traffic = _load(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))
    limits = _load(os.path.join(BENCH, "limits", f"{name}.json"))
    if tuple(traffic["grid"]) != tuple(config["grid"]):
        raise SystemExit(f"{name}: traffic grid {traffic['grid']} does not "
                         f"match configuration grid {config['grid']}")
    if traffic["grid"][0] * traffic["grid"][1] != w["chips"]:
        raise SystemExit(f"{name}: a {traffic['grid']} grid on "
                         f"{w['chips']} chips")
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                limits=limits, end_to_end=spec["end_to_end"],
                per_layer=spec["per_layer"])


def metric_reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


@functools.lru_cache(maxsize=None)
def reference_module(name: str):
    """``references/<name>.py``, the plain reference a configuration
    names (loaded once)."""
    path = os.path.join(BENCH, "references", f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_reference_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod
