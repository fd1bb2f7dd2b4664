"""Finds everything a cell needs by the names in ``BENCHMARK.json``:

* its configuration: the file the ``configs`` entry names;
* its traffic: ``chipbench/mixes/<traffic>.json``;
* each per-layer metric: ``chipbench/metrics/<name>.py``, a reader with
  ``read(ctx) -> float | None``;
* the plain reference: ``chipbench/references/<reference>.py``, as the
  configuration's ``reference`` names it.

So a new configuration, mix, metric or cell is a new file plus a new
entry in ``BENCHMARK.json``, and no existing file changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    mix: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]    # the metrics this cell reports
    per_layer: List[Dict[str, Any]]
    bench_dir: str

    def reader(self, metric: str):
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        f"{metric}.py"), f"metric_{metric}")

    def reference(self):
        name = self.config["reference"]
        return load_module(os.path.join(self.bench_dir, "references",
                                        f"{name}.py"), f"reference_{name}")


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench_dir = os.path.join(root, "chipbench")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(bench_dir, "mixes", f"{w['traffic']}.json")) as f:
        mix = json.load(f)
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name) and m["moves"] in reported]
    return Cell(name, int(w["chips"]), config, mix, e2e, per_layer,
                bench_dir)
