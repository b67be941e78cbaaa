"""Find everything a cell needs by name, from files alone.

``BENCHMARK.json`` names the cells, configurations and metrics.  The rest
sits in files of its own, found by name under ``bench/``:

* ``bench/configs/<config>.json`` (the path ``BENCHMARK.json`` gives): the
  model configuration as it is run; its ``"model"`` key names
  ``bench/models/<model>.py``, which makes the weights, maps them onto the
  program's parameters and holds the plain reference;
* ``bench/traffic/<traffic>.json``: the traffic mix, read by ``traffic.py``;
* ``bench/cells/<workload>.json``: the engine sizes of one cell, its rate
  (open loop) and the limits of its correctness check;
* ``bench/metrics/<metric>.py``: one per-layer metric's reader.

Adding a cell, a mix, a configuration or a per-layer metric takes new files
and ``BENCHMARK.json`` entries, never an edit here.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

__all__ = ["Spec", "load_module"]


def load_module(path: Path, name: str) -> ModuleType:
    """Import one file by path (metric and model files carry dots and
    dashes in their names, so they are not importable by name)."""
    spec = importlib.util.spec_from_file_location(
        "bench_dyn_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Spec:
    """``BENCHMARK.json`` at ``root`` and the files it leads to."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.bench = _read_json(self.root / "BENCHMARK.json")
        self._modules: Dict[str, ModuleType] = {}

    # -- entries ------------------------------------------------------------
    def workload(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")

    def config_entry(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no config named {name!r} in BENCHMARK.json")

    @staticmethod
    def _applies(metric: dict, workload: str) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    def end_to_end(self, workload: str) -> List[dict]:
        return [m for m in self.bench["end_to_end"]
                if self._applies(m, workload)]

    def per_layer(self, workload: str) -> List[dict]:
        """Per-layer metrics of ``workload``: those listing it, and those
        without a list whose moved metric the cell reports."""
        e2e = {m["name"] for m in self.end_to_end(workload)}
        out = []
        for m in self.bench["per_layer"]:
            if "workloads" in m:
                if workload in m["workloads"]:
                    out.append(m)
            elif m["moves"] in e2e:
                out.append(m)
        return out

    # -- files found by name ------------------------------------------------
    def config(self, name: str) -> dict:
        return _read_json(self.root / self.config_entry(name)["file"])

    def traffic(self, name: str) -> dict:
        return _read_json(self.root / "bench" / "traffic" / f"{name}.json")

    def cell(self, workload: str) -> dict:
        return _read_json(self.root / "bench" / "cells" / f"{workload}.json")

    def model_module(self, config_name: str) -> ModuleType:
        model = self.config(config_name)["model"]
        return self._module(self.root / "bench" / "models" / f"{model}.py",
                            "model." + model)

    def reader(self, metric: str) -> Optional[ModuleType]:
        path = self.root / "bench" / "metrics" / f"{metric}.py"
        if not path.exists():
            return None
        return self._module(path, "metric." + metric)

    def _module(self, path: Path, key: str) -> ModuleType:
        if key not in self._modules:
            self._modules[key] = load_module(path, key)
        return self._modules[key]
