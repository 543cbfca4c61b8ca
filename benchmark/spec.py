"""Find a cell's files by the names ``BENCHMARK.json`` gives.

Nothing here knows a cell, a configuration, a driver or a metric by name:
a cell is ``workloads/<cell>.json``, a configuration is the ``file`` its
entry names, a driver is ``drivers/<name>.py`` and a per-layer metric is
``layer_metrics/<name>.py``. Adding one of each is adding files and entries.
"""

from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class SpecError(Exception):
    """The benchmark's data files disagree or name something absent."""


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, tag: str) -> ModuleType:
    if not os.path.isfile(path):
        raise SpecError(f"no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(tag, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Benchmark:
    """``BENCHMARK.json`` and the directory that holds its files."""

    def __init__(self, root: str = ROOT, bench_dir: str | None = None):
        self.root = root
        self.dir = bench_dir or os.path.join(root, "benchmark")
        self.doc = _read_json(os.path.join(root, "BENCHMARK.json"))

    def _entry(self, group: str, name: str) -> dict:
        for entry in self.doc[group]:
            if entry["name"] == name:
                return entry
        have = sorted(e["name"] for e in self.doc[group])
        raise SpecError(f"no {group} entry named {name!r}; have {have}")

    def cell(self, name: str) -> dict:
        """The cell's entry merged over its traffic file's parameters."""
        entry = self._entry("workloads", name)
        params = _read_json(os.path.join(self.dir, "workloads", name + ".json"))
        if params.get("config", entry["config"]) != entry["config"]:
            raise SpecError(f"{name}: file and entry name different configs")
        return {**params, **entry}

    def config(self, name: str) -> dict:
        entry = self._entry("configs", name)
        return {**_read_json(os.path.join(self.root, entry["file"])), **entry}

    def driver(self, name: str) -> ModuleType:
        return _load_module(
            os.path.join(self.dir, "drivers", name + ".py"), f"bench_driver_{name}"
        )

    def end_to_end(self) -> List[dict]:
        """The end-to-end entries; every cell reports each of them."""
        return self.doc["end_to_end"]

    def per_layer(self, cell_name: str) -> List[dict]:
        """The per-layer entries whose ``workloads`` list this cell."""
        for m in self.doc["per_layer"]:
            if "workloads" not in m:
                raise SpecError(f"per-layer metric {m['name']}: no workloads list")
        return [m for m in self.doc["per_layer"] if cell_name in m["workloads"]]

    def layer_metric(self, name: str) -> ModuleType:
        module = _load_module(
            os.path.join(self.dir, "layer_metrics", name + ".py"),
            "bench_metric_" + name.replace(".", "_").replace("-", "_"),
        )
        row = {k: v for k, v in self._entry("per_layer", name).items()
               if k != "workloads"}
        if module.ROW != row:
            raise SpecError(
                f"layer metric {name}: its file's ROW {module.ROW} is not "
                f"its BENCHMARK.json entry {row}"
            )
        return module

    def peaks(self, device_kind: str) -> Dict[str, float]:
        table = _read_json(os.path.join(self.dir, "peaks.json"))
        if device_kind.startswith("_") or device_kind not in table:
            raise SpecError(
                f"device kind {device_kind!r} is not in the peaks table "
                f"({sorted(k for k in table if not k.startswith('_'))})"
            )
        return table[device_kind]
