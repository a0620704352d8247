"""Find everything a cell needs by the names in ``BENCHMARK.json``.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix; the harness finds

* the configuration's file where its ``configs`` entry says (``file``);
* the traffic mix at ``traffic/<traffic>.json``;
* the model kind's reference at ``reference/<kind>.py`` and its operations
  and bytes at ``work/<kind>.py``;
* each per-layer metric's reader at ``metrics/<name>.py``;
* the device's peaks in ``peaks.json``, keyed by ``device_kind``.

So a later change adds a cell, a configuration, a traffic mix or a metric
by adding files and manifest entries only. Unknown names are refused.
"""
from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _read_json(path: str):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _module(bench_dir: str, kind_dir: str, name: str) -> ModuleType:
    path = os.path.join(bench_dir, kind_dir, f"{name}.py")
    if not os.path.isfile(path):
        raise KeyError(f"no {kind_dir} file for {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind_dir}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    """``BENCHMARK.json`` and the files its names lead to."""

    def __init__(self, root: str = ROOT, bench_dir: str = HERE):
        self.root = root
        self.bench_dir = bench_dir
        self.data = _read_json(os.path.join(root, "BENCHMARK.json"))

    def _named(self, key: str, name: str) -> Dict:
        for entry in self.data[key]:
            if entry["name"] == name:
                return entry
        known = [e["name"] for e in self.data[key]]
        raise KeyError(f"no {key} entry named {name!r}; known: {known}")

    def workload(self, name: str) -> Dict:
        return self._named("workloads", name)

    def config(self, name: str) -> Dict:
        """The configuration's file, with its manifest entry's name."""
        entry = self._named("configs", name)
        cfg = _read_json(os.path.join(self.root, entry["file"]))
        return dict(cfg, name=entry["name"])

    def traffic(self, name: str) -> Dict:
        path = os.path.join(self.bench_dir, "traffic", f"{name}.json")
        if not os.path.isfile(path):
            raise KeyError(f"no traffic file for {name!r} at {path}")
        return _read_json(path)

    def metrics(self, cell: str, trace: bool) -> List[Dict]:
        """The cell's end-to-end metrics, or with ``trace`` its per-layer
        ones: those that list it, or list no cells at all."""
        key = "per_layer" if trace else "end_to_end"
        return [m for m in self.data[key]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str) -> ModuleType:
        return _module(self.bench_dir, "metrics", metric)

    def reference(self, kind: str) -> ModuleType:
        return _module(self.bench_dir, "reference", kind)

    def work(self, kind: str) -> ModuleType:
        return _module(self.bench_dir, "work", kind)

    def peaks(self, device_kind: str) -> Dict:
        table = _read_json(os.path.join(self.bench_dir, "peaks.json"))
        if device_kind not in table:
            raise KeyError(f"device_kind {device_kind!r} is not in peaks.json"
                           f" (known: {sorted(table)})")
        return table[device_kind]
