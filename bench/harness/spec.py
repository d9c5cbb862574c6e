"""``BENCHMARK.json`` and the files it names, found by name.

A configuration is ``bench/configs/<config>.json``, a traffic mix
``bench/traffic/<traffic>.json``, the limits of a cell's comparison
``bench/limits/<cell>.json``, a per-layer metric's reader
``bench/metrics/<metric>.py`` and a kernel's work count
``bench/kernels/<kernel>.py``.  Adding a cell, a configuration, a mix or
a metric adds files and entries and edits none.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path


class Bench:
    def __init__(self, root: Path) -> None:
        self.root = Path(root)
        with open(self.root / "BENCHMARK.json") as f:
            self.doc = json.load(f)
        self.dir = self.root / self.doc["paths"][0]

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, cell: dict) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == cell["config"]:
                return _json(self.root / c["file"])
        raise KeyError(f"no config {cell['config']!r} in BENCHMARK.json")

    def traffic(self, cell: dict) -> dict:
        return _json(self.dir / "traffic" / f"{cell['traffic']}.json")

    def limits(self, cell: dict) -> dict:
        return _json(self.dir / "limits" / f"{cell['name']}.json")

    def metrics(self, cell: dict, kind: str) -> list[dict]:
        """The cell's ``end_to_end`` or ``per_layer`` metrics."""
        out = []
        for m in self.doc[kind]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                out.append(m)
        return out

    def reader(self, metric: str):
        return load_module(self.dir / "metrics" / f"{metric}.py")

    def kernel(self, kernel: str):
        return load_module(self.dir / "kernels" / f"{kernel}.py")

    def peak(self, device_kind: str) -> dict:
        """The chip's published peaks; a device not in the table is an
        error, never a default."""
        peaks = _json(self.dir / "peaks.json")
        if device_kind not in peaks:
            raise ValueError(f"no peaks for device kind {device_kind!r} "
                             f"in peaks.json (known: {sorted(peaks)})")
        return peaks[device_kind]


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_file_{path.parent.name}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
