"""Finds a cell's pieces by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration, whose file is
``configs[...]["file"]``, and a traffic mix, read from
``bench/traffic/<traffic>.json``.  Each per-layer metric is read by
``bench/metrics/<name>.py``.  Adding a cell, a configuration, a mix or
a metric adds files and entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class Cell:
    """One workload entry with everything it refers to, loaded."""

    def __init__(self, root: Path, name: str):
        self.root = Path(root)
        self.manifest = json.loads((self.root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        configs = {c["name"]: c for c in self.manifest["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.conf = json.loads(
            (self.root / self.config_entry["file"]).read_text())
        self.traffic = json.loads(
            (self.root / "bench" / "traffic"
             / f"{self.entry['traffic']}.json").read_text())
        self.peaks_table = json.loads(
            (self.root / "bench" / "peaks.json").read_text())
        self.end_to_end = [m for m in self.manifest["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in self.manifest["per_layer"]
                          if name in m.get("workloads", [name])]

    @classmethod
    def from_parts(cls, root: Path, entry: dict, conf: dict, traffic: dict,
                   end_to_end: list, per_layer: list) -> "Cell":
        """A cell assembled in memory (tests: a small configuration)."""
        cell = cls.__new__(cls)
        cell.root, cell.manifest = Path(root), None
        cell.entry, cell.name = entry, entry["name"]
        cell.config_entry, cell.conf, cell.traffic = None, conf, traffic
        cell.peaks_table = json.loads(
            (Path(root) / "bench" / "peaks.json").read_text())
        cell.end_to_end, cell.per_layer = end_to_end, per_layer
        return cell

    def peaks(self, device_kind: str) -> dict:
        """The chip's peaks; a kind not in the table is an error."""
        if device_kind not in self.peaks_table:
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           f"bench/peaks.json; have {sorted(self.peaks_table)}")
        return self.peaks_table[device_kind]

    def reader(self, metric: str):
        """The module that reads one per-layer metric."""
        path = self.root / "bench" / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
