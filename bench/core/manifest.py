"""Finds a cell's pieces by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration, whose file is
``configs[...]["file"]``, and a traffic mix, read from
``bench/traffic/<traffic>.json``.  The configuration names its
architecture by the key ``"arch"``: the module ``bench/arch/<arch>.py``
(``load_arch``).  Each per-layer metric is read by
``bench/metrics/<name>.py``.  Adding a
cell, a configuration, an architecture, a mix or a metric adds files
and entries; nothing here changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _arch_module(path: Path):
    # one module object per file, so that the jitted programs keyed on
    # it are made once per process
    return _load(path, "bench_arch_" + path.stem.replace(".", "_")
                 .replace("-", "_"))


def load_arch(root: Path, conf: dict):
    """The architecture module that configuration ``conf`` names,
    ``bench/arch/<conf["arch"]>.py`` under ``root``; a configuration
    without ``"arch"`` is an error.

    The module tells the shared code (``bench/core``) what the
    architecture's layers are.  It gives:

    - ``dims(conf)``: the sizes, by short name; at least ``d`` (hidden),
      ``V`` (vocabulary rows held) and ``L`` (layers);
    - ``layer_shapes(conf, l)``: (leaf id, shape, scale) of layer
      ``l``'s leaves, by name, leaf ids from 10 (``weights`` draws them);
    - ``segments(conf)``: the program's ``params["segments"]`` as layer
      indices: per segment, per position of its pattern, the layers
      stacked there.  Layers stacked together have the same leaves and
      are computed alike;
    - ``tree(conf, l, flat)``: the program's nesting of layer ``l``'s
      flat leaves (stacked or not);
    - ``model_config(conf)``: the program's ``ModelConfig``;
    - ``block(conf, l, w, x, precision)``: the plain reference of layer
      ``l`` over one sequence, through ``reference.matmul`` and
      ``reference.attention`` (``reference`` runs the rest);
    - ``matmul_flops_per_token(conf)``, ``attn_flops(conf, start,
      take)``, ``attn_bytes(conf, start, take)``: what the math needs
      (``flops``); where a chip holds a share of the experts, the
      expected count of that share, said so in the docstring;
    - optionally ``KERNELS``: the op-name prefix of each kernel it runs,
      by kernel name (``steps.KERNELS`` is the default).
    """
    if "arch" not in conf:
        raise KeyError(f"configuration {conf.get('name')!r} has no 'arch' "
                       "key: name its module under bench/arch/")
    path = Path(root).resolve() / "bench" / "arch" / f"{conf['arch']}.py"
    if not path.is_file():
        raise FileNotFoundError(f"configuration {conf.get('name')!r} "
                                f"names arch {conf['arch']!r}: no {path}")
    return _arch_module(path)


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
        self.arch = load_arch(self.root, self.conf)
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
        """A cell assembled in memory (tests: a small configuration); its
        architecture is the module under ``root`` that ``conf`` names."""
        cell = cls.__new__(cls)
        cell.root, cell.manifest = Path(root), None
        cell.entry, cell.name = entry, entry["name"]
        cell.config_entry, cell.conf, cell.traffic = None, conf, traffic
        cell.arch = load_arch(root, conf)
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
        return _load(self.root / "bench" / "metrics" / f"{metric}.py",
                     f"bench_metric_{metric.replace('.', '_')}")
