"""``BENCHMARK.json`` and the files it names: allowed characters, the
readers' declarations, and what every cell reports."""
import json
import re
import subprocess
import sys

import pytest

import _paths  # noqa: F401
from bench.core import reference
from bench.core.manifest import ROOT, Cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_top_level_keys_and_paths():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for p in MANIFEST["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    for word in MANIFEST["command"][1:]:
        assert any(word.startswith(p + "/") for p in MANIFEST["paths"])


def test_names_units_and_lines():
    metrics = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    names = [m["name"] for m in metrics] + CELLS + \
        [c["name"] for c in MANIFEST["configs"]]
    assert len(set(names)) == len(names)
    for n in names + [w["traffic"] for w in MANIFEST["workloads"]]:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for c in MANIFEST["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        for k in ("why", "source"):
            assert 1 <= len(c[k]) <= 200 and "\n" not in c[k]
    for w in MANIFEST["workloads"]:
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)


def test_every_metric_has_its_reader_and_the_reader_agrees():
    cell = Cell(ROOT, CELLS[0])
    for m in MANIFEST["per_layer"]:
        r = cell.reader(m["name"])
        assert (r.LAYER, r.UNIT, r.SOURCE, r.MOVES) == \
            (m["layer"], m["unit"], m["source"], m["moves"]), m["name"]
        assert "workloads" in m and set(m["workloads"]) <= set(CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_each_cell_reports_what_its_metrics_move(name):
    cell = Cell(ROOT, name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    assert all(m["moves"] in e2e for m in cell.per_layer)
    conf = cell.conf
    assert conf["name"] == cell.entry["config"]
    assert conf["correct"] and \
        set(conf["correct"]) <= set(reference.GAP_NUMBERS)
    assert cell.traffic["loop"]["kind"] in ("open", "closed")


def test_run_exits_nonzero_without_a_tpu():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
