"""The architecture seam: a configuration names its module under
``bench/arch/`` by ``"arch"``, and none is assumed; a new architecture
comes in by new files and entries alone; and the Qwen2 module gives the
weights, reference logits and counts pinned in ``qwen2_pins.json``."""
import hashlib
import json
import shutil
import time
from pathlib import Path

import numpy as np
import pytest

import _arch
import _paths  # noqa: F401
from bench.core import flops
from bench.core.manifest import ROOT, Cell, load_arch

PINS = json.loads((_arch.HERE / "qwen2_pins.json").read_text())


def pinned_conf(name):
    c = PINS["confs"][name]
    return c if isinstance(c, dict) else json.loads((ROOT / c).read_text())


def tree_sha256(tree):
    import jax
    h = hashlib.sha256()
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(a)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}|"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(PINS["pins"]))
def test_qwen2_weights_and_reference_logits_are_pinned(name):
    pytest.importorskip("jax")
    from bench.core import reference, weights
    conf = pinned_conf(name)
    arch, pin = load_arch(ROOT, conf), PINS["pins"][name]
    assert tree_sha256(weights.all_params(arch, conf, PINS["seed"])) == \
        pin["params_sha256"]
    logits = reference.served_logits(
        arch, conf, PINS["seed"], [(PINS["prompt"], PINS["served"])])[0]
    assert list(logits.shape) == pin["logits_shape"]
    assert hashlib.sha256(np.ascontiguousarray(logits, np.float32)
                          .tobytes()).hexdigest() == pin["logits_sha256"]


def test_qwen2_counts_are_pinned():
    c = PINS["counts"]
    conf = json.loads((ROOT / c["conf"]).read_text())
    arch = load_arch(ROOT, conf)
    rows = [tuple(r) for r in c["rows"]]
    assert flops.step_model_flops(arch, conf, rows) == c["step_model_flops"]
    assert [arch.attn_flops(conf, a, b) for a, b in rows] == c["attn_flops"]
    assert [arch.attn_bytes(conf, a, b) for a, b in rows] == c["attn_bytes"]


def test_a_configuration_without_arch_names_the_key():
    conf = {k: v for k, v in _arch.tiny("qwen2").items() if k != "arch"}
    with pytest.raises(KeyError, match="'arch'"):
        Cell.from_parts(ROOT, {"name": "tiny", "chips": 1}, conf, {}, [], [])


def test_an_unknown_arch_is_an_error():
    conf = dict(_arch.tiny("qwen2"), arch="no_such_arch")
    with pytest.raises(FileNotFoundError, match="no_such_arch"):
        load_arch(ROOT, conf)


def test_a_new_architecture_comes_in_by_new_files_only(tmp_path):
    """A checkout's copy of ``bench/`` plus a new architecture module, a
    configuration that names it, a mix, and entries in ``BENCHMARK.json``:
    ``Cell`` finds the module by the name alone and a whole run on the
    CPU comes out ``correct``."""
    pytest.importorskip("jax")
    from _tiny import SEED, TRAFFIC
    from bench.core import harness
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__"))
    new = root / "bench" / "arch" / "qwen2_copy.py"
    shutil.copy(root / "bench" / "arch" / "qwen2.py", new)
    conf = dict(_arch.tiny("qwen2"), name="tiny-copy", arch="qwen2_copy")
    (root / "bench" / "configs" / "tiny-copy.json").write_text(
        json.dumps(conf))
    (root / "bench" / "traffic" / "tiny-mix.json").write_text(
        json.dumps(TRAFFIC))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["configs"].append(
        {"name": "tiny-copy", "source": conf["source"],
         "file": "bench/configs/tiny-copy.json", "reduced": [],
         "why": "a copy of the Qwen2 module under a new name"})
    manifest["workloads"].append(
        {"name": "tiny-copy.tiny-mix", "config": "tiny-copy",
         "traffic": "tiny-mix", "chips": 1, "why": "a new architecture"})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    cell = Cell(root, "tiny-copy.tiny-mix")
    assert Path(cell.arch.__file__) == new.resolve()
    result = harness.run(cell, SEED, 4.0, False, t_start=time.monotonic(),
                         require_tpu=False, compile_cache=False)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0
