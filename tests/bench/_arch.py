"""Every architecture module under ``bench/arch/``, with its tiny
configuration: the one ``tests/bench/tiny*.json`` that names it.  A new
module is checked by the contract tests once its tiny configuration is
beside this file."""
import json

import _paths  # noqa: F401
from bench.core.manifest import ROOT

HERE = ROOT / "tests" / "bench"
ARCHS = sorted(p.stem for p in (ROOT / "bench" / "arch").glob("*.py"))


def tiny(arch: str) -> dict:
    """The tiny configuration that names ``arch``."""
    confs = [json.loads(p.read_text())
             for p in sorted(HERE.glob("tiny*.json"))]
    mine = [c for c in confs if c.get("arch") == arch]
    assert len(mine) == 1, \
        f"want one tests/bench/tiny*.json with arch {arch!r}"
    return mine[0]
