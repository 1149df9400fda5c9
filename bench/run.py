"""Run one benchmark cell on the chip and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell is a ``workloads`` entry of
``BENCHMARK.json``; its configuration, traffic mix and per-layer readers
are found by name under ``bench/``.  The run builds the served path,
warms it with the mix's ramp, measures ``--seconds`` at the client, and
checks what it served against the plain reference.  Earlier lines (on
standard error) give facts of the run and, last, each number compared
beside its limit; the last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(``--trace 1`` adds ``breakdown``) and ``compared``.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result; it never falls back to the CPU.
Everything that touches JAX runs under ``__main__``: the tokenizer
workers are spawned processes that import this file again and must
never open the chip.
"""
import time

T_START = time.monotonic()

import argparse                                        # noqa: E402
import json                                            # noqa: E402
import sys                                             # noqa: E402
from pathlib import Path                               # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from bench.core import harness
    from bench.core.manifest import Cell
    try:
        cell = Cell(ROOT, args.workload)
        result = harness.run(cell, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    except (harness.RunFailed, FileNotFoundError, KeyError) as e:
        print(f"bench: run failed: {e!r}", file=sys.stderr, flush=True)
        return 1
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
