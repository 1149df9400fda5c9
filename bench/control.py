"""The control, and the faults, read at the cell's own size, seed after
seed, in one process.

    python bench/control.py --workload <cell> --seconds <s> --seeds <n,...>
        [--ramp <s>]
        [--fault token_altered|state_unchanged|half_batch|exchange_left_out]

For each seed it makes one run of the cell (a short window at the
cell's own load; ``--ramp`` shortens the mix's ramp, which only warms
the path) and prints a JSON line.  Without ``--fault`` the int8
control (the reference computed in int8, ``reference.py``) is put in
the program's place: at each position of the served sample it puts a
token first, and the run judges those tokens as it judges served ones,
so ``correct`` has to come out false.  The line carries the control's
gap numbers (``compared``) and the program's on the same sample
(``program``).  With ``--fault`` the named fault (``core/faults.py``)
is planted under the timed path and the run is judged as usual.  The
limits in the configuration file are set between the largest program
reading and the smallest control reading (``PERF.md``).  The
benchmark's own runs never run this.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--ramp", type=float, default=None)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args()
    from bench.core import faults, harness
    from bench.core.manifest import Cell
    cell = Cell(ROOT, args.workload)
    if args.ramp is not None:
        cell.traffic = dict(cell.traffic, ramp_s=args.ramp)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        calls = []
        hook = None
        if args.fault:
            plant = faults.ALL[args.fault]
            hook = lambda engine: plant(engine, calls)  # noqa: E731
        try:
            r = harness.run(cell, seed, args.seconds, False, t_start=t0,
                            fault=hook, control=not args.fault)
        except harness.NoChip as e:
            print(f"control: {e}", file=sys.stderr, flush=True)
            return 2
        print(json.dumps({
            "seed": seed, "fault": args.fault, "fault_reached": len(calls),
            "correct": r["correct"], "attempted": r["attempted"],
            "program": r.get("program_gap_numbers"),
            "metrics": r["metrics"], "compared": r["compared"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
