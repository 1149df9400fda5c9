"""Slow-CI regression gate over the engine benchmark trajectory.

Compares a fresh ``benchmarks/out/engine_bench.json`` against the
committed baseline in ``benchmarks/baselines/engine_bench.json`` and
fails (exit 1) when

  * any variant's decode steps/s drops more than ``REPRO_BENCH_TOL``
    (default 20%) below the baseline, or
  * the K-step decode-horizon speedup ``horizon_decode_x`` falls below
    the 1.5x acceptance floor.

Absolute tokens/s numbers vary with the runner, so the tolerance is
deliberately loose — this gate catches trajectory regressions (a path
getting structurally slower), not machine jitter.  Regenerate the
baseline with::

    PYTHONPATH=src:. python benchmarks/engine_bench.py
    cp benchmarks/out/engine_bench.json benchmarks/baselines/

With ``--kv`` (or ``--kv-only``) it additionally re-checks the
multi-tier KV pressure bench's recorded acceptance floors from
``benchmarks/out/kv_pressure.json`` — int8 effective capacity, the
spill tier's TTFT win over drop-and-recompute, and the tier stack's
goodput gain.

With ``--frontend`` (or ``--frontend-only``) it re-checks the HTTP/SSE
front-end smoke bench (``benchmarks/out/frontend_bench.json``): the
socket-level streamed tokens/s floor and the per-token wire-overhead
ceiling.

Usage:  python benchmarks/check_regression.py [--fresh path] [--baseline path]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
HORIZON_FLOOR = 1.5


def check(fresh_path: str, baseline_path: str, tol: float) -> int:
    with open(baseline_path) as f:
        base = json.load(f)
    with open(fresh_path) as f:
        fresh = json.load(f)
    failures = []
    for name, b in base["tokens_per_s"].items():
        fv = fresh["tokens_per_s"].get(name)
        if fv is None:
            failures.append(f"variant {name!r} missing from fresh run")
            continue
        floor = (1.0 - tol) * b["decode_steps_per_s"]
        got = fv["decode_steps_per_s"]
        status = "ok" if got >= floor else "REGRESSION"
        print(f"{name:>12}: decode {got:9.1f} steps/s "
              f"(baseline {b['decode_steps_per_s']:.1f}, "
              f"floor {floor:.1f}) {status}")
        if got < floor:
            failures.append(
                f"{name}: decode {got:.1f} < floor {floor:.1f} "
                f"(baseline {b['decode_steps_per_s']:.1f}, tol {tol:.0%})")
    hx = fresh["speedup"].get("horizon_decode_x", 0.0)
    print(f"{'horizon_x':>12}: {hx:.2f} (floor {HORIZON_FLOOR})")
    if hx < HORIZON_FLOOR:
        failures.append(
            f"horizon_decode_x {hx:.2f} < acceptance floor {HORIZON_FLOOR}")
    if failures:
        print("\nFAIL:\n  " + "\n  ".join(failures))
        return 1
    print("\nOK: no decode regression vs baseline")
    return 0


#: multi-tier KV acceptance floors re-checked from the recorded JSON
#: (the sim is seed-deterministic, so these reproduce across machines)
KV_CAPACITY_FLOOR = 1.8


def check_kv_pressure(path: str) -> int:
    """Gate over benchmarks/out/kv_pressure.json: the int8 tier must
    keep its effective-capacity floor, spill must beat
    drop-and-recompute on mean/p99 TTFT, and the tier stack must win
    goodput under the eviction-forcing pool."""
    with open(path) as f:
        res = json.load(f)
    s = res["summary"]
    checks = [
        ("int8_capacity_ratio", res["int8_capacity_ratio"],
         KV_CAPACITY_FLOOR),
        ("spill_mean_ttft_reduction", s["spill_mean_ttft_reduction"], 0.0),
        ("spill_p99_ttft_reduction", s["spill_p99_ttft_reduction"], 0.0),
        ("tiered_goodput_gain", s["tiered_goodput_gain"], 1.0),
    ]
    failures = []
    for name, got, floor in checks:
        status = "ok" if got > floor else "REGRESSION"
        print(f"{name:>26}: {got:.3f} (floor {floor}) {status}")
        if got <= floor:
            failures.append(f"{name} {got:.3f} <= floor {floor}")
    if failures:
        print("\nFAIL:\n  " + "\n  ".join(failures))
        return 1
    print("\nOK: multi-tier KV floors hold")
    return 0


def check_chaos(path: str) -> int:
    """Gate over benchmarks/out/chaos_bench.json: under the fixed fault
    schedule, recovery-on must strictly beat fail-stop goodput, and the
    schedule must actually have bitten (fail-stop failed requests) —
    otherwise the bench is measuring nothing."""
    with open(path) as f:
        res = json.load(f)
    s = res["summary"]
    failures = []
    gain = s["recovery_goodput_gain"]
    status = "ok" if gain > 1.0 else "REGRESSION"
    print(f"{'recovery_goodput_gain':>26}: {gain:.3f} (floor 1.0) {status}")
    if gain <= 1.0:
        failures.append(f"recovery_goodput_gain {gain:.3f} <= 1.0")
    n_failed = s["failstop_failed"]
    status = "ok" if n_failed > 0 else "REGRESSION"
    print(f"{'failstop_failed':>26}: {n_failed} (floor 1) {status}")
    if n_failed <= 0:
        failures.append("the fault schedule never failed a fail-stop "
                        "request — the bench lost its signal")
    wg = s["warm_goodput_gain"]
    status = "ok" if wg >= 1.0 else "REGRESSION"
    print(f"{'warm_goodput_gain':>26}: {wg:.3f} (floor 1.0) {status}")
    if wg < 1.0:
        failures.append(f"warm_goodput_gain {wg:.3f} < 1.0 — warm "
                        "recovery lost goodput vs cold recompute")
    if failures:
        print("\nFAIL:\n  " + "\n  ".join(failures))
        return 1
    print("\nOK: chaos recovery floors hold")
    return 0


def check_frontend(path: str) -> int:
    """Gate over benchmarks/out/frontend_bench.json: the socket-level
    smoke run must clear its recorded streamed-rate floor and keep the
    per-token wire overhead (engine event -> SSE frame on the socket)
    under its ceiling.  Catches string work leaking back into the token
    hot path or a blocking writer, not runner jitter."""
    with open(path) as f:
        res = json.load(f)
    acc = res["acceptance"]
    tok_s = res["streamed_tokens_per_s"]
    wire_p95 = (res.get("wire") or {}).get("p95_ms")
    failures = []
    status = "ok" if tok_s >= acc["tokens_per_s_floor"] else "REGRESSION"
    print(f"{'streamed_tok_s':>26}: {tok_s:.2f} "
          f"(floor {acc['tokens_per_s_floor']}) {status}")
    if tok_s < acc["tokens_per_s_floor"]:
        failures.append(f"streamed tokens/s {tok_s:.2f} < floor "
                        f"{acc['tokens_per_s_floor']}")
    if wire_p95 is None:
        failures.append("no wire spans recorded — the streaming path "
                        "never reported to telemetry")
    else:
        status = ("ok" if wire_p95 <= acc["wire_p95_ms_ceil"]
                  else "REGRESSION")
        print(f"{'wire_p95_ms':>26}: {wire_p95:.2f} "
              f"(ceiling {acc['wire_p95_ms_ceil']}) {status}")
        if wire_p95 > acc["wire_p95_ms_ceil"]:
            failures.append(f"wire p95 {wire_p95:.2f}ms > ceiling "
                            f"{acc['wire_p95_ms_ceil']}ms")
    if failures:
        print("\nFAIL:\n  " + "\n  ".join(failures))
        return 1
    print("\nOK: front-end streaming floors hold")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fresh",
                    default=os.path.join(HERE, "out", "engine_bench.json"))
    ap.add_argument("--baseline",
                    default=os.path.join(HERE, "baselines",
                                         "engine_bench.json"))
    ap.add_argument("--tol", type=float,
                    default=float(os.environ.get("REPRO_BENCH_TOL", "0.20")))
    ap.add_argument("--kv", nargs="?", const=os.path.join(
        HERE, "out", "kv_pressure.json"),
        help="also gate the multi-tier KV pressure bench JSON "
             "(skips the engine check when given alone with --kv-only)")
    ap.add_argument("--kv-only", action="store_true",
                    help="gate only the KV pressure JSON")
    ap.add_argument("--frontend", nargs="?", const=os.path.join(
        HERE, "out", "frontend_bench.json"),
        help="also gate the HTTP/SSE front-end smoke bench JSON")
    ap.add_argument("--frontend-only", action="store_true",
                    help="gate only the front-end smoke JSON")
    ap.add_argument("--chaos", nargs="?", const=os.path.join(
        HERE, "out", "chaos_bench.json"),
        help="also gate the fault-injection chaos bench JSON")
    ap.add_argument("--chaos-only", action="store_true",
                    help="gate only the chaos bench JSON")
    args = ap.parse_args()
    rc = 0
    if not (args.kv_only or args.frontend_only or args.chaos_only):
        rc |= check(args.fresh, args.baseline, args.tol)
    if args.kv or args.kv_only:
        rc |= check_kv_pressure(args.kv or os.path.join(
            HERE, "out", "kv_pressure.json"))
    if args.frontend or args.frontend_only:
        rc |= check_frontend(args.frontend or os.path.join(
            HERE, "out", "frontend_bench.json"))
    if args.chaos or args.chaos_only:
        rc |= check_chaos(args.chaos or os.path.join(
            HERE, "out", "chaos_bench.json"))
    sys.exit(rc)


if __name__ == "__main__":
    main()
