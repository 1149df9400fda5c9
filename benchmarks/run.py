"""Benchmark harness entry point: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows (see benchmarks/common.py).

  PYTHONPATH=src python -m benchmarks.run [--only fig4,table2]
"""
import argparse
import importlib
import sys
import traceback

MODULES = [
    "table2_slo_matrix",     # Table 2 / Figs 1-2 (Observation 1)
    "fig3_chunk_breakdown",  # Fig 3
    "fig4_interference",     # Fig 4 (Observation 2)
    "fig56_latency_configs", # Figs 5-6
    "fig7_ttft_breakdown",   # Fig 7 (Observation 3)
    "fig8_prefill_capacity", # Fig 8
    "fig1516_goodput",       # Figs 15-16 (headline C4)
    "fig17_latency_reduction",  # Fig 17 (C5)
    "fig18_breakdown",       # Fig 18 (C6)
    "fig19_overhead",        # Fig 19 (C7)
    "prefix_cache_bench",    # shared-prefix KV cache vs. no-cache baseline
    "controller_bench",      # online slider controller vs. static/offline
    "kv_pressure_bench",     # multi-tier KV under a constrained pool
    "chaos_bench",           # goodput under injected faults vs fail-stop
    "frontend_bench",        # HTTP/SSE front-end socket-level smoke
    "kernel_bench",          # kernels microbench
    "roofline_report",       # dry-run roofline table
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    failed = []
    for m in MODULES:
        if only and m not in only and not any(m.startswith(o) for o in only):
            continue
        try:
            mod = importlib.import_module(f"benchmarks.{m}")
            mod.run()
        except Exception as e:
            failed.append(m)
            print(f"{m}.ERROR,0,{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
