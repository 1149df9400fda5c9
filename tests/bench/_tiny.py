"""A whole run of the harness on the CPU at a small size, past its look
for a chip, with the timed path broken underneath where a test asks."""
import json
import time

import _paths  # noqa: F401
from bench.core import harness
from bench.core.faults import exchange_left_out
from bench.core.manifest import ROOT, Cell

SEED = 2 ** 32 + 77
#: short prompts and outputs, closed loop: every request finishes and
#: the P-heavy instances hand KV to the D-heavy ones
TRAFFIC = {"prompt": {"mu": 3.6, "sigma": 0.4, "lo": 16, "hi": 64},
           "output": {"mu": 2.9, "sigma": 0.2, "lo": 12, "hi": 24},
           "loop": {"kind": "closed", "concurrency": 6},
           "ramp_s": 6.0, "drain_cap_s": 30, "sizes_seed": 0,
           "slo": {"ttft_s": 5.0, "tpot_s": 1.0}}


def tiny_cell(policy="taichi"):
    conf = json.loads((ROOT / "tests" / "bench" / "tiny.json").read_text())
    conf["serving"]["policy"] = policy
    e2e = [{"name": n, "unit": u} for n, u in
           [("ttft_p90_ms", "ms"), ("tpot_p90_ms", "ms"),
            ("output_tok_s", "tokens/s"), ("setup_s", "s")]]
    return Cell.from_parts(ROOT, {"name": "tiny", "chips": 1}, conf,
                           TRAFFIC, e2e, [])


def run(fault=None, control=False):
    calls = []
    hook = None if fault is None else (lambda e: fault(e, calls))
    # under disaggregation every request's KV moves to a decode instance
    policy = "disaggregation" if fault is exchange_left_out else "taichi"
    result = harness.run(tiny_cell(policy), SEED, 4.0, False,
                         t_start=time.monotonic(), require_tpu=False,
                         fault=hook, compile_cache=False, control=control)
    return result, calls


