"""Device time per decode-only step: executions of the served step
programs whose plan carries no prefill chunk, over the decode steps
they ran (a fused K-step horizon counts K)."""
from bench.core import steps

LAYER = "executor: engine/engine.py"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(ctx):
    runs = [(e - s, info.K) for s, e, info in steps.matched(ctx)
            if not info.prefill]
    if not runs:
        return None
    return 1e-6 * sum(t for t, _ in runs) / sum(k for _, k in runs)
