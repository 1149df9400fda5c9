"""Whole-step model FLOP utilization: the model FLOPs of the served
steps executed in the traced window (projections, MLP and attention
over each row's context, one row of logits per chunk or token; counted
from the plans, not from padded shapes) over window x the chip's bf16
peak."""
from bench.core import flops, steps

LAYER = "model step: engine/engine.py"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(ctx):
    done = steps.matched(ctx)
    if not done:
        return None
    fl = sum(flops.step_model_flops(ctx.conf, info.rows)
             for _, _, info in done)
    return 100.0 * fl / (ctx.trace.window_s() * ctx.peaks["bf16_flops"])
