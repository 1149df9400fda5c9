"""Paged decode kernel's share of its roofline: the least time its calls
need (per fused step, the larger of attention FLOPs over the bf16 peak
and the bytes attention moves over HBM bandwidth, both as the
architecture module counts them from each row's context length)
over the kernel's summed device time in the traced window."""
from bench.core import steps

LAYER = "kernels: Pallas paged attention"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(ctx):
    return steps.roofline_share(ctx, "paged_decode")
