"""Share of the traced window in which no op ran on the device:
1 - (union of device op intervals) / window."""
LAYER = "device: TPU v5e"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "output_tok_s"


def read(ctx):
    tv = ctx.trace
    if not tv.ops or tv.window_s() <= 0:
        return None
    return 100.0 * (1.0 - tv.busy_s() / tv.window_s())
