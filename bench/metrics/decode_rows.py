"""Mean decode rows per executed decode step over the window, counted
from the plans dispatched (a K-step horizon counts K steps of its
rows; steps without decode rows are left out)."""
LAYER = "instance scheduler: core/instance.py"
UNIT = "rows"
SOURCE = "program_counter"
MOVES = "output_tok_s"


def read(ctx):
    w0, w1 = ctx.window
    rows = steps = 0
    for d in ctx.probe.dispatches:
        if d.decode_rows and w0 <= d.t0 <= w1:
            n = d.K if d.kind == "horizon" else 1
            rows += d.decode_rows * n
            steps += n
    return rows / steps if steps else None
