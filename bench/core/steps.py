"""Device executions of the served step programs, each paired with the
plan the benchmark saw dispatched, for the per-layer readers.

The executor jits its fused mixed prefill+decode step as
``_mixed_fused`` and its K-step decode horizon as ``_horizon_paged``;
the device trace names their executions after those functions
(``jit__mixed_fused(<hash>)``).  The paged kernels appear as custom-call
ops named after their jitted wrappers (``_paged_decode.12``); an
architecture module that runs other kernels names their prefixes in
its own ``KERNELS``.
"""
from __future__ import annotations

from . import flops, trace as T

PROGRAMS = {"mixed": "_mixed_fused", "horizon": "_horizon_paged"}
#: the kernels' ops are named after the jitted wrappers that call them;
#: the default for an architecture module without ``KERNELS``
KERNELS = {"paged_decode": "_paged_decode", "paged_prefill": "_paged_prefill"}


def program_of(module_name: str):
    for kind, fn in PROGRAMS.items():
        if fn in module_name:
            return kind
    return None


def matched(ctx):
    """[(start, end, plan info)] for the step executions in the trace
    window, computed once per run."""
    if getattr(ctx, "_matched", None) is None:
        tv, probe = ctx.trace, ctx.probe
        mods = [(s, e, program_of(n)) for s, e, n in tv.modules
                if program_of(n)]
        disp = [(t, probe.dispatches[seq].kind, probe.dispatches[seq])
                for t, seq in tv.dispatch_starts]
        lo, hi = tv.window
        ctx._matched = [(s, e, info) for s, e, info
                        in T.match_modules(mods, disp)
                        if s >= lo and e <= hi]
    return ctx._matched


def kernel_time(ctx, start, end, kernel: str) -> float:
    """Device seconds of one kernel's ops inside one execution."""
    pat = getattr(ctx.arch, "KERNELS", KERNELS)[kernel]
    return sum(e - s for s, e, n in ctx.trace.ops_between(start, end)
               if n.startswith(pat)) * 1e-9


def roofline_share(ctx, kernel: str):
    """Least time over measured time, in percent, for one kernel."""
    least = spent = 0.0
    for s, e, info in matched(ctx):
        if info.kernel != kernel:
            continue
        t = kernel_time(ctx, s, e, kernel)
        if t <= 0:
            continue
        spent += t
        for call in info.calls:
            fl = sum(ctx.arch.attn_flops(ctx.conf, a, b) for a, b in call)
            by = sum(ctx.arch.attn_bytes(ctx.conf, a, b) for a, b in call)
            least += flops.roofline_seconds(fl, by, ctx.peaks)
    return 100.0 * least / spent if spent > 0 else None
