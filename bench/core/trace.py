"""Reduction of a profiler trace to intervals, shares and sums.

The pure functions (``union_length``, ``gaps``, ``overlap``,
``attribute_gaps``, ``top_ops``, ``match_modules``) work on plain
``(start, end, ...)`` tuples in nanoseconds, so the tests can check them
on small hand-made traces.  ``TraceView`` reads a JAX profiler
``.xplane.pb`` with nothing but ``jax.profiler.ProfileData``: the
device's op and module events, and the host spans the benchmark wrote
with ``jax.profiler.TraceAnnotation``.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

#: host spans the benchmark writes around its calls into the program
WINDOW = "bench.window"
DISPATCH = "exec.dispatch"
READBACK = "exec.readback"
SLEEP = "loop.sleep"
PLAN = "inst.plan"
COMMIT = "inst.commit"
HOST_SPANS = (DISPATCH, READBACK, SLEEP, PLAN, COMMIT)


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, non-overlapping union of the intervals."""
    out: List[List[float]] = []
    for s, e in sorted((a, b) for a, b in intervals if b > a):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def union_length(intervals: Sequence[Interval], lo: float = None,
                 hi: float = None) -> float:
    """Time covered by at least one interval (within [lo, hi])."""
    if lo is not None:
        intervals = clip(intervals, lo, hi)
    return sum(e - s for s, e in merge(intervals))


def gaps(intervals: Sequence[Interval], lo: float, hi: float):
    """The uncovered stretches of [lo, hi]."""
    out, t = [], lo
    for s, e in merge(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def attribute_gaps(idle: Sequence[Interval],
                   spans: Sequence[Tuple[float, float, str]],
                   top: int = 10):
    """The ``top`` longest idle stretches, each named by the host span
    that covers most of it (``host.other`` where none does):
    ``[[name, seconds], ...]``, longest first."""
    spans = sorted(spans)
    starts = [s for s, _, _ in spans]
    out = []
    for g in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
        cover: Dict[str, float] = {}
        # spans starting before the gap ends; long ones may start early
        for s, e, n in spans[:bisect.bisect_right(starts, g[1])]:
            ov = overlap(g, (s, e))
            if ov > 0:
                cover[n] = cover.get(n, 0.0) + ov
        name = max(cover, key=cover.get) if cover else "host.other"
        out.append([name, (g[1] - g[0]) * 1e-9])
    return out


def op_name(hlo_text: str) -> str:
    """The op's own name from the trace's HLO text: ``%fusion.57 = bf16[..]
    fusion(..)`` gives ``fusion.57``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def self_times(events: Sequence[Tuple[float, float, str]]):
    """Each op's time less the time of the ops nested in it (a ``while``
    op spans its body's ops on the same line): ``[(name, self ns)]``."""
    out = []
    stack: List[List] = []           # [end, name, self time]
    for s, e, n in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while stack and stack[-1][0] <= s:
            _, name, t = stack.pop()
            out.append((name, t))
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, n, e - s])
    out.extend((name, t) for _, name, t in stack)
    return out


def top_ops(events: Sequence[Tuple[float, float, str]], top: int = 10):
    """Device self time by op name, most first: ``[[name, seconds]]``."""
    tot: Dict[str, float] = {}
    for n, t in self_times(events):
        tot[n] = tot.get(n, 0.0) + t
    return [[n, t * 1e-9] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:top]]


def match_modules(modules: Sequence[Tuple[float, float, str]],
                  dispatches: Sequence[Tuple[float, str, object]]):
    """Pair each device execution of a program with the host dispatch
    that launched it.  One host thread dispatches every tracked program
    and the device runs them in that order, so the executions seen are
    a run of consecutive dispatches, shifted by the executions whose
    dispatch came before the trace (or whose dispatch is seen but whose
    execution falls after it).  Take the shift under which the most
    pairs agree, where every pair has the same program and no
    execution starts before its dispatch.  ``modules``: (start, end,
    program); ``dispatches``: (time, program, payload).  Returns
    ``[(start, end, payload)]``."""
    E = sorted(modules)
    D = sorted(dispatches, key=lambda d: d[0])
    best: List[Tuple[int, int]] = []
    for k in range(-len(E), len(D) + 1):
        pairs = [(i, i + k) for i in range(len(E)) if 0 <= i + k < len(D)]
        if len(pairs) <= len(best):
            continue
        if all(E[i][2] == D[j][1] and E[i][0] >= D[j][0] for i, j in pairs):
            best = pairs
    return [(E[i][0], E[i][1], D[j][2]) for i, j in best]


class TraceView:
    """One profiler trace, read once."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData
        self.path = path
        pd = ProfileData.from_file(path)
        self.ops: List[Tuple[float, float, str]] = []
        self.modules: List[Tuple[float, float, str]] = []
        self.spans: List[Tuple[float, float, str]] = []
        self.window: Optional[Interval] = None
        #: (start, seq) of each dispatch span, for pairing with plans
        self.dispatch_starts: List[Tuple[float, int]] = []
        self.device_planes: List[str] = []
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                self.device_planes.append(plane.name)
                for line in plane.lines:
                    target = {"XLA Ops": self.ops,
                              "XLA Modules": self.modules}.get(line.name)
                    if target is None:
                        continue
                    for ev in line.events:
                        target.append((ev.start_ns,
                                       ev.start_ns + ev.duration_ns,
                                       op_name(ev.name)))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name == WINDOW:
                            self.window = (ev.start_ns,
                                           ev.start_ns + ev.duration_ns)
                        elif ev.name in HOST_SPANS:
                            self.spans.append(
                                (ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name))
                            if ev.name == DISPATCH:
                                seq = dict(ev.stats).get("seq")
                                if seq is not None:
                                    self.dispatch_starts.append(
                                        (ev.start_ns, int(seq)))
        self.ops.sort()
        self._op_starts = [s for s, _, _ in self.ops]

    @staticmethod
    def find(log_dir: str) -> str:
        paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        return max(paths, key=os.path.getmtime)

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds of the window in which some op ran on the device."""
        return union_length([(s, e) for s, e, _ in self.ops],
                            *self.window) * 1e-9

    def idle_gaps(self, top: int = 10):
        """The longest idle gaps, each named by the benchmark's host span
        over most of it."""
        idle = gaps([(s, e) for s, e, _ in self.ops], *self.window)
        return attribute_gaps(idle, self.spans, top)

    def ops_between(self, lo: float, hi: float):
        """Device ops that start and end within [lo, hi]."""
        i = bisect.bisect_left(self._op_starts, lo)
        j = bisect.bisect_right(self._op_starts, hi)
        return [op for op in self.ops[i:j] if op[1] <= hi]

    def ops_in_window(self):
        return self.ops_between(*self.window)
