"""Request-lifecycle tracing and SLO attribution.

``TelemetryWindow`` answers "how is the fleet doing *right now*";
nothing answers "where did THIS request's latency go" — which is the
question the paper's whole latency-shifting argument turns on (queueing
vs prefill vs transfer vs decode interference, DistServe Fig. 4 /
Tropical §5).  ``Tracer`` records a per-request timeline as a chain of
**phases** plus fine-grained **events**:

phases (contiguous, non-overlapping by construction — each ``phase()``
call closes the current span at the new span's start time):

* ``admission``   — router-side admission-queue wait
* ``routing``     — receipt (or admission release) -> the cluster
                    handles the ARRIVAL: the event-heap wait, which the
                    ``route`` event ends (left out when it took no time)
* ``queue``       — routing -> first prefill chunk dispatched
                    (re-entered after preemption / crash recovery)
* ``prefill``     — first chunk dispatched -> prefill complete
* ``transfer``    — KV/state migration on the wire (incl. retries)
* ``decode_wait`` — landed on the decode instance, awaiting batch slot
* ``decode``      — in the decode batch -> finish (or eject)

events ride on the timeline without breaking it: per-chunk prefill
commits (with cache-hit offset), per-commit decode horizons (with
co-batched prefill interference), transfer retries, preemptions,
recoveries, routing decisions.  Chunk and horizon events name the
``(iid, seq)`` of the executor step that ran them.  Cluster-scoped
happenings (stalls, quarantines, controller actuations) land in a
global event log.

Clocks.  Under a ``VirtualClock`` every stamp is the event time the
hook passes.  Under a ``WallClock`` (live serving) the cluster's event
clock is estimator time and runs behind the wall under load, so each
hook stamps the tracer's clock (the ``WallClock``'s reading, read at
the hook) and keeps the event time beside it as the ``event_t``
attribute.  A request opens at its receipt, which is a wall reading
there, and its first token is stamped where tokens leave the serving
loop.

Step spans time the serving thread's work on each executor step, on
the tracer's clock (the process's monotonic clock under a
``VirtualClock``): ``taichi.step.plan`` (``Instance.build_plan``),
``taichi.step.dispatch`` (the executor's ``step_async``: packing and
launch), ``taichi.step.sync`` (a readback that blocked) and
``taichi.step.commit`` (``Instance.commit_iteration``), each with
``iid`` and the instance's step ``seq``, and ``taichi.loop.pace``, the
live loop's wait for its next event.  Each dispatch under a
``WallClock`` samples the event-clock lag.  While a JAX profiler trace
runs, each step span is also a ``TraceAnnotation`` there, and a
``taichi.clock`` annotation carrying the tracer's reading (``t_ns``),
written at most once a second, lets ``profile_ns`` place the tracer's
stamps on the profiler's timeline.

The tracer is **observational only** — no RNG, no scheduling
influence — so a traced run produces bit-identical request outcomes to
an untraced one (``test_tracing_off_is_bit_identical``), and with
``tracing=None`` every call site short-circuits on ``tracer is None``.

Attribution:

* ``breakdown(rid)`` -> phase -> seconds, summing exactly to the
  request's end-to-end latency (spans share endpoints);
* ``ttft_breakdown(rid)`` clips the timeline at the first token — where
  the TTFT budget went;
* ``violation_report(slo)`` aggregates the per-phase budget of every
  SLO-violating finished request — "where did violated requests lose
  their budget".

Exporters: Chrome-trace/Perfetto JSON (``to_chrome_trace`` /
``dump_chrome`` — load in ui.perfetto.dev), JSONL event log
(``dump_jsonl``), and a Prometheus text renderer over telemetry
snapshots (``prometheus_text``, content-negotiated on the gateway's
``/metrics``).
"""
from __future__ import annotations

import dataclasses
import json
import re
import statistics
import time
from collections import deque
from typing import Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

PH_ADMISSION = "admission"
PH_ROUTING = "routing"
PH_QUEUE = "queue"
PH_PREFILL = "prefill"
PH_TRANSFER = "transfer"
PH_DECODE_WAIT = "decode_wait"
PH_DECODE = "decode"

PHASES = (PH_ADMISSION, PH_ROUTING, PH_QUEUE, PH_PREFILL, PH_TRANSFER,
          PH_DECODE_WAIT, PH_DECODE)

#: step spans, on the tracer's clock and (while a profile runs) in the
#: JAX profiler's trace; one ``taichi.`` prefix keeps them apart from
#: any other annotations in the same trace
STEP_PLAN = "taichi.step.plan"
STEP_DISPATCH = "taichi.step.dispatch"
STEP_SYNC = "taichi.step.sync"
STEP_COMMIT = "taichi.step.commit"
LOOP_PACE = "taichi.loop.pace"
STEP_SPANS = (STEP_PLAN, STEP_DISPATCH, STEP_SYNC, STEP_COMMIT, LOOP_PACE)
#: the profiler annotation that carries the tracer's clock reading
CLOCK_SYNC = "taichi.clock"


@dataclasses.dataclass
class TraceConfig:
    """Tracing knobs.  Constructing one and passing it to
    ``ServingLoop(tracing=...)`` is the ON switch; the default is off
    (no tracer object, every instrumentation site inert)."""
    #: completed traces retained (ring buffer; live requests always kept)
    max_requests: int = 4096
    #: record fine-grained sub-events (chunk/horizon/retry granularity).
    #: Phases are always recorded — they are the attribution substrate.
    events: bool = True
    #: per-request event cap (a 10k-token decode at K=1 would otherwise
    #: log 10k commit events; the counter keeps the truth)
    max_events_per_request: int = 512
    #: cluster-scoped event cap (stalls, quarantines, controller moves)
    max_global_events: int = 8192


class Span:
    __slots__ = ("phase", "t0", "t1", "attrs")

    def __init__(self, phase: str, t0: float,
                 attrs: Optional[dict] = None):
        self.phase = phase
        self.t0 = t0
        self.t1: Optional[float] = None   # open until the next phase
        self.attrs = attrs

    def dur(self) -> float:
        return (self.t1 - self.t0) if self.t1 is not None else 0.0


class RequestTrace:
    __slots__ = ("rid", "t_begin", "t_end", "spans", "events", "state",
                 "finish_reason", "arrival", "first_token_t",
                 "prompt_len", "output_len", "n_recoveries",
                 "events_dropped")

    def __init__(self, rid: int, t_begin: float):
        self.rid = rid
        self.t_begin = t_begin
        self.t_end: Optional[float] = None
        self.spans: List[Span] = []
        self.events: List[tuple] = []     # (t, name, attrs | None)
        self.state: Optional[str] = None
        self.finish_reason: Optional[str] = None
        self.arrival = t_begin
        self.first_token_t: Optional[float] = None
        self.prompt_len = 0
        self.output_len = 0
        self.n_recoveries = 0
        self.events_dropped = 0

    @property
    def done(self) -> bool:
        return self.t_end is not None

    def e2e(self) -> Optional[float]:
        return None if self.t_end is None else self.t_end - self.t_begin


class _NoSpan:
    """What a step-span site enters when tracing is off."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NO_SPAN = _NoSpan()


class StepSpan:
    """One step span: entered around the work it times; ``attrs`` may
    grow inside (the profiler's copy keeps the attributes given at
    entry)."""
    __slots__ = ("tracer", "name", "attrs", "t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self.t0 = 0.0
        self._ann = None

    def __enter__(self):
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self.t0 = self.tracer.now()
        return self

    def __exit__(self, *exc):
        t1 = self.tracer.now()
        self._ann.__exit__(*exc)
        self.tracer.steps.append((self.name, self.t0, t1, self.attrs))
        return False


class Tracer:
    """Low-overhead span recorder, single-writer by design: every hook
    runs on the engine/event thread (exports may run anywhere after the
    run).  ``clock`` is None under a ``VirtualClock`` — stamps are the
    event times the hooks pass — and the ``WallClock`` in live serving,
    read at each hook (``ServingLoop`` keeps it bound)."""

    #: step spans and event-clock lag samples kept (ring buffers)
    STEPS_KEPT = 65536
    LAG_KEPT = 65536
    #: seconds between ``taichi.clock`` annotations
    CLOCK_SYNC_EVERY = 1.0

    def __init__(self, cfg: Optional[TraceConfig] = None):
        self.cfg = cfg or TraceConfig()
        self._live: Dict[int, RequestTrace] = {}
        self._done: Dict[int, RequestTrace] = {}
        self._done_order: deque = deque()
        self.global_events: deque = deque(
            maxlen=self.cfg.max_global_events)
        self.dropped_traces = 0           # evicted past max_requests
        self.clock = None
        #: (name, t0, t1, attrs) per step span, on ``now()``
        self.steps: deque = deque(maxlen=self.STEPS_KEPT)
        #: (tracer clock, event time) at each dispatch under a WallClock
        self.lag: deque = deque(maxlen=self.LAG_KEPT)
        #: (wall time, device end minus modelled end) per device-timed
        #: commit: the cluster's ``commit_model_lead``, which the serving
        #: loop binds here
        self.leads = ()
        self._next_sync = float("-inf")

    # ------------------------------------------------------------------
    # clocks
    # ------------------------------------------------------------------
    def now(self) -> float:
        """The tracer's clock: the bound ``WallClock``, else the
        process's monotonic clock (step spans in simulation)."""
        return self.clock.now if self.clock is not None else time.monotonic()

    def _stamp(self, t: float, attrs: dict, at: Optional[float] = None):
        """The stamp for a hook given event time ``t``: ``t`` itself
        under a VirtualClock, else ``at`` or the clock read now, with
        ``t`` kept in ``attrs`` as ``event_t``."""
        if self.clock is None:
            return t
        attrs["event_t"] = t
        return self.now() if at is None else at

    def step(self, name: str, **attrs) -> StepSpan:
        """A step span to enter around the work it times."""
        return StepSpan(self, name, attrs)

    def on_dispatch(self, t: float) -> float:
        """Stamp one step dispatch at event time ``t``: samples the
        event-clock lag (under a WallClock) and writes the profiler's
        clock-sync annotation when one is due.  Returns the stamp."""
        wall = self.now()
        if self.clock is not None:
            self.lag.append((wall, t))
        if wall >= self._next_sync:
            self._next_sync = wall + self.CLOCK_SYNC_EVERY
            self.clock_sync()
        return wall

    def clock_sync(self):
        """Write a ``taichi.clock`` annotation into the profiler's trace
        (a no-op when no profile runs), carrying this clock's reading
        at its start in integer nanoseconds (``t_ns``)."""
        t_ns = int(self.now() * 1e9)
        with TraceAnnotation(CLOCK_SYNC, t_ns=t_ns):
            pass

    @staticmethod
    def profile_ns(t: float, sync_start_ns: float, sync_t_ns: int) -> float:
        """A tracer stamp ``t`` (seconds) on a profiler trace's timeline,
        given one ``taichi.clock`` event of that trace: its start and
        its ``t_ns``."""
        return sync_start_ns + (t * 1e9 - sync_t_ns)

    # ------------------------------------------------------------------
    # recording
    # ------------------------------------------------------------------
    def begin(self, req, t: float, phase: str = PH_ROUTING):
        """Open a request's trace at its (receipt-stamped) arrival."""
        rid = req.rid
        if rid in self._live or rid in self._done:
            return
        tr = RequestTrace(rid, t)
        tr.prompt_len = getattr(req, "prompt_len", 0)
        tr.spans.append(Span(phase, t))
        self._live[rid] = tr

    def phase(self, rid: int, t: float, name: str, *,
              at: Optional[float] = None, **attrs):
        """Transition to ``name``: closes the current span at the stamp
        and opens the new one there — contiguity by construction.  A
        same-phase transition is a no-op (the original start stands),
        and a ``routing`` span that took no time becomes the new phase.
        ``at``: the wall stamp, for a hook that runs after the moment
        it marks (ignored under a VirtualClock)."""
        tr = self._live.get(rid)
        if tr is None:
            return
        cur = tr.spans[-1]
        if cur.phase == name:
            return
        t = max(self._stamp(t, attrs, at), cur.t0)   # never negative
        if cur.phase == PH_ROUTING and t == cur.t0:
            cur.phase = name
            if attrs:
                cur.attrs = {**(cur.attrs or {}), **attrs}
            return
        cur.t1 = t
        tr.spans.append(Span(name, t, attrs or None))

    def event(self, rid: int, t: float, name: str, *,
              at: Optional[float] = None, **attrs):
        if not self.cfg.events:
            return
        tr = self._live.get(rid)
        if tr is None:
            return
        if len(tr.events) >= self.cfg.max_events_per_request:
            tr.events_dropped += 1
            return
        t = self._stamp(t, attrs, at)
        tr.events.append((t, name, attrs or None))

    def route(self, rid: int, t: float, **attrs):
        """The cluster routed a request: its ``route`` event, which ends
        the ``routing`` phase (one stamp for both)."""
        at = self.now() if self.clock is not None else None
        self.event(rid, t, "route", at=at, **attrs)
        self.phase(rid, t, PH_QUEUE, at=at)

    def global_event(self, t: float, name: str, **attrs):
        if self.cfg.events:
            t = self._stamp(t, attrs)
            self.global_events.append((t, name, attrs or None))

    def first_token(self, rid: int, t: float):
        """A token left the serving loop: stamps the request's first
        (under a WallClock; ``finish`` takes the request's own event
        time otherwise)."""
        tr = self._live.get(rid)
        if tr is not None and tr.first_token_t is None:
            tr.first_token_t = self._stamp(t, {})

    def finish(self, req, t: float):
        """Seal a request's trace at its terminal state.  A request the
        loop refused at the door (graceful drain) may never have begun —
        it still gets a (degenerate) trace, so "every terminal request
        has a trace" holds unconditionally."""
        rid = req.rid
        t = self._stamp(t, {})
        tr = self._live.pop(rid, None)
        if tr is None:
            if rid in self._done:
                return
            t0 = min(getattr(req, "arrival", t) or t, t)
            tr = RequestTrace(rid, t0)
            tr.prompt_len = getattr(req, "prompt_len", 0)
            tr.spans.append(Span(PH_QUEUE, t0))
        last = tr.spans[-1]
        last.t1 = max(t, last.t0)
        tr.t_end = last.t1
        state = getattr(req, "state", None)
        tr.state = getattr(state, "value", state)
        tr.finish_reason = getattr(req, "finish_reason", None)
        tr.arrival = getattr(req, "arrival", tr.t_begin)
        if self.clock is None:
            tr.first_token_t = getattr(req, "first_token_time", None)
        tr.output_len = getattr(req, "output_len", 0)
        tr.n_recoveries = getattr(req, "n_recoveries", 0)
        self._done[rid] = tr
        self._done_order.append(rid)
        while len(self._done_order) > self.cfg.max_requests:
            old = self._done_order.popleft()
            self._done.pop(old, None)
            self.dropped_traces += 1

    # ------------------------------------------------------------------
    # lookup / attribution
    # ------------------------------------------------------------------
    def get(self, rid: int) -> Optional[RequestTrace]:
        return self._done.get(rid) or self._live.get(rid)

    def traces(self) -> Iterator[RequestTrace]:
        yield from self._done.values()
        yield from self._live.values()

    def __len__(self) -> int:
        return len(self._done) + len(self._live)

    def breakdown(self, rid: int,
                  until: Optional[float] = None) -> Optional[Dict[str, float]]:
        """Phase -> seconds for one request.  For a finished request the
        values sum exactly to ``t_end - t_begin`` (spans share their
        endpoints); for a live one the open span is clipped at
        ``until`` (default: its start — i.e. excluded)."""
        tr = self.get(rid)
        if tr is None:
            return None
        out: Dict[str, float] = {}
        for sp in tr.spans:
            t1 = sp.t1 if sp.t1 is not None else max(until or sp.t0, sp.t0)
            out[sp.phase] = out.get(sp.phase, 0.0) + (t1 - sp.t0)
        return out

    @staticmethod
    def _clipped(tr: RequestTrace, lo: float,
                 hi: float) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for sp in tr.spans:
            t1 = sp.t1 if sp.t1 is not None else sp.t0
            a, b = max(sp.t0, lo), min(t1, hi)
            if b > a:
                out[sp.phase] = out.get(sp.phase, 0.0) + (b - a)
        return out

    def ttft_breakdown(self, rid: int) -> Optional[Dict[str, float]]:
        """Where the TTFT budget went: phase seconds clipped at the
        first token (None before one exists)."""
        tr = self.get(rid)
        if tr is None or tr.first_token_t is None:
            return None
        return self._clipped(tr, tr.t_begin, tr.first_token_t)

    def violation_report(self, slo) -> dict:
        """Aggregate SLO attribution over retained finished traces:
        for TTFT violators, mean per-phase seconds up to the first
        token; for TPOT violators, mean per-phase seconds after it —
        "where did violated requests lose their budget"."""
        ttft_acc: Dict[str, float] = {}
        tpot_acc: Dict[str, float] = {}
        n_fin = n_ttft = n_tpot = 0
        n_ttft_rec = n_tpot_rec = 0
        ttft_excess = 0.0
        for tr in self._done.values():
            if tr.state != "finished" or tr.first_token_t is None:
                continue
            n_fin += 1
            ttft = tr.first_token_t - tr.t_begin
            if ttft > slo.ttft:
                n_ttft += 1
                n_ttft_rec += tr.n_recoveries > 0
                ttft_excess += ttft - slo.ttft
                for ph, s in self._clipped(
                        tr, tr.t_begin, tr.first_token_t).items():
                    ttft_acc[ph] = ttft_acc.get(ph, 0.0) + s
            if tr.output_len > 1 and tr.t_end is not None:
                tpot = (tr.t_end - tr.first_token_t) / (tr.output_len - 1)
                if tpot > slo.tpot:
                    n_tpot += 1
                    n_tpot_rec += tr.n_recoveries > 0
                    for ph, s in self._clipped(
                            tr, tr.first_token_t, tr.t_end).items():
                        tpot_acc[ph] = tpot_acc.get(ph, 0.0) + s

        def mean(acc, n):
            return {ph: round(s / n, 6) for ph, s in sorted(acc.items())} \
                if n else {}

        return {
            "finished": n_fin,
            "ttft": {"violations": n_ttft,
                     "budget_s": slo.ttft,
                     "mean_excess_s": round(ttft_excess / n_ttft, 6)
                     if n_ttft else 0.0,
                     "mean_phase_s": mean(ttft_acc, n_ttft),
                     # violators that went through a crash recovery —
                     # separates recovery-dominated violations from
                     # ordinary congestion
                     "recovered_violators": n_ttft_rec},
            "tpot": {"violations": n_tpot,
                     "budget_s": slo.tpot,
                     "mean_phase_s": mean(tpot_acc, n_tpot),
                     "recovered_violators": n_tpot_rec},
        }

    def wait_report(self, lo: float = float("-inf"),
                    hi: float = float("inf")) -> dict:
        """Where requests wait before their first token, on the tracer's
        clock (wall time in live serving).  Over the retained requests
        received in [lo, hi]: the median seconds from receipt to routing
        (``route_wait_s``: admission and routing phases) and from routing
        to the first prefill dispatch (``prefill_queue_s``).  Over the
        dispatches in [lo, hi]: the event-clock lag, wall minus event
        time (``event_clock_lag_s``, and the medians of the first and
        last tenth).  Over the device-timed commits in [lo, hi]: the
        device's end minus the cost model's (``commit_model_lead_s``).
        None where nothing was seen."""
        route, queue = [], []
        for tr in self.traces():
            if not lo <= tr.t_begin <= hi:
                continue
            t_route = None
            for sp in tr.spans:
                if sp.phase == PH_QUEUE and t_route is None:
                    t_route = sp.t0
                    route.append(t_route - tr.t_begin)
                elif sp.phase == PH_PREFILL:
                    if t_route is not None:
                        queue.append(sp.t0 - t_route)
                    break
        lags = [w - t for w, t in self.lag if lo <= w <= hi]
        tenth = max(1, len(lags) // 10)
        leads = [d for w, d in self.leads if lo <= w <= hi]

        def median(xs):
            return statistics.median(xs) if xs else None

        return {"requests": len(route), "route_wait_s": median(route),
                "prefill_queue_s": median(queue), "dispatches": len(lags),
                "event_clock_lag_s": median(lags),
                "lag_first_tenth_s": median(lags[:tenth]),
                "lag_last_tenth_s": median(lags[-tenth:]),
                "device_commits": len(leads),
                "commit_model_lead_s": median(leads)}

    # ------------------------------------------------------------------
    # exporters
    # ------------------------------------------------------------------
    def to_chrome_trace(self) -> dict:
        """Chrome-trace/Perfetto JSON: one row (tid) per request under
        pid 1, cluster-scoped events under pid 2 (one row per
        instance), and in live serving, where they share the requests'
        clock, step spans under pid 3 (one row per instance).  Times in
        microseconds as the format requires."""
        evs: List[dict] = [
            {"ph": "M", "pid": 1, "tid": 0, "name": "process_name",
             "args": {"name": "requests"}},
            {"ph": "M", "pid": 2, "tid": 0, "name": "process_name",
             "args": {"name": "cluster"}},
        ]
        for tr in sorted(self.traces(), key=lambda r: r.rid):
            evs.append({"ph": "M", "pid": 1, "tid": tr.rid,
                        "name": "thread_name",
                        "args": {"name": f"req {tr.rid}"}})
            for sp in tr.spans:
                t1 = sp.t1 if sp.t1 is not None else sp.t0
                ev = {"ph": "X", "pid": 1, "tid": tr.rid, "cat": "request",
                      "name": sp.phase, "ts": round(sp.t0 * 1e6, 3),
                      "dur": round((t1 - sp.t0) * 1e6, 3)}
                if sp.attrs:
                    ev["args"] = sp.attrs
                evs.append(ev)
            for t, name, attrs in tr.events:
                ev = {"ph": "i", "pid": 1, "tid": tr.rid, "cat": "event",
                      "name": name, "ts": round(t * 1e6, 3), "s": "t"}
                if attrs:
                    ev["args"] = attrs
                evs.append(ev)
        for t, name, attrs in self.global_events:
            ev = {"ph": "i", "pid": 2,
                  "tid": (attrs or {}).get("iid", 0), "cat": "cluster",
                  "name": name, "ts": round(t * 1e6, 3), "s": "p"}
            if attrs:
                ev["args"] = attrs
            evs.append(ev)
        if self.clock is not None:
            evs.append({"ph": "M", "pid": 3, "tid": 0,
                        "name": "process_name", "args": {"name": "steps"}})
            for name, t0, t1, attrs in self.steps:
                evs.append({"ph": "X", "pid": 3, "tid": attrs.get("iid", 0),
                            "cat": "step", "name": name,
                            "ts": round(t0 * 1e6, 3),
                            "dur": round((t1 - t0) * 1e6, 3),
                            "args": attrs})
        return {"traceEvents": evs, "displayTimeUnit": "ms"}

    def dump_chrome(self, path: str):
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)

    def dump_jsonl(self, path: str):
        """Flat JSONL event log: one ``meta`` line per request, then its
        spans and events; global events, step spans (``step``) and
        event-clock lag samples (``lag``: tracer clock ``t`` and
        ``event_t``) last.  Grep-able and streamable where the Chrome
        JSON is a single document."""
        with open(path, "w") as f:
            for tr in sorted(self.traces(), key=lambda r: r.rid):
                f.write(json.dumps({
                    "kind": "meta", "rid": tr.rid, "state": tr.state,
                    "finish_reason": tr.finish_reason,
                    "t_begin": tr.t_begin, "t_end": tr.t_end,
                    "prompt_len": tr.prompt_len,
                    "output_len": tr.output_len,
                    "first_token_t": tr.first_token_t,
                    "n_recoveries": tr.n_recoveries,
                    "events_dropped": tr.events_dropped}) + "\n")
                for sp in tr.spans:
                    rec = {"kind": "span", "rid": tr.rid,
                           "phase": sp.phase, "t0": sp.t0, "t1": sp.t1}
                    if sp.attrs:
                        rec["attrs"] = sp.attrs
                    f.write(json.dumps(rec) + "\n")
                for t, name, attrs in tr.events:
                    rec = {"kind": "event", "rid": tr.rid,
                           "name": name, "t": t}
                    if attrs:
                        rec["attrs"] = attrs
                    f.write(json.dumps(rec) + "\n")
            for t, name, attrs in self.global_events:
                rec = {"kind": "global", "name": name, "t": t}
                if attrs:
                    rec["attrs"] = attrs
                f.write(json.dumps(rec) + "\n")
            for name, t0, t1, attrs in self.steps:
                f.write(json.dumps({"kind": "step", "name": name, "t0": t0,
                                    "t1": t1, "attrs": attrs}) + "\n")
            for t, ev_t in self.lag:
                f.write(json.dumps({"kind": "lag", "t": t,
                                    "event_t": ev_t}) + "\n")


# ----------------------------------------------------------------------
# Prometheus text exposition over a telemetry snapshot
# ----------------------------------------------------------------------
_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")


def _metric_name(*parts: str) -> str:
    return _NAME_RE.sub("_", "_".join(p.strip("_") for p in parts if p))


def _samples_from(prefix: str, obj, labels: dict, out: list):
    """Flatten a snapshot subtree into (name, labels, value) samples.
    Strings are skipped (Prometheus samples are numeric); bools become
    0/1; ``None`` (windowed stat with no evidence) is skipped."""
    if isinstance(obj, bool):
        out.append((prefix, labels, int(obj)))
    elif isinstance(obj, (int, float)):
        out.append((prefix, labels, obj))
    elif isinstance(obj, dict):
        for k, v in obj.items():
            _samples_from(_metric_name(prefix, str(k)), v, labels, out)


def prometheus_text(snap: dict, prefix: str = "taichi") -> str:
    """Render a ``ServingLoop.snapshot()`` dict in Prometheus text
    exposition format (one scrape = one snapshot).  Scalar keys become
    gauges (``*_total`` lifetime counters become counters); the
    per-instance gauge list becomes label-dimensioned series
    (``iid``/``itype``); per-class admission depths get a ``cls``
    label."""
    samples: List[tuple] = []
    for key, val in snap.items():
        if key == "instances":
            continue
        if key == "admission" and isinstance(val, dict):
            for k, v in val.items():
                if k == "depth_by_class" and isinstance(v, dict):
                    for cls, d in v.items():
                        samples.append((_metric_name(prefix,
                                                     "admission_depth"),
                                        {"cls": cls}, d))
                elif k == "released_by_class" and isinstance(v, dict):
                    for cls, d in v.items():
                        samples.append((
                            _metric_name(prefix,
                                         "admission_released_by_class_"
                                         "total"),
                            {"cls": cls}, d))
                else:
                    _samples_from(_metric_name(prefix, "admission", k),
                                  v, {}, samples)
            continue
        _samples_from(_metric_name(prefix, key), val, {}, samples)
    for g in snap.get("instances", ()):
        labels = {"iid": str(g.get("iid")), "itype": str(g.get("itype"))}
        for k, v in g.items():
            if k in ("iid", "itype"):
                continue
            if k == "horizon_hist" and isinstance(v, dict):
                for kk, n in v.items():
                    samples.append((
                        _metric_name(prefix, "instance_horizon_hist"),
                        {**labels, "k": str(kk)}, n))
                continue
            if isinstance(v, str):
                # state-style gauges (health) export as labeled 1
                samples.append((_metric_name(prefix, "instance", k),
                                {**labels, k: v}, 1))
                continue
            _samples_from(_metric_name(prefix, "instance", k), v,
                          labels, samples)
    by_name: Dict[str, list] = {}
    for name, labels, value in samples:
        by_name.setdefault(name, []).append((labels, value))
    lines: List[str] = []
    for name in sorted(by_name):
        kind = "counter" if name.endswith("_total") else "gauge"
        lines.append(f"# HELP {name} repro serving telemetry")
        lines.append(f"# TYPE {name} {kind}")
        for labels, value in by_name[name]:
            if isinstance(value, bool):
                value = int(value)
            lbl = ""
            if labels:
                lbl = "{" + ",".join(
                    f'{k}="{v}"' for k, v in sorted(labels.items())) + "}"
            lines.append(f"{name}{lbl} {value}")
    return "\n".join(lines) + "\n"
