"""Online serving loop: continuous ingestion, streaming, adaptation.

``Cluster.run`` replays a pre-materialized request list and returns when
the heap drains — fine for goodput sweeps, useless for serving.
``ServingLoop`` drives the same event core *incrementally*:

* **open-loop ingestion** — arrivals come from an iterator (e.g.
  ``PhaseDriftSpec.iter_requests``) and are submitted one ahead of the
  event horizon, so the trace is never materialized and the workload can
  drift (or be generated live) while the loop runs;
* **streaming** — every emitted token fires per-request and global
  callbacks (``Instance.token_sink``), and each submitted request gets a
  ``RequestHandle`` future that resolves at finish/rejection;
* **telemetry** — token/finish/reject events feed a
  ``TelemetryWindow`` (windowed attainment, goodput, gauges), with
  periodic snapshots accumulated in a ``MetricsLog``;
* **adaptation** — an attached ``SliderController`` observes windowed
  headroom at epoch boundaries and retunes chunk sizes or stages
  drain-and-flip role changes through the cluster's migration machinery.

The loop is executor-agnostic: with ``SimExecutor`` it is a
deterministic virtual-clock simulation; with ``JaxExecutor`` the same
schedule computes real tokens (``--engine live``), optionally paced to
wall time by ``WallClock``.  On a ``WallClock`` the device times the
steps it runs: each in-flight device step is committed when the device
has finished it, at that wall time, and the event clock follows the
wall (``Cluster.commit_device``); steps the executor returns as
``ImmediateStep`` keep their cost-model COMMIT times.
"""
from __future__ import annotations

import dataclasses
import queue as _queue
import threading
from typing import Callable, Dict, Iterable, Iterator, List, Optional

from repro.core.cluster import ARRIVAL, COMMIT, Cluster
from repro.core.instance import (HEALTH_OK, HEALTH_QUARANTINED, Instance)
from repro.core.latency import SLO, RunStats
from repro.engine.request import Request, State
from repro.frontend.admission import AdmissionConfig, AdmissionQueue
from repro.serving.clock import VirtualClock, WallClock
from repro.serving.faults import FaultInjector
from repro.serving.metrics import MetricsLog, TelemetryWindow
from repro.serving.tracing import (LOOP_PACE, NO_SPAN, PH_ADMISSION,
                                   PH_ROUTING, TraceConfig, Tracer)

_DONE_STATES = (State.FINISHED, State.REJECTED, State.CANCELLED,
                State.FAILED)


@dataclasses.dataclass
class WatchdogConfig:
    """Stall/heartbeat detection and probation-based re-admission.

    ``heartbeat_timeout`` is EVENT time: an instance whose dispatched
    step runs this far past its cost-model deadline (``step_deadline``)
    is quarantined — injected stalls and real slowdowns both trip it.
    ``stall_timeout`` is WALL time (live executors only): the oldest
    in-flight ``PendingStep`` still not ready this long past the modeled
    end quarantines the instance instead of blocking the loop on
    ``resolve()``.  Quarantined instances re-admit after ``probation``
    seconds, doubling per repeat offense up to ``max_probation``."""
    heartbeat_timeout: float = 2.0
    stall_timeout: float = 2.0
    probation: float = 5.0
    probation_backoff: float = 2.0
    max_probation: float = 60.0
    check_every: float = 0.25


class RequestHandle:
    """Future for one submitted request: resolves when the request
    finishes (or is rejected/cancelled); streams tokens as they are
    emitted."""

    def __init__(self, req: Request,
                 on_token: Optional[Callable] = None):
        self.req = req
        self.tokens: List[tuple] = []        # (time, token_id | None)
        self._on_token = on_token
        #: resolve notification (network front-end: triggers the final
        #: response frames) — called exactly once, from the loop thread
        self.on_done: Optional[Callable[[Request], None]] = None
        self._resolved = False

    @property
    def done(self) -> bool:
        return self.req.state in _DONE_STATES

    @property
    def rejected(self) -> bool:
        return self.req.state == State.REJECTED

    @property
    def cancelled(self) -> bool:
        return self.req.state == State.CANCELLED

    @property
    def failed(self) -> bool:
        return self.req.state == State.FAILED

    def result(self) -> Request:
        if not self.done:
            raise RuntimeError(
                f"request {self.req.rid} still {self.req.state.value}; "
                "drive the loop further")
        return self.req

    def _emit(self, t: float, tok: Optional[int]):
        self.tokens.append((t, tok))
        if self._on_token is not None:
            self._on_token(self.req, t, tok)

    def _resolve(self):
        if not self._resolved:
            self._resolved = True
            if self.on_done is not None:
                self.on_done(self.req)


@dataclasses.dataclass
class SubmitMsg:
    """One externally-submitted request crossing the thread boundary
    into the loop (the HTTP gateway produces these).  ``receipt`` is
    the wall/clock time the connection actually delivered the request
    — arrival truth for TTFT and queue-wait accounting."""
    req: Request
    priority: Optional[str] = None
    receipt: Optional[float] = None
    on_token: Optional[Callable] = None
    reply: Optional[Callable[["RequestHandle"], None]] = None


@dataclasses.dataclass
class AbortMsg:
    """Client-disconnect propagation: the gateway enqueues one of these
    when an SSE connection drops; the loop aborts the request in the
    engine and frees its blocks.  A no-op if the request already
    resolved (normal completion also closes the connection)."""
    rid: int


class ServingLoop:
    def __init__(self, cluster: Cluster, slo: SLO,
                 arrivals: Optional[Iterable[Request]] = None,
                 clock: Optional[VirtualClock] = None,
                 controller=None, window: float = 10.0,
                 on_token: Optional[Callable] = None,
                 snapshot_every: Optional[float] = None,
                 pace: bool = False, steal: bool = True,
                 admission: Optional[AdmissionConfig] = None,
                 watchdog: Optional[WatchdogConfig] = None,
                 faults: Optional[FaultInjector] = None,
                 tracing: Optional[TraceConfig] = None):
        self.cluster = cluster
        self.slo = slo
        self.tracer: Optional[Tracer] = None
        self.clock = clock or VirtualClock()
        self.telemetry = TelemetryWindow(slo, window=window)
        # rates divide by seconds OBSERVED: the loop's start time is the
        # window's origin (0.0 in simulation — unchanged spans there; a
        # wall clock that starts mid-epoch no longer inflates the
        # denominator of its first snapshots)
        self.telemetry.anchor(cluster.now)
        self.log = MetricsLog()
        self.controller = controller
        self._arrivals: Optional[Iterator[Request]] = (
            iter(arrivals) if arrivals is not None else None)
        self._handles: Dict[int, RequestHandle] = {}
        self.requests: List[Request] = []     # every request ever seen
        self._global_on_token = on_token
        self._snapshot_every = snapshot_every
        self._next_snapshot = snapshot_every
        self._pace = pace
        self._steal = steal
        # router-side admission queue (None = legacy immediate routing)
        self.admission: Optional[AdmissionQueue] = (
            AdmissionQueue(admission) if admission is not None else None)
        self._released: set = set()     # rids admitted past the queue
        self._inflight = 0
        self.shed_rejections = 0
        self.cancelled_count = 0
        # serving-mode ingress: externally-submitted requests cross the
        # thread boundary here (created lazily by ``serve``/``ingress``)
        self._ingress: Optional[_queue.Queue] = None
        self._serving = False
        self._refusing = False       # graceful drain: cancel stragglers
        # fault tolerance: watchdog (stall/heartbeat detection +
        # probation re-admission) and optional fault injection
        self.watchdog = watchdog
        self._next_watchdog = 0.0
        self._probation_until: Dict[int, float] = {}
        self._strikes: Dict[int, int] = {}
        self.aborted_count = 0
        self.failed_count = 0
        # request-lifecycle tracing (off by default: every call site
        # guards on ``tracer is None``, so an untraced run takes the
        # exact pre-tracing path)
        if tracing is not None:
            self.tracer = (tracing if isinstance(tracing, Tracer)
                           else Tracer(tracing))
            self.tracer.clock = self._wall
            self.tracer.leads = cluster.commit_model_lead
            cluster.tracer = self.tracer
            for inst in cluster.instances:
                inst.tracer = self.tracer
        for inst in cluster.instances:
            inst.token_sink = self._token_sink
        cluster.on_finish = self._on_finish
        cluster.on_reject = self._on_reject
        cluster.on_failed = self._on_failed
        cluster.on_abort = self._on_abort
        if faults is not None:
            cluster.attach_faults(faults)
        if controller is not None:
            controller.bind(self)

    @property
    def clock(self):
        return self._clock

    @clock.setter
    def clock(self, clock):
        """Swapping the clock rebinds the tracer's: it reads a
        ``WallClock`` at each hook and keeps event time otherwise.  On a
        ``WallClock`` the cluster commits device steps when the device
        has finished them."""
        self._clock = clock
        self._wall = clock if isinstance(clock, WallClock) else None
        self.cluster.device_clock = self._wall is not None
        if self.tracer is not None:
            self.tracer.clock = self._wall

    # ------------------------------------------------------------------
    # ingestion
    # ------------------------------------------------------------------
    def submit(self, req: Request,
               on_token: Optional[Callable] = None,
               priority: Optional[str] = None,
               receipt: Optional[float] = None) -> RequestHandle:
        """Submit one request (external callers; the arrival iterator
        and the network ingress feed through here too).  Returns its
        streaming future.

        Arrival stamping: a ``receipt`` (actual connection-receipt
        time, or the workload generator's intended arrival) is
        PRESERVED as ``req.arrival`` even when the loop is running
        behind — the heap event is clamped to now so events never land
        behind the clock, but TTFT and queue-wait measure from when
        the request really arrived, not from when the loop got around
        to drawing it.  Without a receipt (bare external submission,
        arrival defaulting to 0.0) the request arrives NOW."""
        if receipt is not None:
            req.arrival = receipt
        else:
            req.arrival = max(req.arrival, self.cluster.now)
        if priority is not None:
            req.priority = priority
        handle = RequestHandle(req, on_token)
        self._handles[req.rid] = handle
        self.requests.append(req)
        if self.tracer is not None:
            self.tracer.begin(req, req.arrival,
                              PH_ADMISSION if self.admission is not None
                              else PH_ROUTING)
        if self.admission is not None:
            self._enqueue_admission(req, priority)
        else:
            self.cluster.submit(req, t=max(req.arrival, self.cluster.now))
        return handle

    def _pump_arrival(self) -> bool:
        """Keep exactly one not-yet-processed arrival in the event heap
        (arrivals are nondecreasing in time, so one look-ahead preserves
        event order while staying incremental)."""
        if self._arrivals is None:
            return False
        req = next(self._arrivals, None)
        if req is None:
            self._arrivals = None
            return False
        # the generator's timestamp is the arrival truth — the pump's
        # draw time must not rewrite it (wall-clock pacing: a loop
        # running behind draws bursts late, and clamping arrivals to
        # the draw would silently shrink measured queue wait and TTFT)
        self.submit(req, receipt=req.arrival)
        return True

    # ------------------------------------------------------------------
    # router-side admission queue
    # ------------------------------------------------------------------
    def _enqueue_admission(self, req: Request, priority: Optional[str]):
        q = self.admission
        ok, displaced = q.push(req, q.resolve_class(priority),
                               max(req.arrival, self.cluster.now))
        for entry in displaced:
            self._finish_unserved(entry.req, State.REJECTED)
        if not ok:
            self._finish_unserved(req, State.REJECTED)
        self._release_admission()

    def _release_admission(self):
        """Move queued work into the cluster while the released
        population is under the in-flight cap — the admission queue
        absorbs the burst, the instance queues stay near their
        sustainable depth."""
        q = self.admission
        if q is None:
            return
        now = self.cluster.now
        while len(q) and self._inflight < q.cfg.max_inflight:
            entry = q.pop(now)
            if entry is None:
                # every queued class is over its token budget for the
                # current window — nothing releasable this tick
                break
            self._inflight += 1
            self._released.add(entry.req.rid)
            self.telemetry.on_queue_wait(
                now, max(now - entry.enq_time, 0.0))
            if self.tracer is not None:
                self.tracer.phase(entry.req.rid, now, PH_ROUTING,
                                  cls=entry.cls)
            self.cluster.submit(entry.req,
                                t=max(entry.req.arrival, now))

    def _finish_unserved(self, req: Request, state: State):
        """Resolve a request that will never reach the cluster
        (displaced/shed -> REJECTED, drained at shutdown ->
        CANCELLED)."""
        now = self.cluster.now
        req.state = state
        req.finish_time = now
        if state == State.REJECTED:
            self.shed_rejections += 1
            self.telemetry.on_reject(req, now)
        else:
            self.cancelled_count += 1
            self.telemetry.on_cancel(req, now)
        if self.tracer is not None:
            self.tracer.finish(req, now)
        handle = self._handles.get(req.rid)
        if handle is not None:
            handle._resolve()

    def shed_admission(self, fraction: Optional[float] = None) -> int:
        """Admission control as an actuator (SliderController, both
        dimensions starved): early-reject queued work from the lowest
        priority classes up.  Returns how many were shed."""
        if self.admission is None:
            return 0
        entries = self.admission.shed(fraction)
        for e in entries:
            self._finish_unserved(e.req, State.REJECTED)
        if entries:
            self.log.record_event(self.cluster.now, "shed", {
                "count": len(entries),
                "classes": sorted({e.cls for e in entries})})
            if self.tracer is not None:
                self.tracer.global_event(self.cluster.now, "shed",
                                         count=len(entries))
        return len(entries)

    def cancel_queued(self) -> int:
        """Graceful drain: everything still in the admission queue
        resolves CANCELLED (in-flight work keeps running to
        completion)."""
        if self.admission is None:
            return 0
        entries = self.admission.drain()
        for e in entries:
            self._finish_unserved(e.req, State.CANCELLED)
        return len(entries)

    # ------------------------------------------------------------------
    # serving-mode ingress (thread boundary to the network front-end)
    # ------------------------------------------------------------------
    @property
    def ingress(self) -> _queue.Queue:
        """Thread-safe submission queue for ``SubmitMsg`` items; the
        loop drains it every cycle while ``serve`` runs."""
        if self._ingress is None:
            self._ingress = _queue.Queue()
        return self._ingress

    def receipt_now(self) -> float:
        """Arrival stamp for an externally-received request: wall time
        under a ``WallClock`` (the connection's actual receipt), the
        event clock otherwise."""
        if isinstance(self.clock, WallClock):
            return self.clock.now
        return self.cluster.now

    def _ingress_pending(self) -> bool:
        return self._ingress is not None and not self._ingress.empty()

    def _submit_msg(self, msg: SubmitMsg):
        if self._refusing:
            # graceful drain already began: never start new work
            handle = RequestHandle(msg.req, msg.on_token)
            self._handles[msg.req.rid] = handle
            self.requests.append(msg.req)
            self._finish_unserved(msg.req, State.CANCELLED)
            if msg.reply is not None:
                msg.reply(handle)
            return
        handle = self.submit(msg.req, on_token=msg.on_token,
                             priority=msg.priority, receipt=msg.receipt)
        if msg.reply is not None:
            msg.reply(handle)

    def _ingress_msg(self, msg):
        if isinstance(msg, AbortMsg):
            self.abort(msg.rid)
        else:
            self._submit_msg(msg)

    def _drain_ingress(self):
        if self._ingress is None:
            return
        while True:
            try:
                msg = self._ingress.get_nowait()
            except _queue.Empty:
                return
            self._ingress_msg(msg)

    # ------------------------------------------------------------------
    # event plumbing
    # ------------------------------------------------------------------
    def _token_sink(self, req: Request, t: float, tok: Optional[int]):
        self.telemetry.on_token(req, t)
        if self.tracer is not None:
            self.tracer.first_token(req.rid, t)
        handle = self._handles.get(req.rid)
        if handle is not None:
            handle._emit(t, tok)
        if self._global_on_token is not None:
            self._global_on_token(req, t, tok)

    def _on_finish(self, req: Request, t: float):
        self.telemetry.on_finish(req, t)
        self._retire(req)

    def _on_reject(self, req: Request, t: float):
        self.telemetry.on_reject(req, t)
        self._retire(req)

    def _on_failed(self, req: Request, t: float):
        self.telemetry.on_failed(req, t)
        self.failed_count += 1
        self._retire(req)

    def _on_abort(self, req: Request, t: float):
        self.telemetry.on_abort(req, t)
        self.aborted_count += 1
        self._retire(req)

    # ------------------------------------------------------------------
    # client-initiated abort (disconnect propagation)
    # ------------------------------------------------------------------
    def abort(self, rid: int) -> bool:
        """Abort one submitted request: pulled straight out of the
        admission queue if unreleased, otherwise handed to the cluster's
        safe-boundary abort machinery (it frees KV blocks the moment the
        request is not mid-flight).  Idempotent; True once the request
        is terminally resolved or the abort is staged."""
        handle = self._handles.get(rid)
        if handle is None:
            return False
        req = handle.req
        if req.state in _DONE_STATES:
            return True
        if self.admission is not None and rid not in self._released:
            entry = self.admission.remove(rid)
            if entry is not None:
                now = self.cluster.now
                req.state = State.CANCELLED
                req.finish_reason = "abort"
                req.finish_time = now
                self.aborted_count += 1
                self.telemetry.on_abort(req, now)
                if self.tracer is not None:
                    self.tracer.finish(req, now)
                handle._resolve()
                return True
        return self.cluster.abort_request(req)

    def _retire(self, req: Request):
        """A released request left the system: free its admission slot
        (pulling the next queued request in) and resolve its handle."""
        if self.tracer is not None:
            self.tracer.finish(req, req.finish_time
                               if req.finish_time is not None
                               else self.cluster.now)
        if req.rid in self._released:
            self._released.discard(req.rid)
            self._inflight -= 1
            self._release_admission()
        handle = self._handles.get(req.rid)
        if handle is not None:
            handle._resolve()

    # ------------------------------------------------------------------
    # control surface (used by SliderController; callable directly)
    # ------------------------------------------------------------------
    def flip_role(self, inst: Instance, itype: str,
                  chunk_size: int) -> bool:
        staged = self.cluster.request_role_flip(inst, itype, chunk_size)
        if staged:
            self.log.record_event(self.cluster.now, "role_flip", {
                "iid": inst.iid, "to": itype, "chunk": chunk_size})
        return staged

    def set_chunks(self, itype: str, chunk_size: int) -> int:
        """Retune the chunk-size slider for every ``itype`` instance
        (instantaneous — chunk size is a per-iteration budget, so no
        drain is needed).  Returns how many instances changed."""
        n = 0
        for inst in self.cluster.instances:
            if inst.itype == itype and not inst.draining \
                    and inst.chunk_size != chunk_size:
                inst.chunk_size = chunk_size
                n += 1
                if chunk_size <= 0 and inst.prefill_queue:
                    # a pure-decode instance can never drain its prefill
                    # queue — hand the queued (not-yet-admitted) work
                    # back to the router with full ARRIVAL semantics
                    # (early rejection included)
                    requeue = [r for r in inst.prefill_queue
                               if not inst.allocator.holds(r.rid)]
                    for r in requeue:
                        inst.prefill_queue.remove(r)
                        self.cluster.reroute(r)
        if n:
            self.log.record_event(self.cluster.now, "set_chunk", {
                "itype": itype, "chunk": chunk_size, "instances": n})
        return n

    # ------------------------------------------------------------------
    # watchdog: stall/heartbeat detection + probation re-admission
    # ------------------------------------------------------------------
    def _start_probation(self, inst: Instance, now: float) -> float:
        """Schedule the quarantined instance's re-admission, doubling
        the probation per repeat offense up to the cap."""
        wd = self.watchdog
        strikes = self._strikes.get(inst.iid, 0)
        self._strikes[inst.iid] = strikes + 1
        probation = min(wd.probation * wd.probation_backoff ** strikes,
                        wd.max_probation)
        until = now + probation
        self._probation_until[inst.iid] = until
        return probation

    def _quarantine(self, inst: Instance, now: float, why: str):
        self.cluster.quarantine_instance(inst, now, reason=why)
        probation = self._start_probation(inst, now)
        self.log.record_event(now, "quarantine", {
            "iid": inst.iid, "why": why,
            "probation_s": round(probation, 3)})

    def _watchdog_check(self, now: float):
        """Periodic health sweep: quarantine instances whose dispatched
        step ran past its cost-model deadline by ``heartbeat_timeout``
        (missed heartbeat — stalls and slowdowns), and re-admit
        quarantined instances whose probation has elapsed.  Instances
        the CLUSTER quarantined on its own (executor exceptions) get a
        probation clock here too — the watchdog owns all re-admission."""
        wd = self.watchdog
        if wd is None or now < self._next_watchdog:
            return
        self._next_watchdog = now + wd.check_every
        for inst in self.cluster.instances:
            if inst.health == HEALTH_OK:
                if now > inst.step_deadline + wd.heartbeat_timeout:
                    self._quarantine(inst, now, "heartbeat")
                elif inst.overrun > wd.heartbeat_timeout:
                    # sync-executor heartbeat: dispatch+commit happen in
                    # one atomic event, so a stall never leaves a live
                    # step_deadline behind for the sweep above to catch.
                    # The instance records how far each dispatch ran
                    # past its cost-model duration; an overrun past the
                    # timeout is the same missed heartbeat, observed
                    # after the fact.
                    inst.overrun = 0.0
                    self._quarantine(inst, now, "overrun")
            elif inst.health == HEALTH_QUARANTINED:
                until = self._probation_until.get(inst.iid)
                if until is None:          # cluster-initiated quarantine
                    until = now + self._start_probation(inst, now)
                if now >= until and self.cluster.recover_instance(inst,
                                                                  now):
                    self._probation_until.pop(inst.iid, None)
                    self.log.record_event(now, "readmit",
                                          {"iid": inst.iid})
                    if self.tracer is not None:
                        self.tracer.global_event(now, "readmit",
                                                 iid=inst.iid)

    def _stall_check(self) -> bool:
        """Live-path stall guard: when the oldest in-flight device step
        STILL is not device-ready ``stall_timeout`` wall seconds past
        its modeled end, quarantine the instance instead of letting
        ``PendingStep.resolve`` block the loop forever.  (Under a
        ``WallClock`` every device step is in flight there; a COMMIT
        event carries a step that is always ready.)  Returns True when
        it intervened (the caller re-peeks: the step is now stale and
        the evacuated work has been rerouted)."""
        wd = self.watchdog
        if wd is None or not self.cluster.in_flight:
            return False
        inst, pending = self.cluster.in_flight[0]
        if inst.pending_step() is not pending or pending.ready():
            return False               # done, or discarded: committed next
        if self.clock.now < inst.step_deadline + wd.stall_timeout:
            return False
        self._quarantine(inst, self.cluster.now, "stall")
        return True

    def _steal_prefill(self):
        """Online-runtime load repair: an idle prefill-capable instance
        pulls queued-but-unadmitted prefill work from the deepest peer
        queue.  Routing decisions pile up behind a slow configuration
        (e.g. the queue an instance accumulated before a slider move);
        stealing lets spare capacity drain the backlog instead of
        leaving it pinned to the original placement."""
        insts = [i for i in self.cluster.instances if i.schedulable]
        if len(insts) < 2:
            return
        idle = [i for i in insts
                if i.chunk_size > 0 and not i.prefill_queue
                and not i.decoding and not i.pending_decode]
        if not idle:
            return
        # one queue-depth scan per call, not per thief — this runs after
        # every event, so it must be cheap when there is nothing to do
        depths = {i.iid: i.queued_prefill_tokens() for i in insts}
        for thief in idle:
            victim = max(insts, key=lambda i: depths[i.iid])
            if depths[victim.iid] == 0 or len(victim.prefill_queue) < 2:
                return                 # no queue anywhere worth raiding
            # steal from the tail: the head may be mid-chunk/admitted
            req = victim.prefill_queue[-1]
            if victim.allocator.holds(req.rid):
                continue
            victim.prefill_queue.pop()
            depths[victim.iid] -= req.prefill_remaining
            depths[thief.iid] = req.prefill_remaining
            thief.enqueue_prefill(req)
            self.cluster._schedule_iter(thief, self.cluster.now)

    # ------------------------------------------------------------------
    # pacing: wait for the next event, a finished device step or ingress
    # ------------------------------------------------------------------
    def _pace_until(self, t: float):
        """The virtual clock's wait: harvest every in-flight executor
        step the device has already finished, so its COMMIT at ``t``
        (the cost model's end of the step) does not block, then jump to
        ``t``.  A wall clock waits in ``_await_device`` instead, on both
        paths: there a device step is committed as soon as the device
        has finished it, and a modelled COMMIT carries a step that is
        always ready."""
        for inst in self.cluster.instances:
            p = inst.pending_step()
            if p is not None and not p.resolved and p.ready():
                p.prefetch()
        self.clock.sleep_until(t)

    #: wall-clock slice between readiness polls while device steps are
    #: in flight: a finished step waits at most this long to be committed
    DEVICE_POLL = 0.001

    def _await_device(self, t: Optional[float]):
        """A wall clock's wait, device-timed or modelled.  Returns on
        whichever comes first: an in-flight step is done (the device
        finished it, or it was discarded), the heap's next event falls
        due at ``t`` (None: the heap is empty), a network ingress
        submission, or the oldest step's stall deadline (with a
        watchdog, so ``_stall_check`` can act).  Polls every
        ``DEVICE_POLL`` inside a ``taichi.loop.pace`` span while steps
        are in flight or ingress may arrive, else sleeps to ``t``."""
        cl = self.cluster
        end = float("inf") if t is None else t
        if cl.in_flight and self.watchdog is not None:
            end = min(end, cl.in_flight[0][0].step_deadline
                      + self.watchdog.stall_timeout)
        if cl.ready_in_flight() or self._ingress_pending():
            return
        if not cl.in_flight and self._ingress is None:
            self.clock.sleep_until(end)    # nothing can cut the wait short
            return
        with (NO_SPAN if self.tracer is None else self.tracer.step(
                LOOP_PACE, **({} if t is None else {"event_t": t}))):
            while True:
                now = self.clock.now
                if now >= end:
                    return
                self.clock.sleep_until(min(end, now + self.DEVICE_POLL))
                if cl.ready_in_flight() or self._ingress_pending():
                    return

    def _commit_ready(self) -> int:
        """Commit every in-flight device step the device has finished,
        in dispatch order, each at the wall time it is committed.
        Returns how many were taken."""
        done = self.cluster.ready_in_flight()
        for inst, pending in done:
            self.cluster.commit_device(inst, pending, self.clock.now)
        return len(done)

    # ------------------------------------------------------------------
    # the loop
    # ------------------------------------------------------------------
    def run(self, until: Optional[float] = None,
            max_steps: Optional[int] = None) -> int:
        """Drive events until the system drains (arrivals exhausted and
        all work finished), ``until`` virtual seconds, or ``max_steps``
        events.  Returns the number of events processed; re-entrant —
        call again to continue."""
        steps = 0
        if self._arrivals is not None and not self.requests:
            self._pump_arrival()
        while max_steps is None or steps < max_steps:
            self._drain_ingress()
            self._watchdog_check(self.cluster.now)
            # device-timed: device steps set the clock, and the event
            # clock follows the wall (heap times are lower bounds)
            device = self._wall is not None and self.cluster.device_timed
            if device:
                n = self._commit_ready()
                if n:
                    steps += n
                    self._after_event(COMMIT)
                    continue
                if self._stall_check():
                    continue          # quarantined a stalled step
            t = self.cluster.peek_time()
            if t is None:
                if device and self.cluster.in_flight:
                    self._await_device(None)
                    continue
                if not self._pump_arrival():
                    break
                continue
            if until is not None and t > until:
                break
            if self._pace:
                if self._wall is None:
                    self._pace_until(t)
                elif self.clock.now < t:
                    self._await_device(t)
                    continue          # re-check commits and ingress
            stepped = self.cluster.step(self.clock.now if device else None)
            if stepped is None:
                continue
            steps += 1
            self._after_event(stepped[1])
        return steps

    def _after_event(self, kind: int):
        """Per-event follow-up: keep one arrival pumped (or repair
        prefill placement), then the controller's epoch and the
        periodic snapshot."""
        if kind == ARRIVAL:
            self._pump_arrival()
        elif self._steal:
            self._steal_prefill()
        now = self.cluster.now
        if self.controller is not None:
            self.controller.maybe_epoch(now)
        if self._snapshot_every is not None \
                and now >= self._next_snapshot:
            self.log.record(self.snapshot(now))
            self._next_snapshot = (
                now - now % self._snapshot_every + self._snapshot_every)

    def snapshot(self, now: Optional[float] = None) -> dict:
        now = self.cluster.now if now is None else now
        snap = self.telemetry.snapshot(now, self.cluster.instances,
                                       admission=self.admission)
        # fault section only when something actually fired — a faults-off
        # run snapshots bit-identically to one without this layer at all
        fc = self.cluster.fault_counters()
        if any(fc.values()):
            snap["faults"] = fc
        if getattr(self.cluster, "recovery", None) is not None:
            snap["recovery"] = self.cluster.recovery_counters()
        if self._wall is not None:
            # how far the event clock (estimator time) runs behind the
            # wall: arrivals wait in the heap until it catches up
            snap["event_clock_lag_s"] = self._wall.now - self.cluster.now
            # which path timed the commits, and how far the device's
            # ends ran past the cost model's
            cl = self.cluster
            snap["device_timed_commits"] = cl.device_timed_commits
            snap["model_timed_commits"] = cl.model_timed_commits
            snap["commit_model_lead_s"] = cl.lead_median()
        return snap

    # ------------------------------------------------------------------
    # serving mode: run until told to stop, blocking on ingress when
    # idle (the network front-end drives this on a dedicated thread)
    # ------------------------------------------------------------------
    #: events per ``run`` slice in serving mode — small enough that a
    #: stop request is noticed promptly even mid-burst
    SERVE_SLICE = 256

    def serve(self, stop: threading.Event, idle_poll: float = 0.02):
        """Drive events indefinitely: drain the ingress every cycle,
        block briefly for new submissions when no work is pending, and
        on ``stop`` perform a graceful drain — stop ingesting (late
        stragglers resolve CANCELLED), resolve everything still queued
        in the admission queue as CANCELLED, and run the in-flight
        population to completion."""
        self._serving = True
        ingress = self.ingress          # materialize before clients race
        try:
            while not stop.is_set():
                self.run(max_steps=self.SERVE_SLICE)
                if self.cluster.peek_time() is None \
                        and not self.cluster.in_flight \
                        and not self._ingress_pending():
                    try:                # idle: wait for the next client
                        self._ingress_msg(ingress.get(timeout=idle_poll))
                    except _queue.Empty:
                        pass
            self._refusing = True
            self._drain_ingress()
            self.cancel_queued()
            self.run()                  # in-flight work finishes, SSE
        finally:                        # streams flush through on_token
            self._serving = False

    # ------------------------------------------------------------------
    def stats(self, qps: float) -> RunStats:
        moves = (self.controller.n_moves if self.controller is not None
                 else 0)
        st = self.cluster.stats(self.requests, self.slo, qps)
        st.slider_moves = moves
        st.early_rejections += self.shed_rejections
        return st
