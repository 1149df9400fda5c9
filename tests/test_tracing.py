"""Request-lifecycle tracing: span-partition invariants under chaos,
tracing-off bit-identicality, SLO attribution arithmetic, the Chrome
trace / JSONL / Prometheus exporters, the controller decision audit
trail, the sync-path watchdog heartbeat, and telemetry snapshot
consistency under concurrent readers."""
import itertools
import time
import json
import threading

import pytest

from repro.core.latency import SLO
from repro.core.policies import Sliders
from repro.engine.request import Request, State, TERMINAL_STATES
from repro.serving import (ControllerConfig, ServingLoop, SliderController,
                           TelemetryWindow, TraceConfig, Tracer,
                           WatchdogConfig, prometheus_text)
from repro.serving.faults import STALL, Fault, FaultInjector
from repro.serving.tracing import PHASES
from repro.sim.simulator import ServingConfig, build_cluster
from repro.sim.workload import DRIFT, SHAREGPT

BAL = SLO(ttft=1.5, tpot=0.030)
LOOSE = SLO(ttft=10.0, tpot=1.0)


def _mk_loop(policy="taichi", sliders=Sliders(2, 2, 1024, 256),
             blocks=4096, slo=LOOSE, ft=None, async_exec=False, **kw):
    sc = ServingConfig(policy=policy, sliders=sliders, hbm_blocks=blocks)
    cluster = build_cluster(sc, slo, ft=ft, async_exec=async_exec)
    return ServingLoop(cluster, slo, **kw)


def _outcome(loop):
    """Per-request outcome signature for bit-identicality checks."""
    return [(r.rid, r.state.value, r.finish_time, r.output_len,
             r.first_token_time) for r in loop.requests]


# ---------------------------------------------------------------------------
# span partition property (chaos included)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_span_partition_under_chaos(seed):
    """Every terminal request has a trace whose spans form a contiguous,
    non-overlapping partition of [t_begin, t_end] with known phase
    names, and the breakdown sums exactly to end-to-end latency — even
    under a randomized fault schedule (preemption, recompute recovery,
    transfer retries, stalls)."""
    reqs = SHAREGPT.sample_requests(100, 60.0, seed=100 + seed)
    t_end = max(r.arrival for r in reqs)
    inj = FaultInjector.random_schedule(
        seed, [0, 1, 2, 3], t_end=t_end, n_crashes=1, n_stalls=1,
        n_exec_errors=1, stall_duration=0.5, recover_after=0.8,
        transfer_drop_p=0.05)
    loop = _mk_loop(arrivals=iter(reqs), steal=False, faults=inj,
                    async_exec=True, tracing=TraceConfig(),
                    watchdog=WatchdogConfig(heartbeat_timeout=0.4,
                                            probation=0.5,
                                            check_every=0.05))
    loop.run()
    tr = loop.tracer
    terminal = [r for r in loop.requests if r.state in TERMINAL_STATES]
    assert terminal and len(tr) >= len(terminal)
    for r in terminal:
        t = tr.get(r.rid)
        assert t is not None, f"terminal request {r.rid} has no trace"
        assert t.done
        assert t.spans[0].t0 == t.t_begin
        for sp in t.spans:
            assert sp.phase in PHASES
            assert sp.t1 is not None and sp.t1 >= sp.t0
        for a, b in zip(t.spans, t.spans[1:]):
            assert a.t1 == b.t0, "spans must share endpoints"
        assert t.spans[-1].t1 == t.t_end
        bd = tr.breakdown(r.rid)
        assert abs(sum(bd.values()) - t.e2e()) < 1e-6
    # the chaos run actually exercised the interesting paths
    assert sum(inj.fired.values()) >= 1
    names = {n for _, n, _ in tr.global_events}
    assert names, "cluster-scoped events must be recorded under faults"


def test_finished_requests_reach_decode_and_ttft_clips():
    reqs = SHAREGPT.sample_requests(60, 40.0, seed=5)
    loop = _mk_loop(arrivals=iter(reqs), steal=False, slo=BAL,
                    tracing=TraceConfig())
    loop.run()
    tr = loop.tracer
    fin = [r for r in loop.requests if r.state == State.FINISHED]
    assert fin
    for r in fin:
        t = tr.get(r.rid)
        phases = [sp.phase for sp in t.spans]
        assert phases[0] == "queue"
        assert "prefill" in phases and "decode" in phases
        tb = tr.ttft_breakdown(r.rid)
        assert abs(sum(tb.values())
                   - (r.first_token_time - t.t_begin)) < 1e-6
        # prefill chunk events carry the cache-hit offset
        chunk = [a for tt, n, a in t.events if n == "prefill_chunk"]
        assert chunk and all("cached" in a and "take" in a for a in chunk)


def test_tracing_off_is_bit_identical():
    """tracing=None (the default) must not perturb a single outcome —
    the tracer is observational only."""
    outs = []
    for tracing in (None, TraceConfig()):
        reqs = SHAREGPT.sample_requests(80, 50.0, seed=9)
        loop = _mk_loop(arrivals=iter(reqs), steal=False, slo=BAL,
                        tracing=tracing)
        loop.run()
        outs.append(_outcome(loop))
    ids0 = [o[1:] for o in outs[0]]
    ids1 = [o[1:] for o in outs[1]]
    assert ids0 == ids1
    assert any(o[1] == "finished" for o in outs[0])


def test_trace_eviction_bound_and_degenerate_finish():
    reqs = SHAREGPT.sample_requests(40, 60.0, seed=3)
    loop = _mk_loop(arrivals=iter(reqs), steal=False,
                    tracing=TraceConfig(max_requests=8))
    loop.run()
    tr = loop.tracer
    assert len(tr._done) <= 8
    assert tr.dropped_traces >= len(reqs) - 8
    # a request finish()ed without ever begin()ing still gets a trace
    ghost = Request(prompt_len=4, max_new_tokens=2, arrival=1.0)
    tr.finish(ghost, 2.5)
    g = tr.get(ghost.rid)
    assert g is not None and g.done and g.t_begin == 1.0
    assert abs(sum(tr.breakdown(ghost.rid).values()) - 1.5) < 1e-6


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def _traced_loop(tmp_path=None):
    reqs = SHAREGPT.sample_requests(50, 40.0, seed=7)
    loop = _mk_loop(arrivals=iter(reqs), steal=False, slo=BAL,
                    tracing=TraceConfig())
    loop.run()
    return loop


def test_chrome_trace_schema(tmp_path):
    loop = _traced_loop()
    doc = loop.tracer.to_chrome_trace()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    assert any(e["ph"] == "M" and e["args"]["name"] == "requests"
               for e in evs)
    for e in evs:
        assert e["ph"] in ("M", "X", "i")
        assert {"pid", "tid", "name"} <= set(e)
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
            assert e["name"] in PHASES
        if e["ph"] == "i":
            assert "ts" in e
    # file dump round-trips as JSON
    out = tmp_path / "trace.json"
    loop.tracer.dump_chrome(str(out))
    assert json.loads(out.read_text())["traceEvents"]


def test_jsonl_export_parses(tmp_path):
    loop = _traced_loop()
    out = tmp_path / "trace.jsonl"
    loop.tracer.dump_jsonl(str(out))
    kinds = set()
    rids = set()
    for line in out.read_text().splitlines():
        rec = json.loads(line)
        kinds.add(rec["kind"])
        if "rid" in rec:
            rids.add(rec["rid"])
    assert {"meta", "span"} <= kinds
    fin = {r.rid for r in loop.requests if r.state == State.FINISHED}
    assert fin <= rids


def test_violation_report_attributes_budget():
    # SLO so tight every finished request violates TTFT and TPOT
    tight = SLO(ttft=1e-6, tpot=1e-9)
    reqs = SHAREGPT.sample_requests(40, 40.0, seed=11)
    loop = _mk_loop(arrivals=iter(reqs), steal=False, slo=BAL,
                    tracing=TraceConfig())
    loop.run()
    rep = loop.tracer.violation_report(tight)
    assert rep["finished"] > 0
    assert rep["ttft"]["violations"] == rep["finished"]
    assert rep["ttft"]["mean_excess_s"] > 0
    assert rep["tpot"]["violations"] > 0
    assert set(rep["ttft"]["mean_phase_s"]) <= set(PHASES)
    assert set(rep["tpot"]["mean_phase_s"]) <= set(PHASES)
    # a loose SLO attributes nothing
    clean = loop.tracer.violation_report(SLO(ttft=1e9, tpot=1e9))
    assert clean["ttft"]["violations"] == 0
    assert clean["tpot"]["mean_phase_s"] == {}


def test_prometheus_text_renders_snapshot():
    reqs = SHAREGPT.sample_requests(50, 40.0, seed=13)
    loop = _mk_loop(arrivals=iter(reqs), steal=False, slo=BAL)
    loop.run()
    text = prometheus_text(loop.snapshot())
    lines = text.splitlines()
    assert "# TYPE taichi_finished_total counter" in lines
    assert "# TYPE taichi_goodput_rps gauge" in lines
    # per-instance series carry iid/itype labels
    assert any(l.startswith("taichi_instance_hbm_util{")
               and 'iid="0"' in l for l in lines)
    # horizon histogram exports one series per K
    assert any(l.startswith("taichi_instance_horizon_hist{")
               and 'k="1"' in l for l in lines)
    # every sample line parses as "name{labels} value"
    for l in lines:
        if l.startswith("#"):
            continue
        name, _, val = l.rpartition(" ")
        float(val)
        assert name


def test_prometheus_handles_admission_and_health_labels():
    snap = {
        "finished_total": 3,
        "admission": {"depth": 2,
                      "depth_by_class": {"interactive": 1, "batch": 1},
                      "released_by_class": {"interactive": 5},
                      "released_total": 5},
        "instances": [{"iid": 0, "itype": "P", "hbm_util": 0.5,
                       "health": "quarantined",
                       "exec": {"host_readbacks": 7, "jit_compiles": 3}}],
    }
    text = prometheus_text(snap)
    assert 'taichi_admission_depth{cls="interactive"} 1' in text
    assert ('taichi_admission_released_by_class_total'
            '{cls="interactive"} 5') in text
    assert ('taichi_instance_health{health="quarantined",iid="0",'
            'itype="P"} 1') in text
    assert ('taichi_instance_exec_host_readbacks{iid="0",itype="P"} 7'
            in text)


# ---------------------------------------------------------------------------
# controller decision audit trail
# ---------------------------------------------------------------------------

def test_controller_audit_explains_every_move():
    ctl = SliderController(ControllerConfig(
        epoch=0.5, cooldown=1, min_evidence=2))
    reqs = itertools.islice(DRIFT.iter_requests(60.0, seed=21), 260)
    loop = _mk_loop(arrivals=reqs, steal=False, slo=BAL, controller=ctl,
                    window=3.0, tracing=TraceConfig())
    loop.run()
    assert ctl.audit, "epochs ran, audit must have records"
    for rec in ctl.audit:
        sig = rec["signals"]
        assert {"ttft_att", "tpot_att", "ttft_bad", "tpot_bad", "s_d",
                "s_p", "n_p", "n_d", "evidence"} <= set(sig)
        # an epoch either acted or says why it held (or which guards
        # blocked the starved branch it took)
        assert rec["actions"] or "hold" in rec or "guards" in rec
    # every recorded move appears in exactly one epoch's action list
    audited = [a for rec in ctl.audit for a in rec["actions"]]
    assert audited == ctl.moves
    assert all("why" in m for m in ctl.moves)
    # all but the trailing epoch closed the loop with the observed effect
    assert all("observed" in rec for rec in ctl.audit[:-1])
    assert ctl.moves, "drift workload should force at least one move"
    # controller actuations also land in the cluster-scoped trace log
    names = [n for _, n, _ in loop.tracer.global_events]
    assert any(n.startswith("controller_") for n in names)


def test_controller_audit_bounded_and_optional():
    ctl = SliderController(ControllerConfig(
        epoch=0.5, audit_max_epochs=4))
    loop = _mk_loop(arrivals=iter(SHAREGPT.sample_requests(
        120, 30.0, seed=2)), steal=False, slo=BAL, controller=ctl)
    loop.run()
    assert len(ctl.audit) <= 4
    off = SliderController(ControllerConfig(epoch=0.5, audit=False))
    loop2 = _mk_loop(arrivals=iter(SHAREGPT.sample_requests(
        60, 30.0, seed=2)), steal=False, slo=BAL, controller=off)
    loop2.run()
    assert off.audit == []


# ---------------------------------------------------------------------------
# sync-path watchdog heartbeat (dispatch-time overrun)
# ---------------------------------------------------------------------------

def test_sync_executor_stall_trips_watchdog():
    """With async_exec=False the dispatch/commit split is atomic, so
    ``step_deadline`` is never observable mid-step — the dispatch-time
    ``overrun`` gauge is the heartbeat signal instead."""
    reqs = SHAREGPT.sample_requests(120, 60.0, seed=10)
    inj = FaultInjector([Fault(0.3, STALL, 0, duration=5.0)])
    wd = WatchdogConfig(heartbeat_timeout=0.3, probation=0.5,
                        check_every=0.05)
    loop = _mk_loop(arrivals=iter(reqs), steal=False, async_exec=False,
                    faults=inj, watchdog=wd)
    loop.run()
    assert inj.fired[STALL] == 1
    assert loop.cluster.quarantines >= 1, \
        "sync-path stall must trip the watchdog heartbeat"
    assert loop.cluster.instance_recoveries >= 1
    kinds = [e["kind"] for e in loop.log.events]
    assert "quarantine" in kinds and "readmit" in kinds
    assert all(r.state == State.FINISHED for r in loop.requests)
    assert loop.cluster.instances[0].overrun == 0.0  # reset on recovery


# ---------------------------------------------------------------------------
# telemetry consistency under concurrent snapshots
# ---------------------------------------------------------------------------

def test_snapshot_consistent_under_concurrent_mutation():
    tw = TelemetryWindow(SLO(ttft=1e9, tpot=1e9), window=1e9)
    stop = threading.Event()
    bad = []

    def reader():
        while not stop.is_set():
            snap = tw.snapshot(50.0)
            # every on_finish here is SLO-ok, so a torn read is the
            # only way these can ever differ
            if snap["finished_total"] != snap["slo_ok_total"]:
                bad.append(snap)

    th = threading.Thread(target=reader)
    th.start()
    for i in range(4000):
        r = Request(prompt_len=4, max_new_tokens=2, arrival=0.0)
        r.record_token(0.1)
        r.record_token(0.2)
        tw.on_token(r, 0.1)
        tw.on_finish(r, 0.2)
    stop.set()
    th.join()
    assert not bad, f"torn snapshot: {bad[0]}"
    assert tw.snapshot(50.0)["finished_total"] == 4000


def test_instance_gauges_surface_executor_counters():
    reqs = SHAREGPT.sample_requests(30, 40.0, seed=4)
    loop = _mk_loop(arrivals=iter(reqs), steal=False, slo=BAL)
    loop.run()
    snap = loop.snapshot()
    for g in snap["instances"]:
        # SimExecutor has no hot-path counters: sim snapshots keep shape
        assert "exec" not in g
    busy = [g for g in snap["instances"] if g.get("horizon_hist")]
    assert busy, "instances that planned iterations export the histogram"

    class FakeExec:
        host_readbacks = 11
        host_syncs = 2
        horizon_calls = 5
        horizon_tokens = 40

        @staticmethod
        def jit_compiles():
            return 9

    inst = loop.cluster.instances[0]
    real_ex = inst.executor
    try:
        inst.executor = FakeExec()
        g = TelemetryWindow._instance_gauges(inst)
        assert g["exec"] == {"host_readbacks": 11, "host_syncs": 2,
                             "horizon_calls": 5, "horizon_tokens": 40,
                             "jit_compiles": 9}
    finally:
        inst.executor = real_ex


def test_admission_released_by_class_counter():
    from repro.frontend import AdmissionConfig, AdmissionQueue
    q = AdmissionQueue(AdmissionConfig())
    q.push(Request(prompt_len=4, max_new_tokens=2), "interactive", 0.0)
    q.push(Request(prompt_len=4, max_new_tokens=2), "batch", 0.0)
    q.pop()
    assert q.released_by_class["interactive"] == 1
    assert q.released_by_class["batch"] == 0
    g = q.gauges(1.0)
    assert g["released_by_class"]["interactive"] == 1


# ---------------------------------------------------------------------------
# live serving: wall-clock stamps, the routing/queue split, step spans
# ---------------------------------------------------------------------------

def _slow_wall_loop(tracing, n=10, gap=0.05, slow=2.0):
    """A WallClock-paced async loop whose executor takes ``slow`` times
    each step's modelled duration, so the event clock falls behind the
    wall as steps run."""
    from repro.engine.engine import ImmediateStep, SimExecutor
    from repro.serving import WallClock

    class SlowExecutor(SimExecutor):
        def __init__(self, inst):
            self.inst = inst

        def step_async(self, plan):
            time.sleep(slow * self.inst.iteration_duration(plan))
            return ImmediateStep()

    reqs = [Request(prompt_len=256, max_new_tokens=8, hidden_output_len=8,
                    arrival=0.2 + i * gap) for i in range(n)]
    loop = _mk_loop(arrivals=iter(reqs), steal=False, async_exec=True,
                    clock=WallClock(), pace=True, tracing=tracing)
    for inst in loop.cluster.instances:
        inst.executor = SlowExecutor(inst)
    finished_at = {}
    on_finish = loop.cluster.on_finish

    def stamp_finish(req, t):
        finished_at[req.rid] = loop.clock.now
        on_finish(req, t)
    loop.cluster.on_finish = stamp_finish
    loop.run()
    return loop, finished_at


def test_wall_clock_phases_route_wait_and_lag():
    """Under a WallClock the phases are wall time: they sum to receipt ->
    finish as seen from outside the tracer, ``routing`` holds the wait
    in the event heap (ended by the ``route`` event), and the event
    clock's lag behind the wall grows while the executor runs slower
    than the cost model."""
    loop, finished_at = _slow_wall_loop(TraceConfig())
    tr = loop.tracer
    assert all(r.state == State.FINISHED for r in loop.requests)
    routing = []
    for r in loop.requests:
        t = tr.get(r.rid)
        assert t.t_begin == r.arrival            # the receipt
        for a, b in zip(t.spans, t.spans[1:]):
            assert a.t1 == b.t0
        assert abs(sum(tr.breakdown(r.rid).values())
                   - (finished_at[r.rid] - r.arrival)) < 1e-3
        phases = [sp.phase for sp in t.spans]
        assert phases[:4] == ["routing", "queue", "prefill", "decode_wait"]
        route = next((tt, a) for tt, n, a in t.events if n == "route")
        assert t.spans[0].t1 == route[0]          # routing ends at route
        assert route[1]["event_t"] <= route[0]    # event time runs behind
        routing.append(t.spans[0].t1 - t.spans[0].t0)
        assert t.first_token_t > t.spans[2].t0    # wall, at the sink
        assert abs(sum(tr.ttft_breakdown(r.rid).values())
                   - (t.first_token_t - t.t_begin)) < 1e-9
        chunk = next(a for _, n, a in t.events if n == "prefill_chunk")
        assert {"iid", "seq", "event_t"} <= set(chunk)
    assert routing[-1] > routing[0] and routing[-1] > 0.05
    rep = tr.wait_report()
    assert rep["requests"] == len(loop.requests)
    assert rep["lag_last_tenth_s"] > rep["lag_first_tenth_s"] + 0.05
    ttft = [tr.get(r.rid).first_token_t - r.arrival for r in loop.requests]
    assert 0 < rep["route_wait_s"] <= max(ttft)
    assert 0 <= rep["prefill_queue_s"] <= max(ttft)
    # step spans: plan, dispatch and commit for every dispatched step
    names = {}
    for name, t0, t1, attrs in tr.steps:
        assert t1 >= t0 and "iid" in attrs
        names[name] = names.get(name, 0) + 1
    assert names["taichi.step.dispatch"] == len(tr.lag)
    assert names["taichi.step.commit"] == names["taichi.step.dispatch"]
    assert names["taichi.step.plan"] >= names["taichi.step.dispatch"]
    snap = loop.snapshot()
    assert snap["event_clock_lag_s"] > 0


def test_tracing_off_makes_no_span_or_annotation(monkeypatch):
    from repro.serving import tracing
    made = {"spans": 0, "annotations": 0}
    real_init, real_ann = tracing.StepSpan.__init__, tracing.TraceAnnotation

    def init(self, *a):
        made["spans"] += 1
        real_init(self, *a)

    def ann(*a, **kw):
        made["annotations"] += 1
        return real_ann(*a, **kw)
    monkeypatch.setattr(tracing.StepSpan, "__init__", init)
    monkeypatch.setattr(tracing, "TraceAnnotation", ann)
    loop, _ = _slow_wall_loop(None, n=3)
    assert loop.tracer is None
    assert all(r.state == State.FINISHED for r in loop.requests)
    assert made == {"spans": 0, "annotations": 0}
    _slow_wall_loop(TraceConfig(), n=3)
    assert made["spans"] > 0 and made["annotations"] > 0
