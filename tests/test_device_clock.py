"""Device-timed commits in the live loop.

On a ``WallClock`` a device step (``PendingStep``) is committed when the
device has finished it, at that wall time, and the event clock follows
the wall.  On a ``VirtualClock``, or over steps the executor returns as
``ImmediateStep``, each commit stays where the cost model puts it:
dispatch plus the modelled duration.  The device steps here are fakes
whose ``ready()`` turns true at a set wall time, run one after another
in dispatch order, as on one chip."""
import math
import statistics
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core.latency import SLO
from repro.engine.engine import ImmediateStep, PendingStep, SimExecutor
from repro.engine.request import Request, State
from repro.serving import (ServingLoop, TraceConfig, VirtualClock, WallClock,
                           WatchdogConfig)
from repro.sim.simulator import ServingConfig, build_cluster

LOOSE = SLO(ttft=10.0, tpot=1.0)
#: smollm-135m on one chip: the cost model puts a step near 2.5 ms
SC = ServingConfig(model="smollm-135m", tp=1)
#: a fake device step's run time, about eighty modelled steps
STEP_S = 0.2
#: how late a commit may land after its step became ready: the 1 ms
#: readiness poll, the host's own work and a busy machine's sleep
#: overshoot, all well inside one step
LATE_S = STEP_S / 2


class FakeStep(PendingStep):
    """A device step whose results are ready from ``ready_at`` (the
    loop's clock); never, when ``ready_at`` is None."""

    def __init__(self, clock, ready_at):
        super().__init__(None, (), lambda arrays, step: {})
        self.clock = clock
        self.ready_at = ready_at

    def ready(self) -> bool:
        return self.ready_at is not None and self.clock.now >= self.ready_at


class FakeChip:
    """One device: steps run one after another in dispatch order, each
    ``STEP_S`` long.  ``grid`` rounds the time each step is seen ready
    up to a multiple of it (several steps then become ready together);
    ``hang`` keeps the first step from ever finishing; ``immediate_every`` returns every n-th step as
    an ``ImmediateStep`` (nothing in flight, as an empty plan is)."""

    def __init__(self, clock, grid=None, hang=False, immediate_every=None):
        self.clock = clock
        self.grid = grid
        self.hang = hang
        self.immediate_every = immediate_every
        self.free_at = 0.0
        self.steps = []              # every step returned, dispatch order

    def attach(self, cluster):
        chip = self

        class ChipExecutor(SimExecutor):
            def step_async(self, plan):
                return chip.dispatch()

        for inst in cluster.instances:
            inst.executor = ChipExecutor()

    def dispatch(self):
        n = len(self.steps) + 1
        if self.immediate_every and n % self.immediate_every == 0:
            step = ImmediateStep()
        elif self.hang and n == 1:
            step = FakeStep(self.clock, None)
        else:
            end = self.free_at = max(self.clock.now, self.free_at) + STEP_S
            if self.grid:
                end = math.ceil(end / self.grid) * self.grid
            step = FakeStep(self.clock, end)
        self.steps.append(step)
        return step


def _requests(n, out=6, arrival=0.0, prompt=64, tokens=False):
    rng = np.random.default_rng(n)
    return [Request(prompt_len=prompt, max_new_tokens=out,
                    hidden_output_len=out, arrival=arrival,
                    prompt_tokens=([int(x) for x in rng.integers(
                        1, 200, size=prompt)] if tokens else None))
            for _ in range(n)]


def _log_commits(loop):
    """Record every commit: its step, dispatch time, modelled duration,
    the end it was given, its result and the instance's ``busy_until``."""
    log = []
    for inst in loop.cluster.instances:
        def commit(defer_emit=False, end=None, inst=inst,
                   orig=inst.commit_iteration):
            plan, step, t0, dur = inst._inflight
            res = orig(defer_emit=defer_emit, end=end)
            log.append(SimpleNamespace(
                iid=inst.iid, plan=plan, step=step, t0=t0, dur=dur, end=end,
                res=res, busy_until=inst.busy_until))
            return res
        inst.commit_iteration = commit
    return log


def _device_loop(reqs, horizon=1, **chip_kw):
    """A paced wall-clock loop over the fake chip."""
    kw = {k: chip_kw.pop(k) for k in ("tracing", "watchdog")
          if k in chip_kw}
    clock = WallClock()
    cluster = build_cluster(SC, LOOSE, async_exec=True)
    if horizon > 1:
        cluster.set_horizon(horizon)
    chip = FakeChip(clock, **chip_kw)
    chip.attach(cluster)
    loop = ServingLoop(cluster, LOOSE, arrivals=iter(reqs), clock=clock,
                       pace=True, steal=False, **kw)
    return loop, chip


def _drive(loop, timeout=60.0):
    """Run the loop to its end on a thread, failing (not hanging) if it
    does not drain."""
    th = threading.Thread(target=loop.run, daemon=True)
    th.start()
    th.join(timeout)
    assert not th.is_alive(), "the loop did not drain"
    assert all(r.state == State.FINISHED for r in loop.requests)


@pytest.mark.parametrize("horizon", [1, 4])
def test_commit_lands_at_device_ready_time(horizon):
    """Each device step commits at the wall time its ``ready()`` turned
    true, not at dispatch + modelled duration: tokens, finish times and
    ``busy_until`` carry that end, and a fused horizon's tokens spread
    back from it over the modelled per-step durations."""
    loop, chip = _device_loop(_requests(3, out=9), horizon=horizon)
    log = _log_commits(loop)
    _drive(loop)
    assert log and len(log) == len(chip.steps)
    stamped = set()
    for c in log:
        assert c.end is not None
        assert c.step.ready_at <= c.end < c.step.ready_at + LATE_S
        assert c.res.model_end == c.t0 + c.dur
        assert c.end - c.res.model_end > STEP_S / 2   # device ran longer
        assert c.busy_until == c.end
        # step s of a K-step horizon ends the later steps' modelled
        # durations before the device's end
        K = c.plan.horizon
        want = [c.end - sum(c.plan.step_durations[s + 1:]) if K > 1
                else c.end for s in range(K)]
        times = {t for _, t, _ in c.res.token_events}
        assert times
        for t in times:
            assert min(abs(t - w) for w in want) < 1e-12
        stamped |= times
    assert max(i.horizon_peak for i in loop.cluster.instances) == horizon
    for r in loop.requests:
        assert r.finish_time in stamped


def test_arrival_routes_within_one_step_of_receipt():
    """While the device runs eighty modelled steps' time per step, the old
    event clock would fall further behind with every step; an arrival
    received then is routed within one step of its receipt, and the
    event clock stays on the wall."""
    busy = _requests(1, out=30)
    late = _requests(1, out=2, arrival=9 * STEP_S)
    loop, _ = _device_loop(busy + late, tracing=TraceConfig())
    _drive(loop)
    tr = loop.tracer
    req = late[0]
    route = next(t for t, name, _ in tr.get(req.rid).events
                 if name == "route")
    assert 0.0 <= route - req.arrival < STEP_S
    # what the cost model's clock would have lost by the receipt
    lost = sum(d for w, d in tr.leads if w <= req.arrival)
    assert lost > 5 * STEP_S
    rep = tr.wait_report()
    assert rep["event_clock_lag_s"] < LATE_S
    assert rep["route_wait_s"] < STEP_S


def test_in_flight_steps_commit_in_dispatch_order():
    """Steps that finish together (ends on a grid of two steps) commit
    in the order they were dispatched."""
    loop, chip = _device_loop(_requests(6, out=5), grid=2 * STEP_S)
    log = _log_commits(loop)
    _drive(loop)
    order = [next(i for i, s in enumerate(chip.steps) if s is c.step)
             for c in log]
    assert order == sorted(order) and len(order) == len(chip.steps)
    # several steps were ready at one pass, on more than one instance
    ready = [c.step.ready_at for c in log]
    assert len(set(ready)) < len(ready)
    assert len({c.iid for c in log}) > 1


@pytest.mark.parametrize("immediate_every", [None, 3])
def test_commit_counters(immediate_every):
    """Device steps count as device-timed commits and ``ImmediateStep``s
    as model-timed; the snapshot and the tracer's wait report carry the
    counts and the median of device end minus modelled end."""
    loop, chip = _device_loop(_requests(4, out=6), tracing=TraceConfig(),
                              immediate_every=immediate_every)
    log = _log_commits(loop)
    _drive(loop)
    cl = loop.cluster
    device = [c for c in log if isinstance(c.step, FakeStep)]
    model = [c for c in log if not isinstance(c.step, FakeStep)]
    assert cl.device_timed_commits == len(device) > 0
    assert cl.model_timed_commits == len(model)
    assert bool(model) == (immediate_every is not None)
    for c in model:
        assert c.end is None
        assert all(t == c.t0 + c.dur for _, t, _ in c.res.token_events)
    leads = [c.end - c.res.model_end for c in device]
    assert [d for _, d in cl.commit_model_lead] == leads
    assert [w for w, _ in cl.commit_model_lead] == [c.end for c in device]
    snap = loop.snapshot()
    assert snap["device_timed_commits"] == len(device)
    assert snap["model_timed_commits"] == len(model)
    assert snap["commit_model_lead_s"] == statistics.median(leads)
    rep = loop.tracer.wait_report()
    assert rep["device_commits"] == len(device)
    assert rep["commit_model_lead_s"] == statistics.median(leads)
    assert not cl.in_flight


def _virtual_jax_loop(reqs):
    from repro.launch import serve
    eng = serve.build_engine("smollm-135m", LOOSE, reduced=True)
    return ServingLoop(eng.cluster, LOOSE, arrivals=iter(reqs))


def _wall_sim_loop(reqs):
    cluster = build_cluster(SC, LOOSE, async_exec=True)
    return ServingLoop(cluster, LOOSE, arrivals=iter(reqs),
                       clock=WallClock(), pace=True)


@pytest.mark.parametrize("make", [_virtual_jax_loop, _wall_sim_loop],
                         ids=["virtual_clock_jax", "wall_clock_sim"])
def test_modelled_paths_commit_at_dispatch_plus_duration(make):
    """A ``VirtualClock`` over ``JaxExecutor`` and a ``WallClock`` over
    ``SimExecutor`` keep every commit at exactly dispatch + the cost
    model's ``iteration_duration``."""
    reqs = _requests(3, out=4, prompt=24, tokens=True)
    for i, r in enumerate(reqs):
        r.arrival = 0.01 * i
    loop = make(reqs)
    log = _log_commits(loop)
    modelled = {}               # id(plan) -> (plan, modelled duration)
    for inst in loop.cluster.instances:
        def duration(plan, orig=inst.iteration_duration):
            modelled[id(plan)] = (plan, orig(plan))
            return modelled[id(plan)][1]
        inst.iteration_duration = duration
    _drive(loop, timeout=300.0)
    cl = loop.cluster
    assert log and cl.model_timed_commits == len(log)
    assert cl.device_timed_commits == 0 and not cl.commit_model_lead
    assert not cl.in_flight and not cl.device_timed
    for c in log:
        assert c.end is None
        assert modelled[id(c.plan)] == (c.plan, c.dur)
        assert c.res.model_end == c.t0 + c.dur
        assert all(t == c.t0 + c.dur for _, t, _ in c.res.token_events)
        assert c.busy_until == c.t0 + c.dur
    if isinstance(loop.clock, VirtualClock):
        assert "device_timed_commits" not in loop.snapshot()


def test_watchdog_quarantines_a_step_that_never_finishes():
    """A device step that never becomes ready is quarantined
    ``stall_timeout`` past its modelled deadline; its request recomputes
    on another instance and finishes."""
    # the timeout leaves the steps that do finish two steps of room
    wd = WatchdogConfig(stall_timeout=3 * STEP_S, heartbeat_timeout=60.0,
                        probation=60.0)
    loop, chip = _device_loop(_requests(1, out=4), hang=True, watchdog=wd)
    _drive(loop)
    hung = chip.steps[0]
    assert hung.ready_at is None and hung.resolved
    stalls = [e for e in loop.log.events
              if e["kind"] == "quarantine" and e["why"] == "stall"]
    assert len(stalls) == 1
    req = loop.requests[0]
    assert req.n_recoveries == 1 and req.output_len == 4
    assert loop.cluster.quarantines == 1 and not loop.cluster.in_flight
    assert loop.cluster.device_timed_commits == len(chip.steps) - 1
