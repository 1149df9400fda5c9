"""Fault-tolerance layer, fast tier (sim executors): deterministic
fault injection, instance crash/quarantine/recovery with evacuation by
recompute, TRANSFER retry/corruption handling, the serving-loop
watchdog's heartbeat + probation machinery, client aborts, and the
chaos property test — randomized fault schedules under which every
submitted request terminally resolves, allocators conserve blocks, and
recovered requests stay token-exact against a fault-free oracle."""
import random

import pytest

from repro.core.cluster import FaultToleranceConfig
from repro.core.instance import (HEALTH_DEAD, HEALTH_OK,
                                 HEALTH_QUARANTINED)
from repro.core.latency import SLO
from repro.core.policies import Sliders
from repro.engine.request import Request, State, TERMINAL_STATES
from repro.serving import ServingLoop, WatchdogConfig
from repro.serving.faults import (CRASH, EXEC_ERROR, RECOVER, STALL,
                                  Fault, FaultInjector, payload_checksum)
from repro.serving.recovery import RecoveryConfig
from repro.sim.simulator import ServingConfig, build_cluster
from repro.sim.workload import SHAREGPT

BAL = SLO(ttft=1.5, tpot=0.030)
LOOSE = SLO(ttft=10.0, tpot=1.0)


def _mk_loop(policy="taichi", sliders=Sliders(2, 2, 1024, 256),
             blocks=4096, slo=LOOSE, ft=None, async_exec=False,
             recovery=None, **kw):
    sc = ServingConfig(policy=policy, sliders=sliders, hbm_blocks=blocks)
    cluster = build_cluster(sc, slo, ft=ft, async_exec=async_exec,
                            recovery=recovery)
    return ServingLoop(cluster, slo, **kw)


def _assert_conserved(cluster):
    """free + cached + used == total on every instance's allocator, and
    nothing still held once every request is terminal."""
    for inst in cluster.instances:
        a = inst.allocator
        cached = getattr(a, "cached_blocks", 0)
        assert a.free_blocks + cached + a.used_blocks == a.num_blocks, \
            f"instance {inst.iid} leaked blocks"


def _assert_all_terminal(loop):
    for r in loop.requests:
        assert r.state in TERMINAL_STATES, \
            f"request {r.rid} stuck in {r.state.value}"
        assert r.finish_time is not None
    _assert_conserved(loop.cluster)
    for inst in loop.cluster.instances:
        assert inst.allocator.used_blocks == 0, \
            f"instance {inst.iid} still holds blocks after drain"


# ---------------------------------------------------------------------------
# injector determinism
# ---------------------------------------------------------------------------

def test_random_schedule_is_deterministic():
    a = FaultInjector.random_schedule(7, [0, 1, 2, 3], t_end=4.0,
                                      recover_after=1.0,
                                      transfer_drop_p=0.3,
                                      transfer_corrupt_p=0.1)
    b = FaultInjector.random_schedule(7, [0, 1, 2, 3], t_end=4.0,
                                      recover_after=1.0,
                                      transfer_drop_p=0.3,
                                      transfer_corrupt_p=0.1)
    assert [(f.t, f.kind, f.iid) for f in a.schedule] == \
        [(f.t, f.kind, f.iid) for f in b.schedule]
    assert [a.transfer_outcome() for _ in range(64)] == \
        [b.transfer_outcome() for _ in range(64)]
    # schedule sorted by time; recover follows its crash
    ts = [f.t for f in a.schedule]
    assert ts == sorted(ts)


def test_fault_kind_validated():
    with pytest.raises(ValueError):
        Fault(1.0, "meteor", 0)


def test_payload_checksum_content_sensitivity():
    import numpy as np
    s1 = {"k": np.arange(8, dtype=np.int32), "meta": [1, 2, "x"]}
    s2 = {"k": np.arange(8, dtype=np.int32), "meta": [1, 2, "x"]}
    assert payload_checksum(s1) == payload_checksum(s2)
    s2["k"] = s2["k"].copy()
    s2["k"][3] += 1                       # one flipped element
    assert payload_checksum(s1) != payload_checksum(s2)
    assert payload_checksum(None) != payload_checksum({})


# ---------------------------------------------------------------------------
# faults disabled == identical behavior
# ---------------------------------------------------------------------------

def test_faults_off_is_bit_identical():
    reqs_a = SHAREGPT.sample_requests(60, 40.0, seed=5)
    reqs_b = SHAREGPT.sample_requests(60, 40.0, seed=5)

    plain = _mk_loop(arrivals=iter(reqs_a), steal=False)
    plain.run()
    # empty schedule + zero probabilities: the layer must be inert
    armed = _mk_loop(arrivals=iter(reqs_b), steal=False,
                     faults=FaultInjector(),
                     watchdog=WatchdogConfig())
    armed.run()
    assert [r.finish_time for r in reqs_b] == \
        [r.finish_time for r in reqs_a]
    assert [r.output_len for r in reqs_b] == \
        [r.output_len for r in reqs_a]
    assert "faults" not in armed.snapshot()
    snap = armed.snapshot()
    assert all("health" not in g for g in snap["instances"])


# ---------------------------------------------------------------------------
# crash: evacuation by recompute vs fail-stop
# ---------------------------------------------------------------------------

def test_crash_evacuates_and_requests_still_finish():
    reqs = SHAREGPT.sample_requests(60, 60.0, seed=2)
    loop = _mk_loop(arrivals=iter(reqs), steal=False)
    cluster = loop.cluster
    loop.run(until=0.4)
    victim = max(cluster.instances,
                 key=lambda i: len(i.decoding) + len(i.prefill_queue))
    evicted = cluster.fail_instance(victim)
    assert victim.health == HEALTH_DEAD
    assert evicted and cluster.instance_failures == 1
    assert cluster.evacuated_requests == len(evicted)
    # the dead instance holds nothing and caches nothing
    assert victim.allocator.used_blocks == 0
    assert not victim.has_work()
    loop.run()
    _assert_all_terminal(loop)
    recovered = [r for r in loop.requests if r.n_recoveries > 0]
    assert recovered, "evacuation must have re-routed someone"
    for r in recovered:
        assert r.state == State.FINISHED
        assert r.output_len == r.target_output_len   # token-exact
    assert all(r.state == State.FINISHED for r in loop.requests)
    counters = cluster.fault_counters()
    assert counters["instance_failures"] == 1
    assert counters["evacuated_requests"] == len(evicted)


def test_fail_stop_fails_victims_terminally():
    reqs = SHAREGPT.sample_requests(60, 60.0, seed=2)
    loop = _mk_loop(arrivals=iter(reqs), steal=False,
                    ft=FaultToleranceConfig.fail_stop())
    cluster = loop.cluster
    loop.run(until=0.4)
    victim = max(cluster.instances,
                 key=lambda i: len(i.decoding) + len(i.prefill_queue))
    evicted = cluster.fail_instance(victim)
    loop.run()
    _assert_all_terminal(loop)
    failed = [r for r in loop.requests if r.state == State.FAILED]
    assert len(failed) >= len(evicted)
    for r in evicted:
        assert r.state == State.FAILED
        assert r.finish_reason.startswith("instance_")
    assert loop.failed_count == len(failed)
    assert loop.telemetry.total_failed == len(failed)


def test_dead_instance_excluded_from_placement():
    loop = _mk_loop(steal=False)
    cluster = loop.cluster
    dead = cluster.instances[0]
    cluster.fail_instance(dead)
    reqs = SHAREGPT.sample_requests(40, 80.0, seed=3)
    for r in reqs:
        loop.submit(r)
    loop.run()
    _assert_all_terminal(loop)
    assert all(r.prefill_instance != dead.iid for r in loop.requests
               if r.prefill_instance is not None)
    assert all(r.decode_instance != dead.iid for r in loop.requests
               if r.decode_instance is not None)


def test_all_instances_down_fails_not_hangs():
    loop = _mk_loop(steal=False)
    for inst in loop.cluster.instances:
        loop.cluster.fail_instance(inst)
    h = loop.submit(Request(prompt_len=64, max_new_tokens=8))
    loop.run()
    assert h.failed and h.req.finish_reason == "no_capacity"


def test_recover_instance_rejoins_rotation():
    loop = _mk_loop(steal=False)
    cluster = loop.cluster
    inst = cluster.instances[0]
    cluster.fail_instance(inst)
    assert cluster.recover_instance(inst)
    assert inst.health == HEALTH_OK
    assert not cluster.recover_instance(inst)      # idempotent
    reqs = SHAREGPT.sample_requests(40, 80.0, seed=4)
    for r in reqs:
        loop.submit(r)
    loop.run()
    _assert_all_terminal(loop)
    assert any(r.prefill_instance == inst.iid for r in loop.requests)


# ---------------------------------------------------------------------------
# TRANSFER faults: retry, recompute fallback, corruption detection
# ---------------------------------------------------------------------------

def test_transfer_drops_are_retried_with_backoff():
    reqs = SHAREGPT.sample_requests(50, 50.0, seed=6)
    inj = FaultInjector(seed=6, transfer_drop_p=0.3)
    loop = _mk_loop(policy="disaggregation", arrivals=iter(reqs),
                    steal=False, faults=inj)
    loop.run()
    _assert_all_terminal(loop)
    assert loop.cluster.transfer_retries > 0
    assert inj.transfer_drops > 0
    assert all(r.state == State.FINISHED for r in loop.requests)
    for r in loop.requests:
        assert r.output_len == r.target_output_len


def test_transfer_exhaustion_falls_back_to_recompute():
    reqs = SHAREGPT.sample_requests(30, 50.0, seed=7)
    # every landing drops: each transfer exhausts its retries, then the
    # request must recompute its way to completion (placement retargets
    # to the prefill instance itself once every D-move keeps failing,
    # or the recovery bound trips -> FAILED; never a hang)
    inj = FaultInjector(seed=7, transfer_drop_p=1.0)
    loop = _mk_loop(policy="disaggregation", arrivals=iter(reqs),
                    steal=False, faults=inj)
    loop.run()
    _assert_all_terminal(loop)
    assert loop.cluster.transfer_recomputes > 0
    for r in loop.requests:
        assert r.state in (State.FINISHED, State.FAILED)
        if r.state == State.FAILED:
            assert r.finish_reason in ("too_many_recoveries",
                                       "transfer_failed")


def test_transfer_corruption_detected_and_retried():
    reqs = SHAREGPT.sample_requests(40, 50.0, seed=8)
    inj = FaultInjector(seed=8, transfer_corrupt_p=0.25)
    loop = _mk_loop(policy="disaggregation", arrivals=iter(reqs),
                    steal=False, faults=inj)
    loop.run()
    _assert_all_terminal(loop)
    assert loop.cluster.transfer_corruptions > 0
    assert loop.cluster.transfer_retries > 0
    assert all(r.state == State.FINISHED for r in loop.requests)


def test_unverified_corruption_delivers_but_counts():
    reqs = SHAREGPT.sample_requests(30, 50.0, seed=9)
    inj = FaultInjector(seed=9, transfer_corrupt_p=0.5)
    ft = FaultToleranceConfig(verify_transfers=False)
    loop = _mk_loop(policy="disaggregation", arrivals=iter(reqs),
                    steal=False, faults=inj, ft=ft)
    loop.run()
    _assert_all_terminal(loop)
    assert loop.cluster.transfer_corruptions > 0
    assert loop.cluster.transfer_retries == 0      # delivered, not retried


# ---------------------------------------------------------------------------
# watchdog: heartbeat quarantine + probation re-admission
# ---------------------------------------------------------------------------

def test_stall_trips_watchdog_and_probation_readmits():
    # the heartbeat keys on the dispatch/commit split's step deadline,
    # so this runs the async pipeline (the live path's event shape)
    reqs = SHAREGPT.sample_requests(120, 60.0, seed=10)
    inj = FaultInjector([Fault(0.3, STALL, 0, duration=5.0)])
    wd = WatchdogConfig(heartbeat_timeout=0.3, probation=0.5,
                        check_every=0.05)
    loop = _mk_loop(arrivals=iter(reqs), steal=False, async_exec=True,
                    faults=inj, watchdog=wd)
    loop.run()
    _assert_all_terminal(loop)
    cluster = loop.cluster
    assert inj.fired[STALL] == 1
    assert cluster.quarantines >= 1, "watchdog never caught the stall"
    assert cluster.instance_recoveries >= 1, "probation never re-admitted"
    assert all(i.health == HEALTH_OK for i in cluster.instances)
    kinds = [e["kind"] for e in loop.log.events]
    assert "quarantine" in kinds and "readmit" in kinds
    assert all(r.state == State.FINISHED for r in loop.requests)


def test_probation_backs_off_per_repeat_offense():
    loop = _mk_loop(steal=False,
                    watchdog=WatchdogConfig(probation=1.0,
                                            probation_backoff=2.0,
                                            max_probation=3.0))
    inst = loop.cluster.instances[0]
    assert loop._start_probation(inst, 0.0) == 1.0
    assert loop._start_probation(inst, 0.0) == 2.0
    assert loop._start_probation(inst, 0.0) == 3.0
    assert loop._start_probation(inst, 0.0) == 3.0  # capped


def test_exec_error_quarantines_and_work_recovers():
    reqs = SHAREGPT.sample_requests(80, 60.0, seed=11)
    inj = FaultInjector([Fault(0.3, EXEC_ERROR, 1)])
    wd = WatchdogConfig(probation=0.5, check_every=0.05)
    loop = _mk_loop(arrivals=iter(reqs), steal=False,
                    faults=inj, watchdog=wd)
    loop.run()
    _assert_all_terminal(loop)
    cluster = loop.cluster
    assert cluster.exec_errors == 1
    assert "InjectedFault" in cluster.last_exec_error
    assert cluster.quarantines >= 1
    assert all(r.state == State.FINISHED for r in loop.requests)
    # the armed executor restored itself: one shot, not a dead instance
    assert cluster.instances[1].health == HEALTH_OK


def test_crash_then_scheduled_recover():
    reqs = SHAREGPT.sample_requests(80, 60.0, seed=12)
    inj = FaultInjector([Fault(0.3, CRASH, 0), Fault(1.0, RECOVER, 0)])
    loop = _mk_loop(arrivals=iter(reqs), steal=False, faults=inj)
    loop.run()
    _assert_all_terminal(loop)
    cluster = loop.cluster
    assert cluster.instance_failures == 1
    assert cluster.instance_recoveries == 1
    assert cluster.instances[0].health == HEALTH_OK
    assert all(r.state == State.FINISHED for r in loop.requests)


# ---------------------------------------------------------------------------
# client aborts
# ---------------------------------------------------------------------------

def test_abort_mid_flight_frees_blocks_and_resolves():
    reqs = SHAREGPT.sample_requests(40, 60.0, seed=13)
    loop = _mk_loop(arrivals=iter(reqs), steal=False)
    loop.run(until=0.3)
    live = [r for r in loop.requests if r.state not in TERMINAL_STATES]
    assert live, "nothing in flight to abort"
    for r in live:
        loop.abort(r.rid)
    loop.run()
    _assert_all_terminal(loop)
    aborted = [r for r in loop.requests if r.state == State.CANCELLED]
    assert aborted
    for r in aborted:
        assert r.finish_reason == "abort"
        for inst in loop.cluster.instances:
            assert not inst.allocator.holds(r.rid)
    assert loop.aborted_count == len(aborted)
    assert loop.telemetry.total_aborted == len(aborted)
    assert loop.snapshot()["faults"]["aborted"] == len(aborted)


def test_abort_unknown_and_finished_rids():
    loop = _mk_loop(steal=False)
    assert not loop.abort(10 ** 9)              # never submitted
    h = loop.submit(Request(prompt_len=32, max_new_tokens=4))
    loop.run()
    assert h.done
    assert loop.abort(h.req.rid)                # terminal: no-op True
    assert h.req.state == State.FINISHED


def test_abort_from_admission_queue_cancels_immediately():
    from repro.frontend.admission import AdmissionConfig
    loop = _mk_loop(steal=False,
                    admission=AdmissionConfig(max_depth=16,
                                              max_inflight=0))
    h = loop.submit(Request(prompt_len=32, max_new_tokens=4))
    assert len(loop.admission) == 1
    assert loop.abort(h.req.rid)
    assert h.cancelled and h.req.finish_reason == "abort"
    assert len(loop.admission) == 0
    assert loop.aborted_count == 1


# ---------------------------------------------------------------------------
# chaos property test: randomized schedules, nothing lost, token-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(6))
def test_chaos_no_request_lost_and_token_exact(seed):
    n, qps = 70, 50.0
    oracle = SHAREGPT.sample_requests(n, qps, seed=100 + seed)
    base = _mk_loop(arrivals=iter(oracle), steal=False)
    base.run()
    want = {r.rid - oracle[0].rid: r.output_len for r in oracle}

    reqs = SHAREGPT.sample_requests(n, qps, seed=100 + seed)
    t_end = max(r.arrival for r in reqs)
    inj = FaultInjector.random_schedule(
        seed, [0, 1, 2, 3],             # iids of the 2P+2D pool below
        t_end=t_end, n_crashes=1, n_stalls=2, n_exec_errors=1,
        stall_duration=0.5, recover_after=0.8,
        transfer_drop_p=0.05, transfer_corrupt_p=0.02)
    rng = random.Random(seed)
    loop = _mk_loop(arrivals=iter(reqs), steal=False, faults=inj,
                    watchdog=WatchdogConfig(heartbeat_timeout=0.4,
                                            probation=0.5,
                                            check_every=0.05))
    # interleave a few client aborts with the fault schedule
    loop.run(until=t_end * 0.5)
    live = [r for r in loop.requests if r.state not in TERMINAL_STATES]
    for r in rng.sample(live, min(3, len(live))):
        loop.abort(r.rid)
    loop.run()

    # 1) every submitted request terminally resolved
    _assert_all_terminal(loop)
    # 2) finished requests are greedy token-exact vs the fault-free
    #    oracle (same workload seed => same per-request target)
    first = reqs[0].rid
    for r in loop.requests:
        if r.state == State.FINISHED:
            assert r.output_len == want[r.rid - first], \
                f"request {r.rid} lost or duplicated tokens"
    # 3) faults actually fired and were survived
    assert sum(inj.fired.values()) >= 1
    recovered = [r for r in loop.requests
                 if r.n_recoveries > 0 and r.state == State.FINISHED]
    if loop.cluster.evacuated_requests:
        assert recovered or loop.cluster.failed_count \
            or loop.cluster.aborted_count
    # 4) loop-side and cluster-side outcome counters agree
    fc = loop.cluster.fault_counters()
    assert fc["failed"] == loop.failed_count
    assert fc["aborted"] == loop.aborted_count


# ---------------------------------------------------------------------------
# warm recovery: checkpoints, restore, bit-identical when off
# ---------------------------------------------------------------------------

def test_recovery_disabled_config_is_inert():
    reqs_a = SHAREGPT.sample_requests(60, 40.0, seed=5)
    reqs_b = SHAREGPT.sample_requests(60, 40.0, seed=5)
    plain = _mk_loop(arrivals=iter(reqs_a), steal=False)
    plain.run()
    # enable=False must leave Cluster.recovery None: bit-identical run
    armed = _mk_loop(arrivals=iter(reqs_b), steal=False,
                     recovery=RecoveryConfig(enable=False))
    assert armed.cluster.recovery is None
    armed.run()
    assert [r.finish_time for r in reqs_b] == \
        [r.finish_time for r in reqs_a]
    assert [r.output_len for r in reqs_b] == \
        [r.output_len for r in reqs_a]
    assert "recovery" not in armed.snapshot()


def test_recovery_on_without_faults_changes_nothing():
    """Checkpointing is pure observation: with no crash there is never
    a restore, and the served schedule matches a recovery-less run."""
    reqs_a = SHAREGPT.sample_requests(60, 40.0, seed=5)
    reqs_b = SHAREGPT.sample_requests(60, 40.0, seed=5)
    plain = _mk_loop(arrivals=iter(reqs_a), steal=False)
    plain.run()
    warm = _mk_loop(arrivals=iter(reqs_b), steal=False,
                    recovery=RecoveryConfig(enable=True))
    warm.run()
    assert [r.finish_time for r in reqs_b] == \
        [r.finish_time for r in reqs_a]
    assert [r.output_len for r in reqs_b] == \
        [r.output_len for r in reqs_a]
    rc = warm.snapshot()["recovery"]
    assert rc["checkpoints"] > 0
    assert rc["warm_restores"] == 0


def test_warm_restore_resumes_from_checkpoint():
    reqs = SHAREGPT.sample_requests(80, 60.0, seed=12)
    oracle = SHAREGPT.sample_requests(80, 60.0, seed=12)
    base = _mk_loop(arrivals=iter(oracle), steal=False)
    base.run()
    want = {r.rid - oracle[0].rid: r.output_len for r in oracle}

    # instance 2 is a decode-role instance under Sliders(2, 2, ...) —
    # crashing it catches mid-decode victims with checkpointed progress
    inj = FaultInjector([Fault(0.5, CRASH, 2), Fault(1.2, RECOVER, 2)])
    loop = _mk_loop(arrivals=iter(reqs), steal=False, faults=inj,
                    recovery=RecoveryConfig(enable=True,
                                            checkpoint_tokens=8))
    # count streamed tokens per request at the sink: a warm restore must
    # never re-emit a token index that already streamed (no double
    # emission across the restore)
    emitted = {}
    orig_sinks = {i.iid: i.token_sink for i in loop.cluster.instances}

    def counting(iid):
        def sink(req, t, tok):
            emitted[req.rid] = emitted.get(req.rid, 0) + 1
            orig_sinks[iid](req, t, tok)
        return sink
    for i in loop.cluster.instances:
        i.token_sink = counting(i.iid)
    loop.run()

    _assert_all_terminal(loop)
    first = reqs[0].rid
    for r in loop.requests:
        assert r.state == State.FINISHED
        assert r.output_len == want[r.rid - first]
        # every emission was a fresh token index
        assert emitted.get(r.rid, 0) == r.output_len
    rc = loop.cluster.recovery_counters()
    assert rc["warm_restores"] > 0, "crash victims never resumed warm"
    assert rc["warm_restored_tokens"] > 0
    assert rc["checkpoints"] > 0
    snap = loop.snapshot()
    assert snap["recovery"]["warm_restores"] == rc["warm_restores"]


@pytest.mark.parametrize("seed", range(4))
def test_warm_chaos_no_request_lost_and_token_exact(seed):
    """The chaos property machine with warm recovery enabled: same
    invariants as the cold-path chaos test — conservation, terminal
    resolution, greedy token-exactness — plus no double emission."""
    n, qps = 70, 50.0
    oracle = SHAREGPT.sample_requests(n, qps, seed=200 + seed)
    base = _mk_loop(arrivals=iter(oracle), steal=False)
    base.run()
    want = {r.rid - oracle[0].rid: r.output_len for r in oracle}

    reqs = SHAREGPT.sample_requests(n, qps, seed=200 + seed)
    t_end = max(r.arrival for r in reqs)
    inj = FaultInjector.random_schedule(
        seed, [0, 1, 2, 3], t_end=t_end, n_crashes=2, n_stalls=2,
        n_exec_errors=1, stall_duration=0.5, recover_after=0.8,
        transfer_drop_p=0.05, transfer_corrupt_p=0.02)
    rng = random.Random(seed)
    loop = _mk_loop(arrivals=iter(reqs), steal=False, faults=inj,
                    recovery=RecoveryConfig(enable=True,
                                            checkpoint_tokens=8),
                    watchdog=WatchdogConfig(heartbeat_timeout=0.4,
                                            probation=0.5,
                                            check_every=0.05))
    emitted = {}
    orig_sinks = {i.iid: i.token_sink for i in loop.cluster.instances}

    def counting(iid):
        def sink(req, t, tok):
            emitted[req.rid] = emitted.get(req.rid, 0) + 1
            orig_sinks[iid](req, t, tok)
        return sink
    for i in loop.cluster.instances:
        i.token_sink = counting(i.iid)
    loop.run(until=t_end * 0.5)
    live = [r for r in loop.requests if r.state not in TERMINAL_STATES]
    for r in rng.sample(live, min(3, len(live))):
        loop.abort(r.rid)
    loop.run()

    _assert_all_terminal(loop)
    first = reqs[0].rid
    for r in loop.requests:
        if r.state == State.FINISHED:
            assert r.output_len == want[r.rid - first], \
                f"request {r.rid} lost or duplicated tokens"
            assert emitted.get(r.rid, 0) == r.output_len, \
                f"request {r.rid} double-emitted across a restore"
    assert sum(inj.fired.values()) >= 1
    rc = loop.cluster.recovery_counters()
    # checkpoints always flow; a restore only if a crash caught victims
    assert rc["checkpoints"] > 0
    fc = loop.cluster.fault_counters()
    assert fc["failed"] == loop.failed_count
    assert fc["aborted"] == loop.aborted_count


# ---------------------------------------------------------------------------
# post-crash KV re-replication
# ---------------------------------------------------------------------------

def test_crash_rereplicates_hot_prefix_immediately():
    """When a hot-prefix replica holder dies, the manager re-establishes
    the path on the coldest healthy peer at fail time instead of waiting
    for the controller's next replication epoch."""
    from repro.serving import ControllerConfig, SliderController
    sc = ServingConfig(policy="taichi", sliders=Sliders(2, 1, 512, 256),
                       hbm_blocks=1024, block_size=16, prefix_cache=True)
    cluster = build_cluster(sc, LOOSE,
                            recovery=RecoveryConfig(enable=True))
    ctl = SliderController(ControllerConfig(
        replicate=True, replicate_min_hits=2, replicate_max_paths=2,
        replicate_max_blocks=64))
    loop = ServingLoop(cluster, LOOSE, controller=ctl)
    base = list(range(1, 257))                     # 16 hot blocks
    for i in range(14):
        tail = list(range(10_000 + 97 * i, 10_000 + 97 * i + 64))
        loop.submit(Request(prompt_len=len(base) + 64, max_new_tokens=4,
                            hidden_output_len=4,
                            prompt_tokens=base + tail,
                            arrival=0.5 * i))
    loop.run()
    assert ctl.replications > 0, "no replica to lose — test is vacuous"
    rec = cluster.recovery
    key, holders = next(iter(rec._replicas.items()))
    victim = cluster._inst_by_id[next(iter(holders))]
    before = rec.rereplications
    cluster.fail_instance(victim)
    assert rec.rereplications > before, \
        "crash of a replica holder never re-replicated its path"
    loop.run()                                     # land the transfer
    survivors = [i for i in cluster.instances
                 if i is not victim
                 and i.prefix_cache.match_tokens(list(key) + [0]) > 0]
    assert survivors, "re-replicated path landed nowhere healthy"
    _assert_conserved(cluster)


# ---------------------------------------------------------------------------
# retry-backoff jitter
# ---------------------------------------------------------------------------

def test_retry_jitter_seeded_and_bounded():
    a = FaultInjector(seed=3)
    b = FaultInjector(seed=3)
    seq_a = [a.retry_jitter(0.05, prev, 0.8)
             for prev in (0.05, 0.1, 0.4, 2.0)]
    seq_b = [b.retry_jitter(0.05, prev, 0.8)
             for prev in (0.05, 0.1, 0.4, 2.0)]
    assert seq_a == seq_b                      # same seed, same delays
    for d, prev in zip(seq_a, (0.05, 0.1, 0.4, 2.0)):
        assert 0.05 <= d <= 0.8                # [base, cap] always
        assert d <= max(0.05, prev) * 3.0
    # the jitter stream is independent of transfer outcomes
    c = FaultInjector(seed=3, transfer_drop_p=0.3)
    outcomes = [c.transfer_outcome() for _ in range(16)]
    c2 = FaultInjector(seed=3, transfer_drop_p=0.3)
    c2.retry_jitter(0.05, 0.1, 0.8)            # consume jitter first
    assert [c2.transfer_outcome() for _ in range(16)] == outcomes
