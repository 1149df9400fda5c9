"""Multi-step fused decode horizon + non-blocking async executor step.

Fast tier: decode-horizon packing, adaptive-K selection under
flowing-decode budgets (prefill pressure, drain barriers, TPOT
headroom, HBM watermark, allocator grants), horizon token-timestamp
spreading, and the async dispatch/commit cluster pipeline on the
simulator's token oracle.

Slow tier: greedy token-exact parity of the K-step horizon against the
K=1 oracle on BOTH tensor paths (paged and packed-dense), including EOS
mid-horizon, preemption-by-recompute, a migration round trip (with the
pipeline-flush guard), single-token requests, the readbacks-per-token
<= 1/K acceptance hook, and an async live serving run that survives a
drain-and-flip role change with token parity."""
import numpy as np
import pytest

from repro.core.cluster import Cluster
from repro.core.instance import HORIZON_HBM_GUARD, Instance
from repro.core.latency import SLO
from repro.core.policies import Sliders
from repro.engine import batching
from repro.engine.engine import ImmediateStep, SimExecutor
from repro.engine.request import Request, State
from repro.sim.simulator import ServingConfig, build_cluster
from repro.sim.workload import SHAREGPT

BAL = SLO(ttft=1.5, tpot=0.030)


# ---------------------------------------------------------------------------
# fast tier: packing
# ---------------------------------------------------------------------------

def _table(bids, width=16):
    row = np.full(width, -1, np.int32)
    row[:len(bids)] = bids
    return row


def test_pack_decode_buckets_batch_and_tables():
    packed = batching.pack_decode(
        last_tokens=[7, 9, 3], positions=[4, 60, 17],
        budgets=[8, 8, 2],
        table_rows=[_table([2]), _table([7, 1, 3, 11, 4]),
                    _table([5, 6])],
        max_blocks=16, block_size=16)
    assert packed.tokens.shape == (4,)            # B pow2 padded
    # row 1's end-of-horizon frontier 60+8 needs 5 blocks -> NB pow2 = 8
    assert packed.tables.shape == (4, 8)
    np.testing.assert_array_equal(packed.tokens, [7, 9, 3, 0])
    np.testing.assert_array_equal(packed.start, [4, 60, 17, 0])
    np.testing.assert_array_equal(packed.budget, [8, 8, 2, 0])
    assert (packed.tables[3] == -1).all()         # pad row frozen+dropped


def test_pack_decode_nb_capped_at_max_blocks():
    packed = batching.pack_decode(
        last_tokens=[1], positions=[250], budgets=[8],
        table_rows=[_table(list(range(16)))], max_blocks=16,
        block_size=16)
    # frontier 258 would need 17 blocks; positions clamp on-device, so
    # the table caps at max_blocks instead of raising
    assert packed.tables.shape == (1, 16)


# ---------------------------------------------------------------------------
# fast tier: adaptive-K selection (flowing-decode budget)
# ---------------------------------------------------------------------------

def _sim_instance(max_horizon=8, hbm_blocks=4096, chunk=256, **kw):
    from repro.configs import get_config
    from repro.core.estimator import CostModel
    from repro.core.hw import InstanceSpec
    cost = CostModel(get_config("qwen2.5-14b"), InstanceSpec(tp=4))
    return Instance(0, "D", chunk, cost, SimExecutor(),
                    hbm_blocks=hbm_blocks, max_horizon=max_horizon, **kw)


def _fake_decoding(inst, n=2, cur_tpot=None, out_len=8):
    """Install decoding requests with a controlled current_tpot."""
    for _ in range(n):
        r = Request(prompt_len=32, max_new_tokens=64)
        r.output_len = out_len
        r.tpot_reset_time = 0.0
        r.first_token_time = 0.0
        r.last_token_time = ((out_len - 1) * cur_tpot
                             if cur_tpot is not None else None)
        inst.decoding[r.rid] = r
        r.state = State.DECODE
    return list(inst.decoding.values())


def test_pick_horizon_pow2_ladder_and_idle():
    inst = _sim_instance(max_horizon=6)          # non-pow2 cap -> 4
    assert inst._pick_horizon() == 1             # no decodes
    _fake_decoding(inst)
    assert inst._pick_horizon() == 4
    inst.max_horizon = 8
    assert inst._pick_horizon() == 8
    inst.max_horizon = 1
    assert inst._pick_horizon() == 1


def test_pick_horizon_prefill_work_forces_one():
    inst = _sim_instance()
    _fake_decoding(inst)
    inst.prefill_queue.append(Request(prompt_len=64, max_new_tokens=8))
    assert inst._pick_horizon() == 1, \
        "a queued chunked prefill must not wait K steps"


def test_pick_horizon_drain_barrier_forces_one():
    inst = _sim_instance()
    _fake_decoding(inst)
    inst.begin_flip("P", 512)
    assert inst._pick_horizon() == 1, \
        "drain-and-flip needs per-step scheduling to evacuate"


def test_pick_horizon_hbm_guard():
    inst = _sim_instance(hbm_blocks=100)
    _fake_decoding(inst)
    inst.allocator.allocate(999, int(16 * 100 * HORIZON_HBM_GUARD) + 32)
    assert inst.allocator.utilization() > HORIZON_HBM_GUARD
    assert inst._pick_horizon() == 1, \
        "near the watermark, degradation must flow per-step"


def test_pick_horizon_tpot_headroom_bands():
    inst = _sim_instance(tpot_slo=0.030, tpot_alpha=1.0)
    _fake_decoding(inst, cur_tpot=0.010)         # 33% of threshold
    assert inst._pick_horizon(now=1.0) == 8
    inst.decoding.clear()
    _fake_decoding(inst, cur_tpot=0.020)         # ~67%
    assert inst._pick_horizon(now=1.0) == 4
    inst.decoding.clear()
    _fake_decoding(inst, cur_tpot=0.024)         # 80%
    assert inst._pick_horizon(now=1.0) == 2
    inst.decoding.clear()
    _fake_decoding(inst, cur_tpot=0.029)         # ~97%: about to flow
    assert inst._pick_horizon(now=1.0) == 1


def test_build_plan_budgets_capped_by_remaining_output():
    inst = _sim_instance()
    reqs = _fake_decoding(inst, n=2, out_len=8)
    reqs[0].max_new_tokens = 11                  # 3 tokens left
    reqs[0].hidden_output_len = None
    for r in reqs:
        inst.allocator.allocate(r.rid, r.context_len + 64)
    plan = inst.build_plan()
    assert plan.horizon == 8
    by_rid = dict(zip([r.rid for r in plan.decode_reqs],
                      plan.decode_budgets))
    assert by_rid[reqs[0].rid] == 3
    assert by_rid[reqs[1].rid] == 8


def test_build_plan_horizon_collapses_to_max_grant():
    inst = _sim_instance()
    reqs = _fake_decoding(inst, n=2, out_len=8)
    for r in reqs:
        r.max_new_tokens = 9                     # 1 token left each
        r.hidden_output_len = None
        inst.allocator.allocate(r.rid, r.context_len + 64)
    plan = inst.build_plan()
    assert plan.horizon == 1, \
        "no row can use K>1 — don't compile/waste an 8-step loop"


def test_horizon_timestamps_spread_like_k1(monkeypatch):
    """A K-horizon's tokens are stamped at the per-step modeled times,
    summing to the K=1 schedule's total — the in-flight TPOT signal
    then reads per-step latency, not duration/1."""
    inst = _sim_instance(max_horizon=4)
    req = Request(prompt_len=32, max_new_tokens=64, hidden_output_len=64,
                  prompt_tokens=list(range(1, 33)))
    inst.enqueue_prefill(req)
    inst.run_iteration(0.0)                      # prefill + first token
    inst.admit_decode(req)
    sink = []
    inst.token_sink = lambda r, t, tok: sink.append(t)
    dur, _, _ = inst.run_iteration(1.0)
    assert inst.last_horizon == 4 and len(sink) == 4
    assert all(b > a for a, b in zip(sink, sink[1:]))
    assert sink[-1] == pytest.approx(1.0 + dur)
    # per-step gaps equal the cost model's single-iteration times
    ctx = req.context_len - 4
    exp = [inst.cost.iteration_time([], [ctx + s]) for s in range(4)]
    gaps = [b - a for a, b in zip([1.0] + sink, sink)]
    assert gaps == pytest.approx(exp)
    assert req.current_tpot(sink[-1]) == pytest.approx(
        (sink[-1] - req.first_token_time) / (req.output_len - 1))


def test_sim_executor_step_async_contract():
    step = SimExecutor().step_async(plan=None)
    assert isinstance(step, ImmediateStep)
    assert step.ready() and not step.resolved
    assert step.resolve() == {} and step.resolved


# ---------------------------------------------------------------------------
# fast tier: async dispatch/commit cluster pipeline (sim oracle)
# ---------------------------------------------------------------------------

def _run_cluster(async_exec, horizon, qps=60, n=150, seed=0):
    sc = ServingConfig(policy="taichi",
                       sliders=Sliders(2, 2, 1024, 256),
                       hbm_blocks=8192)
    cluster = build_cluster(sc, BAL, seed=seed, async_exec=async_exec)
    if horizon > 1:
        cluster.set_horizon(horizon)
    reqs = SHAREGPT.sample_requests(n, qps, seed=seed)
    cluster.run(reqs)
    return cluster, reqs


def test_async_cluster_completes_all_requests():
    cluster, reqs = _run_cluster(async_exec=True, horizon=8)
    assert all(r.state == State.FINISHED for r in reqs)
    assert all(r.output_len == r.target_output_len for r in reqs)
    assert all(r.first_token_time <= r.last_token_time for r in reqs)
    assert any(i.horizon_peak > 1 for i in cluster.instances), \
        "the horizon never engaged"


def test_async_cluster_token_totals_match_sync():
    _, sync_reqs = _run_cluster(async_exec=False, horizon=1)
    _, async_reqs = _run_cluster(async_exec=True, horizon=8)
    assert (sum(r.output_len for r in sync_reqs)
            == sum(r.output_len for r in async_reqs))


def test_async_cluster_survives_role_flip():
    sc = ServingConfig(policy="taichi", sliders=Sliders(1, 1, 1024, 256),
                       hbm_blocks=8192)
    cluster = build_cluster(sc, BAL, async_exec=True)
    cluster.set_horizon(8)
    reqs = SHAREGPT.sample_requests(80, 40, seed=3)
    for r in reqs:
        cluster.submit(r)
    d_inst = next(i for i in cluster.instances if i.itype == "D")
    flipped = False
    while cluster.peek_time() is not None:
        cluster.step()
        if not flipped and d_inst.decoding:
            assert cluster.request_role_flip(d_inst, "P", 1024)
            flipped = True
    assert flipped and d_inst.itype == "P"
    assert all(r.state == State.FINISHED for r in reqs)
    assert all(r.output_len == r.target_output_len for r in reqs)


def test_async_serving_loop_telemetry_consistent():
    from repro.serving import ServingLoop
    sc = ServingConfig(policy="taichi", sliders=Sliders(2, 2, 1024, 256),
                       hbm_blocks=8192)
    cluster = build_cluster(sc, BAL, async_exec=True)
    cluster.set_horizon(8)
    arrivals = SHAREGPT.iter_requests(40, seed=1)
    loop = ServingLoop(cluster, BAL,
                       arrivals=(r for r, _ in zip(arrivals, range(60))))
    loop.run()
    assert all(r.state in (State.FINISHED, State.REJECTED)
               for r in loop.requests)
    done = [r for r in loop.requests if r.state == State.FINISHED]
    # every emitted token reached the telemetry sink, exactly once
    assert loop.telemetry.total_tokens == sum(r.output_len for r in done)
    assert loop.telemetry.total_finished == len(done)
    snap = loop.telemetry.snapshot(cluster.now, cluster.instances)
    assert {"horizon", "inflight"} <= set(snap["instances"][0])


# ---------------------------------------------------------------------------
# slow tier: token-exact parity on the real engine
# ---------------------------------------------------------------------------

jax = pytest.importorskip("jax")

from repro.configs import reduced_config                      # noqa: E402
from repro.core.estimator import CostModel                    # noqa: E402
from repro.core.hw import InstanceSpec                        # noqa: E402
from repro.core.instance import D_HEAVY, P_HEAVY              # noqa: E402
from repro.engine.engine import JaxExecutor                   # noqa: E402
from repro.models import transformer as tf                    # noqa: E402


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("smollm-135m")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    cost = CostModel(cfg, InstanceSpec(tp=1))
    return cfg, params, cost


def _prompts(cfg, seed, lengths=(13, 29, 7, 40)):
    rng = np.random.default_rng(seed)
    return [[int(x) for x in rng.integers(1, cfg.vocab_size, size=n)]
            for n in lengths]


def _generate(cfg, params, cost, prompts, n_out, *, max_horizon,
              paged=None, batched=True, eos_id=None, chunk=32,
              preempt_after=None, n_tokens=None):
    ex = JaxExecutor(cfg, params, n_slots=len(prompts) + 1, max_seq=256,
                     batched=batched, paged=paged, eos_id=eos_id,
                     t_buckets=(8, 16, 32))
    inst = Instance(0, D_HEAVY, chunk, cost, ex, hbm_blocks=512,
                    max_horizon=max_horizon)
    reqs = [Request(prompt_len=len(p),
                    max_new_tokens=n_tokens[i] if n_tokens else n_out,
                    hidden_output_len=None if eos_id is not None
                    else (n_tokens[i] if n_tokens else n_out),
                    prompt_tokens=list(p))
            for i, p in enumerate(prompts)]
    for r in reqs:
        inst.enqueue_prefill(r)
    preempted = False
    now, guard = 0.0, 0
    while not all(r.done() or r.state == State.FINISHED for r in reqs) \
            and guard < 500:
        dur, done, _ = inst.run_iteration(now)
        now += dur
        guard += 1
        for r in done:
            inst.admit_decode(r)
        if preempt_after is not None and not preempted:
            victim = reqs[0]
            if victim.rid in inst.decoding \
                    and victim.output_len >= preempt_after:
                inst._preempt(victim)
                preempted = True
    assert all(r.done() or r.state == State.FINISHED for r in reqs)
    if preempt_after is not None:
        assert preempted
    return [r.output_tokens for r in reqs], ex


@pytest.mark.slow
@pytest.mark.parametrize("paged", [True, False],
                         ids=["paged", "dense-packed"])
def test_horizon_k8_greedy_parity_vs_k1_oracle(setup, paged):
    cfg, params, cost = setup
    prompts = _prompts(cfg, 0)
    base, _ = _generate(cfg, params, cost, prompts, 24, max_horizon=1,
                        paged=paged)
    hor, ex = _generate(cfg, params, cost, prompts, 24, max_horizon=8,
                        paged=paged)
    assert hor == base, "K-step horizon must be greedy token-exact"
    assert ex.horizon_calls > 0, "the fused loop never ran"
    # the rowwise oracle agrees too
    ref, _ = _generate(cfg, params, cost, prompts, 24, max_horizon=1,
                       batched=False, paged=False)
    assert hor == ref


@pytest.mark.slow
def test_horizon_eos_mid_horizon_freezes_row(setup):
    cfg, params, cost = setup
    prompts = _prompts(cfg, 2, lengths=(17, 23))
    base, _ = _generate(cfg, params, cost, prompts, 20, max_horizon=1)
    # pick a token the first request emits mid-stream as EOS: the K=8
    # loop must freeze that row at the same step the K=1 oracle stops
    eos = base[0][10]
    k1, _ = _generate(cfg, params, cost, prompts, 20, max_horizon=1,
                      eos_id=eos)
    k8, _ = _generate(cfg, params, cost, prompts, 20, max_horizon=8,
                      eos_id=eos)
    assert k8 == k1
    assert len(k8[0]) <= 11 and k8[0][-1] == eos


@pytest.mark.slow
def test_horizon_single_token_and_uneven_budgets(setup):
    """max_new_tokens=1 finishes at prefill (never decodes); a 2-token
    request gets a 1-step budget inside a K=8 schedule."""
    cfg, params, cost = setup
    prompts = _prompts(cfg, 3, lengths=(9, 21, 33))
    n_tokens = [1, 2, 24]
    base, _ = _generate(cfg, params, cost, prompts, None,
                        max_horizon=1, n_tokens=n_tokens)
    hor, _ = _generate(cfg, params, cost, prompts, None,
                       max_horizon=8, n_tokens=n_tokens)
    assert hor == base
    assert [len(t) for t in hor] == n_tokens


@pytest.mark.slow
def test_horizon_preemption_recompute_parity(setup):
    cfg, params, cost = setup
    prompts = _prompts(cfg, 4, lengths=(23, 41))
    base, _ = _generate(cfg, params, cost, prompts, 16, max_horizon=1)
    pre, _ = _generate(cfg, params, cost, prompts, 16, max_horizon=8,
                       preempt_after=6)
    assert pre == base, (
        "preemption-by-recompute under a K-step horizon must recover "
        "the exact greedy stream (recompute_offset semantics)")


@pytest.mark.slow
def test_horizon_migration_round_trip_and_flush_guard(setup):
    cfg, params, cost = setup
    prompts = _prompts(cfg, 5, lengths=(19,))
    base, _ = _generate(cfg, params, cost, prompts, 40, max_horizon=1)

    def mk():
        ex = JaxExecutor(cfg, params, n_slots=2, max_seq=256, paged=True,
                         t_buckets=(8, 16, 32))
        return ex, Instance(0, D_HEAVY, 32, cost, ex, hbm_blocks=512,
                            max_horizon=8)
    ex_a, a = mk()
    ex_b, b = mk()
    req = Request(prompt_len=19, max_new_tokens=40, hidden_output_len=40,
                  prompt_tokens=list(prompts[0]))
    a.enqueue_prefill(req)
    now, guard = 0.0, 0
    while req.output_len < 7 and guard < 100:
        dur, done, _ = a.run_iteration(now)
        now += dur
        guard += 1
        for r in done:
            a.admit_decode(r)
    # pipeline-flush guard: an eject mid-flight must fail loudly
    assert a.dispatch_iteration(now) is not None
    with pytest.raises(RuntimeError, match="in flight"):
        ex_a.extract_state(req)
    res = a.commit_iteration()          # flush: now ejecting is legal
    assert res.duration > 0
    state = a.eject(req)
    b.inject(req, state)
    guard = 0
    while not req.done() and guard < 100:
        dur, _, _ = b.run_iteration(now)
        now += dur
        guard += 1
    assert req.output_tokens == base[0], (
        "migration between horizon engines must preserve the stream")
    assert ex_b.horizon_calls > 0


@pytest.mark.slow
def test_readbacks_per_token_bounded_by_horizon(setup):
    """Acceptance hook: in the decode phase, host readbacks per
    generated token <= 1/K."""
    cfg, params, cost = setup
    prompts = _prompts(cfg, 6, lengths=(11, 17, 23, 29))
    ex = JaxExecutor(cfg, params, n_slots=5, max_seq=256, paged=True,
                     t_buckets=(8, 16, 32))
    inst = Instance(0, D_HEAVY, 64, cost, ex, hbm_blocks=512,
                    max_horizon=8)
    reqs = [Request(prompt_len=len(p), max_new_tokens=33,
                    hidden_output_len=33, prompt_tokens=list(p))
            for p in prompts]
    for r in reqs:
        inst.enqueue_prefill(r)
    now, guard = 0.0, 0
    while any(r.prefill_remaining > 0 for r in reqs) and guard < 100:
        dur, done, _ = inst.run_iteration(now)
        now += dur
        guard += 1
        for r in done:
            inst.admit_decode(r)
    rb0, tok0 = ex.host_readbacks, inst.decode_token_count
    while not all(r.done() for r in reqs) and guard < 300:
        dur, _, _ = inst.run_iteration(now)
        now += dur
        guard += 1
    tokens = inst.decode_token_count - tok0
    readbacks = ex.host_readbacks - rb0
    # a few decode tokens may land before the window while other rows
    # still prefill; the bound is about the measured window itself
    assert tokens >= 100
    assert readbacks * 8 <= tokens, (
        f"{readbacks} readbacks for {tokens} tokens breaks the <=1/K "
        "acceptance bound")


@pytest.mark.slow
def test_async_live_loop_role_flip_token_parity():
    """The full stack — ServingLoop + async dispatch/commit cluster +
    K=8 horizons on the real engine — streams every token, survives a
    drain-and-flip, and matches the synchronous K=1 run token-for-
    token."""
    from repro.launch import serve
    from repro.serving import ServingLoop

    cfg = reduced_config("smollm-135m")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    slo = SLO(ttft=5.0, tpot=0.5)

    def live_loop(async_exec, horizon, sink=None):
        sc = ServingConfig(model="smollm-135m", tp=1, policy="taichi",
                           sliders=Sliders(n_p=1, n_d=1, s_p=64, s_d=32),
                           hbm_blocks=512)
        factory = lambda: JaxExecutor(cfg, params, n_slots=8, max_seq=512)
        cluster = build_cluster(sc, slo, executor_factory=factory,
                                async_exec=async_exec)
        cluster.set_horizon(horizon)
        arrivals = serve.TINY.iter_requests(4.0, seed=0,
                                            max_new_tokens=24, limit=8)
        return ServingLoop(cluster, slo, arrivals=arrivals,
                           on_token=sink)

    streamed = {}
    loop = live_loop(True, 8,
                     sink=lambda r, t, tok:
                     streamed.setdefault(r.rid, []).append(tok))
    cluster = loop.cluster
    d_inst = next(i for i in cluster.instances if i.itype == D_HEAVY)
    guard = 0
    while not d_inst.decoding and guard < 4000:
        assert loop.run(max_steps=5) > 0 or loop._arrivals is not None
        guard += 1
    assert loop.flip_role(d_inst, P_HEAVY, 64)
    loop.run()
    assert d_inst.itype == P_HEAVY and cluster.role_flip_count == 1
    assert all(r.state == State.FINISHED for r in loop.requests)
    for r in loop.requests:
        assert streamed[r.rid] == r.output_tokens

    base = live_loop(False, 1)
    base.run()
    assert len(base.requests) == len(loop.requests)
    for a, b in zip(loop.requests, base.requests):
        assert a.prompt_tokens == b.prompt_tokens
        assert a.output_tokens == b.output_tokens, (
            "async horizon pipeline must not perturb greedy streams")
    assert sum(getattr(i.executor, "horizon_calls", 0)
               for i in cluster.instances) > 0
