"""`--engine live` on the real JAX engine: a streamed run where
in-flight requests survive a drain-and-flip role change, with greedy
token parity against an unflipped run (the acceptance bar for the
online serving runtime)."""
import pytest

jax = pytest.importorskip("jax")

from repro.core.instance import D_HEAVY, P_HEAVY          # noqa: E402
from repro.core.latency import SLO                        # noqa: E402
from repro.core.policies import Sliders                   # noqa: E402
from repro.engine.engine import JaxExecutor               # noqa: E402
from repro.engine.request import State                    # noqa: E402
from repro.launch import serve                            # noqa: E402
from repro.models import transformer as tf                # noqa: E402
from repro.serving import ServingLoop                     # noqa: E402
from repro.sim.simulator import ServingConfig, build_cluster  # noqa: E402

BAL = SLO(ttft=5.0, tpot=0.5)          # loose: this test is about tokens
N_REQ = 10


@pytest.fixture(scope="module")
def setup():
    from repro.configs import reduced_config
    cfg = reduced_config("smollm-135m")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _live_loop(cfg, params, on_token=None):
    sc = ServingConfig(model="smollm-135m", tp=1, policy="taichi",
                       sliders=Sliders(n_p=1, n_d=1, s_p=64, s_d=32),
                       hbm_blocks=512)
    factory = lambda: JaxExecutor(cfg, params, n_slots=8, max_seq=512)
    cluster = build_cluster(sc, BAL, executor_factory=factory)
    arrivals = serve.TINY.iter_requests(4.0, seed=0, max_new_tokens=24,
                                        limit=N_REQ)
    return ServingLoop(cluster, BAL, arrivals=arrivals, on_token=on_token)


@pytest.mark.slow
def test_live_streamed_run_survives_role_flip(setup):
    cfg, params = setup
    streamed = {}
    loop = _live_loop(cfg, params,
                      on_token=lambda r, t, tok:
                      streamed.setdefault(r.rid, []).append(tok))
    cluster = loop.cluster
    d_inst = next(i for i in cluster.instances if i.itype == D_HEAVY)

    # drive until the D-heavy instance holds in-flight decodes
    guard = 0
    while not d_inst.decoding and guard < 4000:
        assert loop.run(max_steps=5) > 0 or loop._arrivals is not None
        guard += 1
    inflight = list(d_inst.decoding.values())
    assert inflight, "need in-flight decodes before the flip"
    mid_tokens = {r.rid: len(r.output_tokens) for r in inflight}
    assert loop.flip_role(d_inst, P_HEAVY, 64)
    loop.run()

    # the flip landed, in-flight requests migrated and completed
    assert d_inst.itype == P_HEAVY and cluster.role_flip_count == 1
    assert cluster.drain_count >= len(inflight)
    assert all(r.state == State.FINISHED for r in loop.requests)
    assert all(r.n_migrations >= 1 for r in inflight)
    for r in inflight:
        assert len(r.output_tokens) >= mid_tokens[r.rid]

    # streaming carried the real token ids, in order
    for r in loop.requests:
        assert streamed[r.rid] == r.output_tokens
        assert len(r.output_tokens) == r.output_len

    # greedy parity: the flipped run's tokens match an undisturbed run
    base = _live_loop(cfg, params)
    base.run()
    assert len(base.requests) == len(loop.requests)
    for a, b in zip(loop.requests, base.requests):
        assert a.prompt_tokens == b.prompt_tokens
        assert a.output_tokens == b.output_tokens, (
            "drain-and-flip must not perturb greedy token streams")


@pytest.mark.slow
def test_live_cli_smoke(setup, capsys, monkeypatch):
    # tests keep the persistent compile cache off
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    monkeypatch.setattr("sys.argv", [
        "serve", "--engine", "live", "--reduced", "--arch", "smollm-135m",
        "--qps", "4", "--n", "6", "--controller",
        "--ttft-slo", "5.0", "--tpot-slo", "0.5"])
    serve.main()
    out = capsys.readouterr().out
    assert '"engine": "smollm-135m-smoke"' in out
    assert '"streamed_tokens"' in out
    assert '"real_tokens"' in out
