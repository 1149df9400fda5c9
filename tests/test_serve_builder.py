"""The real-engine builder behind every serving entry point: published
widths by default and the smoke variant with ``--reduced``, kernels
chosen from the kernel backend, sizes from ``ServingConfig`` and the
command line, the compile-cache location, and the checks that keep a
device error or an unknown chip from passing silently."""
import types

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp                                   # noqa: E402

from repro.core import hw                                 # noqa: E402
from repro.core.latency import SLO                        # noqa: E402
from repro.core.policies import Sliders                   # noqa: E402
from repro.launch import serve                            # noqa: E402
from repro.models import attention                        # noqa: E402
from repro.models import transformer as tf                # noqa: E402

SLO_ = SLO(ttft=5.0, tpot=0.5)


def test_default_builds_published_widths():
    cfg = serve.model_config("smollm-135m")
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.head_dim, cfg.vocab_size) == (30, 576, 9, 3, 64, 49152)
    p = tf.abstract_params(cfg)          # shapes only, no weights
    assert p["embed"].shape == (49152, 576)
    assert p["embed"].dtype == jnp.bfloat16
    wq = p["segments"][0][0]["attn"]["wq"]
    assert wq.shape == (30, 576, 9 * 64)
    n = sum(a.size for a in jax.tree.leaves(p))
    assert n == cfg.param_count() + (2 * 30 + 1) * 576   # + norm gains


def test_reduced_builds_the_smoke_config():
    cfg = serve.model_config("smollm-135m", reduced=True)
    assert cfg.name == "smollm-135m-smoke"
    assert (cfg.num_layers, cfg.d_model, cfg.dtype) == (2, 256, "float32")
    p = tf.abstract_params(cfg)
    assert p["embed"].shape == (cfg.vocab_size, 256)
    assert p["embed"].dtype == jnp.float32


@pytest.mark.parametrize("env,kernels", [("1", False), ("0", True)])
def test_kernels_follow_the_backend(monkeypatch, env, kernels):
    monkeypatch.setattr(attention, "_USE_KERNELS", not kernels)
    monkeypatch.setenv("REPRO_KERNELS_INTERPRET", env)
    eng = serve.build_engine("smollm-135m", SLO_, reduced=True)
    assert eng.kernels is kernels
    assert attention._USE_KERNELS is kernels


def test_sizes_come_from_config_and_overrides(monkeypatch):
    monkeypatch.setattr(attention, "_USE_KERNELS", False)
    eng = serve.build_engine("smollm-135m", SLO_, reduced=True)
    assert (eng.sc.n_slots, eng.sc.max_ctx, eng.sc.hbm_blocks) == (
        8, 512, 512)
    ex = eng.cluster.instances[0].executor
    assert (ex.n_slots, ex.max_seq, ex.kv.num_blocks) == (8, 512, 512)
    assert eng.pool_bytes == sum(i.executor.cache_bytes()
                                 for i in eng.cluster.instances)
    eng = serve.build_engine(
        "smollm-135m", SLO_, reduced=True, n_slots=4, max_seq=256,
        hbm_blocks=64, sliders=Sliders(n_p=1, n_d=1, s_p=1024, s_d=256))
    assert len(eng.cluster.instances) == 2
    ex = eng.cluster.instances[0].executor
    assert (ex.n_slots, ex.max_seq, ex.kv.num_blocks) == (4, 256, 64)
    # the smoke model keeps its CPU-sized chunks
    assert (eng.sc.sliders.s_p, eng.sc.sliders.s_d) == (64, 32)


def _fake_device(stats):
    return types.SimpleNamespace(platform="tpu",
                                 memory_stats=lambda: stats)


def test_kv_pool_sized_from_device_memory():
    from repro.engine.paged import PagedKVCache
    cfg = serve.model_config("smollm-135m")
    gib = 1 << 30
    dev = _fake_device({"bytes_limit": 16 * gib, "bytes_in_use": gib})
    blocks = serve.kv_pool_blocks(cfg, 4, 16, device=dev)
    per_block = 16 * PagedKVCache.token_bytes_for(cfg)
    assert per_block == 16 * 30 * 2 * 3 * 64 * 2
    # four pools + one transient + the 2 GiB reserve fill what is free
    free = 16 * gib - gib - 2 * gib
    assert blocks == free // 5 // per_block
    assert 5 * blocks * per_block <= free < 5 * (blocks + 1) * per_block
    # the decode horizon's pool copies take the place of the one transient
    from repro.engine.engine import JaxExecutor
    assert JaxExecutor.transient_pools(1) == 1
    blocks = serve.kv_pool_blocks(
        cfg, 4, 16, device=dev,
        transient_pools=JaxExecutor.transient_pools(8))
    assert blocks == free // (4 + JaxExecutor.transient_pools(8)) // per_block


def test_kv_pool_needs_a_memory_limit():
    cfg = serve.model_config("smollm-135m")
    with pytest.raises(RuntimeError, match="hbm_blocks"):
        serve.kv_pool_blocks(cfg, 4, 16, device=_fake_device(None))
    tiny = _fake_device({"bytes_limit": 1 << 30, "bytes_in_use": 0})
    with pytest.raises(RuntimeError, match="no room"):
        serve.kv_pool_blocks(cfg, 4, 16, device=tiny)


def test_compile_cache_honours_the_environment(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert serve.compile_cache_dir() == "/elsewhere/cache"
    assert serve.enable_compile_cache() == "/elsewhere/cache"
    assert calls == []                   # left to JAX itself


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    import pathlib
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = pathlib.Path(serve.__file__).resolve().parents[3]
    assert (root / "src" / "repro" / "launch" / "serve.py").exists()
    want = str(root / ".jax_cache")
    assert serve.compile_cache_dir() == want
    assert serve.enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]


def test_exec_error_without_injector_exits_nonzero(capsys):
    cluster = types.SimpleNamespace(faults=None, exec_errors=1,
                                    last_exec_error="Traceback: boom")
    with pytest.raises(SystemExit) as e:
        serve.exit_on_exec_errors(cluster)
    assert e.value.code not in (0, None)
    assert "boom" in capsys.readouterr().err
    cluster.exec_errors = 0
    serve.exit_on_exec_errors(cluster)
    # an attached injector owns its errors: they are the experiment
    cluster.faults, cluster.exec_errors = object(), 3
    serve.exit_on_exec_errors(cluster)


def test_device_kind_peaks_are_looked_up():
    assert hw.hardware_for("TPU v5 lite") is hw.V5E
    with pytest.raises(ValueError, match="no peaks"):
        hw.hardware_for("TPU v4")
