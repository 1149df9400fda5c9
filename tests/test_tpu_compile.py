"""Compile-only checks against a described (not attached) TPU v5e: the
paged attention kernels and whole served steps at real widths must pass
the chip's compiler — tiling, VMEM, memory — and keep the Pallas
kernels in the program (``tpu_custom_call``).  Nothing runs.

Native lowering is steered here, in the tests (``interpret=False``, or
the ``REPRO_KERNELS_INTERPRET`` override for the model path): on this
backend the program itself would pick the interpreter.  The topology is
described inside a fixture, never at import, so every test worker
collects the same tests.
"""
import os

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp                                   # noqa: E402
from jax.sharding import SingleDeviceSharding             # noqa: E402

from repro.configs import get_config                      # noqa: E402
from repro.kernels.chunked_prefill_attention.ops import \
    _paged_prefill                                        # noqa: E402
from repro.kernels.decode_attention.ops import _paged_decode  # noqa: E402

BLOCK = 16
NUM_BLOCKS = 4096            # a 65,536-token pool
NB = 64                      # table width: 1,024 tokens per row

#: (name, Hq, Hkv, D): smollm-135m whole, and qwen2.5-14b's share of one
#: chip in its tp=4 deployment (40/8 heads over 4 chips)
WIDTHS = [("smollm-135m", 9, 3, 64), ("qwen2.5-14b-tp4", 10, 2, 128)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip cannot read back what it writes to the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pools(sharding, hkv, d, quant):
    P = NUM_BLOCKS * BLOCK
    dt = jnp.int8 if quant else jnp.bfloat16
    kv = [_sds(sharding, (P, hkv, d), dt) for _ in range(2)]
    scales = ([_sds(sharding, (P, hkv), jnp.float32) for _ in range(2)]
              if quant else [None, None])
    return kv, scales


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("name,hq,hkv,d", WIDTHS,
                         ids=[w[0] for w in WIDTHS])
def test_paged_decode_compiles(one_chip, name, hq, hkv, d, quant):
    B = 16
    (kp, vp), (ks, vs) = _pools(one_chip, hkv, d, quant)
    compiled = _paged_decode.lower(
        _sds(one_chip, (B, hq, d), jnp.bfloat16), kp, vp,
        _sds(one_chip, (B, NB), jnp.int32), _sds(one_chip, (B,), jnp.int32),
        ks, vs, block_size=BLOCK, interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("name,hq,hkv,d", WIDTHS,
                         ids=[w[0] for w in WIDTHS])
def test_paged_prefill_compiles(one_chip, name, hq, hkv, d, quant):
    B, T = 4, 256
    (kp, vp), (ks, vs) = _pools(one_chip, hkv, d, quant)
    i32 = lambda *s: _sds(one_chip, s, jnp.int32)
    compiled = _paged_prefill.lower(
        _sds(one_chip, (B, T, hq, d), jnp.bfloat16), kp, vp, i32(B, NB),
        i32(B), i32(B), ks, vs, block_size=BLOCK, bq=128,
        interpret=False).compile()
    _assert_kernel(compiled)


#: the largest step ``--serve`` asks for by default: every one of
#: ServingConfig's 64 slots, a full 1,024-token P-heavy chunk, and the
#: whole block table of a 16,384-token context
SERVED_MAX = (64, 1024, 1024)


def _served_executor(one_chip, monkeypatch):
    """smollm-135m at published widths (30 layers, bf16), kernels on:
    the executor, its parameters, and a 65,536-token pool, as shapes."""
    from repro.engine import batching
    from repro.engine.engine import JaxExecutor
    from repro.models import attention
    from repro.models import transformer as tf
    from repro.sim.simulator import ServingConfig
    monkeypatch.setenv("REPRO_KERNELS_INTERPRET", "0")
    monkeypatch.setattr(attention, "_USE_KERNELS", True)
    cfg = get_config("smollm-135m")
    on_chip = lambda tree: jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype), tree)
    params = on_chip(tf.abstract_params(cfg))
    pool = on_chip(jax.eval_shape(
        lambda: tf.init_paged_cache(cfg, NUM_BLOCKS, BLOCK)))
    sc = ServingConfig(model=cfg.name)
    # a small real pool: only the jitted steps are lowered, on shapes
    ex = JaxExecutor(cfg, params, n_slots=sc.n_slots, max_seq=sc.max_ctx,
                     hbm_blocks=4, cache_block_size=BLOCK)
    assert (batching.bucket_batch(sc.n_slots),
            batching.bucket(sc.sliders.s_p, ex.t_buckets),
            ex.kv.max_blocks) == SERVED_MAX
    return ex, params, pool


def _pool_bytes(pool):
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))


@pytest.mark.parametrize("B,T,nb", [(16, 1, NB), (4, 256, NB), SERVED_MAX],
                         ids=["decode", "prefill", "served_max"])
def test_served_mixed_step_compiles(one_chip, monkeypatch, B, T, nb):
    """The executor's own fused mixed step, up to the served maximum."""
    ex, params, pool = _served_executor(one_chip, monkeypatch)
    i32 = lambda *s: _sds(one_chip, s, jnp.int32)
    compiled = ex._mixed_fused.lower(
        params, pool, i32(B, T), i32(B), i32(B), i32(B, nb),
        _sds(one_chip, (2,), jnp.uint32)).compile()
    _assert_kernel(compiled)
    # the step fits the chip beside its pool, within what the pool
    # sizing reserves for it
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1 << 30
    assert temp <= ex.transient_pools(1) * _pool_bytes(pool)


def test_served_horizon_fits_transient_pools(one_chip, monkeypatch):
    """The fused 8-step decode horizon at the served maximum holds its
    pool copies within the transients the pool sizing reserves."""
    ex, params, pool = _served_executor(one_chip, monkeypatch)
    B, _, nb = SERVED_MAX
    i32 = lambda *s: _sds(one_chip, s, jnp.int32)
    compiled = ex._horizon_paged.lower(
        params, pool, i32(B), i32(B), i32(B), i32(B, nb),
        _sds(one_chip, (2,), jnp.uint32), K=8).compile()
    _assert_kernel(compiled)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 2 * _pool_bytes(pool) < temp                  # the copies exist
    assert temp <= ex.transient_pools(8) * _pool_bytes(pool)
