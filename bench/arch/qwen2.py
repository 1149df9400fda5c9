"""Qwen2: a dense grouped-query-attention decoder (Qwen2.5, also Llama's
layer with q/k/v biases switched off).

The functions below are what ``bench/core`` asks of an architecture
module (``manifest.load_arch``); a configuration names this one by
``"arch": "qwen2"``.

Layer equations (Qwen2 / Llama): ``h = x + Attn(RMSNorm(x))``,
``x' = h + W_down(silu(W_gate n) * W_up n)`` with ``n = RMSNorm(h)``;
RoPE rotates the two halves of each head (``rotate_half``) at angle
``pos * theta^(-2i/d)``; grouped-query attention with causal masking;
q/k/v biases where the configuration has ``attention_bias``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.core import reference as R
from bench.core import weights as W

# leaf ids: a leaf's values never depend on which other leaves exist
LN1, WQ, WK, WV, WO, BQ, BK, BV, LN2, W_GATE, W_UP, W_DOWN = range(10, 22)
#: bytes of one cached K or V value
BF16 = 2


def dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    dh = conf.get("head_dim") or d // hq
    return dict(d=d, hq=hq, hkv=hkv, dh=dh, ff=conf["intermediate_size"],
                V=conf["vocab_size"], L=conf["num_hidden_layers"])


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def layer_shapes(conf: dict, l: int) -> dict:
    """(leaf id, shape, scale) of every leaf of a layer (all alike)."""
    m = dims(conf)
    d, q, kv, ff = m["d"], m["hq"] * m["dh"], m["hkv"] * m["dh"], m["ff"]
    out = {"ln1": (LN1, (d,), W.SMALL), "ln2": (LN2, (d,), W.SMALL),
           "wq": (WQ, (d, q), d ** -0.5), "wk": (WK, (d, kv), d ** -0.5),
           "wv": (WV, (d, kv), d ** -0.5), "wo": (WO, (q, d), q ** -0.5),
           "w_gate": (W_GATE, (d, ff), d ** -0.5),
           "w_up": (W_UP, (d, ff), d ** -0.5),
           "w_down": (W_DOWN, (ff, d), ff ** -0.5)}
    if conf.get("attention_bias"):
        out.update(bq=(BQ, (q,), W.SMALL), bk=(BK, (kv,), W.SMALL),
                   bv=(BV, (kv,), W.SMALL))
    return out


def segments(conf: dict):
    """One segment of one attention block, stacked over every layer."""
    return ((tuple(range(dims(conf)["L"])),),)


def tree(conf: dict, l: int, flat: dict) -> dict:
    attn = {k: flat[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in flat}
    return {"ln1": flat["ln1"], "attn": attn, "ln2": flat["ln2"],
            "mlp": {k: flat[k] for k in ("w_gate", "w_up", "w_down")}}


def model_config(conf: dict):
    from repro.models.config import ModelConfig
    m = dims(conf)
    return ModelConfig(
        name=conf["name"], family="dense", num_layers=m["L"],
        d_model=m["d"], num_heads=m["hq"], num_kv_heads=m["hkv"],
        d_ff=m["ff"], vocab_size=m["V"], head_dim=m["dh"],
        qkv_bias=bool(conf.get("attention_bias")),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        dtype=conf.get("torch_dtype", "bfloat16"), source=conf["source"])


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def rope(x, theta):
    """x [S, H, D] at positions 0..S-1, rotate-half convention."""
    S, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block(conf: dict, l: int, w: dict, x, precision: str):
    """One decoder layer over one sequence x [S, d] (float32)."""
    m = dims(conf)
    S = x.shape[0]
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    mm = lambda a, b: R.matmul(a, b, precision)
    n = R.rms_norm(x, w["ln1"], eps)
    q, k, v = mm(n, w["wq"]), mm(n, w["wk"]), mm(n, w["wv"])
    if "bq" in w:
        q = q + w["bq"].astype(jnp.float32)
        k = k + w["bk"].astype(jnp.float32)
        v = v + w["bv"].astype(jnp.float32)
    q = rope(q.reshape(S, m["hq"], m["dh"]), theta)
    k = rope(k.reshape(S, m["hkv"], m["dh"]), theta)
    v = v.reshape(S, m["hkv"], m["dh"])
    g = m["hq"] // m["hkv"]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    o = R.attention(q, k, v, m["dh"] ** -0.5, precision)
    h = x + mm(o.reshape(S, m["hq"] * m["dh"]), w["wo"])
    n = R.rms_norm(h, w["ln2"], eps)
    gate = mm(n, w["w_gate"])
    return h + mm(jax.nn.silu(gate) * mm(n, w["w_up"]), w["w_down"])


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------

def matmul_flops_per_token(conf: dict) -> int:
    """Projection and MLP FLOPs of one token through every layer."""
    m = dims(conf)
    q, kv = m["hq"] * m["dh"], m["hkv"] * m["dh"]
    per_layer = 2 * (m["d"] * q + 2 * m["d"] * kv + q * m["d"]
                     + 3 * m["d"] * m["ff"])
    return per_layer * m["L"]


def attn_flops(conf: dict, start: int, take: int) -> int:
    """QK^T and PV FLOPs of ``take`` causal queries at positions
    ``start..start+take-1`` (query i sees ``start + i + 1`` keys), all
    layers."""
    m = dims(conf)
    keys = take * start + take * (take + 1) // 2
    return 4 * m["hq"] * m["dh"] * keys * m["L"]


def attn_bytes(conf: dict, start: int, take: int) -> int:
    """Bytes an attention call must move for those queries, all layers:
    the K and V of every key read once, the queries read and the
    outputs written."""
    m = dims(conf)
    kv = 2 * (start + take) * m["hkv"] * m["dh"] * BF16
    qo = 2 * take * m["hq"] * m["dh"] * BF16
    return (kv + qo) * m["L"]
