"""The plain reference: the configuration's decoder, written out in
``jax.numpy`` from its published description, in float32 with every
matrix product at ``highest`` precision.  It imports nothing of the
program and draws its weights again from the seed (``weights``).

Layer equations (Qwen2 / Llama): ``h = x + Attn(RMSNorm(x))``,
``x' = h + W_down(silu(W_gate n) * W_up n)`` with ``n = RMSNorm(h)``;
RMSNorm scales by its gain after ``x / sqrt(mean(x^2) + eps)``; RoPE
rotates the two halves of each head (``rotate_half``) at angle
``pos * theta^(-2i/d)``; grouped-query attention with causal masking;
q/k/v biases where the configuration has them; logits from the final
RMSNorm through the output head.

It runs layer by layer over a set of whole sequences (prompt plus the
served tokens, teacher-forced), so that one layer's weights and the
hidden states fit beside each other, and returns the logits at the
positions that chose each served token.

``precision="int8"`` is the control: the same network with every
matrix product in int8 (weights per output channel, activations per
token, symmetric, accumulated in int32) and attention in bfloat16 —
the step below the bfloat16 the configuration states.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HIGHEST = jax.lax.Precision.HIGHEST


def _quant(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def matmul(x, w, precision: str):
    """x [..., k] @ w [k, n] in float32 (``f32``) or int8 (``int8``)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "f32":
        return jnp.matmul(x, w, precision=HIGHEST)
    xq, xs = _quant(x, -1)
    wq, ws = _quant(w, 0)
    acc = jnp.matmul(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + gain.astype(jnp.float32))


def rope(x, theta):
    """x [S, H, D] at positions 0..S-1, rotate-half convention."""
    S, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block(conf: dict, w: dict, x, precision: str):
    """One decoder layer over one sequence x [S, d] (float32)."""
    m = W.dims(conf)
    S = x.shape[0]
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    mm = lambda a, b: matmul(a, b, precision)
    n = rms_norm(x, w["ln1"], eps)
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
    if precision == "f32":
        s = jnp.einsum("shd,thd->hst", q, k, precision=HIGHEST)
    else:
        s = jnp.einsum("shd,thd->hst", q.astype(jnp.bfloat16),
                       k.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    s = s * m["dh"] ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    if precision == "f32":
        o = jnp.einsum("hst,thd->shd", p, v, precision=HIGHEST)
    else:
        o = jnp.einsum("hst,thd->shd", p.astype(jnp.bfloat16),
                       v.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    h = x + mm(o.reshape(S, m["hq"] * m["dh"]), w["wo"])
    n = rms_norm(h, w["ln2"], eps)
    gate = mm(n, w["w_gate"])
    return h + mm(jax.nn.silu(gate) * mm(n, w["w_up"]), w["w_down"])


@functools.lru_cache(maxsize=None)
def _programs(conf_json: str, precision: str):
    conf = json.loads(conf_json)

    @jax.jit
    def layer_w(seed_lo, seed_hi, l):
        return W.layer(conf, W.base_key(seed_lo, seed_hi), l)

    @jax.jit
    def embed_rows(seed_lo, seed_hi, tokens):
        e = W.embed(conf, W.base_key(seed_lo, seed_hi))
        return jnp.take(e, tokens, axis=0).astype(jnp.float32)

    @jax.jit
    def run_block(w, x):
        return block(conf, w, x, precision)

    @functools.partial(jax.jit, static_argnames=("rows",))
    def head(seed_lo, seed_hi, x, start, rows):
        key = W.base_key(seed_lo, seed_hi)
        x = jax.lax.dynamic_slice_in_dim(x, start, rows)
        n = rms_norm(x, W.final_norm(conf, key), conf["rms_norm_eps"])
        return matmul(n, W.lm_head(conf, key), precision)

    return layer_w, embed_rows, run_block, head


def pad_len(n: int, step: int = 256) -> int:
    return -(-n // step) * step


def served_logits(conf: dict, seed: int, seqs, precision: str = "f32"):
    """Logits that chose each served token.

    ``seqs``: list of (prompt ids, served ids).  Returns, per sequence, a
    float32 array [len(served), V]: row j holds the logits at the
    position whose next token is served token j (the last prompt token
    for j = 0).  Sequences are padded at the end to one length, which
    causal attention leaves without effect on the positions read."""
    lo, hi = W.split_seed(seed)
    layer_w, embed_rows, run_block, head = _programs(
        json.dumps(conf, sort_keys=True), precision)
    fed = [list(p) + list(s[:-1]) for p, s in seqs]
    rows = pad_len(max(len(s) for _, s in seqs), 128)
    # room past the longest sequence for a whole block of head rows
    S = pad_len(max(len(p) for p, _ in seqs) + rows)
    xs = [embed_rows(lo, hi, jnp.asarray(f + [0] * (S - len(f)), jnp.int32))
          for f in fed]
    with jax.default_matmul_precision("highest"):
        for l in range(W.dims(conf)["L"]):
            w = layer_w(lo, hi, jnp.uint32(l))
            xs = [run_block(w, x) for x in xs]
            del w
        out = []
        for (p, s), x in zip(seqs, xs):
            logits = head(lo, hi, x, len(p) - 1, rows=rows)
            out.append(np.asarray(logits[:len(s)]))
    return out


#: positions whose served token lies further than this below the best
#: count as off the best in ``off_best_pct``
OFF_BEST = 0.01
GAP_NUMBERS = ("widest_logit_gap", "mean_logit_gap", "off_best_pct")


def gaps(ref_logits, tokens) -> np.ndarray:
    """At each position, the amount by which the chosen token's reference
    logit lies below the reference's best (0 where it is the best)."""
    ref = np.asarray(ref_logits, np.float64)
    chosen = ref[np.arange(len(tokens)), np.asarray(tokens)]
    return ref.max(axis=1) - chosen


def widest_gap(ref_logits, tokens) -> float:
    """Largest amount by which a chosen token's reference logit lies
    below the reference's best at that position."""
    return float(np.max(gaps(ref_logits, tokens)))


def gap_numbers(per_seq) -> dict:
    """The numbers a run's sample gives, over all its positions: the
    widest gap, the mean gap, and the share (%) of positions off the
    best by more than ``OFF_BEST``."""
    g = np.concatenate([np.asarray(x, np.float64) for x in per_seq])
    return {"widest_logit_gap": float(g.max()),
            "mean_logit_gap": float(g.mean()),
            "off_best_pct": float(100.0 * np.mean(g > OFF_BEST))}
