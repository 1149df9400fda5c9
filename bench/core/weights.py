"""Seeded random weights, drawn on the device, in the layout the program
serves them in.

Every leaf is a function of (seed, leaf id, layer) alone, so the plain
reference can draw one layer again by itself after the program's state
is freed (``layer``) and get the very values the program served: it
takes nothing the program made.  ``all_params`` draws the whole tree in
one jitted call, in the configuration's dtype.

Values are uniform and centred: weight matrices with standard deviation
``fan_in ** -0.5``, the embedding with deviation 1, biases with 0.1, and
each RMSNorm gain is 1 plus an offset of deviation 0.1 (the program
stores the offset from 1).  They are built from random integers with a
single rounding, so a layer drawn alone and the same layer drawn in the
stacked call are equal bit for bit.  With
``tie_word_embeddings`` the output head is the embedding's transpose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# leaf ids: a leaf's values never depend on which other leaves exist
EMBED, LM_HEAD, FINAL_NORM = 0, 1, 2
LN1, WQ, WK, WV, WO, BQ, BK, BV, LN2, W_GATE, W_UP, W_DOWN = range(10, 22)
SMALL = 0.1


def dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    hq, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    dh = conf.get("head_dim") or d // hq
    return dict(d=d, hq=hq, hkv=hkv, dh=dh, ff=conf["intermediate_size"],
                V=conf["vocab_size"], L=conf["num_hidden_layers"])


def dtype_of(conf: dict):
    return jnp.dtype(conf.get("torch_dtype", "bfloat16"))


def base_key(seed_lo, seed_hi):
    """The run's key from the two 32-bit halves of ``--seed``."""
    return jax.random.fold_in(jax.random.PRNGKey(seed_lo), seed_hi)


def split_seed(seed: int):
    """``--seed`` (any whole number) as two uint32 halves."""
    s = seed % (1 << 64)
    return (jnp.uint32(s & 0xFFFFFFFF), jnp.uint32(s >> 32))


def _uniform(key, shape, std, dtype):
    """Uniform values of deviation ``std``, computed so that any program
    gets the same bits: a random 24-bit integer (exact in float32) times
    one constant, then rounded to ``dtype``."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    centred = (bits >> 8).astype(jnp.int32) - (1 << 23)
    step = std * 3.0 ** 0.5 / (1 << 23)
    return (centred.astype(jnp.float32) * jnp.float32(step)).astype(dtype)


def _leaf(key, leaf, layer, shape, scale, dtype):
    k = jax.random.fold_in(jax.random.fold_in(key, leaf), layer)
    return _uniform(k, shape, scale, dtype)


def layer_shapes(conf: dict) -> dict:
    """(leaf id, shape, scale) of every per-layer leaf, by name."""
    m = dims(conf)
    d, q, kv, ff = m["d"], m["hq"] * m["dh"], m["hkv"] * m["dh"], m["ff"]
    out = {"ln1": (LN1, (d,), SMALL), "ln2": (LN2, (d,), SMALL),
           "wq": (WQ, (d, q), d ** -0.5), "wk": (WK, (d, kv), d ** -0.5),
           "wv": (WV, (d, kv), d ** -0.5), "wo": (WO, (q, d), q ** -0.5),
           "w_gate": (W_GATE, (d, ff), d ** -0.5),
           "w_up": (W_UP, (d, ff), d ** -0.5),
           "w_down": (W_DOWN, (ff, d), ff ** -0.5)}
    if conf.get("attention_bias"):
        out.update(bq=(BQ, (q,), SMALL), bk=(BK, (kv,), SMALL),
                   bv=(BV, (kv,), SMALL))
    return out


def layer(conf: dict, key, l) -> dict:
    """One layer's leaves, flat by name, in the configuration's dtype."""
    dt = dtype_of(conf)
    return {name: _leaf(key, leaf, l, shape, scale, dt)
            for name, (leaf, shape, scale) in layer_shapes(conf).items()}


def embed(conf: dict, key):
    m = dims(conf)
    return _leaf(key, EMBED, 0, (m["V"], m["d"]), 1.0, dtype_of(conf))


def lm_head(conf: dict, key):
    """The output matrix [d, V] (the embedding's transpose when tied)."""
    if conf.get("tie_word_embeddings"):
        return embed(conf, key).T
    m = dims(conf)
    return _leaf(key, LM_HEAD, 0, (m["d"], m["V"]), m["d"] ** -0.5,
                 dtype_of(conf))


def final_norm(conf: dict, key):
    return _leaf(key, FINAL_NORM, 0, (dims(conf)["d"],), SMALL,
                 dtype_of(conf))


def _program_tree(conf: dict, flat: dict) -> dict:
    """The program's nesting of one (stacked) layer's leaves."""
    attn = {k: flat[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in flat}
    return {"ln1": flat["ln1"], "attn": attn, "ln2": flat["ln2"],
            "mlp": {k: flat[k] for k in ("w_gate", "w_up", "w_down")}}


@functools.lru_cache(maxsize=None)
def _all_params_fn(conf_json: str):
    import json
    conf = json.loads(conf_json)
    L = dims(conf)["L"]

    @jax.jit
    def make(seed_lo, seed_hi):
        key = base_key(seed_lo, seed_hi)
        stacked = jax.vmap(lambda l: layer(conf, key, l))(
            jnp.arange(L, dtype=jnp.uint32))
        return {"embed": embed(conf, key), "lm_head": lm_head(conf, key),
                "final_norm": final_norm(conf, key),
                "segments": ((_program_tree(conf, stacked),),)}

    return make


def all_params(conf: dict, seed: int) -> dict:
    """The whole parameter tree, made on the device in one jitted call."""
    import json
    return _all_params_fn(json.dumps(conf, sort_keys=True))(
        *split_seed(seed))
