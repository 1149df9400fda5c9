"""Seeded random weights, drawn on the device, in the layout the program
serves them in.

Every leaf is a function of (seed, leaf id, layer) alone, so the plain
reference can draw one layer again by itself after the program's state
is freed (``layer``) and get the very values the program served: it
takes nothing the program made.  ``all_params`` draws the whole tree in
one jitted call, in the configuration's dtype.  Which leaves a layer
has, which layers are stacked together and how the program nests them
is the architecture module's to say (``bench/arch/<arch>.py``:
``layer_shapes``, ``segments``, ``tree``); the embedding, the output
head and the final norm are drawn here.

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

# leaf ids: a leaf's values never depend on which other leaves exist; an
# architecture module numbers its layers' leaves from 10
EMBED, LM_HEAD, FINAL_NORM = 0, 1, 2
#: deviation of a bias, and of an RMSNorm gain's offset from 1
SMALL = 0.1


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


def layer(arch, conf: dict, key, l, like=None) -> dict:
    """Layer ``l``'s leaves, flat by name, in the configuration's dtype.
    Where ``l`` is traced (under ``vmap`` or ``jit``), ``like`` is a
    layer (a whole number) stacked with it, whose leaf shapes are drawn."""
    dt = dtype_of(conf)
    shapes = arch.layer_shapes(conf, l if like is None else like)
    return {name: _leaf(key, leaf, l, shape, scale, dt)
            for name, (leaf, shape, scale) in shapes.items()}


def like_of(arch, conf: dict) -> list:
    """For each layer, the ``like`` that ``layer`` takes for it: the
    first layer of the stack it is drawn in."""
    heads = {}
    for seg in arch.segments(conf):
        for layers in seg:
            heads.update((l, layers[0]) for l in layers)
    return [heads[l] for l in range(len(heads))]


def embed(arch, conf: dict, key):
    m = arch.dims(conf)
    return _leaf(key, EMBED, 0, (m["V"], m["d"]), 1.0, dtype_of(conf))


def lm_head(arch, conf: dict, key):
    """The output matrix [d, V] (the embedding's transpose when tied)."""
    if conf.get("tie_word_embeddings"):
        return embed(arch, conf, key).T
    m = arch.dims(conf)
    return _leaf(key, LM_HEAD, 0, (m["d"], m["V"]), m["d"] ** -0.5,
                 dtype_of(conf))


def final_norm(arch, conf: dict, key):
    return _leaf(key, FINAL_NORM, 0, (arch.dims(conf)["d"],), SMALL,
                 dtype_of(conf))


def _stacked(arch, conf: dict, key, layers) -> dict:
    """The program's tree of ``layers`` (alike), stacked on a leading
    axis."""
    flat = jax.vmap(lambda l: layer(arch, conf, key, l, like=layers[0]))(
        jnp.asarray(layers, jnp.uint32))
    return arch.tree(conf, layers[0], flat)


@functools.lru_cache(maxsize=None)
def _all_params_fn(arch, conf_json: str):
    import json
    conf = json.loads(conf_json)

    @jax.jit
    def make(seed_lo, seed_hi):
        key = base_key(seed_lo, seed_hi)
        return {"embed": embed(arch, conf, key),
                "lm_head": lm_head(arch, conf, key),
                "final_norm": final_norm(arch, conf, key),
                "segments": tuple(
                    tuple(_stacked(arch, conf, key, layers)
                          for layers in seg)
                    for seg in arch.segments(conf))}

    return make


def all_params(arch, conf: dict, seed: int) -> dict:
    """The whole parameter tree, made on the device in one jitted call."""
    import json
    return _all_params_fn(arch, json.dumps(conf, sort_keys=True))(
        *split_seed(seed))
