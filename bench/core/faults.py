"""Faults planted under the timed path, for the tests and for
``bench/control.py``: each breaks what the served path produces in one
way a served cell can, and a run with it has to come out not
``correct``.  The benchmark's own runs never plant one.

Each takes the built engine and a list, and appends to the list each
time the fault is reached.
"""
import jax
import jax.numpy as jnp


def wrap_step(engine, change):
    """Replace every executor's fused step by ``change(orig, *args)``."""
    for inst in engine.cluster.instances:
        ex = inst.executor
        ex._mixed_fused = (lambda orig: lambda *a: change(orig, *a))(
            ex._mixed_fused)


def token_altered(engine, calls):
    """The first row's sampled token is changed where it is produced."""
    def change(orig, params, pool, tokens, start, valid, tables, key):
        calls.append(1)
        toks, pool = orig(params, pool, tokens, start, valid, tables, key)
        return (toks.at[0].add(1) % engine.cfg.vocab_size), pool
    wrap_step(engine, change)


def state_unchanged(engine, calls):
    """The step writes nothing to the KV pool (and so reads none of it)."""
    def change(orig, params, pool, tokens, start, valid, tables, key):
        calls.append(1)
        return orig(params, pool, tokens, start, valid,
                    jnp.full_like(tables, -1), key)
    wrap_step(engine, change)


def half_batch(engine, calls):
    """Only the first half of a step's rows is computed."""
    def change(orig, params, pool, tokens, start, valid, tables, key):
        B = valid.shape[0]
        if B > 1:
            calls.append(1)
            valid = valid.at[B // 2:].set(0)
        return orig(params, pool, tokens, start, valid, tables, key)
    wrap_step(engine, change)


def exchange_left_out(engine, calls):
    """A migrated request lands on its D-heavy instance without its KV."""
    for inst in engine.cluster.instances:
        ex = inst.executor

        def insert(req, state, orig=ex.insert_state):
            calls.append(1)
            state = dict(state, paged_blocks=jax.tree.map(
                jnp.zeros_like, state["paged_blocks"]))
            return orig(req, state)
        ex.insert_state = insert


ALL = {f.__name__: f for f in (token_altered, state_unchanged, half_batch,
                               exchange_left_out)}
