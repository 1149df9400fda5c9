"""Operations and bytes the algorithm needs, computed from shapes.

The configuration's file gives the widths; a step's plan gives the rows:
prefill chunks as (start position, tokens) and decode rows as the
number of keys each attends over.  Nothing here reads the program: the
counts are what the math requires, not what an implementation happens
to do (padding rows, padded chunk lengths and bucketed block tables
are not work).  Matrix products count 2 operations per multiply-add.
"""
from __future__ import annotations

from . import weights as W

BF16 = 2


def matmul_flops_per_token(conf: dict) -> int:
    """Projection and MLP FLOPs of one token through every layer."""
    m = W.dims(conf)
    q, kv = m["hq"] * m["dh"], m["hkv"] * m["dh"]
    per_layer = 2 * (m["d"] * q + 2 * m["d"] * kv + q * m["d"]
                     + 3 * m["d"] * m["ff"])
    return per_layer * m["L"]


def head_flops(conf: dict) -> int:
    """FLOPs of one row of logits."""
    m = W.dims(conf)
    return 2 * m["d"] * m["V"]


def attn_flops(conf: dict, start: int, take: int) -> int:
    """QK^T and PV FLOPs of ``take`` causal queries at positions
    ``start..start+take-1`` (query i sees ``start + i + 1`` keys), all
    layers."""
    m = W.dims(conf)
    keys = take * start + take * (take + 1) // 2
    return 4 * m["hq"] * m["dh"] * keys * m["L"]


def attn_bytes(conf: dict, start: int, take: int) -> int:
    """Bytes an attention call must move for those queries, all layers:
    the K and V of every key read once, the queries read and the
    outputs written."""
    m = W.dims(conf)
    kv = 2 * (start + take) * m["hkv"] * m["dh"] * BF16
    qo = 2 * take * m["hq"] * m["dh"] * BF16
    return (kv + qo) * m["L"]


def step_model_flops(conf: dict, rows) -> int:
    """Model FLOPs of one step: ``rows`` is a list of (start, take), one
    per prefill chunk or decode token (take 1); each row also computes
    one row of logits, as the served step does."""
    per_tok = matmul_flops_per_token(conf)
    return sum(per_tok * take + attn_flops(conf, start, take)
               + head_flops(conf) for start, take in rows)


def roofline_seconds(flops: int, nbytes: int, peaks: dict) -> float:
    """Least time for one call: the larger of its compute and its memory
    bound."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
