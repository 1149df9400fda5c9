"""Operations and bytes the algorithm needs, computed from shapes.

The configuration's file gives the widths; a step's plan gives the rows:
prefill chunks as (start position, tokens) and decode rows as the
number of keys each attends over.  Nothing here reads the program: the
counts are what the math requires, not what an implementation happens
to do (padding rows, padded chunk lengths and bucketed block tables
are not work).  Matrix products count 2 operations per multiply-add.

What a token's layers and attention cost is the architecture module's
to count (``bench/arch/<arch>.py``: ``matmul_flops_per_token``,
``attn_flops``, ``attn_bytes``); this file adds the logits and the
rows of a step, and bounds a call by the chip's peaks.
"""
from __future__ import annotations


def head_flops(arch, conf: dict) -> int:
    """FLOPs of one row of logits."""
    m = arch.dims(conf)
    return 2 * m["d"] * m["V"]


def step_model_flops(arch, conf: dict, rows) -> int:
    """Model FLOPs of one step: ``rows`` is a list of (start, take), one
    per prefill chunk or decode token (take 1); each row also computes
    one row of logits, as the served step does."""
    per_tok = arch.matmul_flops_per_token(conf)
    return sum(per_tok * take + arch.attn_flops(conf, start, take)
               + head_flops(arch, conf) for start, take in rows)


def roofline_seconds(flops: int, nbytes: int, peaks: dict) -> float:
    """Least time for one call: the larger of its compute and its memory
    bound."""
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
