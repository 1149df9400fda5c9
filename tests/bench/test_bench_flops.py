"""Operation and byte counts against values worked out by hand at both
configurations' shapes."""
import json

import _paths  # noqa: F401
from bench.core import flops
from bench.core.manifest import ROOT


#: smollm-135m's published widths (hf:HuggingFaceTB/SmolLM-135M)
SMOLLM = {"hidden_size": 576, "intermediate_size": 1536,
          "num_hidden_layers": 30, "num_attention_heads": 9,
          "num_key_value_heads": 3, "head_dim": 64, "vocab_size": 49152}


def conf(name):
    if name == "smollm-135m":
        return SMOLLM
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_qwen_stage_matmul_flops_per_token():
    c = conf("qwen2.5-14b-pp4")
    # q 5120x5120, k and v 5120x1024, o 5120x5120, MLP 3 x 5120x13824
    per_layer = 2 * (5120 * 5120 + 2 * 5120 * 1024 + 5120 * 5120
                     + 3 * 5120 * 13824)
    assert per_layer == 550_502_400
    assert flops.matmul_flops_per_token(c) == 12 * per_layer
    assert flops.head_flops(c) == 2 * 5120 * 152064


def test_smollm_matmul_flops_per_token():
    c = conf("smollm-135m")
    per_layer = 2 * (576 * 576 + 2 * 576 * 192 + 576 * 576
                     + 3 * 576 * 1536)
    assert flops.matmul_flops_per_token(c) == 30 * per_layer
    assert flops.head_flops(c) == 2 * 576 * 49152


def test_attention_counts_causal_keys():
    c = conf("qwen2.5-14b-pp4")
    # one decode token at position 99 sees 100 keys
    assert flops.attn_flops(c, 99, 1) == 4 * 40 * 128 * 100 * 12
    # a 3-token chunk from position 10 sees 11 + 12 + 13 keys
    assert flops.attn_flops(c, 10, 3) == 4 * 40 * 128 * 36 * 12
    # K and V of 13 keys (8 heads of 128) and q, o of 3 tokens (40 heads)
    assert flops.attn_bytes(c, 10, 3) == \
        (2 * 13 * 8 * 128 * 2 + 2 * 3 * 40 * 128 * 2) * 12


def test_smollm_attention_bytes():
    c = conf("smollm-135m")
    assert flops.attn_bytes(c, 511, 1) == \
        (2 * 512 * 3 * 64 * 2 + 2 * 1 * 9 * 64 * 2) * 30


def test_step_model_flops_adds_one_logit_row_per_chunk_or_token():
    c = conf("smollm-135m")
    rows = [(0, 4), (7, 1)]
    want = (flops.matmul_flops_per_token(c) * 5
            + flops.attn_flops(c, 0, 4) + flops.attn_flops(c, 7, 1)
            + 2 * flops.head_flops(c))
    assert flops.step_model_flops(c, rows) == want


def test_roofline_takes_the_larger_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(1000, 50, peaks) == 10.0
    assert flops.roofline_seconds(100, 50, peaks) == 5.0
