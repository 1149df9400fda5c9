"""The seeded weights and the plain reference, on the CPU at a small
size: the reference draws the very weights the program is handed, and
agrees with the program's own forward pass in float32; the int8 control
does not."""
import json

import numpy as np
import pytest

import _paths  # noqa: F401

jax = pytest.importorskip("jax")
import jax.numpy as jnp                                 # noqa: E402

from bench.core import harness, reference, weights     # noqa: E402
from bench.core.manifest import ROOT                   # noqa: E402

SEED = 2 ** 33 + 5


@pytest.fixture(scope="module")
def tiny():
    return json.loads((ROOT / "tests" / "bench" / "tiny.json").read_text())


def test_split_seed_takes_any_whole_number():
    lo, hi = weights.split_seed(2 ** 40 + 3)
    assert int(lo) == 3 and int(hi) == 2 ** 8


def test_layers_drawn_alone_equal_the_stacked_draw(tiny):
    params = weights.all_params(tiny, SEED)
    block = params["segments"][0][0]
    key = weights.base_key(*weights.split_seed(SEED))
    for l in range(2):
        one = weights.layer(tiny, key, l)
        np.testing.assert_array_equal(one["wq"], block["attn"]["wq"][l])
        np.testing.assert_array_equal(one["w_down"], block["mlp"]["w_down"][l])
        np.testing.assert_array_equal(one["bk"], block["attn"]["bk"][l])
        np.testing.assert_array_equal(one["ln2"], block["ln2"][l])


def test_weights_match_the_program_tree(tiny):
    from repro.models import transformer as tf
    cfg = harness.model_config(tiny)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), tf.abstract_params(cfg))
    got = jax.tree.map(lambda a: (a.shape, a.dtype),
                       weights.all_params(tiny, SEED))
    assert want == got


def test_tied_head_is_the_embedding(tiny):
    conf = dict(tiny, tie_word_embeddings=True)
    p = weights.all_params(conf, SEED)
    np.testing.assert_array_equal(p["lm_head"], p["embed"].T)


def program_logits(conf, seq):
    from repro.models import transformer as tf
    cfg = harness.model_config(conf)
    params = weights.all_params(conf, SEED)
    logits, _, _ = tf.forward(params, cfg, jnp.asarray([seq], jnp.int32))
    return np.asarray(logits[0], np.float32)


def test_reference_agrees_with_the_program_in_float32(tiny):
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 256, 40).tolist()
    served = rng.integers(0, 512, 9).tolist()
    ref = reference.served_logits(tiny, SEED, [(prompt, served)])[0]
    prog = program_logits(tiny, prompt + served[:-1])[len(prompt) - 1:]
    assert ref.shape == (9, 512)
    scale = np.abs(ref).max()
    assert np.abs(ref - prog).max() <= 1e-4 * scale
    # the program's own choices lie on the reference's best
    assert reference.widest_gap(ref, prog.argmax(-1)) <= 1e-4 * scale
    ctrl = reference.served_logits(tiny, SEED, [(prompt, served)],
                                   precision="int8")[0]
    assert np.abs(ctrl - ref).max() > 100 * np.abs(ref - prog).max()


def test_widest_gap():
    ref = np.array([[1.0, 3.0, 2.0], [0.5, 0.0, -1.0]])
    assert reference.widest_gap(ref, [1, 0]) == 0.0
    assert reference.widest_gap(ref, [2, 2]) == 1.5


def test_gap_numbers_over_all_positions():
    ref = np.array([[1.0, 3.0, 2.0], [0.5, 0.0, -1.0]])
    nums = reference.gap_numbers([reference.gaps(ref, [2, 0]),
                                  reference.gaps(ref[:1], [1])])
    assert nums == pytest.approx({"widest_logit_gap": 1.0,
                                  "mean_logit_gap": 1.0 / 3,
                                  "off_best_pct": 100.0 / 3})
