"""The seeded weights and the plain reference, on the CPU at a small
size, for every architecture module (its tiny configuration): the
reference draws the very weights the program is handed, and agrees with
the program's own forward pass in float32; the int8 control does not."""
import numpy as np
import pytest

import _arch
import _paths  # noqa: F401

jax = pytest.importorskip("jax")
import jax.numpy as jnp                                 # noqa: E402

from bench.core import reference, weights              # noqa: E402
from bench.core.manifest import ROOT, load_arch        # noqa: E402

SEED = 2 ** 33 + 5


@pytest.fixture(params=_arch.ARCHS)
def tiny(request):
    """(architecture module, its tiny configuration)."""
    conf = _arch.tiny(request.param)
    return load_arch(ROOT, conf), conf


def test_split_seed_takes_any_whole_number():
    lo, hi = weights.split_seed(2 ** 40 + 3)
    assert int(lo) == 3 and int(hi) == 2 ** 8


def test_layers_drawn_alone_equal_the_stacked_draw(tiny):
    arch, conf = tiny
    params = weights.all_params(arch, conf, SEED)
    key = weights.base_key(*weights.split_seed(SEED))
    seen = []
    for s, seg in enumerate(arch.segments(conf)):
        for p, layers in enumerate(seg):
            stacked = params["segments"][s][p]
            for i, l in enumerate(layers):
                one = arch.tree(conf, l, weights.layer(arch, conf, key, l))
                row = jax.tree.map(lambda a: a[i], stacked)
                assert jax.tree.structure(one) == jax.tree.structure(row)
                for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(row)):
                    np.testing.assert_array_equal(a, b)
                seen.append(l)
    assert sorted(seen) == list(range(arch.dims(conf)["L"]))


def test_weights_match_the_program_tree(tiny):
    from repro.models import transformer as tf
    arch, conf = tiny
    cfg = arch.model_config(conf)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), tf.abstract_params(cfg))
    got = jax.tree.map(lambda a: (a.shape, a.dtype),
                       weights.all_params(arch, conf, SEED))
    assert want == got


def test_tied_head_is_the_embedding(tiny):
    arch, conf = tiny
    conf = dict(conf, tie_word_embeddings=True)
    p = weights.all_params(arch, conf, SEED)
    np.testing.assert_array_equal(p["lm_head"], p["embed"].T)


def program_logits(arch, conf, seq):
    from repro.models import transformer as tf
    cfg = arch.model_config(conf)
    params = weights.all_params(arch, conf, SEED)
    logits, _, _ = tf.forward(params, cfg, jnp.asarray([seq], jnp.int32))
    return np.asarray(logits[0], np.float32)


def test_reference_agrees_with_the_program_in_float32(tiny):
    arch, conf = tiny
    V = arch.dims(conf)["V"]
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, min(256, V), 40).tolist()
    served = rng.integers(0, V, 9).tolist()
    ref = reference.served_logits(arch, conf, SEED, [(prompt, served)])[0]
    prog = program_logits(arch, conf, prompt + served[:-1])[len(prompt) - 1:]
    assert ref.shape == (9, V)
    scale = np.abs(ref).max()
    assert np.abs(ref - prog).max() <= 1e-4 * scale
    # the program's own choices lie on the reference's best
    assert reference.widest_gap(ref, prog.argmax(-1)) <= 1e-4 * scale
    ctrl = reference.served_logits(arch, conf, SEED, [(prompt, served)],
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
