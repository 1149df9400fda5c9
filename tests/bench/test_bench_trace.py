"""The trace reduction on small hand-made traces (nanoseconds)."""
import json
from types import SimpleNamespace

import pytest

import _paths  # noqa: F401
from bench.core import steps
from bench.core import trace as T
from bench.core.manifest import ROOT, load_arch

MANIFEST_CELL = json.loads((ROOT / "BENCHMARK.json").read_text())[
    "workloads"][0]["name"]


def test_union_merges_overlaps_and_clips():
    ops = [(0, 10), (5, 15), (20, 30), (29, 31), (40, 40)]
    assert T.merge(ops) == [(0, 15), (20, 31)]
    assert T.union_length(ops) == 26
    assert T.union_length(ops, 10, 25) == 10


def test_gaps_and_idle_share():
    ops = [(10, 20), (15, 30), (50, 60)]
    assert T.gaps(ops, 0, 100) == [(0, 10), (30, 50), (60, 100)]
    busy = T.union_length(ops, 0, 100)
    assert 1 - busy / 100 == 0.7


def test_gaps_are_named_by_the_host_span_covering_most():
    idle = [(0, 100), (200, 210), (300, 1300)]
    spans = [(0, 60, T.SLEEP), (50, 100, T.DISPATCH),
             (305, 1300, T.READBACK)]
    got = T.attribute_gaps(idle, spans, top=2)
    assert [n for n, _ in got] == [T.READBACK, T.SLEEP]
    assert [t for _, t in got] == pytest.approx([1000e-9, 100e-9])
    assert T.attribute_gaps([(200, 210)], spans)[0][0] == "host.other"


def test_op_name_from_hlo_text():
    assert T.op_name("%fusion.57 = (bf16[13824]) fusion(%a)") == "fusion.57"
    assert T.op_name("%_paged_decode.12 = bf16[1,8] custom-call(%x)") == \
        "_paged_decode.12"


def test_top_ops_counts_self_time_of_nested_ops():
    # a while op spans its body's ops on the same line
    ev = [(0, 100, "while.1"), (10, 40, "fusion.2"), (50, 60, "fusion.2"),
          (60, 90, "_paged_decode.3")]
    got = dict(T.top_ops(ev))
    assert got["fusion.2"] == pytest.approx(40e-9)
    assert got["_paged_decode.3"] == pytest.approx(30e-9)
    assert got["while.1"] == pytest.approx(30e-9)


def test_top_ops_sums_by_name():
    ev = [(0, 5, "a"), (5, 7, "b"), (7, 12, "a"), (12, 20, "c")]
    got = T.top_ops(ev, top=2)
    assert [n for n, _ in got] == ["a", "c"]
    assert [t for _, t in got] == pytest.approx([10e-9, 8e-9])


def test_match_modules_drops_executions_from_before_the_trace():
    # the first execution belongs to a dispatch made before the trace;
    # the last dispatch executes after it ends
    mods = [(5, 9, "mixed"), (12, 20, "horizon"), (21, 25, "mixed")]
    disp = [(10, "horizon", "h1"), (11, "mixed", "m2"),
            (24, "mixed", "m3")]
    got = T.match_modules(mods, disp)
    assert got == [(12, 20, "h1"), (21, 25, "m2")]


def test_match_modules_never_pairs_an_execution_before_its_dispatch():
    mods = [(0, 4, "mixed"), (6, 8, "mixed")]
    disp = [(5, "mixed", "a")]
    assert T.match_modules(mods, disp) == [(6, 8, "a")]


def _info(kernel, calls, prefill=(), K=1):
    return SimpleNamespace(kernel=kernel, calls=calls, prefill=list(prefill),
                           K=K, kind="horizon" if K > 1 else "mixed")


def test_roofline_share_sums_least_time_over_kernel_time():
    conf = {"hidden_size": 64, "intermediate_size": 128,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512}
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    tv = SimpleNamespace(ops=[], window=(0, 10_000_000))
    tv.ops_between = lambda lo, hi: [
        (100, 1100, "_paged_decode.7"),
        (1100, 1500, "copy_bitcast_fusion.4")]
    ctx = SimpleNamespace(trace=tv, conf=conf, peaks=peaks,
                          arch=load_arch(ROOT, {"arch": "qwen2"}))
    ctx._matched = [(0, 2000, _info("paged_decode", [[(9, 1), (3, 1)]]))]
    # keys 10 + 4; bytes: K,V of (10 + 4) keys and q,o of 2 queries,
    # 2 layers, bf16: memory-bound
    nbytes = (2 * 14 * 2 * 16 + 2 * 2 * 4 * 16) * 2 * 2
    want = 100 * (nbytes / 1e9) / 1000e-9
    assert abs(steps.roofline_share(ctx, "paged_decode") - want) < 1e-9
    assert steps.roofline_share(ctx, "paged_prefill") is None


def test_roofline_share_takes_the_kernel_prefixes_of_the_module():
    """An architecture module that names its own kernels' ops is read by
    those names, with its own counts; one that names none by the
    default's."""
    arch = SimpleNamespace(KERNELS={"paged_decode": "_latent_decode"},
                           attn_flops=lambda conf, a, b: 0,
                           attn_bytes=lambda conf, a, b: 100 * b)
    tv = SimpleNamespace(ops=[], window=(0, 10_000_000))
    tv.ops_between = lambda lo, hi: [
        (100, 600, "_latent_decode.3"), (600, 1100, "_paged_decode.7")]
    ctx = SimpleNamespace(trace=tv, conf={}, arch=arch,
                          peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9})
    ctx._matched = [(0, 2000, _info("paged_decode", [[(9, 1), (3, 1)]]))]
    # 200 bytes at 1e9 bytes/s over the 500 ns of ``_latent_decode``
    assert abs(steps.roofline_share(ctx, "paged_decode") - 40.0) < 1e-9
    del arch.KERNELS
    assert abs(steps.roofline_share(ctx, "paged_decode") - 40.0) < 1e-9
    tv.ops_between = lambda lo, hi: [(100, 600, "_latent_decode.3")]
    assert steps.roofline_share(ctx, "paged_decode") is None


def test_step_mfu_counts_the_steps_through_the_module():
    from bench.core import flops
    from bench.core.manifest import Cell
    arch = load_arch(ROOT, {"arch": "qwen2"})
    conf = json.loads((ROOT / "bench" / "configs" / "qwen2.5-14b-pp4.json")
                      .read_text())
    rows = [(0, 256), (511, 1)]
    info = SimpleNamespace(rows=rows)
    tv = SimpleNamespace(window_s=lambda: 2.0)
    ctx = SimpleNamespace(trace=tv, conf=conf, arch=arch,
                          peaks={"bf16_flops": 1e15}, _matched=[
                              (0, 10, info), (20, 30, info)])
    reader = Cell(ROOT, MANIFEST_CELL).reader("step_mfu")
    want = 100 * 2 * flops.step_model_flops(arch, conf, rows) / 2e15
    assert abs(reader.read(ctx) - want) < 1e-9
