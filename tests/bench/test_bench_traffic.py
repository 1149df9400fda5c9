"""The copied generator: deterministic in the seed, the same work for
every seed, prompts of exactly their token count."""
import json

import _paths  # noqa: F401
from bench.core import client, traffic
from bench.core.manifest import ROOT


#: ShareGPT lengths as TaiChi fits them (``sim/workload.py`` SHAREGPT)
SHAREGPT = {"prompt": {"mu": 5.5, "sigma": 1.1, "lo": 8, "hi": 2048},
            "output": {"mu": 5.3, "sigma": 0.9, "lo": 4, "hi": 1024},
            "loop": {"kind": "closed", "concurrency": 8},
            "ramp_s": 60, "drain_cap_s": 60, "sizes_seed": 0,
            "slo": {"ttft_s": 1.5, "tpot_s": 0.03}}


def mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def open_mix(rate=3.0):
    """The ShareGPT lengths in an open loop (no committed cell uses one
    yet; the generator serves both kinds)."""
    return dict(SHAREGPT, loop={"kind": "open", "rate": rate})


def test_same_seed_same_requests():
    m = open_mix()
    a = traffic.plan(m, 2 ** 31 + 12345, 40)
    b = traffic.plan(m, 2 ** 31 + 12345, 40)
    assert [(r.prompt, r.max_tokens, r.due) for r in a] == \
        [(r.prompt, r.max_tokens, r.due) for r in b]


def test_seeds_permute_one_set_of_sizes_and_gaps():
    """Seeds send one list of sizes and arrivals in one order (a window
    sends a prefix of it); only the prompt text differs."""
    for m in (open_mix(), SHAREGPT):
        a = traffic.plan(m, 1, 40)
        b = traffic.plan(m, 9_000_000_000, 40)
        assert [(len(r.prompt), r.max_tokens, r.due) for r in a] == \
            [(len(r.prompt), r.max_tokens, r.due) for r in b]
        assert [r.prompt for r in a] != [r.prompt for r in b]


def test_lengths_follow_the_mix_bounds():
    m = SHAREGPT
    reqs = traffic.plan(m, 7, 40)
    assert len(reqs) == traffic.CLOSED_POOL
    assert all(r.due is None for r in reqs)
    assert all(8 <= len(r.prompt) <= 2048 for r in reqs)
    assert all(4 <= r.max_tokens <= 1024 for r in reqs)
    assert all(r.prompt.isascii() for r in reqs[:50])


def test_fixed_lengths_of_the_committed_mix():
    m = mix("fixed-512-128.closed-16")
    reqs = traffic.plan(m, 2 ** 31 + 99, 30)
    assert {(len(r.prompt), r.max_tokens) for r in reqs} == {(512, 128)}
    assert m["loop"] == {"kind": "closed", "concurrency": 16, "stagger_s": 30}


def test_open_loop_covers_ramp_and_window():
    m = open_mix()
    reqs = traffic.plan(m, 3, 40)
    assert reqs[-1].due >= m["ramp_s"] + 40
    assert all(x.due < y.due for x, y in zip(reqs, reqs[1:]))


def test_client_counts_marker_and_byte_tokens():
    assert client.count_tokens("⟨300⟩ab⟨49151⟩") == 4
    assert client.count_tokens("é") == 2          # two UTF-8 bytes
    assert client.count_tokens("�") == 1


def test_nearest_rank_percentile():
    xs = list(range(1, 11))
    assert traffic.percentile(xs, 90) == 9
    assert traffic.percentile(xs, 50) == 5
    assert traffic.percentile([3.0], 90) == 3.0
