"""The one traffic generator: turns a mix's data file and a seed into the
requests a run sends.

A mix (``bench/traffic/<name>.json``) gives the prompt and output length
distributions, the loop (open at a rate, or closed at a concurrency),
the ramp before the window, the drain allowed after it and the SLO.
A length is either ``{"fixed": n}`` or lognormal and clipped, as
``sim/workload.py``'s ``LengthDist`` draws them (copied here so that a
change there cannot move the benchmark).

Every seed sends the same work in the same order: the lengths (and, in
an open loop, the gaps between arrivals) are drawn from the mix's
``sizes_seed`` alone, and ``--seed`` only writes the prompt text.  A
window sends a prefix of the list, so a seed that reordered it would
change how much work the window holds.

No JAX here: the load generator's process imports this module.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import numpy as np

#: closed loops draw from a pool of this many sizes, cycled if exhausted
CLOSED_POOL = 4096
#: open loops extend the arrival schedule this far past the window
OPEN_TAIL_S = 5.0


@dataclasses.dataclass
class Planned:
    """One request as the client will send it."""
    index: int
    prompt: str
    max_tokens: int
    due: Optional[float]        # seconds after the client's start (open)


def lengths(dist: dict, rng, n: int) -> np.ndarray:
    """Token counts: ``n`` times a fixed count, or clipped lognormal
    draws, as ``LengthDist.sample``."""
    if "fixed" in dist:
        return np.full(n, int(dist["fixed"]))
    x = rng.lognormal(dist["mu"], dist["sigma"], size=n)
    return np.clip(x.astype(int), dist["lo"], dist["hi"])


def prompt_text(rng, n: int) -> str:
    """ASCII text of exactly ``n`` bytes (one byte-token each): lowercase
    words of 2 to 9 letters between single spaces."""
    letters = rng.integers(97, 123, size=n).astype(np.uint8)
    spaces = np.cumsum(rng.integers(3, 11, size=n // 3 + 1))
    spaces = spaces[spaces < n]
    letters[spaces] = 32
    return letters.tobytes().decode("ascii")


def _seed_rng(seed: int, stream: int):
    """A numpy generator for one stream of one seed (any whole number)."""
    return np.random.default_rng([stream, seed & (2 ** 63 - 1)])


def plan(traffic: dict, seed: int, seconds: float) -> List[Planned]:
    """The requests of one run, in the order they are sent."""
    loop = traffic["loop"]
    sizes = np.random.default_rng([traffic["sizes_seed"], 0])
    if loop["kind"] == "open":
        rate = float(loop["rate"])
        span = traffic["ramp_s"] + seconds + OPEN_TAIL_S
        # gaps drawn until they cover the span
        gaps = np.random.default_rng([traffic["sizes_seed"], 1]).exponential(
            1.0 / rate, size=int(rate * span * 4) + 64)
        n = int(np.searchsorted(np.cumsum(gaps), span)) + 1
        if n > len(gaps):
            raise ValueError("arrival draw too short for the span")
        gaps = gaps[:n]
    elif loop["kind"] == "closed":
        n = CLOSED_POOL
        gaps = None
    else:
        raise ValueError(f"unknown loop kind {loop['kind']!r}")
    plens = lengths(traffic["prompt"], sizes, n)
    olens = lengths(traffic["output"], sizes, n)
    text_rng = _seed_rng(seed, 1)
    due = None if gaps is None else np.cumsum(gaps)
    return [Planned(i, prompt_text(text_rng, int(plens[i])), int(olens[i]),
                    None if due is None else float(due[i]))
            for i in range(n)]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..100) of a non-empty list."""
    xs = sorted(values)
    k = max(math.ceil(q / 100.0 * len(xs)) - 1, 0)
    return xs[k]
