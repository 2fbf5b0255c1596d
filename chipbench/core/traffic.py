"""Traffic generation, kept with the benchmark.

The length generators are copies of the ``lognormal`` / ``uniform``
branches of the program's ``repro.serving.workload._prompt_length``;
``chipbench/tests`` checks each copy against its source.  The program may
change them; the yardstick may not.

Every run of a cell does the same work: the sizes are drawn once from a
fixed master seed, and the run's ``--seed`` only reorders them (and picks
the token ids).  So two seeds differ in order, never in the set of
lengths.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

MASTER_SEED = 20240405


def lognormal_length(rng: np.random.Generator, lo: int, hi: int,
                     long_hi: int) -> int:
    """Median at the midpoint of ``[lo, hi]``, sigma 0.6, clipped to
    ``[lo, long_hi]``."""
    x = rng.lognormal(mean=math.log((lo + hi) / 2.0), sigma=0.6)
    return int(min(max(int(round(x)), lo), long_hi))


def uniform_length(rng: np.random.Generator, lo: int, hi: int) -> int:
    return int(rng.integers(lo, hi + 1))


def draw_length(rng: np.random.Generator, dist: Dict) -> int:
    """One length from a traffic file's length spec:
    ``{"dist": "lognormal", "median": m, "min": a, "max": b}`` (median
    m = (lo + hi) / 2 of the copied generator, clipped to [a, b]) or
    ``{"dist": "uniform", "min": a, "max": b}``."""
    kind = dist["dist"]
    if kind == "lognormal":
        m, a, b = int(dist["median"]), int(dist["min"]), int(dist["max"])
        return lognormal_length(rng, a, 2 * m - a, b)
    if kind == "uniform":
        return uniform_length(rng, int(dist["min"]), int(dist["max"]))
    raise ValueError(f"unknown length distribution {kind!r}")


def fixed_sizes(n: int, prompt: Dict, output: Dict,
                seed: int) -> List[Tuple[int, int]]:
    """``n`` (prompt_len, output_len) pairs: drawn from the master seed,
    shuffled by ``seed``."""
    rng = np.random.default_rng(MASTER_SEED)
    pairs = [(draw_length(rng, prompt), draw_length(rng, output))
             for _ in range(n)]
    order = np.random.default_rng(seed).permutation(n)
    return [pairs[i] for i in order]


def token_ids(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(0, vocab, n).tolist()
