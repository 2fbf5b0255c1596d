"""The benchmark's copies of the length generators against their
sources in the program, the fixed-work property of its traffic (every
seed gets the same set of sizes), and the seed folding."""

import json

import numpy as np
import pytest

from chipbench.core import traffic, weights
from chipbench.tests.tiny import BENCH
from repro.serving import workload as prog_workload

BIG_SEEDS = [0, 7, 2**31 - 1, 2**31 + 17, 2**33 + 5, 2147483001]


@pytest.mark.parametrize("lo,hi,long_hi", [(16, 48, 64), (256, 1280, 2048)])
def test_lognormal_matches_source(lo, hi, long_hi):
    r1, r2 = np.random.default_rng(9), np.random.default_rng(9)
    a = [traffic.lognormal_length(r1, lo, hi, long_hi) for _ in range(500)]
    b = [prog_workload._prompt_length(r2, "lognormal", lo, hi, long_hi)
         for _ in range(500)]
    assert a == b


def test_uniform_matches_source():
    r1, r2 = np.random.default_rng(4), np.random.default_rng(4)
    a = [traffic.uniform_length(r1, 8, 32) for _ in range(300)]
    b = [prog_workload._prompt_length(r2, "uniform", 8, 32, 64)
         for _ in range(300)]
    assert a == b


def test_length_spec_median_and_clip():
    spec = {"dist": "lognormal", "median": 768, "min": 256, "max": 2048}
    rng = np.random.default_rng(0)
    xs = [traffic.draw_length(rng, spec) for _ in range(4000)]
    assert min(xs) >= 256 and max(xs) <= 2048
    assert 700 <= np.median(xs) <= 840


def test_fixed_sizes_same_set_for_every_seed():
    p = {"dist": "lognormal", "median": 32, "min": 16, "max": 64}
    o = {"dist": "uniform", "min": 512, "max": 1536}
    a = traffic.fixed_sizes(256, p, o, seed=1)
    b = traffic.fixed_sizes(256, p, o, seed=2**31 + 17)
    assert a != b and sorted(a) == sorted(b)


@pytest.mark.parametrize("seed", BIG_SEEDS)
def test_closed_loop_traffic_same_set_for_every_seed(seed):
    """The repository's closed-loop mix: every seed, large ones too, gets
    the same pool of sizes, each inside its file's limits."""
    tr = json.loads((BENCH / "traffic" / "decode-sat.json").read_text())
    pool = int(tr["pool"])
    a = traffic.fixed_sizes(pool, tr["prompt"], tr["output"],
                            weights.seed32(seed))
    b = traffic.fixed_sizes(pool, tr["prompt"], tr["output"], 12345)
    assert sorted(a) == sorted(b) and len(a) == pool
    assert all(tr["prompt"]["min"] <= p <= tr["prompt"]["max"] and
               tr["output"]["min"] <= o <= tr["output"]["max"] for p, o in a)


@pytest.mark.parametrize("seed", BIG_SEEDS)
def test_seed32_is_a_fixed_31_bit_seed(seed):
    s = weights.seed32(seed)
    assert 0 <= s < 2**31 and s == weights.seed32(seed)
    assert s != weights.seed32(seed + 1)
