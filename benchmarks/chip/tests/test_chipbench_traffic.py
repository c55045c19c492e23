"""The traffic generator: seeded, repeatable, the same work for every
seed."""
import itertools
import json
from pathlib import Path
from statistics import NormalDist

import numpy as np
import pytest

from benchmarks.chip import traffic

CHIP = Path(__file__).resolve().parents[1]
MIX = {"loop": "closed", "clients": 2,
       "prompt": {"dist": "lognormal", "median": 1020, "sigma": 0.5},
       "output": {"dist": "lognormal", "median": 129, "sigma": 1.0}}
MIXES = sorted(p.stem for p in (CHIP / "traffic").glob("*.json"))


def _take(mix, seed, n):
    rng = np.random.default_rng(seed)
    return list(itertools.islice(traffic.requests(mix, 1000, rng), n))


def test_same_seed_repeats_exactly():
    a, b = _take(MIX, 2 ** 40 + 7, 40), _take(MIX, 2 ** 40 + 7, 40)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert x.max_new == y.max_new


def test_seeds_differ_in_order_not_in_work():
    n = 3 * traffic.BLOCK
    a, b = _take(MIX, 1, n), _take(MIX, 2, n)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    for i in range(0, n, traffic.BLOCK):
        blk_a, blk_b = a[i:i + traffic.BLOCK], b[i:i + traffic.BLOCK]
        assert sorted(len(r.prompt) for r in blk_a) == \
            sorted(len(r.prompt) for r in blk_b)
        assert sorted(r.max_new for r in blk_a) == \
            sorted(r.max_new for r in blk_b)


def test_lognormal_block_holds_its_quantiles():
    reqs = _take(MIX, 3, traffic.BLOCK)
    plens = sorted(len(r.prompt) for r in reqs)
    olens = sorted(r.max_new for r in reqs)
    z = NormalDist().inv_cdf((traffic.BLOCK - 0.5) / traffic.BLOCK)
    assert plens[-1] == traffic.longest(MIX["prompt"]) == \
        round(1020 * np.exp(0.5 * z))
    assert olens[0] == round(129 * np.exp(-1.0 * z))
    # the medians of the block straddle the distribution's median
    assert plens[7] < 1020 < plens[8] and olens[7] < 129 < olens[8]
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 1000
               for r in reqs)


@pytest.mark.parametrize("mix", [dict(MIX, loop="open"),
                                 dict(MIX, output={"dist": "uniform"})])
def test_an_unknown_loop_or_distribution_is_refused(mix):
    with pytest.raises(ValueError):
        next(traffic.requests(mix, 1000, np.random.default_rng(0)))


@pytest.mark.parametrize("name", MIXES)
def test_every_mix_file_names_its_source(name):
    mix = json.loads((CHIP / "traffic" / f"{name}.json").read_text())
    assert mix["source"] and mix["check_requests"] >= 1
    next(traffic.requests(mix, 1000, np.random.default_rng(0)))
