"""The generator offers every seed the same work in another order."""
import itertools

import numpy as np
import pytest

import traffic
from conftest import benchmark


@pytest.mark.parametrize("name", sorted({w["traffic"]
                                         for w in benchmark()["workloads"]}))
def test_every_seed_gets_the_same_block_of_sizes(name):
    mix = traffic.load_mix(name)
    n = mix["stratum"]
    blocks = {}
    for seed in (1, 2 ** 31 + 9, 2 ** 40 + 3):
        reqs = list(itertools.islice(traffic.requests(mix, seed, 1000), 2 * n))
        for b in range(2):
            part = reqs[b * n:(b + 1) * n]
            key = (tuple(sorted(len(r.prompt) for r in part)),
                   tuple(sorted(r.max_new for r in part)),
                   tuple(sorted(round(r.gap_s, 12) for r in part)))
            blocks.setdefault(b, set()).add(key)
        assert all(0 <= r.prompt.min() and r.prompt.max() < 1000 for r in reqs)
    assert all(len(keys) == 1 for keys in blocks.values())


def test_same_seed_same_requests_and_lengths_in_bounds():
    mix = traffic.load_mix("azure-conv")
    a = list(itertools.islice(traffic.requests(mix, 77, 5000), 40))
    b = list(itertools.islice(traffic.requests(mix, 77, 5000), 40))
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt) and x.max_new == y.max_new
    lens = traffic.length_quantiles(mix["prompt"], 32)
    assert lens.min() >= 16 and lens.max() <= 7168
    # the quantiles keep the lognormal's median
    assert np.median(lens) == pytest.approx(1020, rel=0.1)


def test_gaps_have_the_rate_they_are_scaled_to():
    mix = traffic.load_mix("azure-conv")
    # a block of n gaps spans exactly n at rate 1, for every seed
    assert traffic.gap_quantiles(mix, 10).sum() == pytest.approx(10.0)
    burst = dict(mix, arrivals="bursty", burst_factor=5.0)
    assert traffic.gap_quantiles(burst, 32).mean() == pytest.approx(
        (1 / 5 + 5) / 2)
    for seed in (4, 2 ** 35):
        reqs = list(itertools.islice(traffic.requests(mix, seed, 100, 10), 10))
        assert sum(r.gap_s for r in reqs) == pytest.approx(10.0)
