"""The generator's open-loop arrivals: fixed by the seed, at the mix's mean
rate."""

import numpy as np
import pytest

from benchmark.traffic import BLOCK, Generator


class _Informer:
    def on_bind(self, fn):
        pass


def _gen(traffic, seed=2**31 + 5):
    return Generator(dict({"arrivals": "poisson", "lifetime_mean_s": 10},
                          **traffic), None, _Informer(), None, seed, 1)


def _arrivals(g, seconds):
    t, out = 0.0, []
    while t < seconds:
        t += g._gap()
        out.append(t)
    return np.array(out[:-1])


def test_seed_fixes_the_arrivals():
    a = _arrivals(_gen({"rate_per_s": 500}), 10)
    b = _arrivals(_gen({"rate_per_s": 500}), 10)
    c = _arrivals(_gen({"rate_per_s": 500}, seed=3), 10)
    assert np.array_equal(a, b) and not np.array_equal(a[:100], c[:100])


def test_mean_rate():
    a = _arrivals(_gen({"rate_per_s": 2000}), 20)
    assert len(a) / 20 == pytest.approx(2000, rel=0.03)


@pytest.mark.parametrize("draw", ["_gaps", "_lives"])
def test_every_seed_draws_the_same_blocks_in_its_own_order(draw):
    a = getattr(_gen({"rate_per_s": 500}), draw).take(2 * BLOCK)
    b = getattr(_gen({"rate_per_s": 500}, seed=3), draw).take(2 * BLOCK)
    assert a != b
    for lo in (0, BLOCK):
        assert sorted(a[lo:lo + BLOCK]) == sorted(b[lo:lo + BLOCK])
