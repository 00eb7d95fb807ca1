"""The seeded traffic generator: repeatable per seed, the same work for
every seed, and any whole number as a seed."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import traffic  # noqa: E402

BIG = 2 ** 33 + 12345      # above what 32 signed bits hold


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_arrivals_repeat_per_seed(seed):
    a = traffic.arrival_times(seed, 2.3, 45)
    b = traffic.arrival_times(seed, 2.3, 45)
    np.testing.assert_array_equal(a, b)
    assert a[0] == 0.0 and (np.diff(a) > 0).all()


def test_every_schedule_seed_offers_the_same_gaps_in_another_order():
    n = round(2.3 * 45)
    quantiles = -np.log1p(-(np.arange(n) + 0.5) / n) / 2.3
    gaps = {s: np.diff(traffic.arrival_times(s, 2.3, 45))
            for s in (1, 2, BIG)}
    for g in gaps.values():
        assert len(g) == n - 1
        assert (np.abs(g[:, None] - quantiles[None, :]).min(1) < 1e-9).all()
    assert not np.allclose(gaps[1], gaps[2])


def test_arrival_gaps_are_exponential_quantiles():
    rate, n = 2.0, 1000
    t = traffic.arrival_times(3, rate, n / rate)
    gaps = np.diff(t)
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.02)
    # Poisson: the gaps' spread equals their mean.
    assert gaps.std() == pytest.approx(1 / rate, rel=0.05)
    assert t[-1] == pytest.approx(n / rate, rel=0.02)


def test_prompts_repeat_and_differ_by_seed_and_index():
    a = traffic.prompts(BIG, 3, 4, 16, 64000)
    np.testing.assert_array_equal(a, traffic.prompts(BIG, 3, 4, 16, 64000))
    assert a.shape == (4, 16) and a.dtype == np.int32
    assert ((a >= 0) & (a < 64000)).all()
    assert not np.array_equal(a, traffic.prompts(BIG, 4, 4, 16, 64000))
    assert not np.array_equal(a, traffic.prompts(BIG + 1, 3, 4, 16, 64000))
    # Seeds that agree in their low 32 bits still differ.
    assert not np.array_equal(a, traffic.prompts(12345, 3, 4, 16, 64000))
    warm = traffic.warm_up_prompts(BIG, 4, 16, 64000)
    assert not np.array_equal(a, warm)


def test_sample_is_seeded_and_sorted():
    s = traffic.sample(BIG, 100, 5)
    assert s == traffic.sample(BIG, 100, 5) and s == sorted(s)
    assert len(set(s)) == 5 and all(0 <= i < 100 for i in s)
    assert traffic.sample(1, 3, 5) == [0, 1, 2]


def test_quantile_interpolates_over_all_values():
    v = list(range(11))
    assert traffic.quantile(v, 0.9) == 9.0
    assert traffic.quantile([1.0, 2.0], 0.9) == pytest.approx(1.9)
    with pytest.raises(ValueError):
        traffic.quantile([], 0.5)
