"""Percentiles, the Poisson schedule and the interval union, against
fixed seeds and numbers worked by hand."""

import statistics

import pytest

import arith


def test_percentile_by_hand():
    xs = [15, 20, 35, 40, 50]
    assert arith.percentile(xs, 0) == 15
    assert arith.percentile(xs, 100) == 50
    assert arith.percentile(xs, 50) == 35
    assert arith.percentile(xs, 40) == pytest.approx(29.0)   # rank 1.6
    assert arith.percentile([1, 2, 3, 4], 50) == pytest.approx(2.5)
    assert arith.percentile([7], 99) == 7
    assert arith.median([3, 1, 2]) == 2


def test_percentile_is_numpys():
    np = pytest.importorskip("numpy")
    import random
    rng = random.Random(5)
    xs = [rng.random() for _ in range(1001)]
    for q in (1, 25, 50, 95, 99, 99.9):
        assert arith.percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)), rel=1e-12)


def test_percentile_of_nothing():
    with pytest.raises(ValueError):
        arith.percentile([], 50)


def test_poisson_schedule_fixed_seed():
    a = arith.poisson_schedule(100.0, 10.0, seed=3000000011)
    assert a == arith.poisson_schedule(100.0, 10.0, seed=3000000011)
    assert a != arith.poisson_schedule(100.0, 10.0, seed=3000000012)
    assert a == sorted(a) and 0 <= a[0] and a[-1] < 10_000_000_000
    assert abs(len(a) - 1000) < 4 * 1000 ** 0.5
    gaps = [b - c for b, c in zip(a[1:], a)]
    # exponential gaps: mean 10 ms, standard deviation about the mean
    assert statistics.mean(gaps) == pytest.approx(1e7, rel=0.1)
    assert statistics.pstdev(gaps) == pytest.approx(1e7, rel=0.15)


def test_union_seconds():
    assert arith.union_seconds([]) == 0
    # overlapping, nested, touching and apart
    iv = [(0, 10), (5, 10), (6, 2), (15, 5), (30, 5)]
    assert arith.union_seconds(iv) == pytest.approx(25e-9)
    assert arith.union_seconds(reversed(iv)) == pytest.approx(25e-9)
