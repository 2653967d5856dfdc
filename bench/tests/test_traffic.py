"""The open-loop schedule repeats from its seed, and every seed offers
the same amount of work."""
import numpy as np
import pytest

from bench import traffic

MIX = {"arrivals": "poisson", "fractions_log2": [-10, 0], "noise": 0.25}


def _corpus(n=4096, d=8):
    rng = np.random.default_rng(0)
    return (rng.normal(size=(n, d)).astype(np.float32),
            rng.permutation(n).astype(np.float64))


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_schedule_repeats_from_seed(seed):
    v, a = _corpus()
    s1 = traffic.query_schedule(MIX, v, a, rate=300, seconds=2, seed=seed)
    s2 = traffic.query_schedule(MIX, v, a, rate=300, seconds=2, seed=seed)
    for f in ("due", "queries", "ranges", "fractions"):
        np.testing.assert_array_equal(getattr(s1, f), getattr(s2, f))


def test_every_seed_gets_the_same_work():
    v, a = _corpus()
    s1 = traffic.query_schedule(MIX, v, a, rate=300, seconds=2, seed=1)
    s2 = traffic.query_schedule(MIX, v, a, rate=300, seconds=2, seed=2)
    assert len(s1.due) == len(s2.due) == 600
    assert not np.array_equal(s1.due, s2.due)
    np.testing.assert_array_equal(np.sort(s1.fractions), np.sort(s2.fractions))
    assert np.all(np.diff(s1.due) >= 0) and 0 <= s1.due[0] and s1.due[-1] < 2


def test_ranges_hold_their_fraction_of_rows():
    v, a = _corpus()
    s = traffic.query_schedule(MIX, v, a, rate=500, seconds=1, seed=3)
    held = ((a[None, :] >= s.ranges[:, :1]) & (a[None, :] <= s.ranges[:, 1:])).sum(1)
    np.testing.assert_array_equal(held, np.maximum(1, np.floor(len(a) * s.fractions)))


def test_negative_seed_is_refused():
    with pytest.raises(ValueError):
        traffic.stream(-1, traffic.QUERIES)
