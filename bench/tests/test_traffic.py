"""The open-loop schedule repeats from its seed, and every seed offers
the same amount of work."""
import hashlib

import numpy as np
import pytest

from bench import traffic

MIX = {"arrivals": "poisson", "fractions_log2": [-10, 0], "noise": 0.25}
INGEST = {"arrivals": "poisson", "batch_rows": 4, "attrs": "ascending"}
CORPUS = {"n": 4096, "d": 8, "data_seed": 0, "clusters": 32}


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


# digests of query_schedule(MIX, *_corpus(), rate=300, seconds=2, seed)
# from before the ingest stream existed: the query streams must not move
QUERY_DIGESTS = {
    0: "1f434e0a95bf0064c997198f756853ac",
    1: "f01d818bd6aa53705f16001ae4d902ce",
    2**31 + 7: "9ab4adf104c9bf767bcde28e76c66d2f",
    2**40: "4e4f1da759ced836d73369f1728d3b9f",
}


@pytest.mark.parametrize("seed", sorted(QUERY_DIGESTS))
def test_query_schedules_are_bitwise_unchanged(seed):
    v, a = _corpus()
    s = traffic.query_schedule(MIX, v, a, rate=300, seconds=2, seed=seed)
    h = hashlib.sha256()
    for f in ("due", "queries", "ranges", "fractions"):
        h.update(getattr(s, f).tobytes())
    assert h.hexdigest()[:32] == QUERY_DIGESTS[seed]


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_ingest_schedule_repeats_from_seed(seed):
    s1, s2 = (traffic.ingest_schedule(INGEST, CORPUS, rate=5, seconds=2,
                                      seed=seed, first_attr=4096)
              for _ in range(2))
    for f in ("due", "vectors", "attrs"):
        np.testing.assert_array_equal(getattr(s1, f), getattr(s2, f))


@pytest.mark.parametrize("arrivals", ["poisson", "periodic"])
def test_ingest_attrs_ascend_above_the_corpus_in_arrival_order(arrivals):
    mix = dict(INGEST, arrivals=arrivals)
    s1, s2 = (traffic.ingest_schedule(mix, CORPUS, rate=5, seconds=2,
                                      seed=seed, first_attr=4104.0)
              for seed in (1, 2))
    assert s1.vectors.shape == (10, 4, 8) and s1.vectors.dtype == np.float32
    assert np.all(np.diff(s1.due) >= 0) and 0 <= s1.due[0] and s1.due[-1] < 2
    np.testing.assert_array_equal(s1.attrs.ravel(), 4104.0 + np.arange(40))
    # every seed the same work: its attributes, in other rows; a timer
    # flushes at the same times for every seed
    np.testing.assert_array_equal(s1.attrs, s2.attrs)
    assert not np.array_equal(s1.vectors, s2.vectors)
    if arrivals == "periodic":
        np.testing.assert_array_equal(s1.due, s2.due)
        np.testing.assert_allclose(s1.due, np.arange(10) * 0.2)
    else:
        assert not np.array_equal(s1.due, s2.due)


def test_unknown_ingest_order_is_refused():
    with pytest.raises(ValueError, match="attribute order"):
        traffic.ingest_schedule(dict(INGEST, attrs="random"), CORPUS, rate=1,
                                seconds=1, seed=0, first_attr=0.0)


def _grown(n=4096, appended=300, d=8):
    v, a = _corpus(n, d)
    rng = np.random.default_rng(1)
    return (np.concatenate([v, rng.normal(size=(appended, d))]).astype(
        np.float32), np.concatenate([a, n + np.arange(appended, dtype=float)]))


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_append_probes_repeat_from_seed(seed):
    v, a = _grown()
    p1 = traffic.append_probes(MIX, v, a, 300, count=220, seed=seed)
    p2 = traffic.append_probes(MIX, v, a, 300, count=220, seed=seed)
    for f in ("queries", "ranges", "fractions"):
        np.testing.assert_array_equal(getattr(p1, f), getattr(p2, f))
    other = traffic.append_probes(MIX, v, a, 300, count=220, seed=seed + 1)
    assert not np.array_equal(p1.ranges, other.ranges)


def test_append_probes_reach_into_the_appended_rows():
    """Every range holds its fraction of the grown index and at least one
    appended row; most of the narrow ones lie among the appended rows
    alone, and the others cross from the corpus into them."""
    n, appended = 4096, 300
    v, a = _grown(n, appended)
    p = traffic.append_probes(MIX, v, a, appended, count=440, seed=5)
    inside = (a[None, :] >= p.ranges[:, :1]) & (a[None, :] <= p.ranges[:, 1:])
    np.testing.assert_array_equal(
        inside.sum(1), np.maximum(1, np.floor(len(a) * p.fractions)))
    assert inside[:, n:].any(axis=1).all()
    narrow = inside.sum(1) <= appended // 4
    assert (~inside[narrow, :n].any(axis=1)).mean() > 0.5
    crossing = inside[:, :n].any(axis=1) & inside[:, n:].any(axis=1)
    assert crossing.sum() > 0.3 * len(p.ranges)
