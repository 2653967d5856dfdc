"""Fused hop pipeline vs the pre-refactor reference and the host path.

The correctness contract of the ``device_search`` rework: the fused pipeline
(sort-based dedupe, two-way counting merge, slab gather kernel) must produce
bitwise-identical ids and matching DC/hop counters against the pre-refactor
hop (``pipeline="reference"``), and must track the instrumented host
``search_candidates`` reference — across metrics (l2/cosine) and degenerate
ranges (empty, single-value, full).
"""
import numpy as np
import pytest

from repro.core import WoWIndex
from repro.core.device_search import (
    _dedupe_sorted,
    _merge_sorted,
    search_batch,
)
from repro.core.hop_reference import dedupe_pairwise, merge_full_sort
from repro.core.snapshot import take_snapshot

_BIG = 2**30


def _build(metric: str, n=700, d=8, m=8, seed=0):
    # integer-grid vectors: exact f32 arithmetic, no rounding tie-breaks
    rng = np.random.default_rng(seed)
    vecs = rng.integers(-8, 8, size=(n, d)).astype(np.float32)
    attrs = rng.permutation(n).astype(np.float64)
    idx = WoWIndex(dim=d, m=m, ef_construction=48, o=4, seed=0, metric=metric)
    for v, a in zip(vecs, attrs):
        idx.insert(v, a)
    return idx, vecs, attrs


@pytest.fixture(scope="module", params=["l2", "cosine"])
def metric_index(request):
    idx, vecs, attrs = _build(request.param)
    return request.param, idx, vecs, attrs


def _query_set(n, d, attrs, nq=20, seed=1):
    rng = np.random.default_rng(seed)
    qs = rng.integers(-8, 8, size=(nq, d)).astype(np.float32)
    sorted_a = np.sort(attrs)
    ranges = np.empty((nq, 2))
    for i in range(nq):
        f = [1.0, 0.3, 0.05, 0.01][i % 4]
        n_in = max(2, int(n * f))
        s = int(rng.integers(0, max(1, n - n_in)))
        ranges[i] = (sorted_a[s], sorted_a[s + n_in - 1])
    # degenerate ranges ride along: empty, single-value, full
    ranges[0] = (attrs.max() + 10.0, attrs.max() + 20.0)
    ranges[1] = (attrs[5], attrs[5])
    ranges[2] = (attrs.min(), attrs.max())
    return qs, ranges


def _assert_ids_equal_mod_ties(ref_ids, ref_d, got_ids, tol=1e-5):
    """Bitwise id equality, except inside reference-distance tie groups
    (entries within ``tol`` of each other), where any order of the same id
    multiset is accepted — fp-accumulation-order differences between kernels
    may legitimately swap exact ties."""
    B, k = ref_ids.shape
    for b in range(B):
        i = 0
        while i < k:
            j = i + 1
            while (
                j < k
                and np.isfinite(ref_d[b, j])
                and ref_d[b, j] - ref_d[b, j - 1] <= tol
            ):
                j += 1
            if j < k:  # group fully inside the top-k: same ids, any order
                assert sorted(ref_ids[b, i:j]) == sorted(got_ids[b, i:j]), (b, i, j)
            # a group truncated by the k boundary may exchange members with
            # the (equidistant) entries just past k — ids unchecked there
            i = j


def test_fused_matches_reference_pipeline(metric_index):
    """Acceptance: bitwise-identical ids, <=1e-4 distance deltas, equal
    DC/hop counters vs the pre-refactor hop, on every backend.  (On the
    exact-arithmetic l2 grid ids must match bitwise even through the Pallas
    kernel; cosine normalisation is inexact, so kernel runs are compared
    modulo reordering within exact distance ties.)"""
    metric, idx, vecs, attrs = metric_index
    snap = take_snapshot(idx)
    qs, ranges = _query_set(len(attrs), vecs.shape[1], attrs)
    ref = search_batch(snap, qs, ranges, k=10, width=48,
                       pipeline="reference", backend="ref")
    for backend in ("ref", "auto", "interpret"):
        got = search_batch(snap, qs, ranges, k=10, width=48,
                           pipeline="fused", backend=backend)
        rd, gd = np.asarray(ref.dists), np.asarray(got.dists)
        if metric == "l2" or backend in ("ref", "auto"):
            np.testing.assert_array_equal(np.asarray(got.ids), np.asarray(ref.ids))
        else:
            _assert_ids_equal_mod_ties(
                np.asarray(ref.ids), rd, np.asarray(got.ids)
            )
        fin = np.isfinite(rd)
        assert np.array_equal(fin, np.isfinite(gd))
        np.testing.assert_allclose(gd[fin], rd[fin], atol=1e-4)
        np.testing.assert_array_equal(np.asarray(got.dc), np.asarray(ref.dc))
        np.testing.assert_array_equal(np.asarray(got.hops), np.asarray(ref.hops))


def test_fused_matches_host_reference(metric_index):
    """Fused-kernel device search vs the instrumented host path: result
    overlap, distances on the common prefix, and DC counters."""
    metric, idx, vecs, attrs = metric_index
    snap = take_snapshot(idx)
    qs, ranges = _query_set(len(attrs), vecs.shape[1], attrs, nq=16, seed=3)
    res = search_batch(snap, qs, ranges, k=10, width=48,
                       pipeline="fused", backend="interpret")
    dev_ids = np.asarray(res.ids)
    dev_d = np.asarray(res.dists)
    overlap, dc_close = [], 0
    for i in range(len(qs)):
        ids, dists, st = idx.search(qs[i], tuple(ranges[i]), k=10, ef=48)
        h = set(ids.tolist())
        d = set(int(snap.ids_map[j]) for j in dev_ids[i] if j >= 0)
        overlap.append(len(h & d) / len(h) if h else float(h == d))
        dc_close += abs(st.dc - int(res.dc[i])) <= 4
        # distances agree on the common sorted prefix (tie-order slack at
        # the k boundary aside, the distance *values* must match)
        kk = min(len(dists), int(np.sum(np.isfinite(dev_d[i]))))
        np.testing.assert_allclose(dev_d[i][:kk], dists[:kk], atol=1e-4)
    assert np.mean(overlap) >= 0.98
    assert dc_close >= len(qs) - 2  # DC accounting matches (tie-order slack)


def test_degenerate_ranges(metric_index):
    metric, idx, vecs, attrs = metric_index
    snap = take_snapshot(idx)
    d = vecs.shape[1]
    qs = np.zeros((3, d), np.float32)
    qs[1] = vecs[17]
    ranges = np.array([
        [attrs.max() + 10.0, attrs.max() + 20.0],  # empty
        [attrs[5], attrs[5]],  # single value
        [attrs.min(), attrs.max()],  # full
    ])
    for pipeline in ("fused", "reference"):
        res = search_batch(snap, qs, ranges, k=5, width=16,
                           pipeline=pipeline, backend="interpret")
        ids = np.asarray(res.ids)
        # empty range: no results, no distance evaluations
        assert np.all(ids[0] == -1)
        assert int(res.dc[0]) == 0 and int(res.hops[0]) == 0
        # single-value range (attrs unique): exactly the one in-range vertex
        got1 = [int(snap.ids_map[j]) for j in ids[1] if j >= 0]
        assert got1 == [5]
        # full range: valid in-range results, ascending distances
        got2 = ids[2][ids[2] >= 0]
        assert len(got2) == 5
        dd = np.asarray(res.dists)[2][: len(got2)]
        assert np.all(np.diff(dd) >= -1e-6)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n", [12, 2**28])  # packed key path / lexsort path
def test_sorted_dedupe_matches_pairwise(seed, n):
    """Unit: the sort-based dedupe keeps exactly the all-pairs mask's
    surviving (id, rank) set — on both the packed-uint32 single-key path and
    the huge-table two-key fallback.  Eligible ranks are distinct per row,
    as the hop body guarantees (rank is injective over (layer, col) slots)."""
    rng = np.random.default_rng(seed)
    B, F = 5, 48
    ids = rng.integers(0, 12, size=(B, F)).astype(np.int32)  # heavy dup load
    rank = np.empty((B, F), np.int32)
    for b in range(B):
        rank[b] = rng.permutation(F)
    rank[rng.random((B, F)) < 0.4] = _BIG  # ineligible slots
    import jax.numpy as jnp

    ids_j, rank_j = jnp.asarray(ids), jnp.asarray(rank)
    _, r_ref = dedupe_pairwise(ids_j, rank_j)
    sid, r_new = _dedupe_sorted(ids_j, rank_j, n, F)
    i_ref, r_ref = np.asarray(ids), np.asarray(r_ref)
    sid, r_new = np.asarray(sid), np.asarray(r_new)
    for b in range(B):
        ref_set = {(i, r) for i, r in zip(i_ref[b], r_ref[b]) if r < _BIG}
        new_set = {(i, r) for i, r in zip(sid[b], r_new[b]) if r < _BIG}
        assert ref_set == new_set


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_counting_merge_matches_full_sort(seed):
    """Unit: the two-way counting merge reproduces the stable full-width
    sort bit for bit — including distance ties, +inf padding and invalid
    (-1) entries."""
    rng = np.random.default_rng(seed)
    import jax.numpy as jnp

    B, W, K = 4, 24, 9
    # sorted result array with ties and +inf tail
    res_d = np.sort(rng.integers(0, 12, size=(B, W)).astype(np.float32), axis=1)
    n_pad = rng.integers(0, W // 2, size=B)
    for b in range(B):
        if n_pad[b]:
            res_d[b, -n_pad[b]:] = np.inf
    res_i = rng.integers(0, 1000, size=(B, W)).astype(np.int32)
    res_i[np.isinf(res_d)] = -1
    res_e = rng.random((B, W)) < 0.5
    res_e[np.isinf(res_d)] = True
    # unsorted new entries, some invalid
    dd = rng.integers(0, 12, size=(B, K)).astype(np.float32)
    new_valid = rng.random((B, K)) < 0.7
    dd[~new_valid] = np.inf
    new_i = np.where(new_valid, rng.integers(0, 1000, size=(B, K)), -1).astype(np.int32)
    new_e = ~new_valid

    args = tuple(
        jnp.asarray(a)
        for a in (res_d, res_i, res_e, dd, new_i, new_e)
    )
    ed, ei, ee = merge_full_sort(*args, W)
    gd, gi, ge = _merge_sorted(*args, W)
    np.testing.assert_array_equal(np.asarray(gd), np.asarray(ed))
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(ei))
    np.testing.assert_array_equal(np.asarray(ge), np.asarray(ee))
