"""Distributed WoW serving and building.

Serving topology (the production deployment for an index that fits HBM):
queries are sharded over the ``data`` mesh axis; the snapshot (graph +
vectors) is replicated within each data group.  Each device runs the batched
beam search on its query shard — no collectives on the hot path, linear
scaling in devices.  Every piece of per-query hop state (result arrays and
the visited filter — the [B, n/32] bitmap or the [B, v_words] hashed
filter) is leading-dim-B, so each device holds only its shard's
``HopState``; at million-vector scale the hashed filter is the only option
that keeps the per-device state O(batch) instead of O(batch * n).  The
search runs under ``shard_map`` (one hop loop per device, Pallas kernel
included); the ``model`` axis, when larger than 1, holds further replicas.

The sharded serving function runs the lock-step hop loop (``compact=None``
— ragged-batch compaction is host-side scheduling and cannot live inside
the jitted, sharding-annotated callable); incoming batches are padded to
power-of-two buckets (rounded to the data-axis size) so a stream of
distinct batch sizes reuses one compilation per bucket.  Alongside the
results, the serving function reduces the batch's hop histogram across
shards (a one-hot sum over the sharded batch axis — GSPMD lowers it to a
``psum``, so every host observes the *global* histogram), which feeds
measured visited-filter sizing (``visited_adaptive=True``:
``visited_filter_bits_measured`` re-sizes the hash filter from the
accumulated histogram after each wave; pow2 quantisation keeps the jit
cache warm across re-estimates).

Distributed building — ``sharded_build_search`` — shards one micro-batch's
phase-1 candidate beam searches over a build mesh via ``shard_map``: each
shard holds the replicated frozen ``DeviceBuildArena`` snapshot
(``repro.core.snapshot.ShardedBuildArena`` keeps the buffers placed
replicated across commits) and runs the jitted lock-step hop pipeline
(``device_search._build_search_core``) over its member slice — per-member
trajectories are row-independent, so the all-gathered candidate sets are
bitwise those of the single-device build at ANY shard count, and the
phase-2 edge commit (``WoWIndex._insert_micro_batch``'s deterministic host
reduction: vectorised forward RNG prunes + grouped batch-order back-edge
scatters) needs no changes to stay shard-count-invariant.  The per-shard
``lax.while_loop`` stops when that shard's members terminate — the
ragged-batch win without host-side scheduling (which is why the loop runs
under ``shard_map`` rather than a sharding-annotated ``jit``, whose
lock-step loop would pace every shard at the global straggler).

Building at scale across *hosts*: attribute-range partitioned builders.
Hosts own contiguous rank ranges of the attribute space plus a halo of one
top-level window on each side; each host builds its partition incrementally
with the ordinary insert path, and partitions are stitched by
cross-inserting the halo vertices (their windows at every layer are fully
contained in the owner's halo by construction — window size at layer l is
bounded by the top window).  ``partition_bounds`` computes the assignment;
the stitch is exercised in tests at small scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .device_search import (
    DeviceIndex,
    SearchResult,
    _build_search_core,
    _default_max_hops,
    _finish_build_search,
    _pow2ceil,
    _prep_build_inputs,
    device_search,
    to_device_index,
    visited_filter_bits,
    visited_filter_bits_from_hist,
)
from .snapshot import Snapshot

BUILD_AXIS = "build"  # default mesh axis name for sharded construction


@functools.lru_cache(maxsize=None)
def _sharded_build_fn(mesh, axis: str, cfg):
    """jit(shard_map) of the lock-step construction search: the
    ``DeviceIndex`` replicated, every per-member input/output sharded over
    ``axis``.  Cached per (mesh, axis, static cfg) — one compilation per
    padded-batch bucket, exactly like the single-device jit.  ``check_vma``
    is off: the hop loop is a *per-shard* ``lax.while_loop`` (each shard
    stops when its own members terminate), which the replication checker
    cannot type but which is safe — every output is explicitly sharded."""
    fn = jax.shard_map(
        lambda di, *xs: _build_search_core(di, *xs, cfg),
        mesh=mesh,
        in_specs=(P(),) + (P(axis),) * 8,
        out_specs=(P(axis),) * 4,
        check_vma=False,
    )
    return jax.jit(fn)


def sharded_build_search(
    mesh,
    di: DeviceIndex,
    targets: np.ndarray,
    ranges: np.ndarray,
    eps: np.ndarray,
    l_lo: int,
    l_hi: int,
    seed_ids: np.ndarray | None,
    seed_d: np.ndarray | None,
    *,
    width: int,
    m: int,
    o: int,
    metric: str = "l2",
    seed_width: int | None = None,
    deleted: set[int] | None = None,
    backend: str = "auto",
    visited: str = "hash",
    visited_bits: int | None = None,
    visited_fp: float = 0.02,
    visited_hashes: int = 2,
    merge: str = "auto",
    max_hops: int | None = None,
    axis: str = BUILD_AXIS,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Multi-device twin of ``device_search.build_search``: one micro-batch
    phase-1 candidate search, members sharded over ``mesh``'s ``axis``.

    The host prep (seed truncation, padding, layer-span slicing, static
    config) is shared code with the single-device path — the batch is
    additionally padded to a multiple of the shard count so it divides the
    mesh — and the result contract is identical: host ``(res_i, res_d, dc,
    hops)`` with deleted ids masked to -1.  Per-member hop trajectories are
    independent of co-batched members and of the padded batch size, so the
    returned candidate sets are bitwise identical at every shard count
    (including 1 — the conformance harness in
    ``tests/test_build_equivalence.py`` gates this)."""
    prep = _prep_build_inputs(
        di, targets, ranges, eps, l_lo, l_hi, seed_ids, seed_d,
        width=width, m=m, o=o, metric=metric, seed_width=seed_width,
        backend=backend, visited=visited, visited_bits=visited_bits,
        visited_fp=visited_fp, visited_hashes=visited_hashes, merge=merge,
        max_hops=max_hops, multiple=int(mesh.shape[axis]),
    )
    fn = _sharded_build_fn(mesh, axis, prep.cfg)
    out = fn(prep.di, *prep.args)
    return _finish_build_search(*out, prep.B, deleted)


def make_serving_fn(
    mesh,
    snap: Snapshot,
    k: int = 10,
    width: int = 64,
    data_axis: str = "data",
    backend: str = "auto",
    pipeline: str = "fused",
    visited: str = "bitmap",
    visited_bits: int | None = None,
    pad_batch: bool = True,
    visited_adaptive: bool = False,
    max_hops: int | None = None,
    vec_dtype: str = "f32",
):
    """jit-compiled query-sharded serving function.

    ``max_hops`` caps the global hop budget below the width-derived
    default — the sharded twin of the serve engine's deadline-aware
    degraded budget: a capped serving function returns best-so-far beams
    instead of running stragglers to convergence, bounding the per-wave
    wall clock on every shard.

    Returns ``fn(queries, ranges) -> SearchResult`` with queries/ranges/
    results sharded over ``data_axis`` and the index replicated.  With
    ``pad_batch`` (default) batches are padded to the next power-of-two
    bucket divisible by the data-axis size — new batch sizes then hit a
    cached compilation instead of retracing ``device_search``.

    With ``visited_adaptive=True`` every call also reduces the batch's hop
    histogram across shards (a one-hot sum over the sharded batch axis,
    lowered to a cross-shard ``psum`` by GSPMD) and accumulates it in
    ``fn.state["hist"]``; when ``visited="hash"`` subsequent calls re-size
    the per-query visited filter from the last 16 waves' histograms
    (``visited_filter_bits_from_hist``: p99 + slack straight from the bin
    counts, worst-case sizing as the cold-start default, a rolling window
    so the sizing tracks workload shift) — the sharded twin of
    ``RagPipeline(visited_adaptive=True)``.
    The current size is ``fn.state["bits"]``; pow2 quantisation means
    repeated re-estimates land on a handful of cached compilations.
    Non-adaptive callers run the plain searcher jit — no histogram
    compute, no extra device->host transfer on the hot path.
    """
    rep = NamedSharding(mesh, P())
    shq = NamedSharding(mesh, P(data_axis, None))
    sh1 = NamedSharding(mesh, P(data_axis))
    nd = int(mesh.shape[data_axis])
    W = max(width, k)
    # hops <= max_hops: the histogram's last bin
    H = int(max_hops) if max_hops is not None else _default_max_hops(W)
    # scalars extracted eagerly: the serve closure must not keep the whole
    # host-side snapshot (O(n*d) arrays) alive next to the device copy
    m, o = snap.m, snap.o
    metric = "l2" if snap.metric == "l2" else "cosine"
    if visited == "hash":
        bits0 = (int(visited_bits) if visited_bits is not None
                 else visited_filter_bits(W, m, H))
        bits0 = _pow2ceil(max(bits0, 1024))
    else:
        bits0 = None  # bitmap mode: nothing to adapt

    di = jax.device_put(to_device_index(snap, vec_dtype=vec_dtype), rep)

    def _make_fn(bits):
        searcher = functools.partial(
            device_search,
            k=k,
            width=width,
            m=m,
            o=o,
            metric=metric,
            max_hops=max_hops,
            backend=backend,
            pipeline=pipeline,
            visited=visited,
            visited_bits=bits,
        )
        # the per-shard search runs under shard_map: each device drives its
        # own hop loop (and the Pallas gather kernel, which GSPMD cannot
        # partition) over its query slice, stopping when its own queries
        # terminate; per-query trajectories are row-independent, so the
        # results equal the one-device search
        searcher = jax.shard_map(
            searcher, mesh=mesh,
            in_specs=(P(), P(data_axis), P(data_axis)),
            out_specs=SearchResult(*(P(data_axis),) * 4),
            check_vma=False,
        )
        res_sh = SearchResult(ids=shq, dists=shq, dc=sh1, hops=sh1)
        if not visited_adaptive:  # plain hot path: no histogram work
            return jax.jit(
                searcher,
                in_shardings=(jax.tree.map(lambda _: rep, di), shq, shq),
                out_shardings=res_sh,
            )

        def serve_hist(di_, queries, ranges):
            res = searcher(di_, queries, ranges)
            # hop histogram, reduced over the *sharded* batch axis: the sum
            # is the cross-shard psum every host needs for measured filter
            # sizing (the histogram output is replicated).
            bins = jnp.arange(H + 1, dtype=res.hops.dtype)
            oh = jnp.clip(res.hops, 0, H)[:, None] == bins[None, :]
            return res, jnp.sum(oh.astype(jnp.int32), axis=0)

        return jax.jit(
            serve_hist,
            in_shardings=(jax.tree.map(lambda _: rep, di), shq, shq),
            out_shardings=(res_sh, rep),
        )

    fns: dict = {}
    state = {"hist": np.zeros(H + 1, np.int64), "bits": bits0, "calls": 0}
    # rolling per-wave histograms for the measured sizing (matches the host
    # twin's 16-wave window in RagPipeline — all-time accumulation would
    # never adapt to workload shift and grow the resample cost unboundedly)
    from collections import deque

    recent: deque = deque(maxlen=16)

    def serve(queries: np.ndarray, ranges: np.ndarray):
        queries = np.asarray(queries, np.float32)
        ranges = np.asarray(ranges, np.float32)
        B = queries.shape[0]
        Bp = B
        if pad_batch:
            Bp = max(_pow2ceil(B), nd)
            if Bp % nd:  # non-pow2 data axis: fall back to a multiple
                Bp = -(-B // nd) * nd
        if Bp != B:  # padding rows carry an empty range -> inactive
            queries = np.concatenate(
                [queries, np.zeros((Bp - B, queries.shape[1]), np.float32)]
            )
            ranges = np.concatenate(
                [ranges,
                 np.tile(np.asarray([[1.0, 0.0]], np.float32), (Bp - B, 1))]
            )
        bits = state["bits"]
        fn = fns.get(bits)
        if fn is None:
            fn = fns[bits] = _make_fn(bits)
        if visited_adaptive:
            res, hist = fn(di, jnp.asarray(queries), jnp.asarray(ranges))
            hist = np.asarray(hist).astype(np.int64)
            if Bp != B:
                hist[0] -= Bp - B  # padded rows are inactive: exactly 0 hops
            state["hist"] += hist
            recent.append(hist)
            if visited == "hash":
                # measured sizing from the rolling window's histograms; the
                # worst-case bits0 covered the cold start
                state["bits"] = visited_filter_bits_from_hist(
                    np.sum(recent, axis=0), m
                )
        else:
            res = fn(di, jnp.asarray(queries), jnp.asarray(ranges))
        state["calls"] += 1
        if Bp != B:
            res = SearchResult(ids=res.ids[:B], dists=res.dists[:B],
                               dc=res.dc[:B], hops=res.hops[:B])
        return res

    serve.device_index = di  # keep alive / reusable
    serve.state = state  # hop histogram + current visited-filter sizing
    return serve


def partition_bounds(
    attrs_sorted: np.ndarray, num_parts: int, halo: int
) -> list[tuple[int, int, int, int]]:
    """Attribute-range partition assignment for parallel building.

    Returns per-part (own_lo, own_hi, halo_lo, halo_hi) rank bounds
    (inclusive-exclusive own range; halo extends each side by ``halo``).
    """
    n = len(attrs_sorted)
    out = []
    per = int(np.ceil(n / num_parts))
    for p in range(num_parts):
        lo = p * per
        hi = min(n, lo + per)
        if lo >= hi:
            break
        out.append((lo, hi, max(0, lo - halo), min(n, hi + halo)))
    return out
