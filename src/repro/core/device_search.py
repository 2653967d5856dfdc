"""Batched WoW search on device — the TPU serving path.

Executes Algorithm 2+3 for B queries inside a jitted hop loop.  Per hop,
every active query:

  1. selects its nearest unexpanded candidate (the paper's min-heap pop),
  2. gathers that vertex's neighbor block across all layers [0, l_d],
  3. applies the early-stop layer mask — a layer below ``l`` contributes only
     if every layer above it (up to ``l_d``) had an unvisited out-of-range
     neighbor (Alg. 2's ``next`` flag, evaluated vectorially; out-of-range
     neighbors are never marked visited inside a hop, so the flag is
     data-parallel computable up front),
  4. selects at most ``m+1`` eligible (valid, unvisited, in-range) neighbors
     by layer-priority rank (the ``c_n`` cap with high-layer priority),
     deduplicated across layers,
  5. evaluates their distances with the fused gather+distance kernel (the
     MXU-friendly factorised ``|v|^2 - 2 v.q + |q|^2``),
  6. merges them into its sorted fixed-width result array (heap semantics:
     the width-W sorted array is exactly the paper's U; entries beyond W can
     never be expanded by the paper's algorithm either).

Hop-pipeline design (the fused path; ``repro.core.hop_reference`` keeps the
pre-refactor stages as the parity oracle):

  * **Sort-based dedupe** — the F = L*m flattened (id, rank) pairs are
    packed into one uint32 key ``id*(F+1) + rank`` (eligible ranks are < F
    by construction — (l_d-l)*m + col is injective over slots — and
    ineligible slots pack as F), sorted with a *single-key single-operand*
    ``lax.sort`` (markedly cheaper than a variadic lexsort on every
    backend), and unpacked; an entry is dropped iff its sorted predecessor
    carries the same id: within an equal-id run ranks ascend, so the
    predecessor is either a better-ranked *eligible* entry (drop is correct
    — the host marks the id visited at the better slot first) or already
    ineligible, in which case the entry itself is ineligible and the drop
    is a no-op.  The surviving set and its rank order are exactly those of
    the O(F^2) all-pairs mask, with O(F log F) work and no [B, F, F]
    intermediate.  When ``n*(F+1)`` would overflow 32 bits the packing
    falls back to the equivalent two-key lexsort.  The subsequent top-k
    runs directly in id-sorted order — rank order is preserved under any
    permutation, so no unsort is needed.
  * **Two-way counting merge** — the width-W result array is sorted at all
    times (the invariant: it is only ever produced by merging two sorted
    sequences), so the K = m+1 new entries merge *without any sort*: a
    [B, K, K] comparison matrix gives each new entry its stable rank among
    the new entries (ties broken by slot index), a [B, W, K] ``<=`` matrix
    counts cross positions (pos_A[i] = i + #{j : new[j] < res[i]},
    pos_B[j] = rank_new[j] + #{i : res[i] <= new[j]} — the asymmetric
    comparison reproduces the stable tie-break of the old full sort, result
    entries before new entries), the *source index* of each surviving slot
    is written back either by one dropping scatter or by an MXU one-hot
    matmul (``repro.kernels.ops.merge_src_indices``; XLA scatter serialises
    on TPU, the scatter benches faster on CPU — ``merge="auto"`` picks per
    platform), and three gathers produce the merged (dist, id, expanded)
    arrays.  No [B, W+K] full-width sort.
  * **Fused slab gather** — candidate vectors are fetched by the blocked
    Pallas kernel in ``repro.kernels.gather_distance``: ids are
    scalar-prefetched, [rows, D] slabs are assembled in VMEM by
    double-buffered row DMAs, and both the query dot and the squared norm
    are produced in-kernel, so candidate vectors never round-trip through
    HBM as a [B, K, d] tensor.

Visited-set state (``visited=`` static knob) — the per-hop cost must not
scale with the corpus:

  * **"bitmap"** (exact oracle) — a [B, n/32 + 1] packed bitmap.  One word
    gather per candidate, one ``.add`` scatter per selected id (safe:
    a selected id is by construction unvisited, so its bit is unset).
    O(n) per-query *state*, O(1) per-candidate work.
  * **"hash"** (production at scale) — a constant-size double-hashed
    *blocked* Bloom filter: ``v_bits`` bits per query (power of two, sized
    by ``visited_filter_bits`` from the expected O(width) hop budget at
    the ``visited_fp`` false-positive target, with a 1.5x allowance for
    block clustering), where murmur3-finalizer hash h1 picks an id's
    32-bit *block* word and h2 derives ``v_hashes`` distinct bit offsets inside
    it (``(b0 + i*step) & 31`` with odd step).  Blocking is the classic
    cache/SIMD-friendly Bloom variant and is what keeps the per-hop cost
    at bitmap parity: membership is ONE word gather (same width as the
    bitmap path) plus an AND-mask compare, regardless of ``v_hashes``.
    Marking must be an OR (unlike the bitmap, probe bits of an *unvisited*
    id may already be set by other ids), which XLA scatters cannot express
    directly: per-id 2-bit masks landing in the same word are OR-combined
    via a tiny [K, K] equal-word ``lax.reduce``, merged with the gathered
    current words, and written with a ``.set`` scatter (colliding lanes
    write identical values).  A false positive only *skips* a candidate —
    it can never cause an out-of-range vertex to be evaluated — so the
    no-OOR property is invariant and recall degrades gracefully with
    filter load.

Scheduling (``compact=`` knob) — the hop loop must not run at the pace of
the slowest query in the batch:

  * ``compact=None`` — one lock-step ``lax.while_loop`` over the whole
    batch (the only mode usable inside an outer jit, e.g. the sharded
    serving function).
  * ``compact=(h0, h)`` — ragged-batch compaction: the hop state is an
    explicit ``HopState`` pytree, so the loop runs as resumable chunks of
    ``h0`` (first phase) then ``h`` (long phase) hops; between chunks the
    still-active queries are compacted into the next power-of-two batch
    bucket (each bucket size compiles once) and only the survivors resume.
    The short/long schedule lets the fast majority of a ragged batch exit
    after the first chunk while stragglers continue in a small bucket.
    Finished queries are harvested at chunk boundaries; per-query
    trajectories are iteration-indexed and independent, so results are
    bitwise identical to the lock-step loop.

Entry-point fold: hop 0 *is* the entry-point evaluation — the seed
iteration injects the entry vertex as the sole selected candidate through
the same select/eval/merge lanes as every other hop (no standalone K=1
kernel dispatch, no separate visited seeding).  The seed iteration does not
count as a hop, preserving the host path's DC/hop accounting.

Construction searches (``build_search``) run the SAME hop pipeline for
batched builds: the caller overrides what the snapshot's unique-value
tables would derive — explicit layer span ``[l_lo, l_hi]`` (per-query
``l_min`` in the state), host-sampled window entries, and Thm-3.1
carry-seeded beams (already-evaluated candidates preload the sorted result
array at init, cost no DC, and skip the entry fold) — and the graph tensor
is the build arena's frozen snapshot + delta slab
(``repro.core.snapshot.DeviceBuildArena``).  The layer span is sliced to a
pow2-quantised prefix of the neighbor tensor so the per-hop sort/mask width
scales with the sweep, not the full layer count.  Candidate admission and
the counting-merge writeback use packed single-key sorts rather than
``lax.top_k``/scatter (both lower poorly on CPU); the admitted set and
order are bitwise those of the reference pipeline.

Termination per query: no unexpanded candidates, or the nearest unexpanded
is farther than the current worst of a full result set (Alg. 2 line 6).

The lock-step search is a pure jittable function of (snapshot arrays,
queries, ranges) and is shardable over the query batch — all per-query
state including the visited filter is leading-dim-B, so it shards over the
``data`` axis by propagation (see ``repro.core.distributed``).
Out-of-range vertices are never distance-evaluated, preserving the paper's
no-OOR property; per-query DC and hop counters are returned for parity
tests against the instrumented host path.

Knobs (all static): ``backend`` dispatches the distance kernel like every
other kernel in ``repro.kernels.ops``; ``pipeline`` selects "fused"
(production) or "reference" (the pre-refactor hop, for parity and
benchmarks); ``visited``, ``compact`` and ``merge`` as above.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import hop_reference as _hop_ref
from .snapshot import Snapshot

_INF = jnp.float32(np.inf)
_BIG = jnp.int32(2**30)
_MIN_BUCKET = 8  # smallest compaction bucket (avoid degenerate compiles)


class DeviceIndex(NamedTuple):
    """Pytree of snapshot arrays (static config passed separately)."""

    vectors: jax.Array  # {f32|bf16|int8}[n, d] (storage mode = vec_dtype)
    sq_norms: jax.Array  # f32[n]
    attrs: jax.Array  # f32[n]
    neighbors: jax.Array  # i32[L, n, m]
    uvals: jax.Array  # f32[u]
    uval_rep: jax.Array  # i32[u]
    scales: jax.Array | None = None  # f32[n] per-row int8 dequant scales
    #   (f32[1] dummy for f32/bf16 slabs — shape-keyed like every other
    #   field; the None default only suits hand-built f32 indexes)


def _gather_scales(di: DeviceIndex):
    """Per-row dequant scales iff the slab is int8 (dequant is fused inside
    the gather kernel dispatch; no other consumer may touch them)."""
    return di.scales if di.vectors.dtype == jnp.int8 else None


def to_device_index(snap: Snapshot, vec_dtype: str | None = None) -> DeviceIndex:
    """Device-resident snapshot with **pow2-padded row capacity**.

    Every jitted serve function is shape-keyed on the snapshot row count,
    so an ingest-grown snapshot with raw shapes recompiles its first wave
    even though ``ServeEngine.warmup()`` precompiled the whole bucket set.
    Padding rows (and the unique-value table) to the next power of two
    makes refreshed snapshots reuse the warmed executables until the
    corpus actually doubles.

    The padding is made unreachable, so results are bitwise those of the
    unpadded index for finite filter ranges: pad neighbor rows are ``-1``
    (never gathered), pad attrs are ``+inf`` (outside any finite range),
    and pad uvals are ``+inf`` with representative 0 — ``searchsorted``
    positions for finite query bounds are unchanged by an all-``+inf``
    tail, so landing-layer selectivity and entry selection are identical.

    ``vec_dtype`` selects the device slab storage mode ("f32"/"int8"/
    "bf16"; default: the snapshot's own ``vec_dtype``).  Quantized slabs
    already carried by the snapshot (a serve-from-checkpoint cold start)
    are reused as-is; otherwise the f32 slab is quantized here, per row,
    so the result is bitwise independent of when the quantization
    happened.  Pad rows get scale 1.0 (they are unreachable anyway).
    """
    from .store import quantize_rows

    if vec_dtype is None:
        vec_dtype = getattr(snap, "vec_dtype", "f32")
    n = int(snap.vectors.shape[0])
    u = int(snap.uvals.shape[0])
    n_cap = _pow2ceil(max(n, 1))
    u_cap = _pow2ceil(max(u, 1))
    pad_n = n_cap - n
    pad_u = u_cap - u

    def _pad(arr, pad, value):
        width = [(0, pad)] + [(0, 0)] * (arr.ndim - 1)
        return np.pad(arr, width, constant_values=value)

    scales = None
    if (
        getattr(snap, "q_vectors", None) is not None
        and getattr(snap, "vec_dtype", "f32") == vec_dtype
        and vec_dtype != "f32"
    ):
        # checkpointed quantized slab: serve it without requantizing
        vectors = np.asarray(snap.q_vectors)
        scales = (None if snap.q_scales is None
                  else np.asarray(snap.q_scales, np.float32))
    else:
        vectors, scales = quantize_rows(np.asarray(snap.vectors, np.float32),
                                        vec_dtype)
    sq_norms = np.asarray(snap.sq_norms, np.float32)
    attrs = np.asarray(snap.attrs, np.float32)
    neighbors = np.asarray(snap.neighbors, np.int32)
    uvals = np.asarray(snap.uvals, np.float32)
    uval_rep = np.asarray(snap.uval_rep, np.int32)
    if pad_n:
        vectors = _pad(vectors, pad_n, 0.0)
        sq_norms = _pad(sq_norms, pad_n, 0.0)
        attrs = _pad(attrs, pad_n, np.inf)
        neighbors = np.pad(neighbors, ((0, 0), (0, pad_n), (0, 0)),
                           constant_values=-1)
        if scales is not None:
            scales = _pad(scales, pad_n, 1.0)
    if pad_u:
        uvals = _pad(uvals, pad_u, np.inf)
        uval_rep = _pad(uval_rep, pad_u, 0)
    if scales is None:
        scales = np.ones(1, np.float32)  # dummy (f32/bf16 slab)
    return DeviceIndex(
        vectors=jnp.asarray(vectors),
        sq_norms=jnp.asarray(sq_norms, jnp.float32),
        attrs=jnp.asarray(attrs, jnp.float32),
        neighbors=jnp.asarray(neighbors, jnp.int32),
        uvals=jnp.asarray(uvals, jnp.float32),
        uval_rep=jnp.asarray(uval_rep, jnp.int32),
        scales=jnp.asarray(scales, jnp.float32),
    )


class SearchResult(NamedTuple):
    ids: jax.Array  # i32[B, k] snapshot ids, -1 padded
    dists: jax.Array  # f32[B, k], +inf padded
    dc: jax.Array  # i32[B] distance computations
    hops: jax.Array  # i32[B]


class HopCfg(NamedTuple):
    """Static hop-loop configuration (hashable jit key)."""

    k: int
    width: int
    m: int
    o: int
    metric: str
    max_hops: int
    backend: str
    pipeline: str
    visited: str  # "bitmap" | "hash"
    v_words: int  # hash-filter words per query (0 for bitmap)
    v_hashes: int
    merge: str  # counting-merge writeback: "auto" | "scatter" | "onehot"


class HopState(NamedTuple):
    """Resumable per-query hop state — every field is leading-dim B except
    the scalar iteration counter ``t``, so chunk-boundary compaction is one
    row gather and query sharding propagates to the whole state."""

    queries: jax.Array  # f32[B, d] (normalised for cosine)
    q2: jax.Array  # f32[B]
    x: jax.Array  # f32[B] range lo
    y: jax.Array  # f32[B] range hi
    l_d: jax.Array  # i32[B] landing layer
    l_min: jax.Array  # i32[B] lowest layer swept (0 when serving; the
    #   insertion layer during construction searches, Alg. 1 line 5)
    ep: jax.Array  # i32[B] entry vertex (clipped; consumed by the seed hop)
    res_d: jax.Array  # f32[B, W] sorted result distances
    res_i: jax.Array  # i32[B, W]
    res_e: jax.Array  # bool[B, W] expanded
    vstate: jax.Array  # u32[B, Vw+1] visited filter (+1 trash word)
    active: jax.Array  # bool[B]
    dc: jax.Array  # i32[B]
    hops: jax.Array  # i32[B]
    t: jax.Array  # i32 scalar — global iteration counter (0 = seed)


def _pow2ceil(x: int) -> int:
    return 1 << max(0, (int(x) - 1)).bit_length()


def _default_max_hops(width: int) -> int:
    """Global iteration cap from the beam width (the sorted beam drains
    after O(width) expansions; the 8x + 64 slack covers pathological
    workloads without unbounding the loop)."""
    return 8 * int(width) + 64


def _bucket_ceil(x: int) -> int:
    """Compaction bucket size: smallest of {pow2, 1.5*pow2} >= x.  The
    half-step granularity (8, 12, 16, 24, 32, 48, 64, 96, 128, ...) is what
    makes mid-drain compaction pay: a 128-batch with 68 survivors shrinks
    to 96 instead of staying at 128, at a bounded number of compiled
    bucket shapes."""
    x = max(int(x), _MIN_BUCKET)
    p = 1 << (x - 1).bit_length()
    return p * 3 // 4 if p * 3 // 4 >= x else p


def _bloom_bits(budget: int, fp: float, hashes: int) -> int:
    """Blocked-Bloom size (bits, power of two) for ``budget`` insertions at
    the ``fp`` false-positive target: the classic load formula
    ``fp = (1 - exp(-nh*I/bits))^nh`` solved for ``bits``, padded 1.5x as a
    clustering allowance for the 32-bit blocked layout, and rounded up to a
    power of two (so block indices reduce with a mask, not a modulo)."""
    p1 = fp ** (1.0 / hashes)
    need = 1.5 * hashes * max(int(budget), 1) / -math.log1p(-p1)
    return 1 << max(10, math.ceil(math.log2(need)))


def visited_filter_bits(
    width: int,
    m: int,
    max_hops: int,
    fp: float = 0.02,
    hashes: int = 2,
) -> int:
    """Worst-case hash-filter sizing from the search budget.

    At most ``m+1`` ids are inserted per hop; the *expected* hop budget is
    O(width) — the sorted beam drains after about ``width`` expansions, so
    sizing to ``min(max_hops, 2*width + 64)`` hops covers real searches
    with margin while keeping the state small (a runaway query that
    exceeds the budget degrades to graceful extra skipping, not to O(n) or
    O(max_hops) state).  This is the fallback when no measured hop
    histogram is available; see ``visited_filter_bits_measured``.
    """
    budget = (min(max_hops, 2 * width + 64) + 1) * (m + 1)
    return _bloom_bits(budget, fp, hashes)


def _measured_bits_from_p99(
    p99: float, m: int, fp: float, hashes: int, slack: float,
    floor_hops: int,
) -> int:
    budget = (max(floor_hops, int(math.ceil(slack * p99))) + 1) * (m + 1)
    return _bloom_bits(budget, fp, hashes)


def visited_filter_bits_measured(
    hops,
    m: int,
    fp: float = 0.02,
    hashes: int = 2,
    slack: float = 1.5,
    floor_hops: int = 16,
) -> int:
    """Adaptive hash-filter sizing from *measured* per-query hop counts.

    Real searches insert far fewer ids than the worst-case ``2*width + 64``
    budget: sizing to ``slack * p99(observed hops)`` (never below
    ``floor_hops``) typically cuts the per-query filter state 4-8x at the
    same FP target.  An under-estimate only costs graceful extra skipping
    on outlier queries — the no-OOR property and termination are invariant
    to filter load — so serve-time feedback can apply this after the first
    batch and keep the worst-case ``visited_filter_bits`` as the cold-start
    fallback.  Pow2 rounding makes repeated re-estimates quantise to the
    same size, so jit caches stay warm across refreshes."""
    hops = np.asarray(hops)
    p99 = float(np.percentile(hops, 99)) if hops.size else 0.0
    return _measured_bits_from_p99(p99, m, fp, hashes, slack, floor_hops)


def hist_percentile(hist, q: float) -> float:
    """Percentile of a hop *histogram* (bin i = number of searches that
    took i hops) — reproduces ``np.percentile``'s linear interpolation
    exactly via the cumulative counts, without materialising the per-query
    sample.  The form the sharded serving path reduces across shards and
    the serve engine accumulates per wave.  Returns 0.0 for an empty
    histogram."""
    hist = np.asarray(hist, np.int64)
    total = int(hist.sum())
    if total == 0:
        return 0.0
    rank = (total - 1) * (q / 100.0)
    lo_k = int(math.floor(rank))
    hi_k = int(math.ceil(rank))
    cum = np.cumsum(hist)
    v_lo = int(np.searchsorted(cum, lo_k + 1))  # 0-indexed order stats
    v_hi = int(np.searchsorted(cum, hi_k + 1))
    return v_lo + (rank - lo_k) * (v_hi - v_lo)


def visited_filter_bits_from_hist(
    hist,
    m: int,
    fp: float = 0.02,
    hashes: int = 2,
    slack: float = 1.5,
    floor_hops: int = 16,
) -> int:
    """``visited_filter_bits_measured`` computed directly from a hop
    histogram — both entry points size identically for the same data
    (see ``hist_percentile``)."""
    p99 = hist_percentile(hist, 99.0)
    return _measured_bits_from_p99(p99, m, fp, hashes, slack, floor_hops)


def chunk_schedule_from_hist(
    hist, lo: int = 4, hi: int = 64
) -> tuple[int, int]:
    """Adaptive ragged-batch compaction schedule ``(h0, h)`` from a live
    hop histogram (the serve engine's per-wave feedback loop; the static
    twin is the hand-tuned ``compact=(h0, h)`` knob).

    ``h0`` — the first chunk length — targets the median: a boundary just
    past p50 retires the fast half of a wave at the first compaction
    point.  ``h`` — the long-phase chunk — tracks the straggler tail at a
    quarter of the p50..p99 spread, so stragglers are re-bucketed a
    handful of times rather than once (too coarse: the fast majority
    waits) or every hop (too fine: boundary sync cost dominates).  Both
    are pow2-quantised into ``[lo, hi]`` so repeated re-estimates land on
    a handful of cached compilations, exactly like the measured
    visited-filter sizing."""
    p50 = hist_percentile(hist, 50.0)
    p99 = hist_percentile(hist, 99.0)
    h0 = _pow2ceil(max(int(math.ceil(p50)) + 1, 1))
    h1 = _pow2ceil(max(int(math.ceil((p99 - p50) / 4.0)), 1))
    clamp = lambda x: max(lo, min(hi, x))
    return clamp(h0), clamp(h1)


def _hash_probe(ids: jax.Array):
    """One murmur3-fmix32 hash per id -> (block hash, first bit offset b0,
    odd offset stride).  The single 5-op mix keeps per-hop hashing cheap
    enough that the filter test matches the exact bitmap's cost; reusing
    one hash for block and offsets is fine for a visited filter (ids are
    not adversarial).  Must stay bit-identical to the numpy twin
    ``repro.core.search.hash_positions_np``."""
    h = ids.astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * np.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    b0 = (h >> 16) & 31
    step = ((h >> 21) & 31) | jnp.uint32(1)
    return h, b0, step


def _hash_wordmask(ids: jax.Array, v_words: int, nh: int):
    """Blocked-Bloom probe of each id: -> (block word index i32[...],
    nh-bit in-word mask u32[...]): the hash's low bits pick the block,
    the distinct in-word bit offsets are ``(b0 + i*step) & 31``."""
    h, b0, step = _hash_probe(ids)
    word = (h & jnp.uint32(v_words - 1)).astype(jnp.int32)
    mask = jnp.zeros_like(h)
    for i in range(nh):
        mask = mask | (jnp.uint32(1) << ((b0 + i * step) & 31))
    return word, mask


def _hash_positions(ids: jax.Array, v_bits: int, nh: int) -> jax.Array:
    """Flat probe bit positions: ids i32[...] -> u32[..., nh] in
    [0, v_bits) — the blocked layout expressed as positions (all probes of
    one id share a 32-bit block), for the dense oracle and host twin."""
    h, b0, step = _hash_probe(ids)
    word = h & jnp.uint32(v_bits // 32 - 1)
    i = jnp.arange(nh, dtype=jnp.uint32)
    bits = (b0[..., None] + i * step[..., None]) & 31
    return word[..., None] * 32 + bits


def _visited_test(vstate: jax.Array, ids: jax.Array, valid: jax.Array,
                  cfg: HopCfg) -> jax.Array:
    """Membership of clipped ids [B, ...] in the visited filter -> bool.
    Invalid lanes return arbitrary values (callers mask with ``valid``).
    Both modes cost exactly one word gather per candidate."""
    vis, _ = _visited_test_cached(vstate, ids, valid, cfg)
    return vis


def _visited_test_cached(vstate: jax.Array, ids: jax.Array, valid: jax.Array,
                         cfg: HopCfg):
    """``_visited_test`` that also returns the hash mode's probe cache
    ``(word, mask)`` (None for the bitmap mode) so the subsequent mark of
    the selected subset can gather instead of rehashing."""
    B = vstate.shape[0]
    trash = vstate.shape[1] - 1
    if cfg.visited == "bitmap":
        word = jnp.where(valid, ids >> 5, trash)
        got = jnp.take_along_axis(
            vstate, word.reshape(B, -1), axis=1
        ).reshape(ids.shape)
        return ((got >> (ids & 31).astype(jnp.uint32)) & 1) > 0, None
    word, mask = _hash_wordmask(ids, trash, cfg.v_hashes)
    got = jnp.take_along_axis(
        vstate, word.reshape(B, -1), axis=1
    ).reshape(ids.shape)
    return (got & mask) == mask, (word, mask)  # AND over the probe bits


def _visited_mark(vstate: jax.Array, sel_ids: jax.Array, sel_valid: jax.Array,
                  cfg: HopCfg) -> jax.Array:
    """Insert the selected ids [B, K] into the filter."""
    B, K = sel_ids.shape
    rows = jnp.arange(B)[:, None]
    trash = vstate.shape[1] - 1
    if cfg.visited == "bitmap":
        # a selected id is unvisited by construction, so its bit is unset
        # and ``add`` == OR; post-dedupe ids are distinct within a row.
        w = jnp.where(sel_valid, sel_ids >> 5, trash)
        b = jnp.where(
            sel_valid, jnp.uint32(1) << (sel_ids & 31).astype(jnp.uint32), 0
        )
        return vstate.at[rows, w].add(b.astype(jnp.uint32))
    word, mask = _hash_wordmask(sel_ids, trash, cfg.v_hashes)
    return _visited_mark_hash(vstate, word, mask, sel_valid)


def _visited_mark_hash(vstate: jax.Array, word: jax.Array, mask: jax.Array,
                       sel_valid: jax.Array) -> jax.Array:
    """Hash-mode insert from precomputed probe (word, mask) pairs [B, K] —
    the cache handed over from ``_visited_test_cached`` (satellite: no
    rehash of the selected ids between test and mark)."""
    trash = vstate.shape[1] - 1
    rows = jnp.arange(vstate.shape[0])[:, None]
    w = jnp.where(sel_valid, word, trash)
    mask = jnp.where(sel_valid, mask, 0)
    # marking must be an OR (probe bits of an unvisited id may already be
    # set): OR-combine masks of ids sharing a block via a [K, K] equal-word
    # reduce, merge with the gathered current words, and write back with a
    # ``set`` scatter — lanes sharing a word write identical values.
    eqw = w[:, :, None] == w[:, None, :]  # [B, K, K] (tiny)
    comb = lax.reduce(
        jnp.where(eqw, mask[:, None, :], jnp.uint32(0)),
        np.uint32(0), lax.bitwise_or, [2],
    )
    cur = jnp.take_along_axis(vstate, w, axis=1)
    return vstate.at[rows, w].set(cur | comb)


def _dedupe_sorted(ids_f: jax.Array, rank_f: jax.Array, n: int, F: int):
    """Sort-based cross-layer dedupe (see module docstring).  Returns the
    (id-sorted ids, masked ranks) pair — order differs from the input, which
    is fine for the rank top-k that follows."""
    if n * (F + 1) < 2**32:  # packed single-key sort (the common case)
        rix = jnp.where(rank_f < _BIG, rank_f, F).astype(jnp.uint32)
        skey = lax.sort(ids_f.astype(jnp.uint32) * jnp.uint32(F + 1) + rix,
                        dimension=1)
        sid = (skey // jnp.uint32(F + 1)).astype(jnp.int32)
        srank = (skey % jnp.uint32(F + 1)).astype(jnp.int32)
        srank = jnp.where(srank >= F, _BIG, srank)
    else:  # huge tables: equivalent two-key lexsort
        sid, srank = lax.sort((ids_f, rank_f), dimension=1, num_keys=2)
    dup = sid[:, 1:] == sid[:, :-1]
    srank = srank.at[:, 1:].set(jnp.where(dup, _BIG, srank[:, 1:]))
    return sid, srank


def _merge_sorted(res_d, res_i, res_e, dd, new_i, new_e, W: int,
                  method: str = "auto"):
    """Stable sort-free two-way merge of the sorted width-W result arrays
    with K (unsorted) new entries; keeps the W nearest.  Exactly reproduces
    the old full-width stable sort of [res | new] without materialising or
    sorting [B, W+K].  ``method`` selects the source-index writeback (see
    ``repro.kernels.ops.merge_src_indices``)."""
    from repro.kernels.ops import merge_src_indices

    B, K = dd.shape
    kio = jnp.arange(K, dtype=jnp.int32)
    # stable rank of each new entry among the K new entries (K = m+1 is
    # tiny: one [B, K, K] comparison matrix beats any sort)
    lt = dd[:, :, None] > dd[:, None, :]
    eq_earlier = (dd[:, :, None] == dd[:, None, :]) & (
        kio[None, :, None] > kio[None, None, :]
    )
    rank_new = jnp.sum(lt | eq_earlier, axis=2, dtype=jnp.int32)  # [B, K]
    cmp = (res_d[:, :, None] <= dd[:, None, :]).astype(jnp.int32)  # [B, W, K]
    pos_a = jnp.arange(W, dtype=jnp.int32)[None, :] + (K - jnp.sum(cmp, axis=2))
    pos_b = rank_new + jnp.sum(cmp, axis=1)
    # merged positions 0..W+K-1 are a bijection; slots >= W fall off the
    # end.  Write back the source index of each surviving slot, then gather
    # all three payloads.
    src = merge_src_indices(pos_a, pos_b, W, K, method=method)
    out_d = jnp.take_along_axis(jnp.concatenate([res_d, dd], axis=1), src, 1)
    out_i = jnp.take_along_axis(jnp.concatenate([res_i, new_i], axis=1), src, 1)
    out_e = jnp.take_along_axis(jnp.concatenate([res_e, new_e], axis=1), src, 1)
    return out_d, out_i, out_e


def _landing_and_entry(di: DeviceIndex, ranges: jax.Array, o: int, num_layers: int):
    """Alg. 3 steps 1: selectivity (via unique values), landing layer, entry."""
    x, y = ranges[:, 0], ranges[:, 1]
    lo = jnp.searchsorted(di.uvals, x, side="left")
    hi = jnp.searchsorted(di.uvals, y, side="right") - 1
    has = hi >= lo
    n_prime = jnp.maximum(hi - lo + 1, 1)
    # argmax over layers of min(2 o^l, n')/max(2 o^l, n') — the ratio is
    # unimodal in l with its peak at l_h or l_h+1, so the global argmax
    # equals the paper's restricted argmax (Alg. 3 lines 2-3).
    w_l = 2 * (float(o) ** np.arange(num_layers))  # [L]
    w_l = jnp.asarray(w_l, jnp.float32)[None, :]
    npf = n_prime.astype(jnp.float32)[:, None]
    ratio = jnp.minimum(w_l, npf) / jnp.maximum(w_l, npf)
    l_d = jnp.argmax(ratio, axis=1).astype(jnp.int32)
    # entry point: representative vertex of the in-range value closest to the
    # filter median (Alg. 3 line 4).
    med = (x + y) * 0.5
    pos = jnp.searchsorted(di.uvals, med, side="left")
    cand_hi = jnp.clip(pos, lo, hi)
    cand_lo = jnp.clip(pos - 1, lo, hi)
    v_hi = di.uvals[jnp.clip(cand_hi, 0, di.uvals.shape[0] - 1)]
    v_lo = di.uvals[jnp.clip(cand_lo, 0, di.uvals.shape[0] - 1)]
    pick_lo = jnp.abs(v_lo - med) <= jnp.abs(v_hi - med)
    ep_uidx = jnp.where(pick_lo, cand_lo, cand_hi)
    ep = di.uval_rep[jnp.clip(ep_uidx, 0, di.uvals.shape[0] - 1)]
    return l_d, ep, has


def _init_state(di: DeviceIndex, queries: jax.Array, ranges: jax.Array,
                cfg: HopCfg) -> HopState:
    """Empty result set, empty visited filter, entry point staged for the
    seed iteration (hop 0 performs the entry evaluation in-loop)."""
    B, _ = queries.shape
    L, n, _ = di.neighbors.shape
    W = max(cfg.width, cfg.k)
    queries = queries.astype(jnp.float32)
    if cfg.metric != "l2":
        # cosine: match the host path, which normalises the query at search
        # time (stored vectors are pre-normalised at insert)
        qn = jnp.sqrt(jnp.sum(queries * queries, axis=1, keepdims=True))
        queries = queries / jnp.where(qn > 0, qn, 1.0)
    ranges = ranges.astype(jnp.float32)
    l_d, ep, has = _landing_and_entry(di, ranges, cfg.o, L)
    v_words = ((n + 31) // 32) if cfg.visited == "bitmap" else cfg.v_words
    return HopState(
        queries=queries,
        q2=jnp.sum(queries * queries, axis=1),
        x=ranges[:, 0],
        y=ranges[:, 1],
        l_d=l_d,
        l_min=jnp.zeros(B, jnp.int32),
        ep=jnp.where(has, ep, 0),
        res_d=jnp.full((B, W), _INF),
        res_i=jnp.full((B, W), -1, jnp.int32),
        res_e=jnp.ones((B, W), jnp.bool_),  # pad = expanded
        vstate=jnp.zeros((B, v_words + 1), jnp.uint32),
        active=has,
        dc=jnp.zeros(B, jnp.int32),
        hops=jnp.zeros(B, jnp.int32),
        t=jnp.int32(0),
    )


def _hop_body(di: DeviceIndex, cfg: HopCfg, st: HopState) -> HopState:
    """One iteration of the hop loop over the whole (current) batch."""
    B, _ = st.queries.shape
    L, n, m = di.neighbors.shape
    W = st.res_d.shape[1]
    F = L * m
    # per-hop DC cap (c_n <= m admits m+1 evaluations; a single-layer
    # graph only has m candidate slots to begin with)
    K = min(m + 1, F)
    lev = jnp.arange(L, dtype=jnp.int32)[None, :, None]  # [1, L, 1]
    col = jnp.arange(m, dtype=jnp.int32)[None, None, :]  # [1, 1, m]
    is_seed = st.t == 0

    # ---- pop the nearest unexpanded candidate (Alg. 2 line 5) ----
    unexp = jnp.where(st.res_e, _INF, st.res_d)  # [B, W]
    i_star = jnp.argmin(unexp, axis=1)  # [B]
    d_star = jnp.take_along_axis(unexp, i_star[:, None], 1)[:, 0]
    worst = st.res_d[:, W - 1]
    full = st.res_i[:, W - 1] >= 0
    done = jnp.logical_or(d_star == _INF, jnp.logical_and(full, d_star > worst))
    # queries doing work this hop; the seed iteration always works (the
    # empty result set would otherwise read as terminated)
    act = jnp.where(is_seed, st.active, jnp.logical_and(st.active, ~done))

    s = jnp.take_along_axis(st.res_i, i_star[:, None], 1)[:, 0]
    s = jnp.where(act & ~is_seed, s, 0)
    res_e2 = st.res_e.at[jnp.arange(B), i_star].set(True)
    res_e2 = jnp.where((act & ~is_seed)[:, None], res_e2, st.res_e)

    # ---- gather multi-layer neighbor block ----
    nb = jnp.transpose(di.neighbors[:, s, :], (1, 0, 2))  # [B, L, m]
    valid = nb >= 0
    nbc = jnp.clip(nb, 0, n - 1)
    a_nb = di.attrs[nbc]  # [B, L, m]
    vis, probe_cache = _visited_test_cached(st.vstate, nbc, valid, cfg)
    unvis = jnp.logical_and(valid, ~vis)
    inr = jnp.logical_and(
        a_nb >= st.x[:, None, None], a_nb <= st.y[:, None, None]
    )

    # ---- early-stop layer inclusion mask (Alg. 2 lines 7-17) ----
    below_ld = lev <= st.l_d[:, None, None]  # [B, L, 1]
    oor_unvis = jnp.any(
        jnp.logical_and(unvis, ~inr) & below_ld, axis=2
    )  # [B, L]
    neutral = jnp.where(lev[:, :, 0] <= st.l_d[:, None], oor_unvis, True)
    shifted = jnp.concatenate(
        [neutral[:, 1:], jnp.ones((B, 1), jnp.bool_)], axis=1
    )
    include = (
        jnp.cumprod(shifted[:, ::-1].astype(jnp.int32), axis=1)[:, ::-1] > 0
    )
    include = jnp.logical_and(include, lev[:, :, 0] <= st.l_d[:, None])
    # construction searches sweep [l_min, l_d] (Alg. 1 line 5: the insert
    # stops at the insertion layer); serving has l_min == 0 everywhere
    include = jnp.logical_and(include, lev[:, :, 0] >= st.l_min[:, None])

    elig = unvis & inr & include[:, :, None] & act[:, None, None]  # [B, L, m]
    rank = (st.l_d[:, None, None] - lev) * m + col  # [B, L, m]
    rank = jnp.where(elig, rank, _BIG)
    ids_f = nbc.reshape(B, F)
    rank_f = rank.reshape(B, F)
    # dedupe across layers: drop an entry if a better-ranked eligible
    # entry carries the same id (the host marks it visited first).
    if cfg.pipeline == "reference":
        ids_f, rank_f = _hop_ref.dedupe_pairwise(ids_f, rank_f)
        neg, sel_pos = lax.top_k(-rank_f, K)  # best (smallest) K ranks
        sel_rank = -neg
        sel_valid = sel_rank < _BIG
    else:
        ids_f, rank_f = _dedupe_sorted(ids_f, rank_f, n, F)
        # admission = the K best-ranked survivors.  A packed single-key
        # sort of (rank, position) — ranks are injective over slots, so
        # (F+1)-scaled packing is exact — replaces ``lax.top_k``, whose
        # CPU lowering costs ~4x a plain u32 sort at these widths.
        posF = jnp.arange(F, dtype=jnp.uint32)[None, :]
        key2 = jnp.minimum(rank_f, F).astype(jnp.uint32) * jnp.uint32(F + 1)
        key2 = lax.sort(key2 + posF, dimension=1)[:, :K]
        sel_rank = (key2 // jnp.uint32(F + 1)).astype(jnp.int32)
        sel_pos = (key2 % jnp.uint32(F + 1)).astype(jnp.int32)
        sel_valid = sel_rank < F
    sel_ids = jnp.take_along_axis(ids_f, sel_pos, axis=1)  # [B, K]
    sel_ids = jnp.where(sel_valid, sel_ids, 0)

    # ---- entry-point fold: the seed iteration selects exactly {ep} ----
    kio = jnp.arange(K, dtype=jnp.int32)[None, :]
    seed_valid = (kio == 0) & st.active[:, None]
    sel_valid = jnp.where(is_seed, seed_valid, sel_valid)
    sel_ids = jnp.where(is_seed, jnp.where(seed_valid, st.ep[:, None], 0),
                        sel_ids)

    # ---- mark visited ----
    if probe_cache is None or cfg.pipeline == "reference":
        # bitmap mode, or the oracle pipeline (kept on the rehash path so
        # parity tests exercise cached-vs-recomputed probes)
        vstate2 = _visited_mark(st.vstate, sel_ids, sel_valid, cfg)
    else:
        # satellite: reuse the probe positions the visited TEST already
        # computed.  A selected entry's layer-priority rank is injective in
        # its original (layer, col) slot given l_d — invert it and gather
        # the cached (word, mask) instead of rehashing the ids.  The seed
        # iteration's {ep} bypasses the candidate lanes (its probes are not
        # in the cache), so that one iteration folds in the entry's own
        # hash — a [B, 1] rehash, not [B, K].
        pos = jnp.clip(
            (st.l_d[:, None] - sel_rank // m) * m + sel_rank % m, 0, F - 1
        )
        w_sel = jnp.take_along_axis(probe_cache[0].reshape(B, F), pos, 1)
        m_sel = jnp.take_along_axis(probe_cache[1].reshape(B, F), pos, 1)
        w_ep, m_ep = _hash_wordmask(
            st.ep[:, None], st.vstate.shape[1] - 1, cfg.v_hashes
        )
        w_sel = jnp.where(is_seed, w_ep, w_sel)
        m_sel = jnp.where(is_seed, m_ep, m_sel)
        vstate2 = _visited_mark_hash(st.vstate, w_sel, m_sel, sel_valid)

    # ---- fused gather + distance evaluation ----
    idc = jnp.clip(sel_ids, 0, n - 1)
    if cfg.pipeline == "reference":
        dots, v2 = _hop_ref.eval_materialized(
            di.vectors, di.sq_norms, idc, st.queries, cfg.backend
        )
    else:
        # fused gather+distance: no [B, K, d] HBM intermediate (and for
        # quantized slabs the dequant is fused in VMEM behind the row DMAs)
        from repro.kernels.ops import gather_norm_dot

        dots, v2 = gather_norm_dot(di.vectors, idc, st.queries,
                                   scales=_gather_scales(di),
                                   backend=cfg.backend)
    if cfg.metric == "l2":
        dd = jnp.maximum(v2 - 2.0 * dots + st.q2[:, None], 0.0)
    else:
        dd = 1.0 - dots
    dd = jnp.where(sel_valid, dd, _INF)
    dc2 = st.dc + jnp.sum(sel_valid, axis=1).astype(jnp.int32)

    # ---- merge into the sorted fixed-width result set ----
    new_i = jnp.where(sel_valid, sel_ids, -1)
    new_e = ~sel_valid  # invalid entries act as expanded padding
    if cfg.pipeline == "reference":
        nres_d, nres_i, nres_e = _hop_ref.merge_full_sort(
            st.res_d, st.res_i, res_e2, dd, new_i, new_e, W
        )
    else:
        nres_d, nres_i, nres_e = _merge_sorted(
            st.res_d, st.res_i, res_e2, dd, new_i, new_e, W, method=cfg.merge
        )

    # ---- commit only for queries that worked this hop ----
    # (vstate needs no masking: an inactive row has sel_valid all-False, so
    # its mark writes only the trash word — masking would stream the whole
    # filter state through a select every hop, which at hash-filter sizes
    # costs more than the hop itself)
    return st._replace(
        res_d=jnp.where(act[:, None], nres_d, st.res_d),
        res_i=jnp.where(act[:, None], nres_i, st.res_i),
        res_e=jnp.where(act[:, None], nres_e, res_e2),
        vstate=vstate2,
        active=act,
        dc=jnp.where(act, dc2, st.dc),
        hops=st.hops + (act & ~is_seed).astype(jnp.int32),
        t=st.t + 1,
    )


def _run_hops(di: DeviceIndex, st: HopState, cfg: HopCfg, h: int) -> HopState:
    """Run up to ``h`` iterations (stops early when every query terminated;
    the global iteration cap ``max_hops + 1`` counts the seed)."""

    def cond(carry):
        s, i = carry
        return (
            jnp.any(s.active) & (i < h) & (s.t < cfg.max_hops + 1)
        )

    def body(carry):
        s, i = carry
        return _hop_body(di, cfg, s), i + 1

    st, _ = lax.while_loop(cond, body, (st, jnp.int32(0)))
    return st


@functools.partial(jax.jit, static_argnames=("cfg",))
def _init_jit(di, queries, ranges, cfg):
    return _init_state(di, queries, ranges, cfg)


@functools.partial(jax.jit, static_argnames=("cfg", "h"))
def _run_jit(di, st, cfg, h):
    return _run_hops(di, st, cfg, h)


_REC_LANES = 128  # the TPU's vector width: a record row is whole lanes


@functools.partial(jax.jit, static_argnames=("cfg", "h"), donate_argnums=(1,))
def _run_jit_inplace(di, st, cfg, h):
    """The serve engine's hop chunk: ``_run_hops`` over a donated ``st``
    (the returned state reuses its buffers, so the caller drops ``st``),
    plus the chunk-boundary record that the host reads in one transfer.

    The record is ``i32[B, R]``: columns ``active``, ``dc``, ``hops``,
    ``res_i[:, :k]`` and the bits of ``res_d[:, :k]``, zero after
    ``2k+3`` (``chunk_record`` decodes it).  ``R`` rounds ``2k+3`` up to
    whole 128-lane rows: a narrower record gets a column-major layout on
    the chip, and building it then takes transposing copies of
    ``res_i``/``res_d``; built from pads and selects it is one fusion.

    ``_run_jit`` stays as it is because ``_drive_chunked`` keeps earlier
    states' result arrays alive across later chunks.  The name keeps
    ``_run_jit`` as its prefix, so a trace reader that matches programs
    by ``_run_jit`` counts this program's device time too."""
    st = _run_hops(di, st, cfg, h)
    k, B = cfg.k, st.active.shape[0]
    R = -(-(2 * k + 3) // _REC_LANES) * _REC_LANES
    col = lax.broadcasted_iota(jnp.int32, (B, R), 1)
    ids = jnp.pad(st.res_i[:, :k], ((0, 0), (3, R - 3 - k)))
    bits = jnp.pad(lax.bitcast_convert_type(st.res_d[:, :k], jnp.int32),
                   ((0, 0), (3 + k, R - 3 - 2 * k)))
    rec = jnp.where(col == 0, st.active.astype(jnp.int32)[:, None],
                    jnp.where(col == 1, st.dc[:, None],
                              jnp.where(col == 2, st.hops[:, None],
                                        ids + bits)))
    return st, rec


def chunk_record(rec: np.ndarray, k: int):
    """Decode a ``_run_jit_inplace`` record into ``(active bool[B],
    dc i32[B], hops i32[B], res_i i32[B, k], res_d f32[B, k])``; the
    distances come back bit for bit."""
    return (rec[:, 0] != 0, rec[:, 1], rec[:, 2], rec[:, 3:3 + k],
            rec[:, 3 + k:3 + 2 * k].view(np.float32))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _search_whole(di, queries, ranges, cfg) -> SearchResult:
    """Lock-step path: init + one full-length hop loop, all in one jit."""
    st = _init_state(di, queries, ranges, cfg)
    st = _run_hops(di, st, cfg, cfg.max_hops + 1)
    return SearchResult(
        ids=st.res_i[:, : cfg.k], dists=st.res_d[:, : cfg.k],
        dc=st.dc, hops=st.hops,
    )


def _init_build_state(di: DeviceIndex, queries, ranges, eps, l_lo, l_hi,
                      seed_i, seed_d, valid, cfg: HopCfg) -> HopState:
    """Construction-search init: entry/landing override + carry-seeded beams.

    Unlike the serving ``_init_state`` the caller supplies everything the
    snapshot's unique-value tables would otherwise derive: the layer span
    ``[l_lo, l_hi]`` (insertion layer up to the top, Alg. 1 line 5), the
    host-sampled window entry ``eps`` (Alg. 1 line 7) and the Thm-3.1 carry
    ``(seed_i, seed_d)`` — already-evaluated candidates whose distances are
    known, so they preload the beam with no DC and no re-discovery hops.
    Members with a non-empty carry skip the entry evaluation entirely; the
    rest evaluate their entry here (the hop-0 fold, hoisted out of the
    loop), and the state starts at ``t = 1`` so ``_hop_body`` never runs
    its seed iteration.  ``queries`` must be prepared (cosine-normalised)
    rows — they come straight from the store arena."""
    B, _ = queries.shape
    L, n, m = di.neighbors.shape
    W = max(cfg.width, cfg.k)
    queries = queries.astype(jnp.float32)
    q2 = jnp.sum(queries * queries, axis=1)
    ranges = ranges.astype(jnp.float32)
    # carry sorted ascending by distance (stable; invalid lanes +inf), the
    # nearest W preloading the beam — exactly the host path's preload
    sd = jnp.where(seed_i >= 0, seed_d.astype(jnp.float32), _INF)
    sd_s, si_s = lax.sort(
        (sd, seed_i.astype(jnp.int32)), dimension=1, num_keys=1
    )
    S = min(seed_i.shape[1], W)
    res_d = jnp.full((B, W), _INF).at[:, :S].set(sd_s[:, :S])
    res_i = jnp.full((B, W), -1, jnp.int32).at[:, :S].set(
        jnp.where(jnp.isfinite(sd_s[:, :S]), si_s[:, :S], -1)
    )
    has_seed = res_i[:, 0] >= 0
    epc = jnp.clip(eps.astype(jnp.int32), 0, n - 1)
    if cfg.pipeline == "reference":
        dots, v2 = _hop_ref.eval_materialized(
            di.vectors, di.sq_norms, epc[:, None], queries, cfg.backend
        )
    else:
        from repro.kernels.ops import gather_norm_dot

        dots, v2 = gather_norm_dot(di.vectors, epc[:, None], queries,
                                   scales=_gather_scales(di),
                                   backend=cfg.backend)
    if cfg.metric == "l2":
        d_ep = jnp.maximum(v2[:, 0] - 2.0 * dots[:, 0] + q2, 0.0)
    else:
        d_ep = 1.0 - dots[:, 0]
    use_ep = valid & ~has_seed
    res_d = res_d.at[:, 0].set(jnp.where(use_ep, d_ep, res_d[:, 0]))
    res_i = res_i.at[:, 0].set(jnp.where(use_ep, epc, res_i[:, 0]))
    res_e = res_i < 0  # valid entries unexpanded; padding reads expanded
    v_words = ((n + 31) // 32) if cfg.visited == "bitmap" else cfg.v_words
    vstate = jnp.zeros((B, v_words + 1), jnp.uint32)
    # mark exactly the preloaded beam (kept seeds + entries), as the host does
    vstate = _visited_mark(vstate, jnp.maximum(res_i, 0), res_i >= 0, cfg)
    return HopState(
        queries=queries,
        q2=q2,
        x=ranges[:, 0],
        y=ranges[:, 1],
        l_d=l_hi.astype(jnp.int32),
        l_min=l_lo.astype(jnp.int32),
        ep=epc,
        res_d=res_d,
        res_i=res_i,
        res_e=res_e,
        vstate=vstate,
        active=valid,
        dc=use_ep.astype(jnp.int32),  # the entry evaluation, host-identical
        hops=jnp.zeros(B, jnp.int32),
        t=jnp.int32(1),  # the entry fold already happened: skip the seed hop
    )


def _build_search_core(di, queries, ranges, eps, l_lo, l_hi, seed_i, seed_d,
                       valid, cfg):
    """Init + lock-step hop loop of one construction search: the pure
    jittable core, shared by the single-device jit below and the
    ``shard_map``-sharded build path (``repro.core.distributed``) — every
    per-member trajectory is row-independent, so sharding the batch
    dimension preserves results bitwise."""
    st = _init_build_state(di, queries, ranges, eps, l_lo, l_hi, seed_i,
                           seed_d, valid, cfg)
    st = _run_hops(di, st, cfg, cfg.max_hops + 1)
    return st.res_i, st.res_d, st.dc, st.hops


_build_search_jit = functools.partial(jax.jit, static_argnames=("cfg",))(
    _build_search_core
)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _build_init_jit(di, queries, ranges, eps, l_lo, l_hi, seed_i, seed_d,
                    valid, cfg):
    return _init_build_state(di, queries, ranges, eps, l_lo, l_hi, seed_i,
                             seed_d, valid, cfg)


class _BuildPrep(NamedTuple):
    """Device-ready construction-search inputs (see ``_prep_build_inputs``):
    ``args`` is the positional tuple ``_build_search_core`` consumes after
    ``di`` (targets, ranges, eps, lo, hi, seed ids/dists, valid)."""

    di: DeviceIndex  # layer-span-sliced view
    args: tuple
    cfg: HopCfg
    B: int  # real (unpadded) member count


def _prep_build_inputs(
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
    metric: str,
    seed_width: int | None,
    backend: str,
    visited: str,
    visited_bits: int | None,
    visited_fp: float,
    visited_hashes: int,
    merge: str,
    max_hops: int | None,
    multiple: int = 1,
) -> _BuildPrep:
    """Host-side prep of one construction search, shared bit-for-bit by the
    single-device ``build_search`` and the sharded build path: seed
    truncation, pow2 batch padding (additionally rounded up to ``multiple``
    so the batch divides a build mesh), static config, and the layer-span
    slice of the neighbor tensor.  Per-member trajectories are independent
    of the padded batch size, so every consumer of one prep computes
    identical per-member results."""
    targets = np.asarray(targets, np.float32)
    B = targets.shape[0]
    W = int(width)
    if max_hops is None:
        max_hops = _default_max_hops(W)
    C = int(seed_width) if seed_width else (
        seed_ids.shape[1] if seed_ids is not None and seed_ids.ndim == 2 else 0
    )
    # the init keeps only the W nearest seeds (the host preload's S =
    # min(C, W)); truncating host-side shrinks the device-side seed sort
    # from the full carry width to W
    if seed_ids is not None and seed_ids.ndim == 2 and seed_ids.shape[1] > W:
        so = np.argsort(
            np.where(seed_ids >= 0, seed_d, np.inf), axis=1, kind="stable"
        )[:, :W]
        seed_ids = np.take_along_axis(seed_ids, so, 1)
        seed_d = np.take_along_axis(seed_d, so, 1)
    C = max(min(C, W), 1)
    Bp = _pow2ceil(max(B, _MIN_BUCKET))
    if multiple > 1 and Bp % multiple:
        Bp = -(-Bp // multiple) * multiple  # round up to the mesh size
    si = np.full((Bp, C), -1, np.int32)
    sdp = np.full((Bp, C), np.inf, np.float32)
    if seed_ids is not None and seed_ids.size:
        S = min(seed_ids.shape[1], C)
        si[:B, :S] = seed_ids[:, :S]
        sdp[:B, :S] = seed_d[:, :S]
    tp = np.zeros((Bp, targets.shape[1]), np.float32)
    tp[:B] = targets
    rp = np.zeros((Bp, 2), np.float32)
    rp[:B] = np.asarray(ranges, np.float32)
    rp[B:] = (1.0, 0.0)
    ep = np.zeros(Bp, np.int32)
    ep[:B] = np.asarray(eps, np.int32)
    valid = np.arange(Bp) < B
    v_words = 0
    if visited == "hash":
        if visited_bits is None:
            visited_bits = visited_filter_bits(
                W, m, max_hops, fp=visited_fp, hashes=visited_hashes
            )
        else:
            visited_bits = _pow2ceil(max(int(visited_bits), 1024))
        v_words = visited_bits // 32
    cfg = HopCfg(
        k=W, width=W, m=m, o=o, metric=metric, max_hops=int(max_hops),
        backend=backend, pipeline="fused", visited=visited,
        v_words=v_words, v_hashes=int(visited_hashes), merge=merge,
    )
    # layer-span slicing: a search over [l_lo, l_hi] only ever gathers
    # those layers' rows, so slice the neighbor tensor to a pow2-quantised
    # span ending at l_hi (extra lower layers are masked by l_min) — the
    # per-hop sort/mask width then scales with the sweep, not the full
    # layer count, at O(log L) compiled span shapes.
    L_all = di.neighbors.shape[0]
    span_q = min(_pow2ceil(int(l_hi) - int(l_lo) + 1), int(l_hi) + 1)
    base = int(l_hi) + 1 - span_q
    if base > 0 or span_q < L_all:
        di = di._replace(neighbors=di.neighbors[base : int(l_hi) + 1])
    lo = np.full(Bp, int(l_lo) - base, np.int32)
    hi = np.full(Bp, int(l_hi) - base, np.int32)
    args = (
        jnp.asarray(tp), jnp.asarray(rp), jnp.asarray(ep),
        jnp.asarray(lo), jnp.asarray(hi), jnp.asarray(si), jnp.asarray(sdp),
        jnp.asarray(valid),
    )
    return _BuildPrep(di=di, args=args, cfg=cfg, B=B)


def _finish_build_search(
    res_i, res_d, dc, hops, B: int, deleted: set[int] | None
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Device->host readback of one construction search: strip the batch
    padding and mask deleted ids to -1 (they stay traversable in-loop,
    §3.7), mirroring ``search_candidates_batch``'s contract."""
    res_i = np.asarray(res_i)[:B]
    res_d = np.asarray(res_d)[:B]
    dc = np.asarray(dc)[:B]
    hops = np.asarray(hops)[:B]
    if deleted:
        dead = (res_i >= 0) & np.isin(
            res_i, np.fromiter(deleted, dtype=np.int64, count=len(deleted))
        )
        res_i = np.where(dead, -1, res_i)
    return res_i, res_d, dc, hops


def build_search(
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
    compact: tuple[int, int] | None = (8, 8),
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """One micro-batch per-layer candidate search on the device pipeline —
    the accelerator-resident replacement for the host
    ``search_candidates_batch`` during batched builds.

    ``targets`` [B, d] are prepared member vectors, ``ranges`` [B, 2] the
    per-member layer windows, ``eps`` [B] host-sampled entries (used only by
    members with an empty carry) and ``(seed_ids, seed_d)`` the Thm-3.1
    carry.  ``B`` is padded to a power-of-two bucket and the carry to a
    fixed ``seed_width`` so one construction run compiles O(log B) shapes.
    ``compact`` (default ``(8, 8)``) runs the hop loop as resumable
    chunks with ragged-batch compaction between them — carry-seeded members
    finish in a handful of hops, so harvesting them early keeps the
    lock-step loop from running every member at the straggler's pace;
    ``None`` = one whole-loop jit (required inside an outer jit).  Returns
    host ``(res_i, res_d, dc, hops)`` with deleted ids masked to -1 (they
    stay traversable in-loop, §3.7), mirroring the host contract.

    The multi-device twin — the same prep, the same lock-step core, the
    batch sharded over a build mesh — is
    ``repro.core.distributed.sharded_build_search``.
    """
    prep = _prep_build_inputs(
        di, targets, ranges, eps, l_lo, l_hi, seed_ids, seed_d,
        width=width, m=m, o=o, metric=metric, seed_width=seed_width,
        backend=backend, visited=visited, visited_bits=visited_bits,
        visited_fp=visited_fp, visited_hashes=visited_hashes, merge=merge,
        max_hops=max_hops,
    )
    args = (prep.di, *prep.args, prep.cfg)
    if compact is None:
        out = _build_search_jit(*args)
    else:
        st = _build_init_jit(*args)
        out = _drive_chunked(
            prep.di, st, prep.cfg, (int(compact[0]), int(compact[1])),
            prep.B, 1,
        )
    return _finish_build_search(*out, prep.B, deleted)


@jax.jit
def _compact_rows(st: HopState, idx: jax.Array, act_n: jax.Array) -> HopState:
    """Gather surviving rows into the next bucket (rows >= act_n are
    padding duplicates, forced inactive).  ``act_n`` is traced so distinct
    survivor counts share one compilation per bucket shape."""
    take = lambda a: jnp.take(a, idx, axis=0)
    act = jnp.arange(idx.shape[0]) < act_n
    return HopState(
        queries=take(st.queries), q2=take(st.q2), x=take(st.x), y=take(st.y),
        l_d=take(st.l_d), l_min=take(st.l_min), ep=take(st.ep),
        res_d=take(st.res_d),
        res_i=take(st.res_i), res_e=take(st.res_e), vstate=take(st.vstate),
        active=take(st.active) & act, dc=take(st.dc), hops=take(st.hops),
        t=st.t,
    )


def _drive_chunked(di, st: HopState, cfg: HopCfg, compact: tuple[int, int],
                   B: int, t0: int):
    """Ragged-batch compaction driver (host-side scheduling, jitted chunks)
    over an already-initialised ``HopState`` of ``Bp >= B`` rows (rows >= B
    are padding and must be inactive).

    Phase 1 runs ``compact[0]`` iterations on the full bucket; every
    subsequent phase compacts the still-active queries into the next pow2
    bucket and runs ``compact[1]`` more.  Finished queries are harvested at
    chunk boundaries.  Bitwise identical to the lock-step loop — per-query
    trajectories are iteration-indexed and independent.  ``t0`` is the
    state's initial iteration counter (0 for serving, 1 for build states
    whose entry fold happened at init).  Returns host
    ``(ids[B, k], dists[B, k], dc[B], hops[B])`` with ``k = cfg.k``.
    """
    h0, h1 = compact
    k = cfg.k
    out_i = np.full((B, k), -1, np.int32)
    out_d = np.full((B, k), np.inf, np.float32)
    out_dc = np.zeros(B, np.int32)
    out_hops = np.zeros(B, np.int32)
    if B == 0:
        return out_i, out_d, out_dc, out_hops
    Bp = st.res_i.shape[0]
    orig = np.concatenate([np.arange(B), np.full(Bp - B, B)])  # B = sentinel

    h = h0
    t_planned = t0  # upper bound on st.t, tracked host-side (no extra sync)
    harvests = []  # (dst rows, bucket rows, state) — materialised post-loop
    while True:
        st = _run_jit(di, st, cfg, h)
        t_planned += h
        act = np.asarray(st.active)  # the chunk-boundary sync point
        real = orig < B
        live = np.flatnonzero(act & real)
        stop = live.size == 0 or t_planned >= cfg.max_hops + 1
        leave = np.flatnonzero(real if stop else (~act & real))
        if leave.size:  # queries leaving the bucket: defer the device->host
            # reads to after the loop; keep only the result arrays alive
            # (not the whole state — the visited filter dwarfs them)
            harvests.append(
                (orig[leave], leave, st.res_i, st.res_d, st.dc, st.hops))
        if stop:
            break
        Bn = _bucket_ceil(live.size)
        if Bn < len(orig):  # bucket shrinks: gather the survivors
            idx = np.concatenate([live, np.full(Bn - live.size, live[0])])
            st = _compact_rows(st, jnp.asarray(idx), jnp.int32(live.size))
            orig = np.where(np.arange(Bn) < live.size, orig[idx], B)
        else:  # same bucket: skip the gather, just retire harvested rows
            orig[leave] = B
        h = h1
    for dst, rows_, res_i, res_d, dc_, hops_ in harvests:
        out_i[dst] = np.asarray(res_i)[rows_, :k]
        out_d[dst] = np.asarray(res_d)[rows_, :k]
        out_dc[dst] = np.asarray(dc_)[rows_]
        out_hops[dst] = np.asarray(hops_)[rows_]
    return out_i, out_d, out_dc, out_hops


def _search_chunked(di, queries, ranges, cfg: HopCfg,
                    compact: tuple[int, int]) -> SearchResult:
    """Serving entry of the compaction driver: pad, init, drive."""
    B = queries.shape[0]
    if B == 0:
        return SearchResult(
            ids=np.full((0, cfg.k), -1, np.int32),
            dists=np.full((0, cfg.k), np.inf, np.float32),
            dc=np.zeros(0, np.int32), hops=np.zeros(0, np.int32),
        )
    Bp = _pow2ceil(max(B, _MIN_BUCKET))
    qp = jnp.zeros((Bp, queries.shape[1]), jnp.float32).at[:B].set(
        jnp.asarray(queries, jnp.float32))
    # pad rows carry an inverted (empty) range -> inactive from init
    rp = jnp.broadcast_to(jnp.asarray([1.0, 0.0], jnp.float32), (Bp, 2))
    rp = rp.at[:B].set(jnp.asarray(ranges, jnp.float32))
    st = _init_jit(di, qp, rp, cfg)
    return SearchResult(*_drive_chunked(di, st, cfg, compact, B, 0))


def hop_cfg(
    *,
    k: int = 10,
    width: int = 64,
    m: int = 16,
    o: int = 4,
    metric: str = "l2",
    max_hops: int | None = None,
    backend: str = "auto",
    pipeline: str = "fused",
    visited: str = "bitmap",
    visited_bits: int | None = None,
    visited_fp: float = 0.02,
    visited_hashes: int = 2,
    merge: str = "auto",
) -> HopCfg:
    """Resolve user-facing serving knobs into the static ``HopCfg`` jit
    key: beam width floored at k, the default global hop budget, hash
    filter sizing (budget-derived when ``visited_bits`` is None, pow2
    floor otherwise).  Shared by ``device_search`` and the serve engine
    (``repro.serve.lifecycle``), which drives the chunked hop loop itself
    and must produce bit-identical trajectories for equal knobs."""
    if pipeline not in ("fused", "reference"):
        raise ValueError(f"unknown pipeline {pipeline!r}")
    if visited not in ("bitmap", "hash"):
        raise ValueError(f"unknown visited filter {visited!r}")
    W = max(width, k)
    if max_hops is None:
        max_hops = _default_max_hops(W)
    v_words = 0
    if visited == "hash":
        if visited_bits is None:
            visited_bits = visited_filter_bits(
                W, m, max_hops, fp=visited_fp, hashes=visited_hashes
            )
        else:
            visited_bits = _pow2ceil(max(int(visited_bits), 1024))
        v_words = visited_bits // 32
    return HopCfg(
        k=k, width=W, m=m, o=o, metric=metric, max_hops=int(max_hops),
        backend=backend, pipeline=pipeline, visited=visited,
        v_words=v_words, v_hashes=int(visited_hashes), merge=merge,
    )


def device_search(
    di: DeviceIndex,
    queries: jax.Array,  # f32[B, d]
    ranges: jax.Array,  # f32[B, 2]
    *,
    k: int = 10,
    width: int = 64,
    m: int = 16,
    o: int = 4,
    metric: str = "l2",
    max_hops: int | None = None,
    backend: str = "auto",
    pipeline: str = "fused",
    visited: str = "bitmap",
    visited_bits: int | None = None,
    visited_fp: float = 0.02,
    visited_hashes: int = 2,
    merge: str = "auto",
    compact: tuple[int, int] | None = None,
) -> SearchResult:
    """Batched device search.  All keyword knobs are static (jit keys);
    see the module docstring for the ``visited``/``compact``/``merge``
    semantics.  With ``compact=None`` this is a pure jittable function."""
    if pipeline == "reference" and di.vectors.dtype != jnp.float32:
        # the oracle pipeline materializes di.vectors [B, K, d] and reads
        # di.sq_norms directly — it has no dequant stage by design (f32 is
        # the parity oracle; quantized modes are gated against it instead)
        raise ValueError(
            "pipeline='reference' requires an f32 vector slab; quantized "
            f"snapshots (dtype {di.vectors.dtype}) serve via pipeline='fused'"
        )
    cfg = hop_cfg(
        k=k, width=width, m=m, o=o, metric=metric, max_hops=max_hops,
        backend=backend, pipeline=pipeline, visited=visited,
        visited_bits=visited_bits, visited_fp=visited_fp,
        visited_hashes=visited_hashes, merge=merge,
    )
    if compact is None:
        return _search_whole(di, queries, ranges, cfg)
    return _search_chunked(di, jnp.asarray(queries), jnp.asarray(ranges),
                           cfg, (int(compact[0]), int(compact[1])))


def search_batch(
    snap: Snapshot,
    queries: np.ndarray,
    ranges: np.ndarray,
    k: int = 10,
    width: int = 64,
    backend: str = "auto",
    pipeline: str = "fused",
    visited: str = "bitmap",
    visited_bits: int | None = None,
    compact: tuple[int, int] | None = None,
    pad_batch: bool = True,
    max_hops: int | None = None,
    vec_dtype: str | None = None,
) -> SearchResult:
    """Convenience host wrapper: snapshot -> device arrays -> search.

    ``pad_batch`` pads B up to the next power-of-two bucket (padding rows
    carry an empty range, so they are inactive from init and cost no hops)
    — a stream of distinct batch sizes then reuses one compilation per
    bucket instead of recompiling ``device_search`` for every new B.
    ``max_hops`` caps the global hop budget below the width-derived
    default — the deadline-aware degraded-search knob: a truncated search
    returns the best-so-far beam instead of running to convergence.
    ``vec_dtype`` selects the device slab storage mode (see
    ``to_device_index``); quantized modes require ``pipeline="fused"``.
    """
    di = to_device_index(snap, vec_dtype=vec_dtype)
    queries = np.asarray(queries, np.float32)
    ranges = np.asarray(ranges, np.float32)
    B = queries.shape[0]
    Bp = _pow2ceil(max(B, _MIN_BUCKET)) if pad_batch else B
    if Bp != B:
        queries = np.concatenate(
            [queries, np.zeros((Bp - B, queries.shape[1]), np.float32)])
        ranges = np.concatenate(
            [ranges, np.tile(np.asarray([[1.0, 0.0]], np.float32),
                             (Bp - B, 1))])
    res = device_search(
        di,
        jnp.asarray(queries, jnp.float32),
        jnp.asarray(ranges, jnp.float32),
        k=k,
        width=width,
        m=snap.m,
        o=snap.o,
        metric="l2" if snap.metric == "l2" else "cosine",
        max_hops=max_hops,
        backend=backend,
        pipeline=pipeline,
        visited=visited,
        visited_bits=visited_bits,
        compact=compact,
    )
    if Bp != B:
        res = SearchResult(ids=res.ids[:B], dists=res.dists[:B],
                           dc=res.dc[:B], hops=res.hops[:B])
    return res
