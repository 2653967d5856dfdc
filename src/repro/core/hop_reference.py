"""Pre-refactor ``device_search`` hop stages — kept as the parity oracle.

These are the original (correct but slow) implementations of the three hop
stages that the fused pipeline in ``device_search`` replaced:

  * ``dedupe_pairwise``   — O(F^2) all-pairs duplicate mask ([B, F, F]
    intermediate, F = L*m);
  * ``merge_full_sort``   — full-width ``lax.sort`` over [B, W+K] to merge K
    new candidates into the already-sorted width-W result array;
  * ``eval_materialized`` — XLA gather of a [B, K, d] candidate tensor
    followed by a batched dot (the HBM round-trip the slab kernel fuses
    away), with the cached per-vertex squared norms gathered separately.

``device_search(..., pipeline="reference")`` runs the hop with these stages;
parity tests assert bitwise-identical ids and matching DC/hop counters
against the fused pipeline, and benchmarks time old vs new.  Do not use in
production serving — every stage here is strictly dominated.

The hashed visited filter (``visited="hash"``) gets the same treatment:
``hash_positions_ref`` / ``hash_mark_dense`` / ``hash_test_dense`` are a
plain-numpy dense-boolean re-statement of the packed double-hashed filter
(one uint8 per *bit*, direct fancy indexing, no word packing, no scatter
tricks) used by unit tests to pin down the packed uint32 implementation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# numpy (not jnp) scalars: this module may first be imported inside a jit
# trace, and jnp constants created there would leak as tracers
_INF = np.float32(np.inf)
_BIG = np.int32(2**30)


def dedupe_pairwise(ids_f: jax.Array, rank_f: jax.Array):
    """All-pairs dedupe: drop an entry if a better-ranked eligible entry
    carries the same id (the host marks it visited first).  Returns the
    (ids, masked ranks) pair in the original flattened order."""
    eq = ids_f[:, :, None] == ids_f[:, None, :]  # [B, F, F]
    better = rank_f[:, None, :] < rank_f[:, :, None]
    dup = jnp.any(eq & better & (rank_f[:, None, :] < _BIG), axis=2)
    return ids_f, jnp.where(dup, _BIG, rank_f)


def merge_full_sort(res_d, res_i, res_e, dd, new_i, new_e, W: int):
    """Merge K new entries by sorting the full [B, W+K] concatenation."""
    cat_d = jnp.concatenate([res_d, dd], axis=1)
    cat_i = jnp.concatenate([res_i, new_i], axis=1)
    cat_e = jnp.concatenate([res_e, new_e], axis=1)
    srt_d, srt_i, srt_e = lax.sort(
        (cat_d, cat_i, cat_e.astype(jnp.int32)), dimension=1, num_keys=1
    )
    return srt_d[:, :W], srt_i[:, :W], srt_e[:, :W] > 0


def hash_positions_ref(ids: np.ndarray, v_bits: int, nh: int) -> np.ndarray:
    """numpy twin of ``device_search._hash_positions``: ids int[...] ->
    uint32[..., nh] probe positions (shared with the host filter)."""
    from .search import hash_positions_np

    return hash_positions_np(ids, v_bits, nh)


def hash_mark_dense(dense: np.ndarray, ids, valid, nh: int) -> np.ndarray:
    """Insert ids [B, K] into a dense uint8 bit array [B, v_bits]."""
    B, v_bits = dense.shape
    pos = hash_positions_ref(ids, v_bits, nh)  # [B, K, nh]
    rows = np.arange(B)[:, None, None]
    out = dense.copy()
    np.maximum.at(out, (np.broadcast_to(rows, pos.shape),
                        pos.astype(np.int64)),
                  np.asarray(valid)[:, :, None].astype(np.uint8))
    return out


def hash_test_dense(dense: np.ndarray, ids, nh: int) -> np.ndarray:
    """Membership of ids [B, ...] in the dense bit array -> bool."""
    B, v_bits = dense.shape
    pos = hash_positions_ref(ids, v_bits, nh).astype(np.int64)
    rows = np.arange(B).reshape((B,) + (1,) * (pos.ndim - 1))
    return dense[rows, pos].min(axis=-1) > 0


def unpack_filter(vstate: np.ndarray) -> np.ndarray:
    """Packed uint32 filter [B, Vw(+trash)] -> dense uint8 bits [B, Vw*32]
    (the trailing trash word is dropped)."""
    words = np.asarray(vstate)[:, :-1]
    bits = (words[:, :, None] >> np.arange(32, dtype=np.uint32)) & 1
    return bits.reshape(words.shape[0], -1).astype(np.uint8)


def eval_materialized(vectors, sq_norms, idc, queries, backend: str):
    """Gather a [B, K, d] candidate tensor in HBM, then dot.  Returns
    (dots, v2) with v2 taken from the cached norm table."""
    vecs = vectors[idc]
    if backend == "ref":
        dots = jnp.einsum("bkd,bd->bk", vecs, queries,
                          precision=jax.lax.Precision.HIGHEST)
    else:
        from repro.kernels.ops import batched_dot

        dots = batched_dot(vecs, queries, backend=backend)
    return dots, sq_norms[idc]
