"""The plain reference: brute-force range-filtered k nearest neighbours,
and exact distances, over the benchmark's own copy of the corpus.

It imports nothing of the program.  The top-k runs on the device in blocks
of queries with plain ``jnp`` (one matrix product per block at the given
precision, a range mask, ``lax.top_k``); exact distances of given ids are
taken on the host in float64.  ``stored_rows`` reproduces a deployment's
slab as the configuration states it (float32 rows, or int8 rows with one
scale per row, ``max|row| / 127``); ``int4`` is the control's step below
int8.
"""
from __future__ import annotations

import functools

import numpy as np

BLOCK = 256  # queries per device block: [BLOCK, n] f32 distances
_LEVELS = {"int8": 127.0, "int4": 7.0}


def stored_rows(vectors: np.ndarray, vec_dtype: str) -> np.ndarray:
    """The rows a slab of ``vec_dtype`` holds, as float32 values."""
    v = np.ascontiguousarray(vectors, dtype=np.float32)
    if vec_dtype == "f32":
        return v
    if vec_dtype not in _LEVELS:
        raise ValueError(f"unknown slab dtype {vec_dtype!r}")
    top = np.float32(_LEVELS[vec_dtype])
    scale = (np.maximum(np.abs(v).max(axis=1), np.float32(1e-12))
             / top).astype(np.float32)
    q = np.clip(np.rint(v / scale[:, None]), -top, top)
    return (q * scale[:, None]).astype(np.float32)


@functools.lru_cache(maxsize=None)
def _block_fn(k: int, precision: str):
    import jax
    import jax.numpy as jnp
    from jax import lax

    prec = {"highest": lax.Precision.HIGHEST,
            "high": lax.Precision.HIGH}[precision]

    @jax.jit
    def block(x, x2, attrs, q, lo, hi):
        dots = jnp.dot(q, x.T, precision=prec)
        q2 = jnp.sum(q * q, axis=1, keepdims=True)
        d = x2[None, :] - 2.0 * dots + q2
        inside = (attrs[None, :] >= lo[:, None]) & (attrs[None, :] <= hi[:, None])
        d = jnp.where(inside, d, jnp.inf)
        neg, ids = lax.top_k(-d, k)
        ids = jnp.where(jnp.isfinite(neg), ids, -1)
        return ids, -neg

    return block


def topk(rows: np.ndarray, attrs: np.ndarray, queries: np.ndarray,
         ranges: np.ndarray, k: int, precision: str = "highest"):
    """-> (ids i64[N, k], dists f32[N, k]) of the k nearest rows inside
    each query's inclusive range; -1 / +inf where the range holds fewer."""
    import jax.numpy as jnp

    fn = _block_fn(k, precision)
    x = jnp.asarray(rows, jnp.float32)
    x2 = jnp.sum(x * x, axis=1)
    a = jnp.asarray(attrs, jnp.float32)
    N = len(queries)
    ids = np.full((N, k), -1, np.int64)
    dists = np.full((N, k), np.inf, np.float32)
    for s in range(0, N, BLOCK):
        e = min(s + BLOCK, N)
        q = np.zeros((BLOCK, rows.shape[1]), np.float32)
        lo = np.ones(BLOCK, np.float32)
        hi = np.zeros(BLOCK, np.float32)  # empty range for padding rows
        q[: e - s] = queries[s:e]
        lo[: e - s] = ranges[s:e, 0]
        hi[: e - s] = ranges[s:e, 1]
        bi, bd = fn(x, x2, a, jnp.asarray(q), jnp.asarray(lo), jnp.asarray(hi))
        ids[s:e] = np.asarray(bi)[: e - s]
        dists[s:e] = np.asarray(bd)[: e - s]
    return ids, dists


def exact_dists(rows: np.ndarray, queries: np.ndarray,
                ids: np.ndarray) -> np.ndarray:
    """Squared L2 from each query to each of its ids, in float64; NaN
    where the id is -1 or not a row of ``rows``."""
    ok = (ids >= 0) & (ids < len(rows))
    safe = np.where(ok, ids, 0)
    out = np.empty(ids.shape, np.float64)
    for s in range(0, len(ids), 1024):
        diff = (rows[safe[s:s + 1024]].astype(np.float64)
                - queries[s:s + 1024, None, :].astype(np.float64))
        out[s:s + 1024] = np.einsum("nkd,nkd->nk", diff, diff)
    out[~ok] = np.nan
    return out
