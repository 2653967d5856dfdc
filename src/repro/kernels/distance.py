"""Pallas TPU kernel: blocked batched query-candidate distances.

The WoW hot spot (the paper's DC cost) on TPU: for B queries, each with K
gathered candidate vectors, compute all B*K distances.  The kernel tiles
(B, K) over the grid and keeps a [bB, bK, D] candidate block plus the [bB, D]
query block in VMEM; the inner product runs on the MXU via ``dot_general``
and the wrapper composes the exact factorised L2 ``|v|^2 - 2 v.q + |q|^2``
(identical math to the SIMD loop the paper's C++ uses — different
factorisation, fp32 accumulation).

Block-shape guidance (TPU v5e): D padded to a multiple of 128 (lane dim),
bK a multiple of 128 for the MXU contraction, bB sized so the candidate
block fits VMEM: bB*bK*D*4 <= ~4 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ops import _resolve_interpret


def _dot_kernel(v_ref, q_ref, o_ref):
    # v_ref: [bB, bK, D], q_ref: [bB, D], o_ref: [bB, bK]
    v = v_ref[...]
    q = q_ref[...]
    # contract D: [bB, bK, D] x [bB, D] -> [bB, bK]  (batched MXU matvec)
    o_ref[...] = jax.lax.dot_general(
        v,
        q,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("block_b", "block_k", "interpret"))
def batched_dot(
    vecs: jax.Array,  # f32[B, K, D]
    queries: jax.Array,  # f32[B, D]
    block_b: int = 8,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = _resolve_interpret(interpret)
    B, K, D = vecs.shape
    bB = min(block_b, B)
    bK = min(block_k, K)
    # pad to tile multiples
    Bp = -(-B // bB) * bB
    Kp = -(-K // bK) * bK
    if (Bp, Kp) != (B, K):
        vecs = jnp.pad(vecs, ((0, Bp - B), (0, Kp - K), (0, 0)))
        queries = jnp.pad(queries, ((0, Bp - B), (0, 0)))
    grid = (Bp // bB, Kp // bK)
    out = pl.pallas_call(
        _dot_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bB, bK, D), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bB, D), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bB, bK), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Bp, Kp), jnp.float32),
        interpret=interpret,
    )(vecs.astype(jnp.float32), queries.astype(jnp.float32))
    return out[:B, :K]


def l2_distance(
    vecs: jax.Array,
    queries: jax.Array,
    sq_norms: jax.Array,
    **kw,
) -> jax.Array:
    """||vecs[b,k] - queries[b]||^2 with the kernel-computed cross term."""
    q2 = jnp.sum(queries.astype(jnp.float32) ** 2, axis=-1)
    dots = batched_dot(vecs, queries, **kw)
    return jnp.maximum(sq_norms - 2.0 * dots + q2[:, None], 0.0)
