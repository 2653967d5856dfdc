"""Pallas TPU kernel: blocked scalar-prefetch fused gather + distance.

The TPU-native analogue of the CPU index's random-access vector gather: the
candidate ids are *scalar-prefetched* (``PrefetchScalarGridSpec``) so the
kernel can steer HBM->VMEM DMAs to fetch exactly the candidates the beam
search selected — the gather, the distance dot, and the squared-norm term
are fused in one kernel, and candidate vectors never materialise in HBM as a
separate [B, K, D] tensor (the XLA fallback does materialise it).

Tiled DMAs.  A TPU keeps an ``[n, D]`` table in HBM in tiles of ``T`` rows
(f32: 8, bf16: 16, int8: 32 — the narrow dtypes pack rows into sublanes),
and Mosaic only moves whole tiles: a single-row copy is refused whenever a
row is not contiguous (D > 128, or any packed dtype).  So each candidate
costs one copy of the aligned ``[T, D]`` tile holding its row — ``T * D *
itemsize`` = ``32 * D`` bytes for every dtype — and the row is picked out
in VMEM by a one-hot MXU contraction (exact at ``Precision.HIGHEST``; the
table must be finite, since the other rows of a tile are multiplied by
zero).  Tables reach the kernel with ``n`` a multiple of ``T`` (device
tables are pow2-padded).

Grid.  Each grid step takes ``block_q`` queries and assembles their
``block_q * K`` candidate tiles in a VMEM *slab*.  The slab DMAs are
double-buffered across grid steps: while step ``t`` is being contracted the
tile copies of step ``t+1`` are already in flight (their ids are known up
front thanks to the scalar prefetch).  All copies of one slab signal one
DMA semaphore.

Outputs per candidate: the dot ``<table[id], q>`` *and* the squared norm
``|table[id]|^2`` — the latter is reduced from the rows already sitting in
VMEM (no second scattered gather of a precomputed norm table), so the
wrapper can form the exact factorised L2 ``|v|^2 - 2 v.q + |q|^2``.  Both
are MXU contractions of a query's picked ``[Kp, D]`` rows against the
``[block_q, D]`` query block (resp. a ones block) at ``Precision.HIGHEST``:
the TPU's default rounds f32 operands to bf16, which the factorised L2
would amplify.  Row ``b`` of the ``b``-th product is the one kept, so every
block the kernel reads or writes is a whole ``[block_q, ...]`` block — the
layout Mosaic requires (second-minor dim a multiple of 8, minor dim a
multiple of 128 or the full array dim).

VMEM budget: ``2 * block_q * K * 32 * D`` bytes of slab scratch plus one
query's f32 tile rows ``[K * T, D]`` — for block_q=8, K=17, D=768 about
7 MiB.

Quantized tables (the memory-ceiling path): the table may be stored int8
(per-row f32 ``scales``, ``max|row|/127`` discipline) or bf16, halving or
quartering its HBM footprint.  The tiles cross HBM in storage dtype and the
dequant (upcast + scale multiply) happens in VMEM, immediately before the
MXU contraction, so candidate vectors never materialise in f32 in HBM.  For
int8 the wrapper pre-gathers the per-candidate scales (``scales[ids]`` — a
[B, Kp] f32 sliver, ~D times smaller than the vectors) and streams them in
as a column block, so the kernel needs no extra scatter DMAs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import _resolve_interpret

# sublane tile of each slab dtype's HBM layout: a row DMA must move whole
# tiles, so the kernel fetches the aligned tile holding the candidate row
_SUBLANE = {"float32": 8, "bfloat16": 16, "int8": 32}
_HI = lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))  # [M, X] x [X, N]
_NT = (((1,), (1,)), ((), ()))  # [M, D] x [N, D]: contract the minor dims


def _slab_kernel(base_ref, table_ref, q_ref, tgt_ref, *refs, block_q, K, T,
                 kp):
    # base_ref: scalar-prefetch i32[Bp, K] first row of each candidate's
    # aligned tile; table_ref: ANY (HBM) {f32|bf16|int8}[n, D]; q_ref: VMEM
    # f32[block_q, D]; tgt_ref: VMEM i32[block_q * kp, 1] slab row of each
    # candidate within its query's K*T tile rows (-1 = K padding).  For
    # int8 tables a per-candidate scale column sc_ref (VMEM
    # f32[block_q * kp, 1]) follows; dots_ref/v2_ref: VMEM f32[block_q, kp];
    # slab: VMEM table.dtype[2, block_q * K * T, D] double buffer; sems:
    # DMA sem [2], one per slab.
    if len(refs) == 5:
        sc_ref, dots_ref, v2_ref, slab, sems = refs
    else:
        sc_ref = None
        dots_ref, v2_ref, slab, sems = refs
    step = pl.program_id(0)
    total = pl.num_programs(0)
    ncand = block_q * K
    KT = K * T

    def tile_copy(tile, slot, c):
        b = c // K
        k = c - b * K
        base = pl.multiple_of(base_ref[tile * block_q + b, k], T)
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(base, T), :],
            slab.at[slot, pl.ds(pl.multiple_of(c * T, T), T), :],
            sems.at[slot],
        )

    def start_tiles(tile, slot):
        def body(c, carry):
            tile_copy(tile, slot, c).start()
            return carry

        lax.fori_loop(0, ncand, body, 0)

    # warm-up: the very first query tile's copies start here
    @pl.when(step == 0)
    def _():
        start_tiles(step, 0)

    # overlap: issue query tile t+1 while tile t is still arriving
    @pl.when(step + 1 < total)
    def _():
        start_tiles(step + 1, (step + 1) % 2)

    slot = step % 2

    def wait(c, carry):
        # every copy of a slab moves the same bytes on one semaphore
        tile_copy(step, slot, 0).wait()
        return carry

    lax.fori_loop(0, ncand, wait, 0)

    q = q_ref[...]  # [block_q, D] f32
    ones = jnp.ones_like(q)
    sel = lax.broadcasted_iota(jnp.int32, (block_q, kp), 0)
    col = lax.broadcasted_iota(jnp.int32, (kp, KT), 1)
    dots = jnp.zeros((block_q, kp), jnp.float32)
    v2 = jnp.zeros((block_q, kp), jnp.float32)
    for b in range(block_q):
        # pick each candidate's row out of its tile (a one-hot MXU
        # contraction — exact at HIGHEST), then dequant in VMEM: upcast
        # (bf16/int8) and, for int8, the per-row scale multiply
        tiles = slab[slot, pl.ds(b * KT, KT), :].astype(jnp.float32)
        pick = (col == tgt_ref[pl.ds(b * kp, kp), :]).astype(jnp.float32)
        v = lax.dot_general(pick, tiles, _NN, precision=_HI,
                            preferred_element_type=jnp.float32)  # [kp, D]
        if sc_ref is not None:
            v = v * sc_ref[pl.ds(b * kp, kp), :]
        d_b = lax.dot_general(q, v, _NT, precision=_HI,
                              preferred_element_type=jnp.float32)
        n_b = lax.dot_general(ones, v * v, _NT, precision=_HI,
                              preferred_element_type=jnp.float32)
        dots = jnp.where(sel == b, d_b, dots)
        v2 = jnp.where(sel == b, n_b, v2)
    dots_ref[...] = dots
    v2_ref[...] = v2


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def gather_norm_dot(
    table: jax.Array,  # {f32|bf16|int8}[n, D] vector table (stays in HBM)
    ids: jax.Array,  # i32[B, K] candidate row ids
    queries: jax.Array,  # f32[B, D]
    scales: jax.Array | None = None,  # f32[n] per-row scales (int8 tables)
    block_q: int = 8,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """-> (dots, v2) with dots[b,k] = <deq(table[ids[b,k]]), queries[b]> and
    v2[b,k] = |deq(table[ids[b,k]])|^2, both f32[B, K].

    ``deq`` is identity for f32, an upcast for bf16, and
    ``row.astype(f32) * scales[id]`` for int8 — fused in VMEM after the
    DMA, so only quantized bytes cross HBM.  Compiled, ``block_q`` must be
    a multiple of 8 (the f32 sublane tile of the query and output blocks).
    The table must be finite: the one-hot row pick multiplies the other
    rows of a tile by zero."""
    interpret = _resolve_interpret(interpret)
    if str(table.dtype) not in _SUBLANE:
        raise ValueError(f"unsupported table dtype {table.dtype}; "
                         f"expected one of {sorted(_SUBLANE)}")
    if not interpret and block_q % 8:
        raise ValueError(f"block_q={block_q} must be a multiple of 8 on TPU")
    quantized = table.dtype == jnp.int8
    if quantized and scales is None:
        raise ValueError("int8 table requires per-row scales")
    B, K = ids.shape
    n, D = table.shape
    T = min(_SUBLANE[str(table.dtype)], n)
    if not interpret and n % T:
        raise ValueError(f"table rows {n} must be a multiple of {T} on TPU")
    kp = -(-K // 8) * 8
    Bp = -(-B // block_q) * block_q
    idc = jnp.clip(ids.astype(jnp.int32), 0, n - 1)
    queries = queries.astype(jnp.float32)
    if Bp != B:
        idc = jnp.pad(idc, ((0, Bp - B), (0, 0)))
        queries = jnp.pad(queries, ((0, Bp - B), (0, 0)))
    base = jnp.minimum(idc // T * T, n - T)
    tgt = jnp.arange(K, dtype=jnp.int32) * T + (idc - base)
    tgt = jnp.pad(tgt, ((0, 0), (0, kp - K)), constant_values=-1)

    col_spec = pl.BlockSpec((block_q * kp, 1), lambda i, base_ref: (i, 0))
    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),  # table: gathered by DMA
        pl.BlockSpec((block_q, D), lambda i, base_ref: (i, 0)),
        col_spec,
    ]
    operands = [table, queries, tgt.reshape(Bp * kp, 1)]
    if quantized:
        # pre-gathered per-candidate scales, one per candidate: a [Bp*kp, 1]
        # f32 column streamed in as ordinary blocks — no scale DMAs
        sc = jnp.take(scales.astype(jnp.float32), idc, axis=0)
        sc = jnp.pad(sc, ((0, 0), (0, kp - K)), constant_values=1.0)
        in_specs.append(col_spec)
        operands.append(sc.reshape(Bp * kp, 1))

    out_spec = pl.BlockSpec((block_q, kp), lambda i, base_ref: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Bp // block_q,),
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        scratch_shapes=[
            pltpu.VMEM((2, block_q * K * T, D), table.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    dots, v2 = pl.pallas_call(
        functools.partial(_slab_kernel, block_q=block_q, K=K, T=T, kp=kp),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Bp, kp), jnp.float32),
            jax.ShapeDtypeStruct((Bp, kp), jnp.float32),
        ],
        interpret=interpret,
        name="gather_norm_dot",
    )(base, *operands)
    return dots[:B, :K], v2[:B, :K]


def gather_dot(
    table: jax.Array,
    ids: jax.Array,
    queries: jax.Array,
    interpret: bool | None = None,
    block_q: int = 8,
    scales: jax.Array | None = None,
) -> jax.Array:
    """out[b, k] = <deq(table[ids[b, k]]), queries[b]> (slab kernel, dots only)."""
    dots, _ = gather_norm_dot(table, ids, queries, scales=scales,
                              block_q=block_q, interpret=interpret)
    return dots
