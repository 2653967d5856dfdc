"""Pallas TPU kernel: Mamba-1 selective scan with VMEM-resident state.

    h_t = exp(dt_t * A) . h_{t-1} + (dt_t * x_t) B_t
    y_t = C_t . h_t

The XLA reference path (models/mamba._ssm_scan) writes h[B, d_i, N] to HBM
every step — the dominant memory-roofline term of the Jamba cells
(EXPERIMENTS.md §Perf).  This kernel is the TPU analogue of the fused CUDA
selective scan: h lives in a VMEM scratch for the whole sequence; HBM
traffic is inputs + y only (state traffic / sequence-length reduction).

Grid: (B, d_inner/di_tile, T/C) — time is the innermost (sequential) axis so
the scratch legally carries across chunks and resets per (batch, tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba_kernel(A_ref, dt_ref, b_ref, c_ref, x_ref, h0_ref, y_ref, hT_ref, h):
    t = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(t == 0)
    def _init():
        h[...] = h0_ref[0].astype(jnp.float32)

    A = A_ref[...].astype(jnp.float32)  # [dti, N]
    dt = dt_ref[0].astype(jnp.float32)  # [C, dti]
    Bm = b_ref[0].astype(jnp.float32)  # [C, N]
    Cm = c_ref[0].astype(jnp.float32)  # [C, N]
    x = x_ref[0].astype(jnp.float32)  # [C, dti]
    C = dt.shape[0]

    def step(i, hv):
        dti = dt[i][:, None]  # [dti, 1]
        a = jnp.exp(dti * A)  # [dti, N]
        hv = a * hv + (dt[i] * x[i])[:, None] * Bm[i][None, :]
        y = jnp.sum(hv * Cm[i][None, :], axis=1)  # [dti]
        y_ref[0:1, pl.ds(i, 1), :] = y.astype(y_ref.dtype)[None, None, :]
        return hv

    h[...] = jax.lax.fori_loop(0, C, step, h[...])

    @pl.when(t == nt - 1)
    def _fin():
        hT_ref[0] = h[...].astype(hT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "di_tile", "interpret"))
def mamba_scan(
    A: jax.Array,  # [di, N] (negative)
    dt: jax.Array,  # [B, T, di]
    Bm: jax.Array,  # [B, T, N]
    Cm: jax.Array,  # [B, T, N]
    x: jax.Array,  # [B, T, di]
    h0: jax.Array,  # [B, di, N]
    chunk: int = 64,
    di_tile: int = 256,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    B, T, di = x.shape
    N = A.shape[1]
    C = min(chunk, T)
    while T % C:
        C -= 1
    dti = min(di_tile, di)
    assert di % dti == 0
    grid = (B, di // dti, T // C)
    y, hT = pl.pallas_call(
        _mamba_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((dti, N), lambda b, d, t: (d, 0)),
            pl.BlockSpec((1, C, dti), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, C, N), lambda b, d, t: (b, t, 0)),
            pl.BlockSpec((1, C, N), lambda b, d, t: (b, t, 0)),
            pl.BlockSpec((1, C, dti), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, dti, N), lambda b, d, t: (b, d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, C, dti), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, dti, N), lambda b, d, t: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, di), jnp.float32),
            jax.ShapeDtypeStruct((B, di, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dti, N), jnp.float32)],
        interpret=interpret,
    )(A, dt, Bm, Cm, x, h0)
    return y, hT
