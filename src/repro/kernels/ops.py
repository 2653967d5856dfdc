"""Dispatch wrappers: Pallas kernel on TPU, interpret-mode or jnp reference
elsewhere.

Policy:
  * ``backend="auto"`` — compiled Pallas on TPU, jnp reference otherwise
    (interpret mode is for correctness tests, not production CPU perf);
  * ``backend="pallas"`` — force the compiled kernel; raises off-TPU;
  * ``backend="interpret"`` — the kernel in the Pallas interpreter (tests);
  * ``backend="ref"`` — force the jnp oracle.

The dry-run/roofline path always lowers the reference implementations so XLA
cost analysis sees the full computation (see DESIGN.md §5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ref as _ref


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _resolve_interpret(interpret: bool | None) -> bool:
    """A kernel's ``interpret=None`` default: the compiled kernel, which
    needs a TPU.  Interpret mode is never picked silently — off-TPU the
    caller must ask for it."""
    if interpret is None:
        if not _on_tpu():
            raise ValueError(
                "no TPU backend: pass interpret=True to run the Pallas "
                "kernel in the interpreter, or use the jnp reference")
        return False
    return interpret


def _resolve(backend: str) -> tuple[bool, bool]:
    """-> (use_pallas, interpret)."""
    if backend == "ref":
        return False, False
    if backend == "interpret":
        return True, True
    tpu = _on_tpu()
    if backend == "pallas":
        if not tpu:
            raise ValueError(
                'backend="pallas" needs a TPU; use backend="interpret" for '
                "the Pallas interpreter or \"ref\" for the jnp oracle")
        return True, False
    if backend == "auto":
        return tpu, False
    raise ValueError(f"unknown backend {backend!r}")


def batched_dot(vecs, queries, backend: str = "auto", **kw):
    use, interp = _resolve(backend)
    if use:
        from .distance import batched_dot as kern

        return kern(vecs, queries, interpret=interp, **kw)
    return _ref.batched_dot_ref(vecs, queries)


def l2_distance(vecs, queries, sq_norms, backend: str = "auto", **kw):
    use, interp = _resolve(backend)
    if use:
        from .distance import l2_distance as kern

        return kern(vecs, queries, sq_norms, interpret=interp, **kw)
    return _ref.l2_distance_ref(vecs, queries, sq_norms)


def gather_dot(table, ids, queries, backend: str = "auto", **kw):
    use, interp = _resolve(backend)
    if use:
        from .gather_distance import gather_dot as kern

        return kern(table, ids, queries, interpret=interp, **kw)
    return _ref.gather_dot_ref(table, ids, queries)


def gather_norm_dot(table, ids, queries, scales=None, backend: str = "auto",
                    **kw):
    """Fused candidate gather -> (dots, sq-norms); the serving hot path.

    ``table`` may be f32, bf16, or int8 (``scales`` = per-row f32 scales,
    required for int8); dequant is fused in the kernel / folded into the
    reference gather — callers never dequantize the slab themselves."""
    use, interp = _resolve(backend)
    if use:
        from .gather_distance import gather_norm_dot as kern

        return kern(table, ids, queries, scales=scales, interpret=interp, **kw)
    return _ref.gather_norm_dot_ref(table, ids, queries, scales=scales)


def merge_src_indices(pos_a, pos_b, W: int, K: int, method: str = "auto"):
    """Source-index writeback of the counting merge (``_merge_sorted``).

    Given the merged output position of every result entry (``pos_a``
    [B, W]) and new entry (``pos_b`` [B, K]) — a bijection onto
    0..W+K-1 with slots >= W dropped — produce ``src`` [B, W] i32 where
    ``src[b, p]`` is the concatenated-source index (0..W-1 = result row,
    W..W+K-1 = new row) that lands at output slot ``p``.

      * ``"scatter"`` — one dropping scatter of source indices;
      * ``"onehot"`` — two MXU one-hot matmuls: position-equality one-hots
        contracted against the source-index iota.  Every output column has
        exactly one hit and indices are < W+K << 2^24, so the result is
        exact provided the operands are not rounded: the contractions run
        at ``Precision.HIGHEST`` (TPU's default rounds f32 operands to
        bf16, exact only for integers <= 256, so ``W + K > 256`` would
        write back wrong slots).  Preferred on TPU, where XLA serialises
        variable-index scatters;
      * ``"sort"`` — invert the position permutation with one packed
        single-key sort: ``pos * (W+K) + src`` over the concatenated
        [B, W+K] positions sorts into output order, and the low digits of
        the first W keys ARE the source indices.  Exact (the positions are
        a bijection — no ties), scatter-free, O((W+K) log(W+K));
      * ``"auto"`` — per-platform default: onehot on TPU (XLA serialises
        variable-index scatters there), sort elsewhere (on CPU the packed
        sort beats the element-serialised scatter ~4x at serving widths,
        and the [B, W, W+K] one-hots grow quadratically).
    """
    if method == "auto":
        method = "onehot" if _on_tpu() else "sort"
    B = pos_a.shape[0]
    if method == "sort":
        from jax import lax

        WK = W + K
        pos = jnp.concatenate([pos_a, pos_b], axis=1).astype(jnp.uint32)
        key = pos * jnp.uint32(WK) + jnp.arange(WK, dtype=jnp.uint32)[None, :]
        key = lax.sort(key, dimension=1)[:, :W]
        return (key % jnp.uint32(WK)).astype(jnp.int32)
    if method == "scatter":
        row = jnp.arange(B)[:, None]
        src = jnp.zeros((B, W), jnp.int32)
        src = src.at[row, pos_a].set(
            jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (B, W)),
            mode="drop",
        )
        src = src.at[row, pos_b].set(
            W + jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32), (B, K)),
            mode="drop",
        )
        return src
    if method == "onehot":
        out = jnp.arange(W, dtype=jnp.int32)[None, None, :]
        oa = (pos_a[:, :, None] == out).astype(jnp.float32)  # [B, W, W]
        ob = (pos_b[:, :, None] == out).astype(jnp.float32)  # [B, K, W]
        hi = jax.lax.Precision.HIGHEST
        srcf = jnp.einsum("bsw,s->bw", oa,
                          jnp.arange(W, dtype=jnp.float32), precision=hi)
        srcf = srcf + jnp.einsum("bkw,k->bw", ob,
                                 W + jnp.arange(K, dtype=jnp.float32),
                                 precision=hi)
        return srcf.astype(jnp.int32)
    raise ValueError(f"unknown writeback method {method!r}")


def replicate(tree, mesh):
    """Place every leaf of ``tree`` replicated over ``mesh`` (NamedSharding
    with an empty PartitionSpec).  The sharded build arena uses this once
    per full upload; the delta scatters below preserve the placement (jit
    propagates input shardings), so per-batch commits stay O(changed rows)
    with no re-replication."""
    from jax.sharding import NamedSharding, PartitionSpec

    sh = NamedSharding(mesh, PartitionSpec())
    return jax.tree.map(lambda x: jax.device_put(x, sh), tree)


@functools.partial(jax.jit, donate_argnums=(0,))
def _arena_set_rows(dst, idx, rows):
    return dst.at[idx].set(rows)


@functools.partial(jax.jit, donate_argnums=(0,))
def _arena_set_layer_rows(dst, lidx, vidx, rows):
    return dst.at[lidx, vidx].set(rows)


def _pad_pow2(k: int) -> int:
    return 1 << max(3, (max(k, 1) - 1).bit_length())


def _bucket_idx(idx: np.ndarray, k: int):
    """Pad a scatter index batch to the next pow2 bucket by repeating the
    first element — an idempotent rewrite, so duplicate targets are safe —
    bounding the number of compiled scatter shapes to O(log cap)."""
    kp = _pad_pow2(k)
    if kp == k:
        return idx, slice(None)
    pad = np.full(kp - k, idx[0], dtype=idx.dtype)
    return np.concatenate([idx, pad]), None


def arena_scatter(dst, idx, rows):
    """Delta update of a device arena: ``dst[idx] = rows`` through a donated
    jit (in place where the backend supports buffer donation; a bounded
    buffer copy otherwise — never a host-side re-stack or re-upload).
    ``idx``/``rows`` are host arrays of the changed rows only; shapes are
    padded to power-of-two buckets (idempotent repeats of row 0)."""
    idx = np.asarray(idx, np.int64)
    k = idx.shape[0]
    if k == 0:
        return dst
    idx_p, tail = _bucket_idx(idx, k)
    rows = np.asarray(rows)
    if tail is None:
        pad = np.broadcast_to(rows[:1], (idx_p.shape[0] - k,) + rows.shape[1:])
        rows = np.concatenate([rows, pad])
    return _arena_set_rows(dst, jnp.asarray(idx_p), jnp.asarray(rows))


def arena_scatter_layers(dst, lidx, vidx, rows):
    """``dst[lidx, vidx] = rows`` for a [L, cap, m] arena (see
    ``arena_scatter``)."""
    lidx = np.asarray(lidx, np.int64)
    vidx = np.asarray(vidx, np.int64)
    k = lidx.shape[0]
    if k == 0:
        return dst
    kp = _pad_pow2(k)
    rows = np.asarray(rows)
    if kp != k:
        lidx = np.concatenate([lidx, np.full(kp - k, lidx[0], np.int64)])
        vidx = np.concatenate([vidx, np.full(kp - k, vidx[0], np.int64)])
        rows = np.concatenate(
            [rows, np.broadcast_to(rows[:1], (kp - k,) + rows.shape[1:])]
        )
    return _arena_set_layer_rows(
        dst, jnp.asarray(lidx), jnp.asarray(vidx), jnp.asarray(rows)
    )


def wkv6(r, k, v, w, u, state=None, backend: str = "auto", chunk: int = 32):
    use, interp = _resolve(backend)
    if use:
        from .rwkv6 import wkv6 as kern

        return kern(r, k, v, w, u, state=state, chunk=chunk, interpret=interp)
    return _ref.wkv6_ref(r, k, v, w, u, state=state)


def mamba_scan(A, dt, Bm, Cm, x, h0, backend: str = "auto", chunk: int = 64):
    use, interp = _resolve(backend)
    if use:
        from .mamba_scan import mamba_scan as kern

        return kern(A, dt, Bm, Cm, x, h0, chunk=chunk, interpret=interp)
    from repro.models.mamba import _ssm_scan

    return _ssm_scan(A, dt, Bm, Cm, x, h0, chunk)


def flash_attention(
    q, k, v, causal=True, window=None, q_offset=0, backend: str = "auto",
    block_q: int | None = None, **kw,
):
    use, interp = _resolve(backend)
    if use:
        from .flash_attention import flash_attention as kern

        return kern(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            interpret=interp, **kw,
        )
    if block_q is None:
        from repro.models.tuning import TUNING

        if q.shape[1] >= TUNING.attn_blocked_min_t:
            block_q = TUNING.attn_block_q  # statically-blocked span attention
    return _ref.mha_ref(
        q, k, v, causal=causal, window=window, q_offset=q_offset, block_q=block_q
    )
