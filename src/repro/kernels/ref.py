"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def batched_dot_ref(vecs: jax.Array, queries: jax.Array) -> jax.Array:
    """out[b, k] = <vecs[b, k, :], queries[b, :]>."""
    return jnp.einsum("bkd,bd->bk", vecs, queries)


def l2_distance_ref(
    vecs: jax.Array, queries: jax.Array, sq_norms: jax.Array
) -> jax.Array:
    """out[b, k] = ||vecs[b,k] - queries[b]||^2 via the factorised form."""
    q2 = jnp.sum(queries * queries, axis=-1)
    dots = batched_dot_ref(vecs, queries)
    return jnp.maximum(sq_norms - 2.0 * dots + q2[:, None], 0.0)


def gather_dot_ref(
    table: jax.Array, ids: jax.Array, queries: jax.Array
) -> jax.Array:
    """out[b, k] = <table[ids[b, k]], queries[b]>  (fused gather + dot)."""
    return jnp.einsum("bkd,bd->bk", table[ids], queries,
                      precision=jax.lax.Precision.HIGHEST)


def gather_norm_dot_ref(
    table: jax.Array, ids: jax.Array, queries: jax.Array,
    scales: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """-> (<deq(table[ids[b,k]]), queries[b]>, |deq(table[ids[b,k]])|^2).

    Dequantizing twin of the Pallas kernel: bf16 tables upcast, int8 tables
    multiply the gathered rows by their per-row f32 ``scales`` — the same
    math the kernel fuses in VMEM, expressed over a materialized gather."""
    n = table.shape[0]
    idc = jnp.clip(ids, 0, n - 1)
    vecs = table[idc].astype(jnp.float32)
    if scales is not None:
        vecs = vecs * scales.astype(jnp.float32)[idc][..., None]
    queries = queries.astype(jnp.float32)
    # HIGHEST: the TPU's default rounds f32 operands to bf16, which the
    # factorised L2 (|v|^2 - 2 v.q + |q|^2) would amplify
    hi = jax.lax.Precision.HIGHEST
    return (
        jnp.einsum("bkd,bd->bk", vecs, queries, precision=hi),
        jnp.einsum("bkd,bkd->bk", vecs, vecs, precision=hi),
    )


def wkv6_ref(
    r: jax.Array,  # [B, H, T, N]
    k: jax.Array,  # [B, H, T, N]
    v: jax.Array,  # [B, H, T, N]
    w: jax.Array,  # [B, H, T, N] decay in (0, 1)
    u: jax.Array,  # [H, N] bonus
    state: jax.Array | None = None,  # [B, H, N, N]
) -> tuple[jax.Array, jax.Array]:
    """RWKV-6 recurrence, step by step (the oracle).

    S_t = diag(w_t) S_{t-1} + k_t v_t^T
    y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)
    """
    B, H, T, N = r.shape
    if state is None:
        state = jnp.zeros((B, H, N, N), r.dtype)

    def step(S, inputs):
        rt, kt, vt, wt = inputs  # each [B, H, N]
        kv = kt[..., :, None] * vt[..., None, :]  # [B, H, N, N]
        y = jnp.einsum("bhn,bhnm->bhm", rt, S + u[None, :, :, None] * kv)
        S = wt[..., :, None] * S + kv
        return S, y

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (r, k, v, w))
    state, ys = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(ys, 0, 2), state  # [B, H, T, N], [B, H, N, N]


def wkv6_chunked(
    r: jax.Array,  # [B, H, T, N]
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,  # [H, N]
    state: jax.Array | None = None,
    chunk: int = 32,
) -> tuple[jax.Array, jax.Array]:
    """Chunk-parallel WKV-6 in pure jnp — the same stable closed form the
    Pallas kernel uses (exponents <= 0 everywhere), differentiable, used by
    the training path off-TPU and by the dry-run lowering.  Memory is
    O(C^2 N) per chunk instead of O(T N^2) scan carries."""
    B, H, T, N = r.shape
    C = min(chunk, T)
    while T % C:  # largest chunk size dividing T (odd T: smaller chunks)
        C -= 1
    nc = T // C
    if state is None:
        state = jnp.zeros((B, H, N, N), jnp.float32)

    chunks = lambda a: jnp.moveaxis(
        a.reshape(B, H, nc, C, N), 2, 0
    )  # [nc, B, H, C, N]
    rc, kc, vc, wc = (chunks(a.astype(jnp.float32)) for a in (r, k, v, w))
    mask = (jnp.arange(C)[:, None] > jnp.arange(C)[None, :])[:, :, None]

    def chunk_step(S, xs):
        rt, kt, vt, wt = xs  # [B, H, C, N]
        lw = jnp.log(wt)
        L = jnp.cumsum(lw, axis=2)
        L_prev = L - lw
        r_dec = rt * jnp.exp(L_prev)
        y_state = jnp.einsum("bhcn,bhnm->bhcm", r_dec, S)
        expo = L_prev[..., :, None, :] - L[..., None, :, :]  # [B,H,C,C,N]
        term = jnp.where(mask[None, None], jnp.exp(jnp.minimum(expo, 0.0)), 0.0)
        scores = jnp.einsum("bhtn,bhsn,bhtsn->bhts", rt, kt, term)
        y_intra = jnp.einsum("bhts,bhsn->bhtn", scores, vt)
        y_diag = jnp.sum(rt * u[None, :, None, :] * kt, axis=-1, keepdims=True) * vt
        L_end = L[..., -1:, :]  # [B, H, 1, N]
        k_dec = kt * jnp.exp(L_end - L)
        S = jnp.exp(L_end[..., 0, :])[..., :, None] * S + jnp.einsum(
            "bhcn,bhcm->bhnm", k_dec, vt
        )
        return S, y_state + y_intra + y_diag

    chunk_step = jax.checkpoint(chunk_step)
    S, ys = jax.lax.scan(chunk_step, state, (rc, kc, vc, wc))
    y = jnp.moveaxis(ys, 0, 2).reshape(B, H, T, N)  # [B,H,nc,C,N] -> merge
    return y.astype(r.dtype), S


def mha_ref(
    q: jax.Array,  # [B, Tq, Hq, D]
    k: jax.Array,  # [B, Tk, Hkv, D]
    v: jax.Array,  # [B, Tk, Hkv, D]
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    block_q: int | None = None,
) -> jax.Array:
    """GQA attention oracle with optional causal/sliding-window masking.

    ``q_offset``: absolute position of q[0] relative to k[0] (decode steps).
    ``block_q``: evaluate query rows in blocks (lax.map) so the [Tq, Tk]
    score matrix never fully materialises — required for 32k+ prefill.
    """
    B, Tq, Hq, D = q.shape
    Tk = k.shape[1]
    Hkv = k.shape[2]
    group = Hq // Hkv

    def blk(q_blk: jax.Array, q_lo) -> jax.Array:
        tq = q_blk.shape[1]
        qg = q_blk.reshape(B, tq, Hkv, group, D)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) / jnp.sqrt(D).astype(
            q.dtype
        )
        qpos = q_lo + jnp.arange(tq)[:, None] + q_offset
        kpos = jnp.arange(Tk)[None, :]
        mask = jnp.ones((tq, Tk), bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
        return out.reshape(B, tq, Hq, D)

    if block_q is None or block_q >= Tq:
        return blk(q, 0)
    assert Tq % block_q == 0
    nb = Tq // block_q

    def blk_span(q_blk, q_lo, k_lo, k_hi):
        """Attention for one q block against the static kv span [k_lo,k_hi)."""
        ks, vs = k[:, k_lo:k_hi], v[:, k_lo:k_hi]
        tq = q_blk.shape[1]
        qg = q_blk.reshape(B, tq, Hkv, group, D)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ks) / jnp.sqrt(D).astype(
            q.dtype
        )
        qpos = q_lo + jnp.arange(tq)[:, None] + q_offset
        kpos = k_lo + jnp.arange(k_hi - k_lo)[None, :]
        mask = jnp.ones((tq, k_hi - k_lo), bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vs)
        return out.reshape(B, tq, Hq, D)

    # static python loop over q blocks with a *statically sliced* kv span:
    # causal/window structure becomes real FLOP and HBM savings that the
    # compiled-HLO cost analysis sees (the fair stand-in for the Pallas
    # kernel's block skipping), instead of compute-then-mask waste.
    def _seq_shard(a):
        from repro.models.tuning import seq_spec

        sp = seq_spec(extra_dims=a.ndim - 2)
        if sp is None:
            return a
        return jax.lax.with_sharding_constraint(a, sp)

    outs = []
    for i in range(nb):
        q_lo = i * block_q
        k_hi = min(q_lo + block_q + q_offset, Tk) if causal else Tk
        k_lo = 0
        if window is not None:
            k_lo = max(0, (q_lo + q_offset - window + 1) // block_q * block_q)
        outs.append(
            _seq_shard(blk_span(_seq_shard(q[:, q_lo : q_lo + block_q]), q_lo, k_lo, k_hi))
        )
    return jnp.concatenate(outs, axis=1)
