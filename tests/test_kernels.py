"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.distance import batched_dot, l2_distance
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gather_distance import gather_dot, gather_norm_dot
from repro.kernels.rwkv6 import wkv6

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("B,K,D", [(1, 1, 8), (3, 17, 24), (8, 128, 64), (5, 200, 33)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batched_dot_sweep(B, K, D, dtype):
    vecs = jnp.asarray(RNG.normal(size=(B, K, D)), dtype)
    qs = jnp.asarray(RNG.normal(size=(B, D)), dtype)
    out = batched_dot(vecs, qs, interpret=True)
    exp = ref.batched_dot_ref(vecs.astype(jnp.float32), qs.astype(jnp.float32))
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(out, exp, rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("B,K,D", [(2, 9, 16), (4, 64, 32)])
def test_l2_distance_sweep(B, K, D):
    vecs = jnp.asarray(RNG.normal(size=(B, K, D)), jnp.float32)
    qs = jnp.asarray(RNG.normal(size=(B, D)), jnp.float32)
    nr = jnp.sum(vecs**2, -1)
    out = l2_distance(vecs, qs, nr, interpret=True)
    exp = ref.l2_distance_ref(vecs, qs, nr)
    np.testing.assert_allclose(out, exp, rtol=1e-4, atol=1e-4)
    # exactness property: distance to itself is ~0
    same = l2_distance(qs[:, None, :], qs, jnp.sum(qs**2, -1, keepdims=True), interpret=True)
    assert float(jnp.max(same)) < 1e-3


@pytest.mark.parametrize("n,B,K,D", [(50, 2, 7, 16), (200, 4, 33, 8)])
def test_gather_dot_sweep(n, B, K, D):
    table = jnp.asarray(RNG.normal(size=(n, D)), jnp.float32)
    ids = jnp.asarray(RNG.integers(0, n, size=(B, K)), jnp.int32)
    qs = jnp.asarray(RNG.normal(size=(B, D)), jnp.float32)
    out = gather_dot(table, ids, qs, interpret=True)
    np.testing.assert_allclose(out, ref.gather_dot_ref(table, ids, qs), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,B,K,D,rows", [
    (50, 2, 7, 16, 4),    # ragged K and n: last tile clamped inside the table
    (200, 4, 33, 8, 8),
    (33, 1, 1, 5, 8),     # B padded up to one query tile
    (64, 5, 9, 128, 3),
])
def test_gather_norm_dot_slab_sweep(n, B, K, D, rows):
    """Blocked slab kernel (``rows`` queries per grid step): fused dots +
    in-kernel squared norms, with double-buffered tile DMAs and B/K
    padding."""
    table = jnp.asarray(RNG.normal(size=(n, D)), jnp.float32)
    ids = jnp.asarray(RNG.integers(0, n, size=(B, K)), jnp.int32)
    qs = jnp.asarray(RNG.normal(size=(B, D)), jnp.float32)
    dots, v2 = gather_norm_dot(table, ids, qs, block_q=rows, interpret=True)
    ed, ev = ref.gather_norm_dot_ref(table, ids, qs)
    np.testing.assert_allclose(dots, ed, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(v2, ev, rtol=1e-5, atol=1e-5)
    # out-of-range ids are clipped, not OOB
    bad = jnp.full((B, K), n + 99, jnp.int32)
    dots_b, _ = gather_norm_dot(table, bad, qs, block_q=rows, interpret=True)
    np.testing.assert_allclose(
        dots_b, jnp.broadcast_to(table[n - 1] @ qs.T, (K, B)).T, rtol=1e-5, atol=1e-5
    )


def test_interpret_default_resolves_from_platform():
    """The kernels' `interpret=None` default resolves from the platform: the
    compiled kernel on TPU; off-TPU it raises rather than silently running
    the interpreter (callers ask for it with interpret=True)."""
    from repro.kernels.ops import _on_tpu

    table = jnp.asarray(RNG.normal(size=(24, 8)), jnp.float32)
    ids = jnp.asarray(RNG.integers(0, 24, size=(2, 4)), jnp.int32)
    qs = jnp.asarray(RNG.normal(size=(2, 8)), jnp.float32)
    vecs = jnp.asarray(RNG.normal(size=(2, 4, 8)), jnp.float32)
    if not _on_tpu():
        with pytest.raises(ValueError, match="no TPU backend"):
            gather_norm_dot(table, ids, qs)  # no interpret kwarg
        with pytest.raises(ValueError, match="no TPU backend"):
            batched_dot(vecs, qs)
        return
    dots, _ = gather_norm_dot(table, ids, qs)
    np.testing.assert_allclose(
        dots, ref.gather_dot_ref(table, ids, qs), rtol=1e-5, atol=1e-5
    )
    out = batched_dot(vecs, qs)
    np.testing.assert_allclose(
        out, ref.batched_dot_ref(vecs, qs), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("backend", ["pallas", "interpret", "auto", "ref"])
def test_dispatch_never_hides_the_device(backend):
    """`backend="pallas"` off-TPU raises; the interpreter only runs when
    asked for by name; "auto" is the jnp reference off-TPU."""
    from repro.kernels import ops

    table = jnp.asarray(RNG.normal(size=(32, 16)), jnp.float32)
    ids = jnp.asarray(RNG.integers(0, 32, size=(3, 5)), jnp.int32)
    qs = jnp.asarray(RNG.normal(size=(3, 16)), jnp.float32)
    if backend == "pallas" and not ops._on_tpu():
        with pytest.raises(ValueError, match="needs a TPU"):
            ops.gather_norm_dot(table, ids, qs, backend=backend)
        return
    dots, v2 = ops.gather_norm_dot(table, ids, qs, backend=backend)
    ed, ev = ref.gather_norm_dot_ref(table, ids, qs)
    np.testing.assert_allclose(dots, ed, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(v2, ev, rtol=1e-5, atol=1e-5)


def test_gather_norm_dot_rejects_other_dtypes():
    """No silent cast: a table outside {f32, bf16, int8} is an error."""
    table = jnp.zeros((16, 8), jnp.float16)
    ids = jnp.zeros((1, 2), jnp.int32)
    with pytest.raises(ValueError, match="unsupported table dtype"):
        gather_norm_dot(table, ids, jnp.zeros((1, 8)), interpret=True)


@pytest.mark.parametrize("vec_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("n,B,K,D", [(64, 3, 17, 128), (100, 9, 5, 24)])
def test_gather_norm_dot_quantized_slab(vec_dtype, n, B, K, D):
    """Quantized tables: tile DMAs in storage dtype, dequant in VMEM —
    parity with the dequantizing reference."""
    from repro.core.store import quantize_rows

    slab, scales = quantize_rows(RNG.normal(size=(n, D)), vec_dtype)
    table = jnp.asarray(slab)
    sc = None if scales is None else jnp.asarray(scales)
    ids = jnp.asarray(RNG.integers(0, n, size=(B, K)), jnp.int32)
    qs = jnp.asarray(RNG.normal(size=(B, D)), jnp.float32)
    dots, v2 = gather_norm_dot(table, ids, qs, scales=sc, interpret=True)
    ed, ev = ref.gather_norm_dot_ref(table, ids, qs, scales=sc)
    np.testing.assert_allclose(dots, ed, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(v2, ev, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("B,H,T,N,chunk", [(1, 1, 16, 8, 4), (2, 3, 64, 16, 16), (1, 2, 96, 32, 32)])
def test_wkv6_kernel_vs_ref(B, H, T, N, chunk):
    r = jnp.asarray(RNG.normal(size=(B, H, T, N)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, H, T, N)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, H, T, N)), jnp.float32)
    w = jnp.asarray(RNG.uniform(0.05, 0.999, size=(B, H, T, N)), jnp.float32)
    u = jnp.asarray(RNG.normal(size=(H, N)), jnp.float32)
    s0 = jnp.asarray(RNG.normal(size=(B, H, N, N)), jnp.float32)
    y1, s1 = wkv6(r, k, v, w, u, state=s0, chunk=chunk, interpret=True)
    y2, s2 = ref.wkv6_ref(r, k, v, w, u, state=s0)
    np.testing.assert_allclose(y1, y2, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(s1, s2, rtol=3e-4, atol=3e-4)


def test_wkv6_chunked_jnp_matches_step():
    B, H, T, N = 2, 2, 48, 16
    r = jnp.asarray(RNG.normal(size=(B, H, T, N)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, H, T, N)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, H, T, N)), jnp.float32)
    w = jnp.asarray(RNG.uniform(0.2, 0.99, size=(B, H, T, N)), jnp.float32)
    u = jnp.asarray(RNG.normal(size=(H, N)), jnp.float32)
    y1, s1 = ref.wkv6_chunked(r, k, v, w, u, chunk=12)
    y2, s2 = ref.wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(y1, y2, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(s1, s2, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("window", [None, 16])
def test_flash_attention_sweep(Hq, Hkv, window):
    B, T, D = 2, 64, 16
    q = jnp.asarray(RNG.normal(size=(B, T, Hq, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, T, Hkv, D)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=16, block_k=16, interpret=True)
    exp = ref.mha_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(out, exp, rtol=2e-4, atol=2e-4)


def test_mha_blocked_span_equals_dense():
    B, T, Hq, Hkv, D = 2, 96, 4, 2, 16
    q = jnp.asarray(RNG.normal(size=(B, T, Hq, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, T, Hkv, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, T, Hkv, D)), jnp.float32)
    for window in (None, 32):
        dense = ref.mha_ref(q, k, v, causal=True, window=window)
        blocked = ref.mha_ref(q, k, v, causal=True, window=window, block_q=16)
        np.testing.assert_allclose(dense, blocked, rtol=2e-5, atol=2e-5)


def test_flash_attention_decode_offset():
    """q_offset semantics: one-row attention against a longer K."""
    B, Tk, H, D = 1, 32, 2, 8
    q = jnp.asarray(RNG.normal(size=(B, 1, H, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, Tk, H, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, Tk, H, D)), jnp.float32)
    out = ref.mha_ref(q, k, v, causal=True, q_offset=Tk - 1)
    # equals full attention's last row
    qf = jnp.concatenate([jnp.zeros((B, Tk - 1, H, D), jnp.float32), q], axis=1)
    full = ref.mha_ref(qf, k, v, causal=True)
    np.testing.assert_allclose(out[:, 0], full[:, -1], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,T,di,N,chunk,tile", [(1, 12, 4, 4, 4, 4), (2, 32, 16, 8, 8, 8)])
def test_mamba_scan_kernel_vs_ref(B, T, di, N, chunk, tile):
    from repro.kernels.mamba_scan import mamba_scan
    from repro.models.mamba import _ssm_scan

    A = -jnp.asarray(RNG.uniform(0.1, 2.0, size=(di, N)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(B, T, di)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, T, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, T, N)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(B, T, di)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(size=(B, di, N)), jnp.float32)
    y1, h1 = mamba_scan(A, dt, Bm, Cm, x, h0, chunk=chunk, di_tile=tile, interpret=True)
    y2, h2 = _ssm_scan(A, dt, Bm, Cm, x, h0, chunk=max(chunk - 1, 1))
    np.testing.assert_allclose(y1, y2, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h1, h2, rtol=2e-5, atol=2e-5)
