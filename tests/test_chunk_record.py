"""The serve engine's chunk boundary (``device_search._run_jit_inplace``):
one launch over a donated ``HopState`` and one packed record, read back in
one transfer, that decodes to exactly what separate reads of the state
would give — in every wave bucket, for f32 and int8 slabs and both
metrics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import WoWIndex, make_workload
from repro.core.device_search import (
    _init_jit,
    _run_jit,
    _run_jit_inplace,
    chunk_record,
    hop_cfg,
    to_device_index,
)
from repro.core.snapshot import take_snapshot

K, WIDTH, D = 5, 16, 12


@pytest.fixture(scope="module")
def snaps():
    wl = make_workload(n=400, d=D, nq=64, seed=5, k=K, with_gt=False)
    out = {}
    for metric in ("l2", "cosine"):
        ix = WoWIndex(dim=D, m=8, ef_construction=32, o=4, seed=0,
                      metric=metric)
        ix.insert_batch(wl.vectors, wl.attrs, batch_size=128,
                        backend="numpy")
        out[metric] = take_snapshot(ix)
    return wl, out


def _state(di, cfg, wl, B):
    """A fresh state of ``B`` rows: the workload's first queries, with
    the last two rows padding (an empty range, inactive from init)."""
    qp = np.zeros((B, D), np.float32)
    rp = np.tile(np.asarray([[1.0, 0.0]], np.float32), (B, 1))
    qp[:B - 2], rp[:B - 2] = wl.queries[:B - 2], wl.ranges[:B - 2]
    return _init_jit(di, jnp.asarray(qp), jnp.asarray(rp), cfg)


@pytest.mark.parametrize("metric", ["l2", "cosine"])
@pytest.mark.parametrize("vec_dtype", ["f32", "int8"])
@pytest.mark.parametrize("B", [8, 16, 32, 64])
def test_chunk_record_decodes_to_the_state(snaps, metric, vec_dtype, B):
    """Two chunks through the in-place entry and through ``_run_jit``:
    the record decodes to bitwise the ``active``, ``dc``, ``hops``,
    ``res_i[:, :k]`` and ``res_d[:, :k]`` that separate reads give, and
    the returned state equals ``_run_jit``'s leaf for leaf."""
    wl, by_metric = snaps
    di = to_device_index(by_metric[metric], vec_dtype=vec_dtype)
    cfg = hop_cfg(k=K, width=WIDTH, m=8, o=4, metric=metric,
                  visited="bitmap")
    ref, st = _state(di, cfg, wl, B), _state(di, cfg, wl, B)
    for h in (4, 8):
        ref = _run_jit(di, ref, cfg, h)
        st, rec = _run_jit_inplace(di, st, cfg, h)
        rec = np.asarray(rec)
        assert rec.dtype == np.int32 and rec.shape[0] == B
        assert rec.shape[1] % 128 == 0 and rec.shape[1] >= 2 * K + 3
        assert not rec[:, 2 * K + 3:].any()
        act, dc, hops, res_i, res_d = chunk_record(rec, K)
        assert np.array_equal(act, np.asarray(ref.active))
        assert np.array_equal(dc, np.asarray(ref.dc))
        assert np.array_equal(hops, np.asarray(ref.hops))
        assert np.array_equal(res_i, np.asarray(ref.res_i)[:, :K])
        assert res_d.dtype == np.float32
        assert np.asarray(ref.res_d)[:, :K].tobytes() == res_d.tobytes()
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(st)):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert act[:B - 2].any() or hops[:B - 2].any()  # the chunks did work
    assert not act[B - 2:].any()  # padding rows never run


def test_chunk_donates_the_state(snaps):
    """The chunk consumes its input state: every leaf of it is deleted
    after the launch (the CPU backend honours donation as the TPU's
    does), so a stale read raises instead of reading reused memory, and
    the returned state is live."""
    wl, by_metric = snaps
    di = to_device_index(by_metric["l2"])
    cfg = hop_cfg(k=K, width=WIDTH, m=8, o=4, visited="bitmap")
    st = _state(di, cfg, wl, 8)
    new, rec = _run_jit_inplace(di, st, cfg, 4)
    np.asarray(rec)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(st))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(new))
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(st.res_i)
