"""Compile the serving hot path for a described TPU v5e (no chip needed).

The TPU compiler installed with jaxlib compiles for a topology that is
described, not attached: what Mosaic or XLA:TPU would refuse on the chip
(block shapes off the (8, 128) tiling, unaligned DMAs, VMEM overuse) is
refused here too, which interpret mode never shows.  The topology is
described inside a module fixture — only the worker that runs these tests
loads the TPU library — and every test skips where it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Trace as the chip would: the dispatch asks ``jax.devices()``, which
    here is the CPU, so steer it to the compiled kernel and the TPU merge."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("D", [128, 768])
def test_gather_norm_dot_compiles_for_v5e(one_chip, D, dtype):
    from repro.kernels.gather_distance import gather_norm_dot

    n, B, K = 1 << 20, 128, 17
    args = (_spec((n, D), jnp.dtype(dtype), one_chip),
            _spec((B, K), jnp.int32, one_chip),
            _spec((B, D), jnp.float32, one_chip),
            _spec((n,), jnp.float32, one_chip) if dtype == "int8" else None)
    fn = jax.jit(lambda t, i, q, s: gather_norm_dot(t, i, q, scales=s,
                                                     interpret=False))
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_gather_kernel_keeps_its_name_for_v5e(one_chip):
    """The kernel's custom call is named ``gather_norm_dot`` whatever jit
    calls it, so the trace's ``gather_norm_dot.<i>`` events (the roofline
    reader's input) survive a refactor of the code around it."""
    from repro.kernels.gather_distance import gather_norm_dot

    def caller_of_another_name(t, i, q):
        return gather_norm_dot.__wrapped__(t, i, q, interpret=False)

    args = (_spec((1 << 16, 128), jnp.float32, one_chip),
            _spec((64, 17), jnp.int32, one_chip),
            _spec((64, 128), jnp.float32, one_chip))
    text = jax.jit(caller_of_another_name).lower(*args).compile().as_text()
    calls = [ln.split(" = ", 1)[0].strip() for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and all(c.startswith("%gather_norm_dot") for c in calls)


@pytest.mark.parametrize("visited", ["hash", "bitmap"])
def test_serve_chunk_compiles_for_v5e(one_chip, as_on_tpu, visited):
    """The serve engine's chunk jit (``_run_jit``) at n = 2^20, d = 128,
    B = 128 with the compiled gather kernel and the one-hot merge."""
    from repro.core.device_search import (
        DeviceIndex, _init_state, _run_jit, hop_cfg,
    )

    n, d, B, L, m = 1 << 20, 128, 128, 6, 16
    di = DeviceIndex(
        vectors=_spec((n, d), jnp.float32, one_chip),
        sq_norms=_spec((n,), jnp.float32, one_chip),
        attrs=_spec((n,), jnp.float32, one_chip),
        neighbors=_spec((L, n, m), jnp.int32, one_chip),
        uvals=_spec((n,), jnp.float32, one_chip),
        uval_rep=_spec((n,), jnp.int32, one_chip),
        scales=_spec((1,), jnp.float32, one_chip),
    )
    cfg = hop_cfg(k=10, width=64, m=m, o=4, visited=visited)
    st = jax.eval_shape(
        lambda di_, q, r: _init_state(di_, q, r, cfg), di,
        _spec((B, d), jnp.float32, one_chip),
        _spec((B, 2), jnp.float32, one_chip))
    st = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), st)
    text = _run_jit.lower(di, st, cfg=cfg, h=8).compile().as_text()
    assert "tpu_custom_call" in text


def test_build_search_compiles_for_v5e(one_chip, as_on_tpu):
    """The device build's construction-search jit at n = 2^20, d = 128 and
    a 512-member micro-batch with a 128-wide beam."""
    from repro.core.device_search import (
        DeviceIndex, _build_search_jit, hop_cfg,
    )

    n, d, B, L, m, W = 1 << 20, 128, 512, 6, 16, 128
    di = DeviceIndex(
        vectors=_spec((n, d), jnp.float32, one_chip),
        sq_norms=_spec((n,), jnp.float32, one_chip),
        attrs=_spec((n,), jnp.float32, one_chip),
        neighbors=_spec((L, n, m), jnp.int32, one_chip),
        uvals=_spec((1,), jnp.float32, one_chip),
        uval_rep=_spec((1,), jnp.int32, one_chip),
        scales=_spec((1,), jnp.float32, one_chip),
    )
    cfg = hop_cfg(k=W, width=W, m=m, o=4, visited="hash")
    args = [_spec((B, d), jnp.float32, one_chip),
            _spec((B, 2), jnp.float32, one_chip)]
    args += [_spec((B,), jnp.int32, one_chip)] * 3
    args += [_spec((B, W), jnp.int32, one_chip),
             _spec((B, W), jnp.float32, one_chip),
             _spec((B,), jnp.bool_, one_chip)]
    text = _build_search_jit.lower(di, *args, cfg=cfg).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("B", [8, 64])
def test_inplace_chunk_aliases_state_for_v5e(one_chip, as_on_tpu, B):
    """The serve engine's chunk entry (``_run_jit_inplace``) at the
    benchmark's serving size, n = 2^16, d = 128, f32: the compiled
    program writes every ``HopState`` leaf in place (an
    ``input_output_alias`` per leaf) and builds the boundary record
    inside its fusions, with no copy or slice op of its own — the only
    top-level copies are the scalar ones ``_run_jit`` has too."""
    import re

    from repro.core.device_search import (
        DeviceIndex, HopState, _init_state, _run_jit_inplace, hop_cfg,
    )

    n, d, L, m = 1 << 16, 128, 9, 16
    di = DeviceIndex(
        vectors=_spec((n, d), jnp.float32, one_chip),
        sq_norms=_spec((n,), jnp.float32, one_chip),
        attrs=_spec((n,), jnp.float32, one_chip),
        neighbors=_spec((L, n, m), jnp.int32, one_chip),
        uvals=_spec((n,), jnp.float32, one_chip),
        uval_rep=_spec((n,), jnp.int32, one_chip),
        scales=_spec((1,), jnp.float32, one_chip),
    )
    cfg = hop_cfg(k=10, width=64, m=m, o=4, visited="bitmap")
    st = jax.eval_shape(
        lambda di_, q, r: _init_state(di_, q, r, cfg), di,
        _spec((B, d), jnp.float32, one_chip),
        _spec((B, 2), jnp.float32, one_chip))
    st = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), st)
    text = _run_jit_inplace.lower(di, st, cfg=cfg, h=8).compile().as_text()
    alias = text.split("input_output_alias=", 1)[1].split("_layout=", 1)[0]
    outs = {int(o) for o in re.findall(r"\{(\d+)\}: \(\d+,", alias)}
    assert outs == set(range(len(HopState._fields)))
    entry = text[text.index("\nENTRY"):]
    own = re.findall(r"= (\S+) (copy|copy-start|slice|slice-start|"
                     r"concatenate|transpose)\(", entry)
    assert all(shape.startswith("s32[]") for shape, _ in own), own
