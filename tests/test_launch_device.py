"""Start-up helpers of the device entry points: compile-cache placement and
the device line."""
import jax

from repro.launch import device


def test_compile_cache_placement(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    otherwise the cache goes to the fixed ``<checkout>/.jax_cache``."""
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert device.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = device.use_compile_cache()
        assert path == str(device.CHECKOUT / ".jax_cache")
        assert (device.CHECKOUT / "chip_smoke.py").is_file()
        assert jax.config.jax_compilation_cache_dir == path
        assert device.use_compile_cache() == path  # stable across calls
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_describe_devices_reports_what_jax_sees():
    dev = device.describe_devices()
    assert dev == {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())}
