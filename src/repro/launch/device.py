"""Start-up helpers for every entry point that runs on the device: where
JAX keeps its persistent compile cache, and the device line.

    from repro.launch.device import describe_devices, use_compile_cache
    use_compile_cache()          # before the first compile
    print(describe_devices())
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: root of the checkout (src/repro/launch/device.py -> three levels up)
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Place JAX's persistent compile cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX (which reads it
    itself) and nothing is set here.  Otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache`` — never a temp, pid or time-stamped name,
    since a directory that moves between runs is never found again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def describe_devices() -> dict:
    """The device line: platform, kind and count as JAX reports them."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
