"""The chip a run measures, and where JAX keeps its compile cache.

Every entry point (``run.py`` and the tools beside it) calls ``find``
before it touches the chip and refuses to run when it is not a TPU: a CPU
timing is never reported as a device metric.
"""
from __future__ import annotations

import os
import sys


def find(chips: int = 1) -> dict | None:
    """The device line, or None (with the reason on stderr) when JAX finds
    no TPU or fewer than ``chips`` of them."""
    # libtpu would log under /tmp, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {device}", file=sys.stderr, flush=True)
    if device["platform"] != "tpu":
        print("no TPU found: the benchmark measures the chip only",
              file=sys.stderr)
        return None
    if device["count"] < chips:
        print(f"{chips} chips asked for, {device['count']} found",
              file=sys.stderr)
        return None
    return device


def use_compile_cache(layout) -> None:
    """JAX's persistent compile cache at a fixed path in this checkout,
    holding every program however fast it compiled, so that only a cell's
    first run in a checkout compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(layout.cache / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
