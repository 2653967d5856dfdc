"""Bytes a snapshot refresh put on the device, in MB of 10^6 bytes, per
refresh made after the index mutated, in the window and drain: the host
arrays the refresh handed to ``jnp.asarray``, ``jnp.array`` or
``jax.device_put`` (``bench/ingest.py`` ``Taps``).  A refresh that sends
only what changed reads less; one that re-uploads the whole index reads
its size."""
UNIT = "MB/refresh"


def read(ctx):
    n = ctx.stats.get("refreshes", 0)
    return ctx.stats["refresh_bytes"] / 1e6 / n if n else None
