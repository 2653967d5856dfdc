"""Host time of one snapshot refresh (``take_snapshot`` and
``to_device_index``: pad, quantize, upload), per refresh made after the
index mutated, in the window and drain."""
UNIT = "ms/refresh"


def read(ctx):
    n = ctx.stats.get("refreshes", 0)
    return ctx.stats["refresh_s"] * 1e3 / n if n else None
