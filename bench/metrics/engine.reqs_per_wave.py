"""Wave occupancy of the serve engine: requests admitted per wave
assembled (``ServeStats`` counters, over the traced window and drain)."""
UNIT = "reqs/wave"


def read(ctx):
    waves = ctx.stats["waves"]
    return ctx.stats["admitted"] / waves if waves else None
