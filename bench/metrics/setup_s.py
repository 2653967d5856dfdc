"""Set-up: process start to the window's open (cold start from the cached
build through ``open_durable`` and the engine's warm-up).  A first run in
a checkout also builds the corpus; that time is printed on its own line
and left out."""
UNIT = "s"


def read(ctx):
    return ctx.setup_s
