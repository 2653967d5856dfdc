"""Queries answered inside the window, over the window's seconds."""
UNIT = "queries/s"


def read(ctx):
    w = ctx.window
    done = sum(1 for r in ctx.replies if r.finish_t <= w.t_close)
    return done / w.seconds
