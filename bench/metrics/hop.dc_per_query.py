"""Distance computations per answered query (mean ``Reply.dc``)."""
UNIT = "dc/query"


def read(ctx):
    if not ctx.replies:
        return None
    return sum(r.dc for r in ctx.replies) / len(ctx.replies)
