"""Mean recall@10 of the window's replies against the brute-force
reference over the float32 corpus."""
UNIT = "ratio"


def read(ctx):
    if ctx.recall.size == 0:
        return None
    return float(ctx.recall.mean())
