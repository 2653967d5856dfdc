"""95th percentile of query latency, due time to reply, over every query
due in the window; a rejected or lost query counts beyond any reply."""
import numpy as np

UNIT = "ms"


def read(ctx):
    if ctx.latency_ms.size == 0:
        return None
    return float(np.percentile(ctx.latency_ms, 95))
