"""95th percentile, over the window's ingest batches, of the time from a
batch's due time to the first step after which the engine had applied it
(from then on a query finds its rows); a batch never acked, or never
applied, counts past the drain's end."""
import numpy as np

UNIT = "ms"


def read(ctx):
    if ctx.visible_ms is None or ctx.visible_ms.size == 0:
        return None
    return float(np.percentile(ctx.visible_ms, 95))
