"""Host time inside ``ServeEngine.submit_ingest`` per batch offered in the
window, timed by the driving loop: per-row validation, the WAL append and the
one fsync before the ack."""
import numpy as np

UNIT = "ms/batch"


def read(ctx):
    log = ctx.window.ingest
    if log is None or not np.any(~np.isnan(log.ack_s)):
        return None
    return float(np.nanmean(log.ack_s)) * 1e3
