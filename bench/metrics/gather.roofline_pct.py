"""Share of the HBM roofline reached by the gather kernel
(``kernels/gather_distance.py``): the bytes the algorithm needs, one
stored row (with its int8 scale) per distance computation of the
queries answered in the traced interval, at the chip's peak HBM
bandwidth, over the device time of the kernel's events in it.  The
bytes bound it: two multiply-adds per byte at most, far under the
compute peak.  The kernel's own copies (a whole aligned tile per row)
are not the algorithm's work and do not count."""
UNIT = "%"
KERNEL = "gather_norm_dot"  # the custom call of the Pallas kernel


def read(ctx):
    if ctx.trace is None or not ctx.traced_replies:
        return None
    secs = ctx.trace.kernel_seconds(KERNEL)
    if secs <= 0:
        return None
    dc = sum(r.dc for r in ctx.traced_replies)
    least = dc * ctx.row_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / secs
