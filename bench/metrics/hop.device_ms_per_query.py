"""Device time of the hop loop's programs (``_init_jit``, ``_run_jit``)
per query answered in the traced interval (the replies that came
inside it)."""
UNIT = "ms/query"
PROGRAMS = ("_init_jit", "_run_jit")


def read(ctx):
    if ctx.trace is None or not ctx.traced_replies:
        return None
    secs = ctx.trace.program_seconds(PROGRAMS)
    if secs <= 0:
        return None
    return secs * 1e3 / len(ctx.traced_replies)
