"""Host time of one ingest apply: the index's ``insert_batch`` (the host
build of the engine's ``build_backend``), timed around the call, per
apply in the window and drain."""
UNIT = "ms/batch"


def read(ctx):
    n = ctx.stats.get("ingest_applied", 0)
    return ctx.stats["ingest_apply_s"] * 1e3 / n if n else None
