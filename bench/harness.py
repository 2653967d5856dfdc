"""One run of one cell: cold start, warm-up, the measured window, the
checks against the reference, and the result line.

``run.py`` calls ``run_cell`` after it has found the chip; tests call it
on the CPU with a small configuration of their own.
"""
from __future__ import annotations

import gc
import shutil
import sys
import time
from dataclasses import dataclass, field

import numpy as np

from . import check, corpus, drive, ingest, reference, traffic
from .spec import Cell, Layout

# the seed of every set-up draw: far above any run's --seed, so set-up is
# the same work in every run
WARM_SEED = 2**40
WARM_QUERIES = 1024  # a closed burst that settles the adaptive chunk schedule
# every (first, later) chunk length the adaptive engine can choose: powers
# of two in [4, 64] (device_search.chunk_schedule_from_hist); each pair
# warms both of its lengths
CHUNK_PAIRS = ((4, 8), (16, 32), (64, 64))


@dataclass
class Context:
    """What a metric reader may read (``bench/metrics/<name>.py``)."""

    window: drive.Window
    latency_ms: np.ndarray  # every attempted query; failed ones beyond all
    visible_ms: np.ndarray | None  # every ingest batch, due to applied
    recall: np.ndarray  # recall@k per replied query
    replies: list  # the window's Reply objects
    setup_s: float
    stats: dict  # counters gained over the window and drain (ServeStats;
    # with ingest also ingest.Taps)
    row_bytes: int  # bytes of one stored row, with its scale
    trace: object = None  # devtrace.Summary of a traced run
    traced_replies: list = field(default_factory=list)  # answered in it
    peaks: dict = field(default_factory=dict)


def engine_config(cfg: dict):
    from repro.serve.lifecycle import EngineConfig

    return EngineConfig(**cfg["engine"])


def stored_row_bytes(cfg: dict) -> int:
    d = cfg["corpus"]["d"]
    vd = cfg["engine"]["vec_dtype"]
    return {"f32": 4 * d, "bf16": 2 * d, "int8": d + 4}[vd]


def _counters(engine) -> dict:
    s = engine.stats
    return {"admitted": s.admitted, "waves": s.waves, "chunks": s.chunks,
            "served": s.served, "rejected": s.rejected}


def warm_up(engine, cell: Cell, vectors, attrs) -> None:
    """Compile every shape the window will use, with draws from
    ``WARM_SEED``: the engine's wave buckets under every chunk schedule
    the adaptive engine can pick, the mix's set-up ingest batches if it
    has any (the first apply and snapshot refresh), then a closed burst of
    the mix's queries (which fills the hop histogram the schedule is read
    from)."""
    cfg = engine.config
    chunk = cfg.chunk
    for pair in CHUNK_PAIRS:
        cfg.chunk = pair
        engine.warmup()
    cfg.chunk = chunk
    if "ingest" in cell.traffic:
        ingest.warm(engine, warm_feed(cell, len(attrs)))
    burst = traffic.query_schedule(cell.traffic["queries"], vectors, attrs,
                                   rate=WARM_QUERIES, seconds=1.0,
                                   seed=WARM_SEED)
    drive.burst(engine, burst.queries, burst.ranges)


def warm_feed(cell: Cell, n: int):
    """The set-up ingest batches of a cell whose mix appends to ``n``
    rows."""
    return traffic.ingest_schedule(
        cell.traffic["ingest"], cell.config["corpus"],
        rate=ingest.WARM_BATCHES, seconds=1.0, seed=WARM_SEED, first_attr=n)


def stats_line(engine) -> str:
    s = engine.stats
    return (f"engine: {s.admitted} admitted, {s.rejected} rejected, "
            f"{s.waves} waves, {s.chunks} chunks, queue peak {s.queue_peak}")


def memory_peak() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def run_cell(layout: Layout, name: str, seed: int, seconds: float,
             trace: bool, t_start: float, device: dict,
             rate: float | None = None, control: bool = False,
             keep_trace=None, ingest_rate: float | None = None) -> dict:
    """One run of cell ``name``; returns the result line as a dict.

    ``setup_s`` runs from ``t_start`` to the window's open, less the time
    a first run in a checkout spends building the corpus (printed on a
    line of its own).  The tools beside ``run.py`` use the rest: ``rate``
    and ``ingest_rate`` offer other rates than the cell's (the sweeps),
    ``control`` also reads the compared numbers of each lower-precision
    control on the same queries (under ``"control"``), ``keep_trace``
    copies the trace file there."""
    from repro.analysis.compile_guard import CompileCounter
    from repro.persist import open_durable
    from repro.persist.recovery import wal_dir
    from repro.serve.lifecycle import ServeEngine

    cell = Cell.load(layout, name)
    cfg = cell.config
    k = engine_config(cfg).k
    t_build = time.monotonic()
    built = corpus.cached_build(cfg, layout.cache)
    build_s = time.monotonic() - t_build
    run_dir = corpus.fresh_run_dir(built, layout.cache)
    taps = None
    try:
        vectors = corpus.corpus_vectors(cfg["corpus"])
        attrs = corpus.corpus_attrs(cfg["corpus"])
        index = open_durable(str(run_dir))
        engine = ServeEngine(index=index, config=engine_config(cfg))
        with CompileCounter() as cc_warm:
            warm_up(engine, cell, vectors, attrs)
        sched = traffic.query_schedule(cell.traffic["queries"], vectors,
                                       attrs, rate or cell.rate, seconds, seed)
        mix, feed = cell.traffic.get("ingest"), None
        if mix is not None:
            first = len(attrs) + ingest.WARM_BATCHES * mix["batch_rows"]
            feed = traffic.ingest_schedule(
                mix, cfg["corpus"], ingest_rate or cell.ingest_rate, seconds,
                seed, first_attr=first)
            ingest.check_capacity(len(attrs), first - len(attrs)
                                  + feed.attrs.size)
            taps = ingest.Taps(engine)
        before = _counters(engine)
        tracer, marks = None, []
        if trace:
            from . import devtrace

            tracer = devtrace.Capture(run_dir / "trace")
            marks = [(max(0.0, seconds - devtrace.TRACE_S), tracer.start)]
        setup_s = time.monotonic() - t_start - build_s
        print(f"setup: {setup_s:.3f} s ({cc_warm.count} compiles, "
              f"{cc_warm.total_secs:.3f} s compiling; the build's "
              f"{build_s:.3f} s not counted)", file=sys.stderr, flush=True)
        with CompileCounter() as cc_win:
            window = drive.run_window(engine, sched, seconds, trace=trace,
                                      marks=marks, ingest=feed)
            if tracer:
                tracer.stop()  # after the drain: writing it out takes seconds
        if taps:
            taps.close()
        after = _counters(engine)
        stats = {key: after[key] - before[key] for key in after}
        ref = cfg["reference"]
        limits = dict(ref.get("limits", {}))
        numbers, visible_ms, failed_batches = {}, None, 0
        if feed is not None:
            stats.update(taps.counters())
            visible_ms = window.visible_ms(feed.due)
            failed_batches = int(np.sum(np.isnan(window.ingest.visible_t)))
            print(ingest.summary_line(feed, window.ingest, visible_ms, stats,
                                      len(attrs)), file=sys.stderr, flush=True)
            limits.update(mix["limits"])
            numbers["unread_acked"] = ingest.unread_acked(
                engine, feed, window.ingest, limits["dist_gap"])
            grown = ingest.index_rows(vectors, attrs,
                                      warm_feed(cell, len(attrs)), feed,
                                      window.ingest)
            probes = traffic.append_probes(
                cell.traffic["queries"], *grown, len(grown[1]) - len(attrs),
                mix["probes"], seed)
            probed = drive.burst(engine, probes.queries, probes.ranges)
        lateness = window.submit_t - (window.t_open + sched.due)
        _, lat = window.latency_ms(sched.due)
        p50, p95, p99 = np.percentile(lat, [50, 95, 99]) if lat.size else [0] * 3
        hops = [r.hops for r in window.replies.values()]
        print(f"window: {len(window.rid)} queries offered in "
              f"{window.seconds:.3f} s, drained {window.t_drained - window.t_close:.3f} s "
              f"after the close; generator lateness p50 "
              f"{np.median(lateness) * 1e3:.3f} ms, max "
              f"{np.max(lateness, initial=0.0) * 1e3:.3f} ms; latency p50 "
              f"{p50:.3f} / p95 {p95:.3f} / p99 {p99:.3f} ms from due; "
              f"mean hops {np.mean(hops) if hops else 0.0:.2f}; "
              f"{stats_line(engine)}; "
              f"{cc_win.count} compiles in the window "
              f"({cc_win.total_secs:.3f} s)", file=sys.stderr, flush=True)
        device = dict(device, memory_peak_bytes=memory_peak())
        del engine, index
        gc.collect()

        # the reference, once the program's state is freed
        replied, ids, dists = check.reply_arrays(window, k)
        rows = reference.stored_rows(vectors, ref["vec_dtype"])
        gold, _ = reference.topk(vectors, attrs, sched.queries, sched.ranges,
                                 k)
        rec = check.recall(ids[replied], gold[replied])
        numbers.update(check.answer_numbers(ids, dists, sched.ranges, attrs,
                                            rows, sched.queries))
        numbers.update(check.recall_numbers(rec))
        numbers["missing"] = int(np.sum((window.rid >= 0) & ~replied))
        if feed is not None:
            # the WAL on disk, and the probes over the corpus plus the
            # acked rows
            numbers.update(ingest.wal_numbers(
                wal_dir(str(run_dir)), feed, window.ingest,
                taps.synced_at_ack))
            p_replied, p_ids, p_dists = check.reply_arrays(probed, k)
            p_gold, _ = reference.topk(*grown, probes.queries, probes.ranges,
                                       k)
            numbers["append_recall_loss"] = float(
                1.0 - check.recall(p_ids, p_gold).mean())
            more = check.answer_numbers(
                p_ids, p_dists, probes.ranges, grown[1],
                reference.stored_rows(grown[0], ref["vec_dtype"]),
                probes.queries)
            more["missing"] = int(np.sum((probed.rid >= 0) & ~p_replied))
            for key, value in more.items():
                numbers[key] = (max(numbers[key], value) if key == "dist_gap"
                                else numbers[key] + value)
        correct, table = check.judge(numbers, limits)
        ctrl = None
        if control:
            ctrl = {}
            for c in ref["controls"]:
                cids, cd = reference.topk(
                    reference.stored_rows(vectors, c["vec_dtype"]), attrs,
                    sched.queries, sched.ranges, k, precision=c["precision"])
                got = check.answer_numbers(cids, cd, sched.ranges, attrs,
                                           rows, sched.queries)
                got.update(check.recall_numbers(check.recall(cids, gold)))
                ctrl[f"{c['vec_dtype']}@{c['precision']}"] = got
        ctx = Context(
            window=window, latency_ms=lat, visible_ms=visible_ms, recall=rec,
            replies=[window.replies[r] for r in window.rid[replied]],
            setup_s=setup_s, stats=stats, row_bytes=stored_row_bytes(cfg))
        kind = "end_to_end"
        if tracer:
            kind = "per_layer"
            if keep_trace is not None:
                for f in (run_dir / "trace").rglob("*.xplane.pb"):
                    shutil.copy(f, keep_trace)
            ctx.trace = tracer.summary()
            ctx.traced_replies = [r for r in ctx.replies
                                  if tracer.t0 <= r.finish_t <= tracer.t1]
            ctx.peaks = layout.peaks(device["kind"])
            device.update(busy_s=ctx.trace.busy_s, window_s=ctx.trace.window_s)
        metrics = {}
        for metric in cell.spec["metrics"][kind]:
            reader = layout.reader(metric)
            value = reader.read(ctx)
            if value is not None:
                metrics[metric] = {"value": float(value), "unit": reader.UNIT}
        result = {"correct": bool(correct),
                  "attempted": len(window.rid) + (len(feed.due) if feed
                                                  else 0),
                  "failed": int(np.sum(~replied)) + failed_batches,
                  "metrics": metrics, "device": device}
        if tracer:
            result["breakdown"] = ctx.trace.breakdown()
        if ctrl is not None:
            result["control"] = ctrl
        result["checks"] = table
        check.print_table(table)
        return result
    finally:
        if taps:
            taps.close()
        shutil.rmtree(run_dir, ignore_errors=True)
