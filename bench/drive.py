"""The measured window: an open loop over ``ServeEngine``.

One thread drives the engine, as its docstring asks.  Each query is
submitted once its due time has passed; the gap between the two is the
generator's lateness, reported so that a starved generator is not read as
a fast server.  Latency runs from the due time to the reply's ``finish_t``
on the same monotonic clock.

Ingest batches, where the cell has them, are offered by the same thread
in the same way: each is submitted once due, and ``submit_ingest`` returns
only when the batch is durable in the WAL (the ack).  After every step the
loop notes which acked batches the engine has applied (its applied LSN
has reached theirs): from then on a query can find their rows.

When ``--seconds`` have passed, no more work is offered; the loop then
steps until every admitted query has its reply and every acked batch is
applied, for at most ``DRAIN_S``.  A reply that comes in that time is
late, not lost.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

DRAIN_S = 60.0


def host_span(enabled: bool):
    """``jax.profiler.TraceAnnotation`` when tracing, else a no-op."""
    if enabled:
        from jax.profiler import TraceAnnotation

        return TraceAnnotation
    return lambda name: contextlib.nullcontext()


@dataclass
class IngestLog:
    """What became of each ingest batch of the schedule, in its order."""

    ack_s: np.ndarray  # f64[B] seconds inside submit_ingest; NaN = not due
    acked: np.ndarray  # bool[B] every row accepted, durable in the WAL
    lsn: np.ndarray  # i64[B] the ack's LSN
    visible_t: np.ndarray  # f64[B] first seen applied; NaN = never

    @classmethod
    def empty(cls, count: int) -> "IngestLog":
        return cls(ack_s=np.full(count, np.nan), acked=np.zeros(count, bool),
                   lsn=np.zeros(count, np.int64),
                   visible_t=np.full(count, np.nan))


@dataclass
class Window:
    t_open: float = 0.0
    t_close: float = 0.0
    t_drained: float = 0.0
    submit_t: np.ndarray = None  # f64[N] when each query was submitted
    rid: np.ndarray = None  # i64[N] engine request id, -1 = rejected
    replies: dict = field(default_factory=dict)  # rid -> Reply
    ingest: IngestLog | None = None  # the cell's ingest batches, if any

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def visible_ms(self, due: np.ndarray) -> np.ndarray:
        """Milliseconds from each ingest batch's due time to the first
        check that found it applied; a batch never acked, or never
        applied, counts as past the drain's end, as a lost query does."""
        vis = self.ingest.visible_t
        fin = np.where(np.isnan(vis), self.t_drained + DRAIN_S, vis)
        return (fin - (self.t_open + np.asarray(due))) * 1e3

    def latency_ms(self, due: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """-> (replied bool[N], latency ms[N]) of the attempted queries,
        from each one's due time (``due``: seconds after the open) to its
        reply.  A query with no reply (rejected, or lost) counts as
        beyond any reply the run could have had: past the drain's end."""
        at = self.t_open + np.asarray(due)
        fin = np.full(len(self.rid), np.nan)
        for i, rid in enumerate(self.rid):
            rep = self.replies.get(int(rid)) if rid >= 0 else None
            if rep is not None:
                fin[i] = rep.finish_t
        replied = ~np.isnan(fin)
        fin = np.where(replied, fin, self.t_drained + DRAIN_S)
        return replied, (fin - at) * 1e3


def run_window(engine, sched, seconds: float, trace: bool = False,
               marks=(), clock=time.monotonic, ingest=None) -> Window:
    """Offer ``sched`` (and the ``ingest`` schedule, if any) to
    ``engine`` for ``seconds``, then drain.  ``marks`` are ``(seconds
    after the open, callable)`` pairs, called once that time has passed
    (the profiler's start and stop); any left at the close are called
    then.  Returns what the window produced."""
    span = host_span(trace)
    N = len(sched.due)
    w = Window(submit_t=np.full(N, np.nan), rid=np.full(N, -1, np.int64))
    B = 0 if ingest is None else len(ingest.due)
    if ingest is not None:
        w.ingest = log = IngestLog.empty(B)
    w.t_open = clock()
    due = w.t_open + sched.due
    end = w.t_open + seconds
    i = b = 0
    unseen: deque = deque()  # acked batches not yet seen applied

    def ingest_due(upto: float) -> int:
        j = b
        while j < B and w.t_open + ingest.due[j] <= upto:
            with span("bench.ingest"):
                t0 = clock()
                res = engine.submit_ingest(ingest.vectors[j], ingest.attrs[j])
                log.ack_s[j] = clock() - t0
            log.acked[j] = (res.accepted == len(ingest.attrs[j])
                            and not res.rejected)
            log.lsn[j] = res.lsn
            if log.acked[j]:
                unseen.append(j)
            j += 1
        return j

    def note_applied() -> None:
        """Mark the batches the engine has applied by now.  The engine has
        no public applied LSN; its index's is read directly."""
        if not unseen:
            return
        lsn, t = engine.index._applied_lsn, clock()
        while unseen and log.lsn[unseen[0]] <= lsn:
            log.visible_t[unseen.popleft()] = t

    def submit_due(upto: float) -> int:
        j = i
        with span("bench.submit"):
            while j < N and due[j] <= upto:
                t = engine.submit(sched.queries[j], sched.ranges[j])
                w.submit_t[j] = clock()
                w.rid[j] = -1 if hasattr(t, "retry_after") else t.rid
                j += 1
        return j

    marks = sorted(marks, key=lambda m: m[0])
    while True:
        now = clock()
        while marks and now >= w.t_open + marks[0][0]:
            marks.pop(0)[1]()
        if now >= end:
            # all work due inside the window is attempted, late or not
            b = ingest_due(end)
            i = submit_due(end)
            break
        b = ingest_due(now)
        i = submit_due(now)
        if engine.idle:
            nxt = min(due[i] if i < N else end,
                      w.t_open + ingest.due[b] if b < B else end)
            with span("bench.wait"):
                time.sleep(max(0.0, min(nxt, end) - clock()))
            continue
        with span("bench.step"):
            for rep in engine.step():
                w.replies[rep.rid] = rep
        note_applied()
    for _, fn in marks:
        fn()
    w.t_close = clock()
    limit = w.t_close + DRAIN_S
    with span("bench.drain"):
        while not engine.idle and clock() < limit:
            for rep in engine.step():
                w.replies[rep.rid] = rep
            note_applied()
    w.t_drained = clock()
    return w


def burst(engine, queries: np.ndarray, ranges: np.ndarray) -> Window:
    """Offer ``queries`` closed-loop, ``queue_cap // 2`` at a time, each lot
    drained before the next; -> a ``Window`` holding only their request ids
    and replies (no times)."""
    w = Window(rid=np.full(len(queries), -1, np.int64))
    step = max(1, engine.config.queue_cap // 2)
    for s in range(0, len(queries), step):
        for j in range(s, min(s + step, len(queries))):
            t = engine.submit(queries[j], ranges[j])
            w.rid[j] = -1 if hasattr(t, "retry_after") else t.rid
        for rep in engine.drain():
            w.replies[rep.rid] = rep
    return w
