"""The measured window: an open loop over ``ServeEngine``.

One thread drives the engine, as its docstring asks.  Each query is
submitted once its due time has passed; the gap between the two is the
generator's lateness, reported so that a starved generator is not read as
a fast server.  Latency runs from the due time to the reply's ``finish_t``
on the same monotonic clock.

When ``--seconds`` have passed, no more work is offered; the loop then
steps until every admitted query has its reply, for at most
``DRAIN_S``.  A reply that comes in that time is
late, not lost.
"""
from __future__ import annotations

import contextlib
import time
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
class Window:
    t_open: float = 0.0
    t_close: float = 0.0
    t_drained: float = 0.0
    submit_t: np.ndarray = None  # f64[N] when each query was submitted
    rid: np.ndarray = None  # i64[N] engine request id, -1 = rejected
    replies: dict = field(default_factory=dict)  # rid -> Reply

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

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
               marks=(), clock=time.monotonic) -> Window:
    """Offer ``sched`` to ``engine`` for
    ``seconds``, then drain.  ``marks`` are ``(seconds after the open,
    callable)`` pairs, called once that time has passed (the profiler's
    start and stop); any left at the close are called then.  Returns what
    the window produced."""
    span = host_span(trace)
    N = len(sched.due)
    w = Window(submit_t=np.full(N, np.nan), rid=np.full(N, -1, np.int64))
    w.t_open = clock()
    due = w.t_open + sched.due
    end = w.t_open + seconds
    i = 0

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
            # every query due inside the window is attempted, late or not
            i = submit_due(end)
            break
        i = submit_due(now)
        if engine.idle:
            nxt = due[i] if i < N else end
            with span("bench.wait"):
                time.sleep(max(0.0, min(nxt, end) - clock()))
            continue
        with span("bench.step"):
            for rep in engine.step():
                w.replies[rep.rid] = rep
    for _, fn in marks:
        fn()
    w.t_close = clock()
    limit = w.t_close + DRAIN_S
    with span("bench.drain"):
        while not engine.idle and clock() < limit:
            for rep in engine.step():
                w.replies[rep.rid] = rep
    w.t_drained = clock()
    return w
