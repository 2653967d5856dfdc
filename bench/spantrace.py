#!/usr/bin/env python3
"""The serve engine's own spans in a device trace, on the device's clock.

``ServeEngine`` marks its host work with ``serve.*`` spans
(``repro.serve.lifecycle``: ``serve.step`` and, inside it,
``serve.assemble``, ``serve.chunk``, ``serve.dispatch``, ``serve.sync``,
``serve.harvest``, ``serve.compact``).  This module reads them from the
``.xplane.pb`` of a traced run beside ``devtrace``'s reduction, which it
leaves as it is, and gives:

* the device clock's lag behind the host's.  The profiler stamps device
  events and host events on clocks that disagree by about a millisecond,
  as long as the phases of a chunk.  Each device program (an ``XLA
  Modules`` event of device 0) is paired with the host event that
  enqueued it, ``DoEnqueueProgram``, by the ``run_id`` both carry.  A
  program cannot start before it is enqueued, so the lag is at least
  (enqueue - start) for every pair; the smallest such shift, their
  maximum, is the lag;
* ``idle_gaps``: the device-idle time of the traced interval (the gaps
  of ``Summary.busy(0)``, so they add up to the time
  ``device.idle_pct.query`` reads), each instant given to the innermost
  host span (on one thread, the covering span that started last) at the
  same instant on the host's clock;
* per chunk and per call: device-idle time under ``serve.step`` spans,
  host time of ``serve.harvest`` spans, host time of ``serve.dispatch``
  spans.  Chunks and calls are the spans that start in the traced
  interval.

    python3 bench/spantrace.py <trace dir or .xplane.pb>

prints these as one JSON object.
"""
from __future__ import annotations

import heapq
import json
import sys
from dataclasses import dataclass
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import devtrace  # noqa: E402

SERVE_PREFIX = "serve."
HOST_PREFIXES = (devtrace.HOST_PREFIX, SERVE_PREFIX)
LAUNCH = "DoEnqueueProgram"  # host event that enqueues a device program
OTHER = "host (other)"


@dataclass
class SpanTrace:
    """A trace's ``devtrace.Summary`` with the host spans of the benchmark
    and the engine (``(name, start, end, args)``) and the (enqueue,
    program start) pairs of device 0; times in ns."""

    summary: devtrace.Summary
    spans: list
    pairs: list

    def clock_offset(self) -> tuple[int, int, int] | None:
        """-> (lag, smallest, largest) of (enqueue - program start) over
        the pairs, in ns; None without pairs.  A device time plus the lag
        is a host time."""
        if not self.pairs:
            return None
        d = [launch - start for launch, start in self.pairs]
        return max(d), min(d), max(d)

    def lag(self) -> int:
        off = self.clock_offset()
        return off[0] if off else 0

    def _in_window(self, name: str) -> list:
        t0, t1 = self.summary.window
        return [(s, e) for n, s, e, _ in self.spans
                if n == name and t0 <= s < t1]

    def gaps(self) -> list[tuple[int, int]]:
        """Device-idle intervals of device 0 in the traced interval, on
        the host's clock."""
        t0, t1 = self.summary.window
        off = self.lag()
        out, cur = [], t0
        for s, e in self.summary.busy(0):
            if s > cur:
                out.append((cur + off, s + off))
            cur = max(cur, e)
        if cur < t1:
            out.append((cur + off, t1 + off))
        return out

    def idle_gaps(self, limit: int = 10) -> list:
        """[[span name, seconds], ...]: device-idle time by the innermost
        host span; time under no span is ``host (other)``.  Adds up to
        the traced interval less ``Summary.busy_s`` on device 0."""
        segs = innermost([(n, s, e) for n, s, e, _ in self.spans
                          if n != devtrace.WINDOW_SPAN])
        acc: dict[str, int] = {}
        for gs, ge, name in _overlay(self.gaps(), segs):
            acc[name] = acc.get(name, 0) + ge - gs
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:limit]
        return [[n, v / 1e9] for n, v in top]

    def host_idle_ms_per_chunk(self) -> float | None:
        """Device-idle time under ``serve.step`` spans per chunk."""
        chunks = self._in_window("serve.chunk")
        if self.summary.devices == 0 or not self.pairs or not chunks:
            return None
        # one thread's steps follow one another: sorted, they are disjoint
        steps = sorted((s, e, n) for n, s, e, _ in self.spans
                       if n == "serve.step")
        idle = sum(ge - gs for gs, ge, name in _overlay(self.gaps(), steps)
                   if name == "serve.step")
        return idle / 1e6 / len(chunks)

    def harvest_ms_per_chunk(self) -> float | None:
        """Host time of ``serve.harvest`` spans per chunk."""
        chunks = self._in_window("serve.chunk")
        if not chunks:
            return None
        harvest = self._in_window("serve.harvest")
        return sum(e - s for s, e in harvest) / 1e6 / len(chunks)

    def dispatch_ms_per_call(self) -> float | None:
        """Host time of a ``serve.dispatch`` span: one program's launch,
        which returns before the device finishes."""
        calls = self._in_window("serve.dispatch")
        if not calls:
            return None
        return sum(e - s for s, e in calls) / 1e6 / len(calls)

    def report(self) -> dict:
        off = self.clock_offset()
        out = {"clock_offset_ms": off[0] / 1e6 if off else None,
               "clock_offset_range_ms": ([off[1] / 1e6, off[2] / 1e6]
                                         if off else None),
               "pairs": len(self.pairs),
               "idle_s": sum(e - s for s, e in self.gaps()) / 1e9,
               "idle_gaps": self.idle_gaps(limit=20)}
        for name in ("host_idle_ms_per_chunk", "harvest_ms_per_chunk",
                     "dispatch_ms_per_call"):
            out[name] = getattr(self, name)()
        return out


def innermost(spans) -> list[tuple[int, int, str]]:
    """``(name, start, end)`` spans -> sorted disjoint ``(start, end,
    name)`` segments that give each covered instant to the covering span
    with the latest start (of equal starts, the one that ends first)."""
    bounds = sorted({t for _, s, e in spans for t in (s, e)})
    order = sorted(spans, key=lambda x: x[1])
    heap: list = []
    out: list = []
    j = 0
    for a, b in zip(bounds, bounds[1:]):
        while j < len(order) and order[j][1] <= a:
            n, s, e = order[j]
            heapq.heappush(heap, (-s, e, j, n))
            j += 1
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if not heap:
            continue
        name = heap[0][3]
        if out and out[-1][1] == a and out[-1][2] == name:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def _overlay(gaps, segs):
    """Cut sorted disjoint ``gaps`` by sorted disjoint ``(start, end,
    name)`` segments -> ``(start, end, name)`` pieces covering the gaps
    exactly; pieces under no segment are named ``OTHER``."""
    out = []
    j = 0
    for gs, ge in gaps:
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        cur, k = gs, j
        while k < len(segs) and segs[k][0] < ge:
            s, e, name = segs[k]
            if s > cur:
                out.append((cur, s, OTHER))
            lo, hi = max(s, cur), min(e, ge)
            if hi > lo:
                out.append((lo, hi, name))
                cur = hi
            k += 1
        if cur < ge:
            out.append((cur, ge, OTHER))
    return out


def from_profile(pd) -> SpanTrace:
    summary = devtrace.from_profile(pd)
    spans, launches, starts = [], {}, {}
    for plane in pd.planes:
        device = plane.name.startswith(devtrace.DEVICE_PREFIX)
        if device and plane.name != devtrace.DEVICE_PREFIX + "0":
            continue
        for line in plane.lines:
            if device and line.name != devtrace.MODULES_LINE:
                continue
            for ev in line.events:
                if device:
                    run = dict(ev.stats).get("run_id")
                    if run is not None:
                        starts[run] = int(ev.start_ns)
                elif ev.name == LAUNCH:
                    run = dict(ev.stats).get("run_id")
                    if run is not None:
                        launches.setdefault(run, int(ev.start_ns))
                elif ev.name.startswith(HOST_PREFIXES):
                    s = int(ev.start_ns)
                    spans.append((ev.name, s, s + int(ev.duration_ns),
                                  dict(ev.stats)))
    pairs = [(launches[r], starts[r]) for r in sorted(starts)
             if r in launches]
    return SpanTrace(summary=summary, spans=spans, pairs=pairs)


def load(path: Path) -> SpanTrace:
    """Read ``path`` (an ``.xplane.pb``) or the newest one under it."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        files = sorted(path.rglob("*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    return from_profile(ProfileData.from_file(str(path)))


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    rep = load(Path(args[0])).report()
    if rep["clock_offset_range_ms"]:
        lo, hi = rep["clock_offset_range_ms"]
        print(f"clock offset: {rep['clock_offset_ms']:.6f} ms over "
              f"{rep['pairs']} pairs (enqueue - start {lo:.6f}..{hi:.6f} "
              f"ms)", file=sys.stderr)
    print(json.dumps(rep), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
