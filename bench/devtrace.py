"""The profiler trace of a ``--trace 1`` run, reduced to what the per-layer
metrics and the breakdown read.

``Capture`` records the last ``TRACE_S`` seconds of the window and its
drain with ``jax.profiler`` (a whole window's device events would take
minutes to read: a hop chunk writes about two thousand), and stops after
the drain, since writing the trace out stalls the host for seconds;
``load`` reads the ``.xplane.pb`` it wrote with ``ProfileData`` and keeps
three kinds of events, with start and end in nanoseconds:

* device operations: the ``XLA Ops`` line of each ``/device:TPU:<i>``
  plane, named by their HLO instruction (``gather_norm_dot.9`` of the
  event ``%gather_norm_dot.9 = (...) custom-call(...)``).  Their union is
  the time the device was busy;
* device programs: the ``XLA Modules`` line, one event per executed
  jitted program, named after it (``jit__run_jit(...)``);
* host spans: the benchmark's own ``TraceAnnotation``s (``bench.*``).

The traced interval is the ``bench.window`` span.  The metrics that
divide trace times by work take that work over the same interval: the
queries whose replies came inside it (``Capture.t0``..``t1`` on the host
clock, which the span brackets).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from pathlib import Path

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "bench."
TRACE_S = 2.0


class Capture:
    """Start and stop the profiler around the window."""

    def __init__(self, out: Path):
        self.out = Path(out)
        self._span = None

    def start(self) -> None:
        import jax

        # the Python tracer would add an event per Python call and slow
        # the host loop it is meant to observe
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.out), profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.t0 = time.monotonic()

    def stop(self) -> None:
        import jax

        self.t1 = time.monotonic()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self) -> "Summary":
        return load(self.out)


def _merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, t0: int, t1: int) -> list[tuple[int, int]]:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


@dataclass
class Summary:
    """Events of one trace; times in ns on the profiler's clock."""

    ops: list  # (name, start, end, device) device operations
    modules: list  # (name, start, end, device) device programs
    spans: list  # (name, start, end) host spans of the benchmark
    devices: int

    @property
    def window(self) -> tuple[int, int]:
        w = [(s, e) for n, s, e in self.spans if n == WINDOW_SPAN]
        if not w:
            raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
        return w[0]

    @property
    def window_s(self) -> float:
        t0, t1 = self.window
        return (t1 - t0) / 1e9

    def busy(self, device: int) -> list[tuple[int, int]]:
        t0, t1 = self.window
        return _clip(_merge([(s, e) for _, s, e, d in self.ops
                             if d == device]), t0, t1)

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        total = sum(e - s for d in range(self.devices)
                    for s, e in self.busy(d))
        return total / max(self.devices, 1) / 1e9

    def _seconds(self, events, match) -> float:
        t0, t1 = self.window
        return sum(min(e, t1) - max(s, t0) for n, s, e, _ in events
                   if match(n) and e > t0 and s < t1) / 1e9

    def kernel_seconds(self, name: str) -> float:
        """Device time of the operations named ``name`` or ``name.<i>``."""
        return self._seconds(
            self.ops, lambda n: n == name or n.startswith(name + "."))

    def program_seconds(self, names) -> float:
        """Device time of the programs whose name contains any of
        ``names``."""
        return self._seconds(self.modules,
                             lambda n: any(p in n for p in names))

    def top_ops(self, limit: int = 10) -> list:
        t0, t1 = self.window
        acc: dict[str, int] = {}
        for n, s, e, _ in self.ops:
            if e > t0 and s < t1:
                acc[n] = acc.get(n, 0) + min(e, t1) - max(s, t0)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:limit]
        return [[n, v / 1e9] for n, v in top]

    def idle_gaps(self, limit: int = 10) -> list:
        """Device-idle time of device 0 attributed to what the host was
        doing: each gap's overlap with each benchmark span, summed per
        span name; time under no span is ``host (other)``."""
        t0, t1 = self.window
        gaps, cur = [], t0
        for s, e in self.busy(0):
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < t1:
            gaps.append((cur, t1))
        # the benchmark's spans follow one another (one thread), so sorted
        # by start they are sorted by end too: one pass over both lists
        spans = sorted((s, e, n) for n, s, e in self.spans
                       if n != WINDOW_SPAN)
        acc: dict[str, int] = {}
        first = 0
        for gs, ge in gaps:
            while first < len(spans) and spans[first][1] <= gs:
                first += 1
            covered = 0
            for j in range(first, len(spans)):
                s, e, n = spans[j]
                if s >= ge:
                    break
                ov = min(e, ge) - max(s, gs)
                if ov > 0:
                    acc[n] = acc.get(n, 0) + ov
                    covered += ov
            rest = (ge - gs) - covered
            if rest > 0:
                acc["host (other)"] = acc.get("host (other)", 0) + rest
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:limit]
        return [[n, v / 1e9] for n, v in top]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def load(trace_dir: Path) -> Summary:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(str(files[-1])))


def op_name(event_name: str) -> str:
    """The HLO instruction's name: ``%fusion.80 = f32[...] fusion(...)``
    gives ``fusion.80``."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def from_profile(pd) -> Summary:
    ops, modules, spans = [], [], []
    devices = 0
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):])
            devices = max(devices, dev + 1)
            for line in plane.lines:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is None:
                    continue
                name = op_name if dest is ops else str
                for ev in line.events:
                    s = int(ev.start_ns)
                    dest.append((name(ev.name), s, s + int(ev.duration_ns),
                                 dev))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
    return Summary(ops=ops, modules=modules, spans=spans, devices=devices)
