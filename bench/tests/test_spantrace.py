"""The engine's spans in a trace (``bench/spantrace.py``): innermost
attribution of idle time, the device clock's lag, the per-chunk and
per-call readings, on synthetic events, on the two traces recorded on a
TPU v5e (``bench/testdata/``), and on a traced run on the CPU."""
import gzip
import importlib.util
import time

import pytest

from bench import devtrace, spantrace
from bench.spec import BENCH, Layout

TESTDATA = BENCH / "testdata"
FIRST = TESTDATA / "sift128-f32.query-mixed.xplane.pb.gz"
SERVE = TESTDATA / "sift128-f32.query-mixed.serve.xplane.pb.gz"
MS = 1_000_000


def _trace(ops, spans, pairs=(), window=(0, 100)):
    """Times in ms: ops (name, start, end) on device 0; spans (name,
    start, end[, args]); pairs (enqueue, program start)."""
    summary = devtrace.Summary(
        ops=[(n, s * MS, e * MS, 0) for n, s, e in ops], modules=[],
        spans=[("bench.window", window[0] * MS, window[1] * MS)],
        devices=1)
    host = [(sp[0], sp[1] * MS, sp[2] * MS, sp[3] if len(sp) > 3 else {})
            for sp in spans]
    host.append(("bench.window", window[0] * MS, window[1] * MS, {}))
    return spantrace.SpanTrace(summary=summary, spans=host,
                               pairs=[(a * MS, b * MS) for a, b in pairs])


def _gaps(t):
    return {n: v for n, v in t.idle_gaps(limit=50)}


def test_idle_goes_to_the_innermost_span_and_adds_up():
    # device busy 0-10 and 60-70; the host nests step > chunk > sync
    t = _trace([("a", 0, 10), ("b", 60, 70)],
               [("bench.step", 5, 80), ("serve.step", 6, 79),
                ("serve.chunk", 20, 78), ("serve.sync", 30, 50),
                ("serve.dispatch", 20, 25), ("bench.wait", 85, 95)])
    g = _gaps(t)
    assert g["serve.sync"] == pytest.approx(0.020)
    assert g["serve.dispatch"] == pytest.approx(0.005)
    # chunk's self time in the gaps: 25-30, 50-60, 70-78
    assert g["serve.chunk"] == pytest.approx(0.023)
    assert g["serve.step"] == pytest.approx(0.010 + 0.001)  # 10-20, 78-79
    assert g["bench.step"] == pytest.approx(0.001)  # 79-80
    assert g["bench.wait"] == pytest.approx(0.010)
    assert g["host (other)"] == pytest.approx(0.010)  # 80-85, 95-100
    idle = t.summary.window_s - t.summary.busy_s
    assert sum(g.values()) == pytest.approx(idle)


def test_spans_of_equal_start_give_the_instant_to_the_shorter():
    segs = spantrace.innermost([("outer", 0, 10), ("inner", 0, 4)])
    assert segs == [(0, 4, "inner"), (4, 10, "outer")]


def test_the_lag_is_the_smallest_shift_that_orders_every_pair():
    # device programs start 1.0-1.5 ms before their enqueue on the host
    pairs = [(11.0, 10.0), (21.5, 20.0), (31.2, 30.0)]
    t = _trace([("p", 10, 12), ("p", 20, 22), ("p", 30, 31)], [],
               pairs=pairs)
    lag, lo, hi = t.clock_offset()
    assert lag == hi == pytest.approx(1.5 * MS)
    assert lo == pytest.approx(1.0 * MS)
    assert all(start + lag >= launch for launch, start in t.pairs)
    # gaps move onto the host clock by the lag, and keep their length
    assert t.gaps()[1] == pytest.approx((12 * MS + lag, 20 * MS + lag))
    assert _trace([], []).clock_offset() is None


def test_readers_on_synthetic_spans():
    # two steps of one chunk each, and a device clock 1 ms behind
    t = _trace(
        [("p", 0, 9), ("p", 15, 40), ("p", 45, 100)],
        [("serve.step", 8, 14),
         ("serve.chunk", 9, 14, {"wave": 0}),
         ("serve.dispatch", 9, 10, {"program": "_run_jit"}),
         ("serve.harvest", 12, 13, {"n": 3}),
         ("serve.step", 40, 48),
         ("serve.chunk", 40, 48, {"wave": 0}),
         ("serve.dispatch", 40, 43, {"program": "_run_jit"}),
         ("serve.dispatch", 200, 201, {"program": "_run_jit"})],
        pairs=[(1, 0)])
    # gaps on the host clock: 10-16 (4 ms under the first step: 10-14)
    # and 41-46 (5 ms under the second: 41-46)
    assert t.host_idle_ms_per_chunk() == pytest.approx((4 + 5) / 2)
    assert t.harvest_ms_per_chunk() == pytest.approx(1 / 2)
    # the dispatch at 200 ms lies outside the traced interval
    assert t.dispatch_ms_per_call() == pytest.approx((1 + 3) / 2)
    quiet = _trace([("p", 0, 9)], [])
    assert quiet.host_idle_ms_per_chunk() is None
    assert quiet.harvest_ms_per_chunk() is None
    assert quiet.dispatch_ms_per_call() is None


def _load(path, tmp_path):
    out = tmp_path / "run.xplane.pb"
    out.write_bytes(gzip.decompress(path.read_bytes()))
    return spantrace.load(out)


def _reader(name):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_first_trace_keeps_its_readings_and_gains_a_lag(tmp_path):
    """The first recorded trace (30 q/s, no engine spans): the benchmark's
    trace readers read what they read before these spans existed, and
    five programs pair with their enqueues."""
    t = _load(FIRST, tmp_path)
    s = t.summary
    assert s.busy_s == pytest.approx(0.008573433, abs=1e-9)
    assert s.kernel_seconds("gather_norm_dot") == pytest.approx(
        0.000786, abs=1e-9)
    assert s.program_seconds(("_init_jit", "_run_jit")) == pytest.approx(
        0.00857883, abs=1e-9)

    class Reply:
        dc = 1000

    class Ctx:
        trace = s
        traced_replies = [Reply()] * 5
        row_bytes = 512
        peaks = Layout().peaks("TPU v5 lite")

    assert _reader("device.idle_pct.query").read(Ctx) == pytest.approx(
        100 * (1 - 0.008573433 / 0.04))
    assert _reader("hop.device_ms_per_query").read(Ctx) == pytest.approx(
        8.57883 / 5)
    assert _reader("gather.roofline_pct").read(Ctx) == pytest.approx(
        100 * 5 * 1000 * 512 / 819e9 / 0.000786)
    assert len(t.pairs) == 5
    lag, lo, hi = t.clock_offset()
    assert 1.0 * MS < lo <= hi == lag < 2.0 * MS
    assert sum(v for _, v in t.idle_gaps(limit=50)) == pytest.approx(
        s.window_s - s.busy_s)
    assert t.host_idle_ms_per_chunk() is None  # no engine spans yet


def test_serve_trace_attributes_the_idle_time_to_engine_spans(tmp_path):
    """The second recorded trace (400 q/s, the engine's spans): a positive
    lag, every reading present, and the engine's spans below
    ``serve.step`` hold most of the idle time under the benchmark's
    ``bench.step``."""
    assert SERVE.stat().st_size < 1_000_000
    t = _load(SERVE, tmp_path)
    lag, lo, hi = t.clock_offset()
    assert 0 < lag and len(t.pairs) > 5
    assert all(start + lag >= launch for launch, start in t.pairs)
    for name in ("host_idle_ms_per_chunk", "harvest_ms_per_chunk",
                 "dispatch_ms_per_call"):
        assert getattr(t, name)() > 0, name
    g = _gaps(t)
    s = t.summary
    assert sum(g.values()) == pytest.approx(s.window_s - s.busy_s)
    engine = sum(v for n, v in g.items()
                 if n.startswith("serve.") and n != "serve.step")
    assert engine >= 0.9 * (engine + g.get("serve.step", 0)
                            + g.get("bench.step", 0))


def test_a_traced_cpu_run_reports_the_host_span_readings(layout, tmp_path):
    """A traced run on the CPU writes the engine's spans: the host-span
    readings report; the idle one needs a TPU plane and reports nothing."""
    from bench.harness import run_cell

    dev = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
    res = run_cell(layout, "tiny.query-narrow", 12, 2.0, True,
                   time.monotonic(), dev, keep_trace=tmp_path)
    assert res["correct"], res["checks"]
    t = spantrace.load(tmp_path)
    names = {n for n, *_ in t.spans}
    assert {"serve.step", "serve.assemble", "serve.chunk", "serve.dispatch",
            "serve.sync", "serve.harvest"} <= names
    assert t.harvest_ms_per_chunk() > 0
    assert t.dispatch_ms_per_call() > 0
    assert t.host_idle_ms_per_chunk() is None
    assert t.clock_offset() is None
    rep = t.report()
    assert rep["idle_gaps"] and rep["clock_offset_ms"] is None
