"""The reduction from a device trace to busy time, idle share, kernel
time and roofline share: on synthetic events, and on a trace recorded on
a TPU v5e (``bench/testdata/``: a traced run of sift128-f32.query-mixed
at 30 q/s, cut to the 40 ms that start 0.2 s into its window, with the
``bench.window`` span set to bracket the cut)."""
import importlib.util

import pytest

from bench import devtrace
from bench.spec import BENCH, Layout

TRACE = BENCH / "testdata" / "sift128-f32.query-mixed.xplane.pb.gz"
MS = 1_000_000


def _summary(ops, spans, modules=()):
    window = [("bench.window", 0, 100 * MS)]
    return devtrace.Summary(ops=[(n, s * MS, e * MS, 0) for n, s, e in ops],
                            modules=[(n, s * MS, e * MS, 0) for n, s, e in modules],
                            spans=window + [(n, s * MS, e * MS) for n, s, e in spans],
                            devices=1)


def test_busy_is_the_union_clipped_to_the_window():
    s = _summary([("a", -5, 10), ("b", 5, 20), ("c", 30, 40),
                  ("gather_norm_dot.3", 32, 35), ("d", 95, 120)], [])
    assert s.busy(0) == [(0, 20 * MS), (30 * MS, 40 * MS), (95 * MS, 100 * MS)]
    assert s.busy_s == pytest.approx(0.035)
    assert s.window_s == pytest.approx(0.1)
    assert s.kernel_seconds("gather_norm_dot") == pytest.approx(0.003)
    assert s.kernel_seconds("gather") == 0


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    s = _summary([("a", 0, 20), ("b", 30, 40)],
                 [("bench.step", 15, 35), ("bench.wait", 50, 90)])
    gaps = dict(map(tuple, s.idle_gaps()))
    assert gaps["bench.step"] == pytest.approx(0.010)
    assert gaps["bench.wait"] == pytest.approx(0.040)
    assert gaps["host (other)"] == pytest.approx(0.010 + 0.010)


def test_op_names_are_hlo_instruction_names():
    assert devtrace.op_name("%gather_norm_dot.9 = (f32[16,24]) custom-call("
                            "s32[16,17] %fusion.2)") == "gather_norm_dot.9"
    assert devtrace.op_name("jit__run_jit(123)") == "jit__run_jit(123)"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import gzip

    out = tmp_path_factory.mktemp("trace") / "run.xplane.pb"
    out.write_bytes(gzip.decompress(TRACE.read_bytes()))
    return devtrace.load(out.parent)


def test_recorded_trace_has_device_ops_and_spans(recorded):
    assert TRACE.stat().st_size < 1_000_000
    assert recorded.devices == 1
    assert 0 < recorded.busy_s < recorded.window_s
    assert recorded.kernel_seconds("gather_norm_dot") > 0
    assert recorded.program_seconds(("_run_jit", "_init_jit")) > 0
    names = {n for n, *_ in recorded.spans}
    assert {"bench.window", "bench.step"} <= names
    b = recorded.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10


def test_roofline_arithmetic_against_the_peaks_table(recorded):
    path = BENCH / "metrics" / "gather.roofline_pct.py"
    spec = importlib.util.spec_from_file_location("roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    class Reply:
        dc = 1000

    class Ctx:
        trace = recorded
        traced_replies = [Reply()] * 50
        row_bytes = 512
        peaks = Layout().peaks("TPU v5 lite")

    got = mod.read(Ctx)
    secs = recorded.kernel_seconds("gather_norm_dot")
    assert got == pytest.approx(100 * 50 * 1000 * 512 / 819e9 / secs)
