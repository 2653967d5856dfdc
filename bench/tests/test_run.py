"""The whole run on the CPU, at a small size: a run without a TPU prints
no result, and a cell of the tests' own files runs end to end."""
import os
import subprocess
import sys
import time

import pytest

from bench.spec import BENCH


def test_no_tpu_exits_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "sift128-f32.query-mixed", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=BENCH.parent)
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "no TPU" in res.stderr


def _run(layout, cell, trace=False, seconds=2.0, seed=11):
    from bench.harness import run_cell

    dev = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
    return run_cell(layout, cell, seed, seconds, trace, time.monotonic(), dev)


def test_a_query_cell_runs_end_to_end(layout):
    res = _run(layout, "tiny.query-narrow")
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"query_p95_ms", "query_qps",
                                   "recall_at_10", "setup_s"}
    assert res["attempted"] == 400 and res["failed"] == 0
    assert res["metrics"]["recall_at_10"]["value"] > 0.9
    assert res["checks"]["recall_loss"]["value"] == pytest.approx(
        1 - res["metrics"]["recall_at_10"]["value"])
    assert not (layout.cache / "run").exists()


def test_a_traced_run_reports_per_layer_metrics(layout):
    res = _run(layout, "tiny.query-narrow", trace=True)
    assert res["correct"], res["checks"]
    # a CPU trace holds no TPU plane: the device readers find nothing to
    # read and leave their metrics out; the counters are there
    assert "engine.reqs_per_wave" in res["metrics"]
    assert "hop.dc_per_query" in res["metrics"]
    assert "gather.roofline_pct" not in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}



INGEST_METRICS = {"ingest.ack_ms_per_batch", "ingest.apply_ms_per_batch",
                  "snapshot.refresh_ms_per_refresh",
                  "snapshot.upload_mb_per_refresh"}


@pytest.mark.parametrize("trace", [False, True])
def test_an_ingest_cell_runs_end_to_end(layout, trace):
    res = _run(layout, "tiny.ingest-append", trace=trace)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    # 200 queries and 8 batches of 8 rows, each acked, applied and read back
    assert res["attempted"] == 208 and res["failed"] == 0
    for name in ("lost_acked", "unread_acked", "unsynced_acked"):
        assert res["checks"][name] == {"value": 0, "limit": 0}
    probes = res["checks"]["append_recall_loss"]
    assert probes["value"] <= probes["limit"]
    if trace:
        assert INGEST_METRICS <= set(res["metrics"])
        m = res["metrics"]
        # each refresh uploads at least the padded f32 slab: 2^11 rows of 16
        assert m["snapshot.upload_mb_per_refresh"]["value"] >= 2048 * 16 * 4 / 1e6
    else:
        assert set(res["metrics"]) == {"ingest_visible_p95_ms", "query_p95_ms",
                                       "query_qps", "recall_at_10", "setup_s"}
        assert res["metrics"]["ingest_visible_p95_ms"]["value"] > 0
    assert not (layout.cache / "run").exists()
