"""Cells, mixes and metric readers are found by name from files, and
agree with BENCHMARK.json."""
import json

import pytest

from bench.spec import BENCH, Cell, Layout

BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def _metrics_of(cell: str, kind: str) -> set:
    return {m["name"] for m in BENCHMARK[kind]
            if cell in m.get("workloads", CELLS)}


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_match_benchmark(name):
    entry = next(w for w in BENCHMARK["workloads"] if w["name"] == name)
    cell = Cell.load(Layout(), name)
    assert cell.spec["config"] == entry["config"]
    assert cell.spec["traffic"] == entry["traffic"]
    assert cell.spec["chips"] == entry["chips"]
    assert cell.rate > 0
    for kind in ("end_to_end", "per_layer"):
        assert set(cell.spec["metrics"][kind]) == _metrics_of(name, kind)
    units = {m["name"]: m["unit"]
             for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]}
    for kind in ("end_to_end", "per_layer"):
        for metric in cell.spec["metrics"][kind]:
            assert Layout().reader(metric).UNIT == units[metric]


def test_configs_name_their_files():
    for cfg in BENCHMARK["configs"]:
        data = json.loads((BENCH.parent / cfg["file"]).read_text())
        assert data["name"] == cfg["name"]
        assert data["source"] == cfg["source"]
        assert data["reduced"] == cfg["reduced"]


def test_a_mix_used_only_by_the_tests_is_found_by_name(layout):
    cell = Cell.load(layout, "tiny.query-narrow")
    assert cell.traffic["queries"]["fractions_log2"] == [-6, -3]
    with pytest.raises(FileNotFoundError):
        Layout().traffic("tiny-narrow")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        Layout().peaks("TPU v99")
    v5e = Layout().peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
