"""Fixtures of the harness's CPU tests: a layout holding the benchmark's
own files plus the test-only ones under ``data/``, and a cache of its own."""
import os
import shutil
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH.parent / "src"))

os.environ.setdefault("OMP_NUM_THREADS", "1")


def make_layout(root: Path, cache: Path):
    from bench.spec import Layout

    for kind in ("configs", "traffic", "workloads", "metrics"):
        shutil.copytree(BENCH / kind, root / kind,
                        ignore=shutil.ignore_patterns("__pycache__"))
        extra = HERE / "data" / kind
        if extra.is_dir():
            shutil.copytree(extra, root / kind, dirs_exist_ok=True)
    shutil.copy(BENCH / "peaks.json", root / "peaks.json")
    return Layout(root=root, cache=cache)


@pytest.fixture(scope="module")
def layout(tmp_path_factory):
    base = tmp_path_factory.mktemp("bench")
    return make_layout(base / "root", base / "cache")
