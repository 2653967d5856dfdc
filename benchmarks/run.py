"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; detailed CSVs land in
benchmarks/results/.  Scale with REPRO_BENCH_N (default 3000).
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from . import bench_ablations, bench_build, bench_dc, bench_device, bench_query


def main() -> None:
    from repro.launch.device import use_compile_cache

    use_compile_cache()
    t0 = time.time()
    print("name,us_per_call,derived")
    for mod, tag in [
        (bench_build, "build (Table 4/6, §3.6)"),
        (bench_query, "query QPS-recall (Fig. 4)"),
        (bench_dc, "DC vs oracle (Fig. 5)"),
        (bench_ablations, "ablations (Tbl 5, Figs 7/8/10/11/12)"),
        (bench_device, "device serving path (ours)"),
    ]:
        print(f"# --- {tag} ---", flush=True)
        mod.run()
    print(f"# total {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
