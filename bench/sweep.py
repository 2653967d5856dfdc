#!/usr/bin/env python3
"""Find a cell's knee: the highest offered rate it sustains.

    python3 bench/sweep.py --workload <cell> --rates 100,200,300 --seconds 15
    python3 bench/sweep.py --workload <cell> --ingest-rates 1,2,4 --seconds 30

Runs the cell once per rate in one process (so the compile cache is warm
after the first), seed 1, and prints per rate the offered and answered
rates, latency p50/p95/p99 from the due time, and how long the drain
took after the close.  ``--ingest-rates`` varies the ingest batches per
second of a cell that appends, at the cell's own query rate; its
``ingest:`` lines on standard error give each batch's lag from due to
applied.  A rate is sustained while the answered rate keeps up with the
offered one and the drain stays short.  The cell file then takes a fixed
share of the knee; the benchmark never searches.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT / "src"), str(CHECKOUT)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    rates = ap.add_mutually_exclusive_group(required=True)
    rates.add_argument("--rates", help="comma-separated q/s")
    rates.add_argument("--ingest-rates", help="comma-separated batches/s")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    from bench import chip
    from bench.harness import run_cell
    from bench.spec import Layout

    device = chip.find()
    if device is None:
        return 2
    layout = Layout()
    chip.use_compile_cache(layout)
    key = "ingest_rate" if args.ingest_rates else "rate"
    for rate in (float(r) for r in (args.rates or args.ingest_rates).split(",")):
        t0 = time.monotonic()
        res = run_cell(layout, args.workload, args.seed, args.seconds, False,
                       t0, device, **{key: rate})
        m = {k: v["value"] for k, v in res["metrics"].items()}
        print(json.dumps({key: rate, "correct": res["correct"],
                          "failed": res["failed"], **m}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
