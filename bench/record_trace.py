#!/usr/bin/env python3
"""Record the small device trace that the CPU tests of the trace
reduction read (``bench/testdata/``): a traced run of a cell with a short
window at a low rate.

    python3 bench/record_trace.py --workload <cell> --rate 300 --seconds 0.05 --out <file>

Then ``gzip`` the file into ``bench/testdata/<cell>.xplane.pb.gz``.
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
    ap.add_argument("--rate", type=float, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from bench import chip
    from bench.harness import run_cell
    from bench.spec import Layout

    device = chip.find()
    if device is None:
        return 2
    layout = Layout()
    chip.use_compile_cache(layout)
    res = run_cell(layout, args.workload, 1, args.seconds, True, T_START,
                   device, rate=args.rate, keep_trace=Path(args.out))
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
