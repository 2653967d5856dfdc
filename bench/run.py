#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's files are found by name under ``bench/`` (see ``spec.py``).  A
run cold-starts the cell's index from its cached build (building it first
when the cache is missing), warms up, offers the cell's open-loop traffic
for ``--seconds``, checks the answers against the plain reference, and
prints one JSON object as the last line of standard output: the
end-to-end metrics with ``--trace 0``, the per-layer ones with
``--trace 1``.  The compared numbers, each beside its limit, are the last
lines of standard error.

Exits non-zero with no result line when JAX finds no TPU, or fewer chips
than the cell asks for.  JAX's persistent compilation cache lives in
``bench/.cache/jax`` of this checkout.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT / "src"), str(CHECKOUT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import chip
    from bench.harness import run_cell
    from bench.spec import Layout

    layout = Layout()
    cell = layout.workload(args.workload)
    device = chip.find(cell["chips"])
    if device is None:
        return 2
    chip.use_compile_cache(layout)
    result = run_cell(layout, args.workload, args.seed, args.seconds,
                      bool(args.trace), T_START, device)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
