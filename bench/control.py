#!/usr/bin/env python3
"""Read the compared numbers of the program and of its controls, seed by
seed, at a cell's own size and load: the readings that the limits in the
configuration files are set from.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 51
    python3 bench/control.py --workload <cell> --seeds 1,2,3 --max-hops 16
    python3 bench/control.py --workload <cell> --seeds 1,2,3 --fault no-edges

The controls are the plain reference put in the program's place, one step
of precision below what the configuration states (its
``reference.controls``: ``Precision.HIGH`` for float32 at HIGHEST; int4
rows, and int8 rows at ``HIGH``, for int8 at HIGHEST).  ``--max-hops``
plants a fault in the program instead: a hop loop that stops after that
many hops; ``--fault`` plants one of ``faults.py``'s, in an ingest cell,
once the corpus is built.  Prints one JSON line per seed, then the largest reading of
the program and the smallest of each control for each number.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT / "src"), str(CHECKOUT)]

from bench import faults  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--max-hops", type=int, default=None,
                    help="plant a hop loop that stops after this many hops")
    ap.add_argument("--fault", choices=faults.FAULTS, default=None,
                    help="plant this fault of the ingest path")
    args = ap.parse_args()

    from bench import chip, corpus, harness
    from bench.spec import Cell, Layout

    device = chip.find()
    if device is None:
        return 2
    layout = Layout()
    chip.use_compile_cache(layout)
    if args.max_hops is not None:
        sound = harness.engine_config
        harness.engine_config = lambda cfg: dataclasses.replace(
            sound(cfg), max_hops=args.max_hops)
    if args.fault is not None:
        corpus.cached_build(Cell.load(layout, args.workload).config,
                            layout.cache)
        faults.plant(args.fault, setattr)
    planted = args.max_hops is not None or args.fault is not None
    prog: dict = {}
    ctrl: dict = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(layout, args.workload, seed, args.seconds,
                               False, time.monotonic(), device,
                               control=not planted)
        got = {k: v["value"] for k, v in res["checks"].items()}
        metrics = {k: v["value"] for k, v in res["metrics"].items()}
        print(json.dumps({"seed": seed, "max_hops": args.max_hops,
                          "fault": args.fault,
                          "correct": res["correct"], "program": got,
                          "control": res.get("control"),
                          "metrics": metrics}), flush=True)
        for k, v in got.items():
            prog[k] = max(prog.get(k, v), v)
        for name, nums in res.get("control", {}).items():
            low = ctrl.setdefault(name, {})
            for k, v in nums.items():
                low[k] = min(low.get(k, v), v)
    print(json.dumps({"max_hops": args.max_hops, "fault": args.fault,
                      "program_max": prog,
                      "control_min": ctrl}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
