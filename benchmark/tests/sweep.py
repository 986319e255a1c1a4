#!/usr/bin/env python3
"""Many seeds of one cell behind one set-up: the readings a limit is set from.

    python3 benchmark/tests/sweep.py --workload put64m-c8 --seeds 1,2,3 --seconds 6
    python3 benchmark/tests/sweep.py --workload put64m-c8 --seeds 4,5,6 --seconds 6 --control parity-1

Set-up on the chip is most of a run, so the dozen seeds of the output check and
the control's three are read in one process each: one server, then for every
seed new clients, ramp, a short window at the cell's own load, drain and the
whole check. One JSON line per seed: the numbers compared beside their limits,
and the window's end-to-end numbers for the reader's eye (not a measurement:
`setup_s` is left out, and a window shorter than `run_seconds` is noisier).
Not run by the benchmark's own runs or by the driver.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import check, run  # noqa: E402
from benchmark.harness.server import Deployment  # noqa: E402


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", choices=run.CONTROLS, default=None)
    args = ap.parse_args(argv)
    cell = run.Cell(args.workload, rehearse=args.rehearse)
    parity = cell.config["parity"] - 1 if args.control == "parity-1" else None
    dep = Deployment(cell.config, rehearse=args.rehearse, parity=parity)
    bad = 0
    try:
        dep.start(cell.footprint_bytes(), cell.traffic.get("mallopt"))
        run.make_bucket(dep)
        for seed in (int(s) for s in args.seeds.split(",")):
            dep.wipe_objects()  # every overwrite leaves its old data directory behind
            clients = run.Clients(cell, seed, dep)
            out = run.run_window(cell, dep, clients, seed, args.seconds, False, run.T_IMPORT)
            correct, compared = check.decide(out["numbers"], check.limits(bool(cell.replaced)))
            # A CPU number is never written under the name of a device metric.
            e2e = {} if args.rehearse else {k: v for k, v in out["e2e"].items() if k != "setup_s"}
            print(json.dumps({"workload": args.workload, "seed": seed, "control": args.control,
                              "correct": correct, "attempted": out["attempted"],
                              "compiles_in_window": out["compiles_in_window"],
                              "window": e2e, "compared": compared}), flush=True)
            bad += (not correct) if args.control is None else bool(correct)
    finally:
        dep.close()
    return 1 if bad else 0


if __name__ == "__main__":
    rc = main(sys.argv[1:])
    sys.stdout.flush()
    os._exit(rc)
