#!/usr/bin/env python3
"""Entry of the benchmark: one run of one cell (see benchmark/harness/run.py).

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness.run import main  # noqa: E402

if __name__ == "__main__":
    rc = main(sys.argv[1:])
    sys.stdout.flush()
    sys.stderr.flush()
    # Teardown is done; a thread of the served program that will not end must
    # not hold the exit past the run's time limit.
    os._exit(rc)
