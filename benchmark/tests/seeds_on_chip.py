#!/usr/bin/env python3
"""Read many seeds of one cell in one process on the chip: the program's
comparison on each, and with ``--control`` the control's beside it (the
reference with one probe fewer in the program's place).  A chip belongs
to one process, so this saves a process start per seed; the benchmark's
own runs (``run.py``) never run the control.

    python3 benchmark/tests/seeds_on_chip.py --workload bulk-contains \
        --seconds 10 --control --seeds 1 2 3
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", action="store_true")
    a = ap.parse_args()
    for seed in a.seeds:
        args = argparse.Namespace(workload=a.workload, seed=seed,
                                  seconds=a.seconds, trace=a.trace)
        out = run.run(args, control=a.control)
        print(json.dumps({"seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.stdout.flush()
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
