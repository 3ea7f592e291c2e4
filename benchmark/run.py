"""The benchmark's command: one run of one cell.

    python3 benchmark/run.py --workload NAME --seed N --seconds S --trace 0|1

Prints the devices first and exits non-zero unless jax found the TPU chips
the cell asks for. The last line of standard output is the result object.
"""

import time

T_START = time.time()  # the set-up clock starts with the process

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from benchmark import harness

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return harness.run(ap.parse_args(argv), T_START)


if __name__ == "__main__":
    sys.exit(main())
