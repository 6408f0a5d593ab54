"""One run of one benchmark cell on the chips of this machine.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Exits non-zero, printing no result
line, when JAX finds no TPU, fewer chips than the cell asks for, or a
device kind missing from chipbench/peaks.json.  See chipbench/harness.py
for the result line and PERF.md for the cells.
"""
import time

T_START = time.perf_counter()       # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
