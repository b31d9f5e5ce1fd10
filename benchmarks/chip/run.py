"""DACP chip benchmark, one cell of BENCHMARK.json per run:

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell asks
for.  The last line of standard output is the result's JSON object; a run
exits with code 3 and prints no result when jax finds no TPU, fewer chips
than the cell needs, or a device kind that ``peaks.json`` does not list.
"""

import time

T0 = time.perf_counter()  # process start, for setup_s

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cb_harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(cb_harness.main(t0=T0))
