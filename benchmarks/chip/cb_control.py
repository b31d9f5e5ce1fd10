"""The control of ``correct``: the plain reference put in the program's
place, with every float sum accumulated in float32 (``cb_reference``,
``accumulate="float32"``), judged by the run's own check
(``cb_harness.check_answers`` and ``verdict``).  It has to come out not
correct.

    python3 benchmarks/chip/cb_control.py --workload <cell> --seeds <n> [<n> ...]

It runs at the cell's own size: the configuration's tables and the traffic's
requests from each seed, as many as a window answers (an open loop's whole
window; a closed loop's sample), and the same seeded sample of them as a run
compares.  It prints one JSON line per seed with the numbers compared beside
their limits and the verdict, and exits 0 when every seed is not correct.
It needs no chip: it is numpy on the host.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cb_harness  # noqa: E402
import cb_reference  # noqa: E402
import cb_traffic  # noqa: E402


def control_check(cell: dict, seed: int) -> tuple:
    """``(checks, answers compared)`` of the control for one seed."""
    config, traffic = cell["config"], cell["traffic"]
    query, check = traffic["query"], traffic["check"]
    tables = cb_harness.load_module("data", config["generator"]).make(config, seed)
    if traffic["loop"] == "open":
        n = cb_traffic.request_count(traffic, cell["run_seconds"])
    else:
        n = int(check["sample"])
    done = [{"request": r} for r in cb_traffic.build_requests(traffic, config, seed, n)]

    def control_answer(r):
        return cb_reference.answer(tables, r["request"], query, accumulate="float32")

    return cb_harness.check_answers(tables, query, check, done, [], seed, answer_of=control_answer)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="the float32-accumulation control of a cell's correctness check")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = cb_harness.load_cell(args.workload)
    all_fail = True
    for seed in args.seeds:
        checks, compared = control_check(cell, seed)
        correct = cb_harness.verdict(checks, compared)
        all_fail &= not correct
        line = {"workload": args.workload, "seed": seed, "compared": compared, "checks": checks, "correct": correct}
        print(json.dumps(line), flush=True)
    return 0 if all_fail else 1


if __name__ == "__main__":
    sys.exit(main())
