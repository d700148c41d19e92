"""Run a cell with the control or a fault planted, at the cell's own size.

    python bench/control.py --workload <cell> --plant control --seeds 1 2 3 \\
        --seconds 25

Each seed is one run of the cell in this process (set-up is paid once per
program), with the planted program in the timed path; it prints each run's
compared numbers and ``correct``. The control and every fault must come out
not correct. The benchmark's own runs never plant anything.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness import faults, runner  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--plant", required=True,
                   choices=("none", "control") + faults.FAULTS)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    bad = 0
    for seed in args.seeds:
        if args.plant == "none":
            res = runner.run_cell(args.workload, seed, args.seconds, False)
        else:
            with faults.plant(args.plant):
                res = runner.run_cell(args.workload, seed, args.seconds, False)
        bad += int(res["correct"] != (args.plant == "none"))
        print(json.dumps({"plant": args.plant, "seed": seed,
                          "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
