"""Run one benchmark cell once and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. The run warms
up the cell's programs (set-up), measures for ``--seconds``, checks what the
timed path produced against the plain reference, and prints one JSON object
as the last line of standard output. With ``--trace 0`` its metrics are the
cell's end-to-end metrics; with ``--trace 1`` the run is a traced run of its
own and its metrics are the cell's per-layer metrics. The numbers compared
for ``correct`` come last, on standard error and under ``checks``.

It exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for. ``--rehearse`` runs a small copy of the cell on the
CPU (16 lanes, a small budget) to try the harness; it prints no metrics.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness import runner  # noqa: E402

T_PROCESS = runner.process_start_time()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="small copy of the cell on the CPU; no metrics")
    args = p.parse_args(argv)
    try:
        if args.rehearse:
            result = runner.rehearse(args.workload, args.seed, args.seconds,
                                     bool(args.trace))
        else:
            result = runner.run_cell(args.workload, args.seed, args.seconds,
                                     bool(args.trace), t_process=T_PROCESS)
    except runner.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
