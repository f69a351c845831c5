"""The control: the reference in bfloat16, put in the program's place.

    python benchmark/control.py --workload <cell> --seconds 2 --seeds 1 2 3

Each seed is one whole run of the cell (set-up, a short window at the
cell's own load and size, the check), with every job computed by the plain
reference at bfloat16, the precision below the configuration's float32.
It has to come out not correct on every seed: that is the comparison shown
to fail.  The benchmark's own runs never run it.  Prints one line per seed
and exits 0 only when every seed failed the check.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import reference, run  # noqa: E402

PRECISION = "bfloat16"


def control_job(cell: run.Cell):
    taps = reference.taps_of(cell.config)
    iters = int(cell.config["iters_per_job"])
    return lambda x: reference.device_run(x, taps, iters, PRECISION)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.cell_of(run.load_spec(), args.workload)
    failed = 0
    for seed in args.seeds:
        try:
            res = run.run_cell(cell, seed, args.seconds, False,
                               job=control_job(cell))
        except run.NoAccelerator as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        failed += not res["correct"]
        print(json.dumps({"control": PRECISION, "workload": cell.name,
                          "seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"]}), flush=True)
    return 0 if failed == len(args.seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
