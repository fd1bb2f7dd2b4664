"""Readings for the limits of ``correct``, many seeds in one process.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --precision bfloat16 --seeds 11 12 13

Runs the cell as ``run.py`` does, once per seed, with the system serving
at ``--precision``: ``bfloat16`` switches on the program's own bf16
weight path, the control, which has to come out as not correct;
``float32`` (the configurations' precision) gives the program's own
readings.  Prints each run's compared numbers and, last, one JSON line
with every reading.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as R  # noqa: E402
from lib.cells import load_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--precision", choices=("float32", "bfloat16"),
                    required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    readings = []
    for seed in args.seeds:
        run_args = R.parse(["--workload", args.workload, "--seed", str(seed),
                            "--seconds", str(args.seconds)])
        res = R.run(run_args, cell, precision=args.precision)
        if res is None:
            return 2
        readings.append({"seed": seed, "correct": res["correct"],
                         "checks": {k: v["value"]
                                    for k, v in res["checks"].items()}})
        print(json.dumps(readings[-1]), flush=True)
    print(json.dumps({"workload": args.workload,
                      "precision": args.precision, "readings": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
