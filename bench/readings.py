"""Readings of the numbers that decide ``correct``, over many seeds in one
process: the program's, and the control's (the reference computed with its
projections rounded as a ``Precision.HIGH`` matmul rounds them).  A limit is
set between the largest reading of sound runs and the smallest of the
control, as ``PERF.md`` records.

    python3 bench/readings.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3 [--control-seeds 1,2,3]

One JSON line per seed on standard output.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import common, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        d = run.drive(args.workload, seed, args.seconds, False, pending=True)
        t = common.clock()
        program = d.out.check()
        ref_s = common.clock() - t
        line = {"workload": args.workload, "seed": seed,
                "program": program, "reference_s": ref_s,
                "control": d.out.check(control=True) if seed in control
                else None,
                "setup_s": d.run.setup_s, "metrics": d.out.metrics,
                "attempted": d.out.attempted, "failed": d.out.failed,
                "notes": d.out.notes}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
