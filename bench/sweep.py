"""The knee sweep of an open-loop cell: the same cell at a ladder of offered
rates, one window each, in one process.  The knee is the highest rate at
which no request is shed and none is served after its deadline; the cells
offer fixed shares of it, written into their traffic mixes.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 4000,6000,8000
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    for rate in (float(r) for r in args.rates.split(",")):
        d = run.drive(args.workload, args.seed, args.seconds, False,
                      mix_override={"rate_per_s": rate}, pending=True)
        s, m = d.out.samples, d.out.metrics
        print(json.dumps({
            "offered_per_s": rate, "due": d.out.attempted,
            "served": s["served"], "shed": s["shed"], "late": s["late"],
            "served_per_s": m["guard_served_per_s"],
            "p99_ms": m["admit_p99_ms"],
            "frontend_wait_p99_ms": 1e3 * common.nearest_rank(
                s["frontend_wait_s"], 0.99),
            "admit_call_ms": 1e3 * common.median(s["admit_call_s"]),
            "notes": d.out.notes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
