#!/usr/bin/env python3
"""Many runs of one cell in one process: the readings a correctness limit
is set from, and the rate sweep that places a latency cell below its knee.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 11,12,13 [--rates 1.5,2,2.5] [--control | --fault <name>] \\
        --seconds 10

Each (rate, seed) is a whole run of the cell (``run.execute``) at its own
load and sample size; compiled programs carry over from one run to the
next, so set-up is paid once.  With ``--control`` the fp8 control's picks
are judged in place of the served tokens, and with ``--fault`` one of
``faults.py``'s faults is planted in the timed path: either has to come out
not correct.  One JSON line per run: ``correct``, the gap judged and the
served tokens' own, the end-to-end metrics and the loop's diagnostics.
"""
from __future__ import annotations

import argparse
import json
import sys

import faults
import run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=sorted(faults.ALL), default=None)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    cell = run.lookup(args.workload)
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    for rate in rates:
        for seed in (int(s) for s in args.seeds.split(",")):
            res = run.execute(cell, seed, args.seconds, False, rate=rate,
                              control=args.control,
                              fault=faults.ALL.get(args.fault))
            print(json.dumps({
                "seed": seed, "rate": rate, "control": args.control,
                "fault": args.fault, "correct": res["correct"],
                "gap": res["checks"]["max_logit_gap"]["value"],
                "program_gap": res["info"]["program_max_logit_gap"],
                "failed": res["failed"],
                "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                "info": res["info"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
