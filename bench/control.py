#!/usr/bin/env python3
"""Readings that the limit of a cell's check is set from.

    python3 bench/control.py --workload NAME --seconds S --seeds 1,2,... \\
        [--control-seeds 1,2,3] [--out FILE]

For each seed, one whole run of the cell (``harness.run_cell``: set-up,
window at the cell's own load, check) in this one process, and its
program reading: the widest gap, per reference logit std, of a served token
below the float32 reference's best. For a control seed the same run also
goes through the control: the harness's own check and verdict, with the
token judged at each position the one that the reference computed in
float8 (``reference/<family>.py``, ``quant="fp8"``) puts first. The control
has to come out not correct. The lower reading of a limit is the largest
program reading, the upper one the smallest control reading. One JSON line
per seed (also appended to ``--out``), and a summary as the last line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    ap.add_argument("--cpu", action="store_true", help="allow a CPU run (tests)")
    args = ap.parse_args()
    seeds = [int(x) for x in args.seeds.split(",") if x]
    ctrl = {int(x) for x in args.control_seeds.split(",") if x}
    rows = []
    for seed in seeds:
        res = harness.run_cell(args.workload, seed, args.seconds, False, t0=time.perf_counter(),
                               allow_cpu=args.cpu, control=seed in ctrl)
        prog = res.get("program_checks", res["checks"])
        row = {"workload": args.workload, "seed": seed, "device": res["device"]["kind"],
               "program": prog["served_gap_per_std"]["value"],
               "program_correct": harness.passes(prog),
               "tokens": prog["tokens_checked"]["value"], "failed": res["failed"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
        if seed in ctrl:
            row["control"] = res["checks"]["served_gap_per_std"]["value"]
            row["control_correct"] = res["correct"]
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    prog = [r["program"] for r in rows]
    ctl = [r["control"] for r in rows if "control" in r]
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "lower": max(prog), "program_readings": prog,
                      "upper": min(ctl) if ctl else None, "control_readings": ctl,
                      "control_ever_correct": any(r.get("control_correct") for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
