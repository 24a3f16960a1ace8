"""The readings a cell's limit is set from: the program's number on many
seeds and the control's on some of them, in one process on the chip.

    python3 chipbench/tools/limits.py --workload <cell> --seeds 101,102,... \
        --control 3 --seconds 8

Each seed is a whole run of the cell (set-up, a window at the cell's own
load, the check); on the first ``--control`` seeds the float8 control
takes the program's place in the check, which then grades the control:
``correct`` has to come out false. One JSON line per seed, with the
program's reading and the control's, then a summary: the largest program
reading, the smallest control reading, and whether every control run
came out not correct.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        run, result, _ = bench_run.measure(
            args.workload, seed, args.seconds, False, control=i < args.control, t_start=t0
        )
        res = run.check_result
        prog = next(k for k in res if k.startswith("max_"))
        row = {"seed": seed, "control_run": i < args.control, "correct": result["correct"],
               "program": res[prog],
               "control": res.get("control_" + prog), "metrics": result["metrics"],
               "limit": result["compared"][prog]["limit"],
               "memory_peak_bytes": result["device"]["memory_peak_bytes"]}
        rows.append(row)
        print("LIMITS " + json.dumps(row), flush=True)
    controls = [r["control"] for r in rows if r["control"] is not None]
    print("LIMITS_SUMMARY " + json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "program_max": max(r["program"] for r in rows),
        "control_min": min(controls) if controls else None,
        "controls_not_correct": all(not r["correct"] for r in rows if r["control_run"]),
        "programs_within_limit": all(r["program"] <= r["limit"] for r in rows),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
