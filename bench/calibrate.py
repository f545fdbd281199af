#!/usr/bin/env python3
"""Run one cell on several seeds, one process after another, and sum up
the spread of each metric and the numbers ``correct`` compared.

    python bench/calibrate.py --workload reloc_ycsb_zipf --seeds 11 12 13 \\
        --seconds 10 [--trace 1] [--control 1] [--out runs.jsonl]

Each run is ``bench/run_cell.py`` in a fresh process, as the check runs
it, so each pays its own set-up and reads the compile cache the first
left behind.  The summary gives, per metric, the median and the spread:
the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of the median.  The
benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def spread(values: list) -> float | None:
    if len(values) < 2:
        return None
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    runs = []
    for seed in args.seeds:
        cmd = [sys.executable, str(ROOT / "bench" / "run_cell.py"),
               "--workload", args.workload, "--seed", str(seed),
               "--seconds", str(args.seconds), "--trace", str(args.trace),
               "--control", str(args.control)]
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        lines = [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
        info = json.loads(lines[0]) if len(lines) > 1 else {}
        result = json.loads(lines[-1]) if p.returncode == 0 and lines else None
        run = {"seed": seed, "rc": p.returncode, "info": info,
               "result": result}
        if result is None:
            run["stderr"] = p.stderr[-4000:]
        runs.append(run)
        print(json.dumps(run), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(run) + "\n")
    ok = [r["result"] for r in runs if r["result"]]
    summary = {"workload": args.workload, "runs": len(runs),
               "ok": len(ok), "correct": sum(r["correct"] for r in ok),
               "metrics": {}, "checks": {}, "program_checks": {}}
    # with --control 1, "checks" holds the control's numbers and
    # "program_checks" the program's
    for key in ("metrics", "checks", "program_checks"):
        names = sorted({n for r in ok for n in (r.get(key) or {})})
        for n in names:
            vals = [r[key][n]["value"] for r in ok
                    if n in (r.get(key) or {})]
            summary[key][n] = {"median": statistics.median(vals),
                               "min": min(vals), "max": max(vals),
                               "spread": spread(vals), "n": len(vals)}
    print(json.dumps({"summary": summary}), flush=True)
    return 0 if len(ok) == len(runs) else 1


if __name__ == "__main__":
    sys.exit(main())
