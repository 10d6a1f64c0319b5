#!/usr/bin/env python3
"""Several runs of one cell in one call, one new process each, as the
driver makes them:

    python3 benchmarks/runs.py --workload <name> --seeds 1,2,3 \\
        --seconds <s> --trace <0|1> --out chiprun_out/<file>.jsonl

Each run's result line (or its exit code and the end of its standard
error) is appended to ``--out`` and summarised on standard output. Extra
arguments after ``--`` go to ``run.py`` (``--control int8``).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", required=True)
    parser.add_argument("--trace", default="0")
    parser.add_argument("--out", required=True)
    args, extra = parser.parse_known_args()
    extra = [a for a in extra if a != "--"]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    worst = 0
    for seed in args.seeds.split(","):
        t0 = time.time()
        r = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload",
             args.workload, "--seed", seed, "--seconds", args.seconds,
             "--trace", args.trace] + extra, text=True, capture_output=True)
        wall = time.time() - t0
        row = {"workload": args.workload, "seed": int(seed),
               "trace": int(args.trace), "rc": r.returncode, "wall_s": wall}
        lines = r.stdout.strip().splitlines()
        try:
            row["line"] = json.loads(lines[-1])
        except (IndexError, ValueError):
            row["stdout_tail"] = r.stdout[-2000:]
        row["stderr_end"] = r.stderr[-1500:]
        if r.returncode != 0 or not row.get("line", {}).get("correct"):
            row["stderr_tail"] = r.stderr[-8000:]
            worst = worst or r.returncode or 1
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        line = row.get("line", {})
        print(json.dumps({
            "seed": row["seed"], "rc": row["rc"], "wall_s": round(wall, 1),
            "correct": line.get("correct"), "faults": line.get("faults"),
            "attempted": line.get("attempted"), "failed": line.get("failed"),
            "metrics": {k: v["value"] for k, v in
                        line.get("metrics", {}).items()},
            "compared": {k: v["value"] for k, v in
                         line.get("compared", {}).items()},
            "device": line.get("device"),
            "setup_phases": [[n, round(t, 1)] for n, t in
                             line.get("setup_phases", [])],
            "ended": [x for x in r.stderr.splitlines()
                      if "process(es) of the session" in x
                      or "outlived" in x]}), flush=True)
        if "stderr_tail" in row:
            print(row["stderr_tail"][-3000:], flush=True)
    return worst


if __name__ == "__main__":
    sys.exit(main())
