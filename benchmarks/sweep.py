#!/usr/bin/env python3
"""Find an open-loop cell's knee: one replica, set up once, offered the
cell's own mix at each of a few rates in turn.

    python3 benchmarks/sweep.py --workload <name> --seed <n> \\
        --seconds <s> --rates 4,6,8,10 --out chiprun_out/<file>.jsonl

One line for each rate: what was offered and answered, the waits from
each request's due time, and how far the waits of the window's last
fifth lie above those of its first (a queue that grows all through the
window is past the knee). The cell's file then takes 4/5 of the highest
rate that held. This is a tool for the PR that sets a rate, not a part
of a run.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import loader, run as run_mod, traffic      # noqa: E402


def summarise(rate: float, seconds: float, records: list, t_end: float) -> dict:
    sent = [r for r in records if "sent" in r]
    good = [r for r in sent if r.get("ok")]
    waits = sorted(r["done"] - r["due"] for r in good)
    fifth = max(1, len(good) // 5)
    by_due = sorted(good, key=lambda r: r["due"])
    mean_wait = lambda rs: statistics.fmean(       # noqa: E731
        r["done"] - r["due"] for r in rs) if rs else None
    pick = lambda q: waits[min(len(waits) - 1,     # noqa: E731
                               int(q * len(waits)))] if waits else None
    return {"rate_per_s": rate, "offered": len(sent),
            "failed": len(sent) - len(good),
            "answered_in_window_per_s":
                sum(r["done"] <= t_end for r in good) / seconds,
            "wait_p50_s": pick(0.5), "wait_p95_s": pick(0.95),
            "wait_max_s": waits[-1] if waits else None,
            "wait_first_fifth_s": mean_wait(by_due[:fifth]),
            "wait_last_fifth_s": mean_wait(by_due[-fifth:]),
            "late_ms_median": statistics.median(
                (r["sent"] - r["due"]) * 1e3 for r in sent) if sent else None}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--out", required=True)
    args = parser.parse_args()
    bench = loader.load_benchmark()
    cell = loader.find_cell(bench, args.workload)
    run = {"seed": args.seed, "chips": int(cell["chips"]),
           "config": loader.load_config(bench, cell["config"]),
           "traffic": loader.load_traffic(bench, cell["traffic"])}
    run["family"] = loader.find_family(bench, run["config"])
    vocab = int(run["config"]["vocab_size"])
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    from ray_tpu import serve

    with run_mod.session(run["chips"]):
        serve.start()
        try:
            url = run_mod.deploy_lm(run)
            run_mod.require_device(
                run_mod.http_json(url + "/stats?t0=inf")["device"], "tpu",
                run["chips"])
            for i, rate in enumerate(float(x) for x in args.rates.split(",")):
                mix = dict(run["traffic"], rate_per_s=rate)
                plan = traffic.serve_plan(mix, args.seed + i, args.seconds)
                t0 = time.perf_counter()
                records = run_mod.open_loop(url, plan, args.seed + i, vocab,
                                             t0, t0 + args.seconds)
                row = summarise(rate, args.seconds, records,
                                t0 + args.seconds)
                print(json.dumps(row), flush=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
        finally:
            serve.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
