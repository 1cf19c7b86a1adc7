#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains: run its window at
each offered rate, on one opened store, in one process.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 4,8,12

Rates run in ascending order. For each it prints one JSON line: the
offered rate, p50 and p95 from the due time, the median service time,
how long past the window's close the last answer came (``drain_s``), the
ratio of the last third's median latency to the first third's
(``growth``; near 1 when the queue does not grow), and whether the
answers were correct. A rate is sustained when ``drain_s`` is under two
median service times, ``growth`` under 2, and p50 at most twice the
median service time at the lowest rate swept (where requests barely
queue). The last limit is there because requests slow each other (one
process: the GIL and the per-shard engine locks): past it the queue
need not grow within one window, but the service time inflates and the
tails swing from run to run. Start the sweep at a light rate. It stops
at the first rate not sustained, so the knee is bracketed; the last line
gives the capacity (the highest sustained rate), the first rate that
failed and the p50 limit. The cell's traffic file then takes about four
fifths of the capacity, as a number.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
from bench import run  # noqa: E402


def summarize(rate: float, seconds: float, records: list, correct: bool) -> dict:
    done = sorted((r for r in records if r.done and r.error is None), key=lambda r: r.due)
    lat = np.array([r.end - r.due for r in done])
    svc = np.array([r.end - r.start for r in done])
    third = max(len(lat) // 3, 1)
    last_end = max((r.end for r in done), default=0.0)
    growth = float(np.median(lat[-third:]) / np.median(lat[:third])) if len(lat) else 0.0
    drain = max(last_end - seconds, 0.0)
    med = float(np.median(svc)) if len(svc) else 0.0
    return {"rate_per_s": rate, "requests": len(records), "completed": len(done),
            "p50_ms": float(np.percentile(lat, 50) * 1e3) if len(lat) else None,
            "p95_ms": float(np.percentile(lat, 95) * 1e3) if len(lat) else None,
            "service_ms": med * 1e3, "drain_s": drain, "growth": growth,
            "sustained": bool(drain < 2 * med and growth < 2), "correct": correct}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    kept = {}

    def keep_records(records):
        kept["records"] = records

    rows = []
    failed_at = None
    for rate in sorted(float(r) for r in args.rates.split(",")):
        try:
            result = run.run_cell(run.ROOT, args.workload, args.seed, args.seconds, False,
                                  traffic_overrides={"rate_per_s": rate},
                                  records_out=keep_records)
        except run.NoChip as e:
            print(f"sweep: {e}", file=sys.stderr)
            return 2
        row = summarize(rate, args.seconds, kept["records"], bool(result["correct"]))
        rows.append(row)
        limit = 2 * rows[0]["service_ms"]
        print(json.dumps(row), flush=True)
        if not (row["sustained"] and row["correct"] and row["p50_ms"] <= limit):
            failed_at = rate
            break
    ok = [r["rate_per_s"] for r in rows[:-1 if failed_at is not None else None]]
    print(json.dumps({"capacity_per_s": max(ok, default=None),
                      "first_failed_per_s": failed_at, "p50_limit_ms": limit}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
