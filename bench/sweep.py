"""Finds a cell's knee: one process, one set-up, rising Poisson rates.

    python3 bench/sweep.py --workload bert_base_rope_allmiss.templ_r80 \\
        --seed 5 --rates 80,100,120,140,160 --seconds 6

For each rate it serves ``--seconds`` of the cell's traffic at that rate
and prints offered and completed requests per second, the p50 and p95
latency, and the backlog when the arrivals stop (requests that arrived
and were not done). The knee is the highest rate whose backlog stays
under two batches and whose completed rate keeps up with the offered
one; the last line gives it and 0.8 of it, the rate a cell is fixed at.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def knee(rows, batch: int) -> float:
    ok = [r["offered_per_s"] for r in rows
          if r["backlog_at_end"] <= 2 * batch
          and r["completed_per_s"] >= 0.95 * r["offered_per_s"]]
    return max(ok) if ok else 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import driver
    from bench.run import Cell, NoChip, log
    try:
        c = Cell(args.workload, args.seed)
    except NoChip as e:
        log(f"sweep: {e}")
        return 2
    rows = []
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        reqs = c.requests(args.seconds, dict(c.traffic, rate_per_s=rate),
                          stream=10 + k)
        win, counters, _, _ = c.window(reqs, args.seconds)
        s = list(win.served.values())
        lat = [x.latency for x in s]
        row = {"offered_per_s": len(reqs) / args.seconds,
               "completed_per_s": sum(x.done <= args.seconds for x in s)
               / args.seconds,
               "latency_p50_ms": driver.percentile(lat, 50) * 1e3,
               "latency_p95_ms": driver.percentile(lat, 95) * 1e3,
               "backlog_at_end": sum(x.done > args.seconds for x in s)
               + win.unfinished,
               "batches": counters["n_batches"],
               "hit_share": counters["n_hits"]
               / max(1, counters["n_layer_attempts"])}
        rows.append(row)
        print(json.dumps(row), flush=True)
    k = knee(rows, c.rows)
    c.release()
    print(json.dumps({"knee_per_s": k, "rate_0.8_per_s": 0.8 * k}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
