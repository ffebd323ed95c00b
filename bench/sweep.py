"""Find an open-loop cell's knee: the highest rate without a growing backlog.

    python3 bench/sweep.py --workload <cell> --rates 100,200,400 [--seconds 6]

Sets the cell up once and runs one window per rate (the cell's traffic mix
with its rate replaced). A rate keeps up when the requests due in the last
quarter of the window wait no longer at the median than those of the first
quarter (within 1.5x and 2 ms) and the server answers the last request
within 0.25 s of the window's close. The knee is written into the cell's
traffic file by hand, at 0.8x; the benchmark's runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run as bench_run  # noqa: E402


def summarize(ctx) -> dict:
    recs = ctx.due_in_window()
    lat = np.array([(r.done - r.due) * 1e3 if r.ok else np.inf for r in recs])
    drain = max((r.done for r in ctx.records if r.ok), default=ctx.t1) - ctx.t1
    med_first, med_last = bench_run.quarter_medians(ctx)
    return {
        "requests": len(recs),
        "p50_ms": float(np.percentile(lat, 50)),
        "p95_ms": float(np.percentile(lat, 95)),
        "p99_ms": float(np.percentile(lat, 99)),
        "median_first_quarter_ms": med_first,
        "median_last_quarter_ms": med_last,
        "drain_s": float(drain),
        "late_p95_ms": float(np.percentile(np.asarray(ctx.late_s) * 1e3, 95)),
        "failed": int(sum(1 for r in ctx.records if not r.ok)),
        "keeps_up": bool(med_last <= max(1.5 * med_first, med_first + 2.0)
                         and drain <= 0.25),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=5_000_000_011)
    args = ap.parse_args(argv)
    try:
        cell = bench_run.Cell(args.workload)
    except bench_run.NoAccelerator as e:
        print(f"sweep: {e}; nothing was run", file=sys.stderr)
        return 2
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            cell.mix["rate_per_s"] = rate
            due, batches = cell.plan(args.seed, args.seconds)
            ctx = cell.window(due, batches, args.seconds, False, 0.0)
            print(json.dumps({"rate_per_s": rate, **summarize(ctx)}), flush=True)
    finally:
        cell.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
