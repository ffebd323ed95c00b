"""Readings that set the limits of ``correct``, for one cell.

    python3 bench/control.py --workload <cell> --seeds <n> [--seconds <s>]

Sets the cell up once, then for each of ``n`` seeds runs a short window at
the cell's own load and compares the seed's sample of answers twice: the
program's (the lower reading) and the control's, the reference computed at
the precision below the program's (``Precision.HIGH``, three bfloat16
passes), put in the program's place (the upper reading). Prints one JSON
line per seed and a summary. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run as bench_run  # noqa: E402


def readings(name: str, seeds: list[int], seconds: float,
             require_accelerator: bool = True) -> list[dict]:
    cell = bench_run.Cell(name, require_accelerator)
    plans = []
    try:
        for seed in seeds:
            due, batches = cell.plan(seed, seconds)
            ctx = cell.window(due, batches, seconds, False, 0.0)
            plans.append((seed, ctx, batches))
    finally:
        cell.close()
    out = []
    for seed, ctx, batches in plans:
        checks, result = bench_run.verify(cell, ctx, batches, seed)
        _, control = bench_run.verify(cell, ctx, batches, seed, "high")
        out.append({
            "seed": seed, "program": result, "control": control,
            "failed": sum(1 for r in ctx.records if not r.ok),
            "correct": bench_run.is_correct(checks, result["rows_compared"]),
            "control_correct": bench_run.is_correct(
                bench_run.checks_of(control), control["rows_compared"]),
        })
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=4_000_000_001)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    try:
        rows = readings(args.workload, seeds, args.seconds)
    except bench_run.NoAccelerator as e:
        print(f"control: {e}; nothing was run", file=sys.stderr)
        return 2
    for r in rows:
        print(json.dumps(r), flush=True)
    prog = [r["program"]["score_gap"] for r in rows]
    ctl = [r["control"]["score_gap"] for r in rows]
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "lower_reading": max(prog), "upper_reading": min(ctl),
        "limit": bench_run.SCORE_GAP_LIMIT,
        "program_all_correct": all(r["correct"] for r in rows),
        "control_all_fail": not any(r["control_correct"] for r in rows),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
