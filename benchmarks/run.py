"""Benchmark runner: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig6,fig10,...]

Prints CSV blocks per figure (the same rows each module prints standalone).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

HEADERS = {
    "fig4": "fig4,strategy,accuracy,speedup_median,speedup_p25,speedup_min",
    "fig6": "fig6,dataset,model,rows,noopt_s,none_s,sql_s,dnn_s,best,speedup",
    "fig7": "fig7,model,rows,noopt_s,raven_s,speedup",
    "fig8": "fig8,model,rows,dop1_s,dop8_s,identical",
    "fig9": "fig9,alpha,zero_weights,noopt_s,modelproj_s,mltosql_s,both_s,speedup",
    "fig10": "fig10,depth,noopt_s,modelproj_s,mltosql_s,mltodnn_s,verdict",
    "fig11": "fig11,depth,partition,noopt_s,nopart_s,part_s,avg_pruned,speedup",
    "fig12": "fig12,estimators,depth,interp_s,dnn_s,speedup",
}

ALL = ["fig6", "fig7", "fig8", "fig9", "fig10", "fig11", "fig12", "fig4"]


def _module(name: str) -> str:
    return {
        "fig4": "fig4_strategies",
        "fig6": "fig6_end_to_end",
        "fig7": "fig7_scalability",
        "fig8": "fig8_dop",
        "fig9": "fig9_lr_sparsity",
        "fig10": "fig10_tree_depth",
        "fig11": "fig11_data_induced",
        "fig12": "fig12_mltodnn",
    }[name]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced scales (CI smoke)")
    ap.add_argument("--only", default=None,
                    help="comma-separated figure list")
    args = ap.parse_args()

    todo = args.only.split(",") if args.only else ALL
    failures = 0
    t_all = time.time()
    for name in todo:
        mod = __import__(f"benchmarks.{_module(name)}", fromlist=["run"])
        print(f"\n# === {name} {'(quick)' if args.quick else ''} ===")
        print(HEADERS[name])
        t0 = time.time()
        try:
            mod.run(quick=args.quick)
        except Exception:
            traceback.print_exc()
            failures += 1
        print(f"# {name} done in {time.time()-t0:.1f}s")

    print(f"\n# all benchmarks done in {time.time()-t_all:.1f}s; "
          f"{failures} failures")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
