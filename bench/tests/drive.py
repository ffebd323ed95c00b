"""Drive a run of a checkout's harness without its look for a chip, with
an optional fault planted in the program underneath (tests only).

    python drive.py <checkout> <workload> <seed> <seconds> <trace> [fault]

Faults, each altering what the timed path returns where it is produced:
``alter_answer`` adds 1e-3 to one row's score of every request;
``nan_answer`` makes one row's score NaN;
``drop_rows`` returns every request one row short.
"""
import argparse
import json
import os
import sys

root = os.path.abspath(sys.argv[1])
sys.path.insert(0, root)

from bench import run as bench_run  # noqa: E402


def plant(fault: str) -> None:
    from repro.serve import query_server

    finish = query_server.PredictionQueryServer._finish

    def broken(self, req):
        if req.result is not None and "score" in req.result:
            res = dict(req.result)
            score = res["score"].copy()
            if fault == "alter_answer":
                score[0] += 1e-3
            elif fault == "nan_answer":
                score[0] = float("nan")
            elif fault == "drop_rows":
                score = score[:-1]
            else:
                raise ValueError(fault)
            res["score"] = score
            req.result = res
        return finish(self, req)

    query_server.PredictionQueryServer._finish = broken


if len(sys.argv) > 6:
    plant(sys.argv[6])
args = argparse.Namespace(workload=sys.argv[2], seed=int(sys.argv[3]),
                          seconds=float(sys.argv[4]), trace=int(sys.argv[5]))
print(json.dumps(bench_run.run(args, require_accelerator=False)), flush=True)
