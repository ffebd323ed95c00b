"""Fig. 8 analog: degree-of-parallelism — 1-shard vs 8-shard shard_map
execution of the fused (MLtoSQL) plan vs the un-optimized plan.

The paper's DOP1/DOP16 comparison on SQL Server shows the *fused* plan
benefits more from parallelism than the UDF plan (the UDF host boundary
serializes). We reproduce the mechanism with the data-parallel engine: the
fused plan shards rows over the `data` mesh axis with one psum at the
aggregate. This container exposes one physical core, so 8 'devices' measure
partitioning overhead rather than speedup — the record of interest is that
the sharded fused plan produces identical results with per-shard work 1/8,
plus the wall-time ratio on real parallel hardware (noted in EXPERIMENTS).
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _measure(devices: int, rows: int, kind: str) -> str:
    code = f"""
        import time
        import numpy as np, jax, jax.numpy as jnp
        from benchmarks.common import NOOPT, build_query, make_dataset, train_model
        from repro.core.optimizer import OptimizerOptions, RavenOptimizer
        from repro.relational.engine import compile_plan, compile_plan_sharded

        train, infer = make_dataset('hospital', {rows})
        pipe = train_model(train, {kind!r})
        q = build_query(infer, pipe, agg='COUNT(*), SUM(score)')
        plan, _ = RavenOptimizer(options=OptimizerOptions(transform='sql')).optimize(q)
        mesh = jax.make_mesh(({devices},), ('data',))
        run = compile_plan_sharded(plan, mesh, fact_table='patients')
        db = {{t: {{c: jnp.asarray(v) for c, v in cols.items()}}
              for t, cols in infer.tables.items()}}
        out = run(db)  # warmup/compile
        ts = []
        for _ in range(3):
            t0 = time.perf_counter(); jax.block_until_ready(run(db).columns)
            ts.append(time.perf_counter() - t0)
        print('TIME=', min(ts), 'COUNT=', float(np.asarray(out.columns['count_rows'])[0]))
    """
    env = dict(os.environ)
    # the shards are host CPU devices: the child never opens the accelerator,
    # which a parent that ran other figures (benchmarks.run) already holds
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + ":" + REPO
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(r.stderr[-2000:])
    return r.stdout


def run(quick: bool = False):
    rows_n = 20_000 if quick else 200_000
    out = []
    for kind in ("dt",) if quick else ("lr", "dt"):
        r1 = _measure(1, rows_n, kind)
        r8 = _measure(8, rows_n, kind)
        t1 = float(r1.split("TIME=")[1].split()[0])
        t8 = float(r8.split("TIME=")[1].split()[0])
        c1 = float(r1.split("COUNT=")[1].split()[0])
        c8 = float(r8.split("COUNT=")[1].split()[0])
        assert c1 == c8, "sharded plan changed the result"
        out.append({"model": kind, "dop1_s": t1, "dop8_s": t8,
                    "identical": c1 == c8})
        print(f"fig8,{kind},{rows_n},{t1:.3f},{t8:.3f},identical={c1 == c8}")
    return out


if __name__ == "__main__":
    print("fig8,model,rows,dop1_s,dop8_s,identical")
    run()
