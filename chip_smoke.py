"""Chip smoke: the served prediction-query path, once, on one TPU.

Drives the front door a user calls — ``raven.connect`` → ``db.sql`` →
``prepare`` → ``serve`` → ``submit``/``wait`` — in three phases, and checks
every answer against a reference computed on the host:

  A  full width: flights (a 4-table star, 4 numerics and 33 categoricals,
     ~6.5k one-hot columns), a 20-tree depth-3 gradient-boosted model trained
     on 4,096 rows, 50,000 fact rows in the database, served with
     ``transform="dnn"`` (featurize and tree scoring in one pure stage), about
     eight requests of 200–4,096 rows. Scores and labels must agree with
     ``run_pipeline`` on the same rows; a re-submitted bucket must not trace.
  B  relational kernels: filter → join → PREDICT → aggregate over a star
     whose 16,384-row dimension carries an f32 payload (so ``gather_join`` and
     ``segment_agg`` qualify), requests of 4k–16k fact rows, against a numpy
     oracle.
  C  warm start: phase A's query re-prepared with the plan cache cleared, its
     stage programs loaded from the artifact store phase A filled
     (``jax.export`` round trip of programs holding Pallas kernels): disk hits,
     zero traces, no quarantined or incompatible entries.

On a TPU each phase also lowers its stage and requires a ``tpu_custom_call``
for every kernel ``explain()`` places, and every phase requires zero retries,
failed groups and breaker trips. Any failure exits non-zero. When JAX finds no
TPU the script exits non-zero before running anything; there is no CPU path.
On success the last line of standard output is::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Usage::

    python chip_smoke.py [--seed N]

The phase functions take their sizes as arguments, so the tests run them end
to end at a tiny size on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

import repro as raven  # noqa: E402
from repro.compile_cache import configure_compile_cache  # noqa: E402

MAX_MISMATCH = 0.005  # share of predictions allowed to differ from the host

# explain() wording -> the Pallas kernel's name in the lowered program
_KERNEL_MARKERS = {
    "tree_gemm kernel": "tree_gemm",
    "fused featurize kernel": "featurize",
    "tensor/kernel: gather_join": "gather_join",
    "tensor/kernel: segment_agg": "segment_agg",
}


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# shared checks
# ---------------------------------------------------------------------------


def placed_kernels(explain: str) -> set[str]:
    """The Pallas kernels ``explain()`` says the optimizer placed."""
    return {k for marker, k in _KERNEL_MARKERS.items() if marker in explain}


def placement_lines(explain: str) -> list[str]:
    """The runtime-placement lines of ``explain()`` (per-op placement,
    relational placement, and the optimizer's kernel/strategy notes)."""
    out, keep = [], False
    for line in explain.splitlines():
        if line.startswith("-- "):
            keep = "runtime placement" in line
            if keep:
                out.append(line)
            continue
        if keep or "kernel" in line or "tree ensemble" in line:
            out.append(line)
    return out


def lowered_kernels(text: str) -> set[str]:
    """Pallas kernels present in a lowered program's text."""
    if "tpu_custom_call" not in text:
        return set()
    return set(re.findall(r'kernel_name = "([a-z_]+)"', text))


def check_kernels(prep, tables, fact, batch, *, require: bool, phase: str):
    """Lower the query's single pure stage for ``batch`` and compare the
    kernels in it with those ``explain()`` places. With ``require`` (on a
    TPU) every placed kernel must be in the program."""
    import jax.numpy as jnp

    graph = prep.compiled.graph
    if not (graph.is_pure and len(graph.stages) == 1):
        raise AssertionError(f"{phase}: expected one pure stage, got\n"
                             + graph.describe())
    explain = prep.explain()
    for line in placement_lines(explain):
        log(f"{phase}:   {line}")
    want = placed_kernels(explain)
    db = {t: {c: jnp.asarray(v) for c, v in cols.items()}
          for t, cols in tables.items()}
    db[fact] = {c: jnp.asarray(v) for c, v in batch.items()}
    text = prep.compiled.lower_entry(db, params=prep.params or None).as_text()
    have = lowered_kernels(text)
    log(f"{phase}: kernels placed={sorted(want)} in program={sorted(have)}")
    if require and not want <= have:
        raise AssertionError(
            f"{phase}: placed kernels missing from the lowered program: "
            f"{sorted(want - have)}"
        )
    return want, have


def check_server_health(db, phase: str) -> None:
    """Zero retries, failed groups and breaker trips: no failure may be
    absorbed out of sight."""
    srv = db.cache_stats()["server"]
    bad = {k: srv[k] for k in ("retries", "retries_exhausted", "breaker_trips")
           if srv[k]}
    for name, route in srv["routes"].items():
        for label, v in route["versions"].items():
            if v["errors"] or v["degraded"] or v["breaker_trips"]:
                bad[f"{name}/{label}"] = {
                    k: v[k] for k in ("errors", "degraded", "breaker_trips")
                }
    if bad:
        raise AssertionError(f"{phase}: failures absorbed by the server: {bad}")


# ---------------------------------------------------------------------------
# phase A: flights at full width
# ---------------------------------------------------------------------------


def join_star(batch, tables, join_keys):
    """Host FK join of a fact batch onto its dimension tables (the reference
    input for ``run_pipeline``)."""
    out = dict(batch)
    for fk, dim_table, dk in join_keys:
        dim = tables[dim_table]
        order = np.argsort(dim[dk], kind="stable")
        pos = order[np.searchsorted(dim[dk], out[fk], sorter=order)]
        for c, v in dim.items():
            if c != dk:
                out[c] = v[pos]
    return out


def flights_query(ds) -> str:
    joins = "".join(f" JOIN {d} ON {fk} = {dk}" for fk, d, dk in ds.join_keys)
    return f"SELECT * FROM PREDICT(model='m', data={ds.fact}{joins}) AS p"


def phase_a(
    seed: int, cache_dir: str, *, train_rows: int = 4096,
    db_rows: int = 50_000, n_requests: int = 8, min_rows: int = 200,
    max_rows: int = 4096, n_estimators: int = 20, depth: int = 3,
    require_kernels: bool = True,
) -> dict:
    """Serve the flights query with ``transform="dnn"``; returns what phase
    C needs (the model, tables, requests and their results)."""
    from benchmarks.common import train_model
    from repro.data.datasets import make_flights
    from repro.ml.pipeline import prediction_mismatch, run_pipeline

    t0 = time.perf_counter()
    pipe = train_model(make_flights(train_rows, seed=seed), "gb",
                       n_estimators=n_estimators, depth=depth)
    ds = make_flights(db_rows, seed=seed)  # same seed: same category domains
    fact = ds.tables[ds.fact]
    rng = np.random.default_rng(seed + 2)
    sizes = [int(n) for n in rng.integers(min_rows, max_rows + 1, n_requests)]
    n_fact = len(next(iter(fact.values())))
    requests = []
    for n in sizes:  # existing fact rows: every dimension key hits
        rows = np.sort(rng.choice(n_fact, size=n, replace=False))
        requests.append({c: v[rows] for c, v in fact.items()})
    log(f"A: trained GB({n_estimators}x{depth}) on {train_rows} rows, "
        f"{db_rows} fact rows, requests {sizes} "
        f"({time.perf_counter() - t0:.1f}s)")

    db = raven.connect(ds.tables, options=raven.ConnectOptions(
        cache_dir=cache_dir))
    try:
        db.models.publish("m", pipe)
        prep = db.sql(flights_query(ds)).prepare(transform="dnn")
        prep.serve(options=raven.ServeOptions(max_latency_ms=2.0))
        check_kernels(prep, ds.tables, ds.fact, requests[0],
                      require=require_kernels, phase="A")
        t0 = time.perf_counter()
        first = [prep.submit(b).wait(timeout=600) for b in requests]
        t_first = time.perf_counter() - t0
        traces = db.cache_stats()["traces"]
        t0 = time.perf_counter()
        reqs = [prep.submit(b) for b in requests]  # coalesced
        second = [r.wait(timeout=600) for r in reqs]
        t_second = time.perf_counter() - t0
        traces_coalesced = db.cache_stats()["traces"] - traces
        traces = db.cache_stats()["traces"]
        again = prep.submit(requests[0]).wait(timeout=600)
        retraces = db.cache_stats()["traces"] - traces
        check_server_health(db, "A")
        db.artifact_store.drain()
        store = db.artifact_store.stats.snapshot()
    finally:
        db.close()

    worst = 0.0
    for b, out1, out2 in zip(requests, first, second):
        ref = run_pipeline(pipe, join_star(b, ds.tables, ds.join_keys))
        for col, o in (("score", pipe.outputs[0]), ("pred", pipe.outputs[1])):
            worst = max(worst, prediction_mismatch(out1[col], ref[o]),
                        prediction_mismatch(out2[col], ref[o]))
    for col in ("score", "pred"):
        if not np.array_equal(again[col], first[0][col]):
            raise AssertionError(f"A: re-submitted request changed {col}")
    log(f"A: {len(requests)} requests sequential in {t_first:.2f}s, "
        f"coalesced in {t_second:.2f}s ({traces_coalesced} new traces); "
        f"worst mismatch vs run_pipeline {worst:.4%}; "
        f"re-submitted bucket traced {retraces} time(s)")
    if worst > MAX_MISMATCH:
        raise AssertionError(f"A: {worst:.4%} of predictions differ")
    if retraces:
        raise AssertionError(f"A: a seen bucket re-traced {retraces} time(s)")
    if store["stage_saves"] < 1 or store["save_errors"]:
        raise AssertionError(f"A: stage programs not exported: {store}")
    return {"pipe": pipe, "ds": ds, "requests": requests, "results": first}


# ---------------------------------------------------------------------------
# phase B: filter -> join -> PREDICT -> aggregate over the relational kernels
# ---------------------------------------------------------------------------

RELATIONAL_SQL = (
    "SELECT COUNT(*), SUM(v0), AVG(score), MIN(v1), MAX(x) "
    "FROM PREDICT(model='m', data=f JOIN d ON fk = k) AS p WHERE x > 0"
)


def _dyadic(rng, n):
    """Small multiples of 1/4: f32 sums of them are exact in any order."""
    return (rng.integers(-40, 40, size=n) * 0.25).astype(np.float32)


def relational_star(seed: int, dim_rows: int, fact_rows: int):
    rng = np.random.default_rng(seed)
    dim = {"k": rng.permutation(dim_rows).astype(np.int64),
           "v0": _dyadic(rng, dim_rows), "v1": _dyadic(rng, dim_rows)}
    fact = {
        # a fifth of the keys miss the dimension, so the join filters too
        "fk": rng.integers(0, dim_rows + dim_rows // 4, fact_rows)
        .astype(np.int64),
        "x": _dyadic(rng, fact_rows),
    }
    return fact, dim


def relational_oracle(pipe, fact, dim) -> dict:
    """numpy filter → join → predict → aggregate, f32-exact on the dyadic
    columns."""
    from repro.ml.pipeline import run_pipeline

    order = np.argsort(dim["k"], kind="stable")
    keys = dim["k"][order]
    pos = np.clip(np.searchsorted(keys, fact["fk"]), 0, len(keys) - 1)
    mask = (keys[pos] == fact["fk"]) & (fact["x"] > 0)
    rows = order[pos[mask]]
    cols = {"x": fact["x"][mask], "v0": dim["v0"][rows],
            "v1": dim["v1"][rows]}
    score = np.asarray(run_pipeline(pipe, cols)[pipe.outputs[0]],
                       np.float64).reshape(-1)
    n = int(mask.sum())
    return {
        "count": float(n),
        "sum_v0": float(cols["v0"].astype(np.float64).sum()),
        "avg_score": float(score.mean()) if n else 0.0,
        "min_v1": float(cols["v1"].min()) if n else 0.0,
        "max_x": float(cols["x"].max()) if n else 0.0,
    }


def phase_b(
    seed: int, *, dim_rows: int = 16_384,
    request_rows: tuple = (4096, 16_384, 8192, 12_288),
    train_rows: int = 4096, require_kernels: bool = True,
) -> None:
    from repro.ml import GradientBoostingClassifier, fit_pipeline

    fact, dim = relational_star(seed, dim_rows, max(request_rows))
    # a model over the joined view: the label is a planted rule on it
    t_fact, _ = relational_star(seed + 1, dim_rows, train_rows)
    pos = np.searchsorted(np.sort(dim["k"]), t_fact["fk"].clip(0, dim_rows - 1))
    by_key = np.argsort(dim["k"], kind="stable")[pos]
    train = {"x": t_fact["x"], "v0": dim["v0"][by_key],
             "v1": dim["v1"][by_key]}
    label = ((train["x"] + train["v0"] - 0.5 * train["v1"]) > 0).astype(
        np.int64)
    pipe = fit_pipeline(train, label, ["x", "v0", "v1"], [],
                        GradientBoostingClassifier(n_estimators=20,
                                                   max_depth=3))
    rng = np.random.default_rng(seed + 3)
    requests = []
    for n in request_rows:
        rows = rng.choice(len(fact["x"]), size=n, replace=False)
        requests.append({c: v[rows] for c, v in fact.items()})
    tables = {"f": fact, "d": dim}
    db = raven.connect(tables)
    try:
        db.models.publish("m", pipe)
        prep = db.sql(RELATIONAL_SQL).prepare(transform="dnn")
        prep.serve(options=raven.ServeOptions(max_latency_ms=2.0))
        want_k, _ = check_kernels(prep, tables, "f", requests[0],
                                  require=require_kernels, phase="B")
        if require_kernels and not {"gather_join", "segment_agg"} <= want_k:
            raise AssertionError(
                f"B: the relational kernels were not placed: {sorted(want_k)}"
            )
        t0 = time.perf_counter()
        seq = [prep.submit(b).wait(timeout=600) for b in requests]
        reqs = [prep.submit(b) for b in requests]  # coalesced, segmented
        coal = [r.wait(timeout=600) for r in reqs]
        elapsed = time.perf_counter() - t0
        check_server_health(db, "B")
    finally:
        db.close()
    names = {"count": "count_rows", "sum_v0": "sum_v0",
             "avg_score": "mean_score", "min_v1": "min_v1", "max_x": "max_x"}
    for b, o1, o2 in zip(requests, seq, coal):
        ref = relational_oracle(pipe, b, dim)
        for key, col in names.items():
            for got in (o1, o2):
                v = float(np.asarray(got[col]).reshape(-1)[0])
                exact = key != "avg_score"
                ok = v == ref[key] if exact else abs(v - ref[key]) <= 1e-4
                if not ok:
                    raise AssertionError(
                        f"B: {col} = {v!r}, oracle {ref[key]!r} "
                        f"({len(b['x'])}-row request)"
                    )
    log(f"B: {2 * len(requests)} requests ({list(request_rows)} rows over a "
        f"{dim_rows}-row dimension) match the numpy oracle "
        f"({elapsed:.2f}s)")


# ---------------------------------------------------------------------------
# phase C: warm start from the artifact store
# ---------------------------------------------------------------------------


def phase_c(a: dict, cache_dir: str) -> None:
    from repro.relational.engine import clear_plan_cache

    ds, requests = a["ds"], a["requests"]
    clear_plan_cache()
    db = raven.connect(ds.tables, options=raven.ConnectOptions(
        cache_dir=cache_dir))
    try:
        db.models.publish("m", a["pipe"])
        t0 = time.perf_counter()
        prep = db.sql(flights_query(ds)).prepare(transform="dnn")
        prep.serve(options=raven.ServeOptions(max_latency_ms=2.0))
        outs = [prep.submit(b).wait(timeout=600) for b in requests]
        elapsed = time.perf_counter() - t0
        stats = db.cache_stats()
        check_server_health(db, "C")
        store = stats["artifact_store"]
    finally:
        db.close()
    log(f"C: re-prepared and served {len(requests)} requests in "
        f"{elapsed:.2f}s: disk_hits={stats['disk_hits']} "
        f"traces={stats['traces']} stage_hits={store['stage_hits']} "
        f"corrupt={store['corrupt']} incompatible={store['incompatible']} "
        f"fallbacks={store['fallbacks']}")
    if stats["disk_hits"] < 1 or store["stage_hits"] < 1:
        raise AssertionError("C: no stage program came from the disk tier")
    if stats["traces"]:
        raise AssertionError(f"C: warm start traced {stats['traces']} time(s)")
    bad = {k: store[k] for k in ("corrupt", "incompatible", "fallbacks")
           if store[k]}
    if bad:
        raise AssertionError(f"C: the store fell back to live compiles: {bad}")
    for out, ref in zip(outs, a["results"]):
        for col in ("score", "pred"):
            if not np.array_equal(out[col], ref[col]):
                raise AssertionError(f"C: warm {col} differs from phase A")
    log("C: warm results are bitwise equal to phase A's")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = configure_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    from repro.exec.stages import donation_enabled

    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, "
        f"jax {jax.__version__}, compile cache {cache}, "
        f"donation {'on' if donation_enabled() else 'off'}")
    store_dir = tempfile.mkdtemp(prefix="chip_smoke_store_")
    try:
        t0 = time.perf_counter()
        a = phase_a(args.seed, store_dir)
        log(f"A: ok ({time.perf_counter() - t0:.1f}s)")
        t0 = time.perf_counter()
        phase_b(args.seed)
        log(f"B: ok ({time.perf_counter() - t0:.1f}s)")
        t0 = time.perf_counter()
        phase_c(a, store_dir)
        log(f"C: ok ({time.perf_counter() - t0:.1f}s)")
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
