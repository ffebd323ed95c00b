"""Prediction-query serving layer: fingerprints, caching, bucketed padding,
micro-batching (the cached hot path the paper's optimize-once model implies)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ir import TableStats, plan_fingerprint as logical_fingerprint
from repro.core.optimizer import OptimizerOptions, RavenOptimizer
from repro.data.datasets import make_hospital
from repro.relational.engine import (
    PLAN_CACHE_STATS,
    clear_plan_cache,
    compile_plan,
    execute_plan,
    plan_fingerprint,
)
from repro.serve import PredictionQueryServer, query_server, row_bucket
from repro.sql.parser import parse_prediction_query

SQL_STAR = "SELECT * FROM PREDICT(model='m', data=patients) AS p WHERE score >= 0.6"
SQL_AGG = (
    "SELECT COUNT(*), AVG(score) FROM PREDICT(model='m', data=patients) AS p "
    "WHERE score >= 0.6"
)


def _query(hospital, pipe, sql=SQL_STAR):
    stats = {"patients": TableStats.of(hospital.tables["patients"])}
    return parse_prediction_query(sql, {"m": pipe}, hospital.tables, stats=stats)


@pytest.fixture(scope="module")
def dt_query(hospital, hospital_dt):
    return _query(hospital, hospital_dt)


def _optimize(query, **opts):
    return RavenOptimizer(options=OptimizerOptions(**opts)).optimize(query)[0]


# ---------------------------------------------------------------------------
# Fingerprints
# ---------------------------------------------------------------------------


def test_fingerprint_stable_across_plan_objects(hospital, dt_query):
    plan_a = _optimize(dt_query, transform="sql")
    plan_b = _optimize(dt_query, transform="sql")
    assert plan_a is not plan_b
    assert plan_fingerprint(plan_a) == plan_fingerprint(plan_b)
    # logical plans too (the server's optimized-plan cache key)
    assert logical_fingerprint(dt_query.plan) == logical_fingerprint(
        dt_query.copy().plan
    )


def test_fingerprint_sensitive_to_content(hospital, dt_query, hospital_gb):
    sql_plan = _optimize(dt_query, transform="sql")
    none_plan = _optimize(dt_query, transform="none")
    assert plan_fingerprint(sql_plan) != plan_fingerprint(none_plan)
    other = _optimize(_query(hospital, hospital_gb), transform="sql")
    assert plan_fingerprint(sql_plan) != plan_fingerprint(other)
    # perturbing one model weight must change the hash (pipeline copies share
    # the ensemble arrays, so swap in a deep-copied ensemble before editing)
    q2 = dt_query.copy()
    node = q2.predict_nodes()[0].pipeline.model_nodes()[0]
    ens = node.attrs["ensemble"].copy()
    ens.leaf_value[0] += 1.0
    node.attrs["ensemble"] = ens
    assert logical_fingerprint(q2.plan) != logical_fingerprint(dt_query.plan)


# ---------------------------------------------------------------------------
# Engine compiled-plan cache
# ---------------------------------------------------------------------------


def test_compile_plan_cache_hit_accounting(hospital, dt_query):
    clear_plan_cache()
    plan_a = _optimize(dt_query, transform="sql")
    plan_b = _optimize(dt_query, transform="sql")
    c1 = compile_plan(plan_a)
    assert (PLAN_CACHE_STATS.hits, PLAN_CACHE_STATS.misses) == (0, 1)
    c2 = compile_plan(plan_b)  # distinct object, identical content
    assert c2 is c1
    assert (PLAN_CACHE_STATS.hits, PLAN_CACHE_STATS.misses) == (1, 1)
    assert compile_plan(plan_a, cache=False) is not c1  # opt-out path


def test_execute_plan_reuses_compiled_stages(hospital, dt_query):
    clear_plan_cache()
    plan = _optimize(dt_query, transform="sql")
    out1 = execute_plan(plan, hospital.tables)
    traces_after_first = PLAN_CACHE_STATS.traces
    assert traces_after_first >= 1
    out2 = execute_plan(plan, hospital.tables)
    assert PLAN_CACHE_STATS.traces == traces_after_first  # no re-jit per call
    a, b = out1.to_numpy(), out2.to_numpy()
    for k in a:
        np.testing.assert_allclose(a[k], b[k])


# ---------------------------------------------------------------------------
# Padded-bucket execution
# ---------------------------------------------------------------------------


def test_row_bucket():
    assert row_bucket(1) == 64
    assert row_bucket(64) == 64
    assert row_bucket(65) == 128
    assert row_bucket(1000) == 1024
    assert row_bucket(0, min_bucket=8) == 8


@pytest.mark.parametrize("sql", [SQL_STAR, SQL_AGG], ids=["rows", "agg"])
def test_padded_execution_equals_unpadded(hospital, hospital_dt, sql):
    plan = _optimize(_query(hospital, hospital_dt, sql), transform="sql")
    ref = execute_plan(plan, hospital.tables).to_numpy()
    n = hospital.n_rows()
    pad = 513  # non-power-of-two padding, pad rows full of zeros
    tables = {t: dict(cols) for t, cols in hospital.tables.items()}
    tables["patients"] = {
        c: np.concatenate([v, np.zeros(pad, v.dtype)])
        for c, v in hospital.tables["patients"].items()
    }
    got = execute_plan(
        plan, tables, row_valid=np.arange(n + pad) < n
    ).to_numpy()
    assert set(got) == set(ref)
    for k in ref:
        np.testing.assert_allclose(got[k], ref[k], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# PredictionQueryServer
# ---------------------------------------------------------------------------


def _batch(n, seed):
    return make_hospital(n, seed=seed).tables["patients"]


def test_server_matches_execute_plan(hospital, dt_query):
    srv = PredictionQueryServer(options=OptimizerOptions(transform="sql"))
    srv.register("risk", dt_query, hospital.tables)
    rows = _batch(300, seed=9)
    got = srv.execute("risk", rows)
    tables = {t: dict(cols) for t, cols in hospital.tables.items()}
    tables["patients"] = rows
    plan = _optimize(dt_query, transform="sql")
    ref = execute_plan(plan, tables).to_numpy()
    assert set(ref) <= set(got)
    for k in ref:
        np.testing.assert_allclose(got[k], ref[k], rtol=1e-5, atol=1e-6)


def test_server_zero_recompiles_after_warmup(hospital, dt_query):
    clear_plan_cache()
    srv = PredictionQueryServer(options=OptimizerOptions(transform="sql"))
    srv.register("risk", dt_query, hospital.tables)
    srv.execute("risk", _batch(100, seed=3))  # warm the 64..128 bucket
    warm = srv.recompiles()
    assert warm >= 1
    for i, n in enumerate((65, 128, 80, 127)):  # all land in bucket 128
        srv.execute("risk", _batch(n, seed=20 + i))
    assert srv.recompiles() == warm  # zero XLA recompiles after warmup
    assert srv.stats.bucket_misses == 1
    assert srv.stats.bucket_hits == 4
    # a new bucket compiles exactly once, then is hot too
    srv.execute("risk", _batch(200, seed=30))
    grown = srv.recompiles()
    assert grown == warm + 1
    srv.execute("risk", _batch(129, seed=31))
    assert srv.recompiles() == grown


def test_server_shares_optimized_plan_across_registrations(hospital, dt_query):
    srv = PredictionQueryServer(options=OptimizerOptions(transform="sql"))
    a = srv.register("a", dt_query, hospital.tables)
    b = srv.register("b", dt_query.copy(), hospital.tables)
    assert srv.stats.plan_cache_misses == 1
    assert srv.stats.plan_cache_hits == 1
    assert a.plan is b.plan
    assert a.compiled is b.compiled


def test_server_microbatch_matches_per_request(hospital, dt_query):
    srv = PredictionQueryServer(options=OptimizerOptions(transform="sql"))
    srv.register("risk", dt_query, hospital.tables)
    sizes = (50, 40, 30, 60)
    batches = [_batch(n, seed=40 + i) for i, n in enumerate(sizes)]
    reqs = [srv.submit("risk", b) for b in batches]
    srv.flush()
    assert srv.stats.coalesced_requests == len(sizes)
    assert srv.stats.batches_executed == 1  # one padded execution for all
    solo = PredictionQueryServer(options=OptimizerOptions(transform="sql"))
    solo.register("risk", dt_query, hospital.tables)
    for req, b in zip(reqs, batches):
        assert req.done
        ref = solo.execute("risk", b)
        for k in ref:
            np.testing.assert_allclose(req.result[k], ref[k], rtol=1e-5, atol=1e-6)


def test_server_aggregate_and_udf_paths(hospital, hospital_dt):
    # aggregates and host-boundary (UDF) plans coalesce via segment ids:
    # one padded execution per flush, split back per request
    agg_q = _query(hospital, hospital_dt, SQL_AGG)
    srv = PredictionQueryServer(options=OptimizerOptions(transform="sql"))
    srv.register("agg", agg_q, hospital.tables)
    udf_q = _query(hospital, hospital_dt)
    srv_udf = PredictionQueryServer(options=OptimizerOptions(transform="none"))
    srv_udf.register("udf", udf_q, hospital.tables)

    rows = _batch(200, seed=8)
    tables = {t: dict(cols) for t, cols in hospital.tables.items()}
    tables["patients"] = rows

    agg = srv.execute("agg", rows)
    ref = execute_plan(_optimize(agg_q, transform="sql"), tables).to_numpy()
    for k in ref:
        np.testing.assert_allclose(agg[k], ref[k], rtol=1e-5)

    batch2 = _batch(77, 9)
    r1, r2 = srv_udf.submit("udf", rows), srv_udf.submit("udf", batch2)
    srv_udf.flush()
    assert srv_udf.stats.batches_executed == 1  # coalesced across the boundary
    assert srv_udf.stats.segmented_batches == 1
    assert srv_udf.stats.coalesced_requests == 2
    ref = execute_plan(_optimize(udf_q, transform="none"), tables).to_numpy()
    for k in ref:
        np.testing.assert_allclose(r1.result[k], ref[k], rtol=1e-5, atol=1e-6)
    tables["patients"] = batch2
    ref2 = execute_plan(_optimize(udf_q, transform="none"), tables).to_numpy()
    assert r2.done
    for k in ref2:
        np.testing.assert_allclose(r2.result[k], ref2[k], rtol=1e-5, atol=1e-6)


def test_server_coalesces_aggregates_with_segment_ids(hospital, hospital_dt):
    # two aggregate requests share one segmented execution, each getting its
    # own fold — bitwise-identical to serving them alone
    agg_q = _query(hospital, hospital_dt, SQL_AGG)
    srv = PredictionQueryServer(options=OptimizerOptions(transform="sql"))
    srv.register("agg", agg_q, hospital.tables)
    b1, b2 = _batch(150, seed=21), _batch(90, seed=22)
    r1, r2 = srv.submit("agg", b1), srv.submit("agg", b2)
    srv.flush()
    assert srv.stats.batches_executed == 1
    assert srv.stats.segmented_batches == 1
    solo = PredictionQueryServer(options=OptimizerOptions(transform="sql"))
    solo.register("agg", agg_q, hospital.tables)
    for req, b in ((r1, b1), (r2, b2)):
        ref = solo.execute("agg", b)
        for k in ref:
            assert req.result[k].shape == ref[k].shape
            np.testing.assert_allclose(req.result[k], ref[k], rtol=1e-4)


def test_server_validates_batch_schema(hospital, dt_query):
    srv = PredictionQueryServer(options=OptimizerOptions(transform="sql"))
    srv.register("risk", dt_query, hospital.tables)
    with pytest.raises(KeyError):
        srv.submit("risk", {"age": np.zeros(4)})
    ragged = dict(_batch(10, seed=2))
    ragged["age"] = ragged["age"][:7]  # mismatched column length
    with pytest.raises(ValueError, match="ragged"):
        srv.submit("risk", ragged)


def test_server_chunks_oversized_batches(hospital, dt_query):
    clear_plan_cache()
    srv = PredictionQueryServer(
        options=OptimizerOptions(transform="sql"), min_bucket=8, max_bucket=64,
    )
    srv.register("risk", dt_query, hospital.tables)
    srv.execute("risk", _batch(64, seed=1))  # warm the max_bucket program
    warm = srv.recompiles()
    rows = _batch(200, seed=7)  # 200 > max_bucket: 64+64+64+8-bucket chunks
    got = srv.execute("risk", rows)
    # chunking keeps every compiled program at or below max_bucket: only the
    # 8-row tail bucket is new; no bucket above 64 was compiled
    # (snapshot before the reference run below, which shares the cached
    # compiled plan and traces once more for its unpadded shape)
    assert srv.recompiles() == warm + 1
    assert all(b <= 64 for _, _, b in srv._seen_buckets)
    tables = {t: dict(cols) for t, cols in hospital.tables.items()}
    tables["patients"] = rows
    ref = execute_plan(_optimize(dt_query, transform="sql"), tables).to_numpy()
    for k in ref:
        np.testing.assert_allclose(got[k], ref[k], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Host <-> device copies: one batched call per direction per group
# ---------------------------------------------------------------------------


class _CountingJax:
    """``jax`` as ``query_server`` sees it, counting the batched copies."""

    def __init__(self):
        self.gets: list[int] = []
        self.puts: list[int] = []

    def __getattr__(self, name):
        return getattr(jax, name)

    def device_get(self, x):
        self.gets.append(len(jax.tree_util.tree_leaves(x)))
        return jax.device_get(x)

    def device_put(self, x, *args, **kwargs):
        self.puts.append(len(jax.tree_util.tree_leaves(x)))
        return jax.device_put(x, *args, **kwargs)


def _plan_answers(srv, reg, batches, mode):
    """What the group's requests should get: the compiled plan's ``run`` on
    the same padded batch, every column copied on its own, split on the
    host the way each serving path promises."""
    cat = {
        c: np.concatenate([np.asarray(b[c]) for b in batches])
        .astype(reg.fact_dtypes[c])
        for c in reg.scan_columns
    }
    sizes = [len(next(iter(b.values()))) for b in batches]
    n = sum(sizes)
    seg_ids = np.repeat(np.arange(len(batches), dtype=np.int32), sizes)

    def run(cols, rows, segmented):
        bucket = row_bucket(rows, srv.min_bucket)

        def pad(a):
            return np.concatenate([a, np.zeros(bucket - rows, a.dtype)])

        db = dict(reg.database)
        db[reg.fact_table] = {c: jnp.asarray(pad(v)) for c, v in cols.items()}
        res = reg.active.run(
            database=db,
            row_valid=jnp.asarray(np.arange(bucket) < rows),
            params=reg.params if reg.param_names else None,
            segments=(pad(seg_ids), len(batches)) if segmented else None,
            bucketer=lambda m: row_bucket(m, srv.min_bucket),
        )
        out = {k: np.asarray(v) for k, v in res.table.columns.items()}
        seg = None if res.seg is None else np.asarray(res.seg)
        return out, np.asarray(res.table.valid), seg

    def positional(cols, valid):
        out, off = [], 0
        for size in sizes:
            m = valid[off:off + size]
            out.append({k: v[off:off + size][m] for k, v in cols.items()})
            off += size
        return out

    if mode == "chunked":
        parts = []
        for off in range(0, n, srv.max_bucket):
            rows = min(srv.max_bucket, n - off)
            chunk = {c: v[off:off + rows] for c, v in cat.items()}
            cols, valid, _ = run(chunk, rows, False)
            parts.append(({k: v[:rows] for k, v in cols.items()}, valid[:rows]))
        cols = {k: np.concatenate([p[0][k] for p in parts]) for k in parts[0][0]}
        return positional(cols, np.concatenate([p[1] for p in parts]))
    cols, valid, seg = run(cat, n, mode in ("segmented", "aggregate"))
    if mode in ("sliceable", "shadow"):
        return positional({k: v[:n] for k, v in cols.items()}, valid[:n])
    if mode == "lone":
        return [{k: v[valid] for k, v in cols.items()}]
    if mode == "aggregate":
        return [{k: v[i:i + 1] for k, v in cols.items()}
                for i in range(len(batches))]
    return [{k: v[valid & (seg == i)] for k, v in cols.items()}
            for i in range(len(batches))]


# (mode, SELECT, transform, request sizes, groups or chunks the server runs)
COPY_PATHS = {
    "sliceable": (SQL_STAR, "sql", (50, 40, 30), 1),
    "lone": (SQL_STAR, "none", (90,), 1),
    "segmented": (SQL_STAR, "none", (70, 45), 1),
    "aggregate": (SQL_AGG, "sql", (150, 90), 1),
    "chunked": (SQL_STAR, "sql", (200,), 4),  # 64+64+64+8 rows
    "shadow": (SQL_STAR, "sql", (60, 35), 2),  # the group and its mirror
}


@pytest.mark.parametrize("mode", list(COPY_PATHS))
def test_each_group_copies_in_one_call_per_direction(
    hospital, hospital_dt, monkeypatch, mode
):
    sql, transform, sizes, calls = COPY_PATHS[mode]
    kw = {"min_bucket": 8, "max_bucket": 64} if mode == "chunked" else {}
    srv = PredictionQueryServer(options=OptimizerOptions(transform=transform),
                                **kw)
    query = _query(hospital, hospital_dt, sql)
    reg = srv.register("q", query, hospital.tables)
    if mode == "shadow":
        srv.stage_version("q", query.copy(), hospital.tables,
                          version_label="v2")
        srv.set_shadow("q", "v2")
    batches = [_batch(n, seed=60 + i) for i, n in enumerate(sizes)]
    want = _plan_answers(srv, reg, batches, mode)

    counting = _CountingJax()
    monkeypatch.setattr(query_server, "jax", counting)
    reqs = [srv.submit("q", b) for b in batches]
    srv.flush()
    got = [r.wait(timeout=60) for r in reqs]
    if mode == "shadow":
        deadline = time.monotonic() + 60
        while (srv.route_snapshot("q")["versions"]["v2"]["shadow_groups"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.005)
        v2 = srv.route_snapshot("q")["versions"]["v2"]
        assert v2["shadow_groups"] == 1 and v2["shadow_errors"] == 0
        assert v2["shadow_diff_rows"] == 0  # the mirror's split agrees bitwise
    srv.shutdown()

    assert len(counting.puts) == calls
    assert len(counting.gets) == calls
    # columns and validity, plus the row ids where requests share segments
    seg_in = mode in ("segmented", "aggregate")
    assert set(counting.puts) == {len(reg.scan_columns) + 1 + seg_in}
    assert set(counting.gets) == {len(want[0]) + 1 + (mode == "segmented")}
    for r, out, ref in zip(reqs, got, want):
        assert r.done and r.error is None
        assert sorted(out) == sorted(ref)
        for k in ref:
            assert out[k].dtype == ref[k].dtype
            np.testing.assert_array_equal(out[k], ref[k], err_msg=k)
