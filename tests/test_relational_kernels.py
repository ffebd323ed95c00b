"""Relational Pallas kernels vs oracles: the bitwise parity contract.

Extends the kernel-vs-host parity oracle (``kernel_parity`` marker — the CI
kernel-parity job runs exactly these) to the relational kernels:

  * ``gather_join`` (dim-table equi-join gather, upstream filter mask fused)
    and ``segment_agg`` (masked segmented sum/count/min/max) in Pallas
    interpret mode must match their pure-jnp oracles *bit-for-bit* across
    ragged rows, non-multiple-of-block shapes, zero-row inputs,
    all-rows-filtered masks, and single-segment aggregates;
  * at the plan level, ``RAVEN_KERNELS=off`` (the legacy inline-jnp stage
    composition) must be bitwise equal to the kernel path — data is dyadic
    rational (small ints × 0.25) so f32 sums are exact and order-free;
  * the Join stage consumes the stage-build-time baked dim order: the
    entry stage's lowered StableHLO contains no sort when the dimsort env
    entry is present, and the kernel-mode token forks stage and plan
    fingerprints so the two modes never alias compiled artifacts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _bits(a) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(a, np.float32)).view(np.uint32)


def _assert_bitwise(got, want, what: str) -> None:
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, f"{what}: shape {got.shape} != {want.shape}"
    if got.dtype == bool:
        assert np.array_equal(got, want), f"{what}: boolean mismatch"
    else:
        assert np.array_equal(_bits(got), _bits(want)), f"{what}: bit mismatch"


def _dyadic(rng, shape, lo=-40, hi=40):
    return (rng.integers(lo, hi, size=shape) * 0.25).astype(np.float32)


# ---------------------------------------------------------------------------
# gather_join: kernel (interpret) vs jnp oracle
# ---------------------------------------------------------------------------


@pytest.mark.kernel_parity
@pytest.mark.parametrize("N", [0, 1, 100, 256, 257])
@pytest.mark.parametrize("M", [1, 7, 128, 130])
def test_gather_join_kernel_bitwise(N, M):
    """Ragged row counts (incl. non-multiples of ``block_n`` and zero rows)
    × dim-table sizes straddling the 128-lane boundary; ~1/3 of fact keys
    miss the dim table — misses must zero their payload and clear ``hit``
    identically in both paths."""
    rng = np.random.default_rng(N * 1000 + M)
    keys = np.sort(rng.choice(3 * M, size=M, replace=False)).astype(np.int32)
    fk = rng.integers(0, 3 * M, size=N).astype(np.int32)  # ~2/3 hit rate
    spay = _dyadic(rng, (M, 3))
    got_out, got_hit = ops.gather_join_op(
        jnp.asarray(fk), jnp.asarray(keys), jnp.asarray(spay), interpret=True
    )
    want_out, want_hit = ref.gather_join_ref(
        jnp.asarray(fk), jnp.asarray(keys), jnp.asarray(spay)
    )
    _assert_bitwise(got_out, want_out, "payload")
    _assert_bitwise(np.asarray(got_hit), np.asarray(want_hit), "hit mask")
    # the hit mask is the ground-truth membership test
    assert np.array_equal(np.asarray(got_hit), np.isin(fk, keys))


@pytest.mark.kernel_parity
def test_gather_join_all_misses_and_all_hits():
    rng = np.random.default_rng(5)
    keys = np.arange(10, dtype=np.int32)
    spay = _dyadic(rng, (10, 2))
    miss = (np.arange(50, dtype=np.int32) + 100)
    out, hit = ops.gather_join_op(
        jnp.asarray(miss), jnp.asarray(keys), jnp.asarray(spay), interpret=True
    )
    assert not np.asarray(hit).any()
    assert not np.asarray(out).any()
    every = np.repeat(keys, 5)
    out2, hit2 = ops.gather_join_op(
        jnp.asarray(every), jnp.asarray(keys), jnp.asarray(spay), interpret=True
    )
    assert np.asarray(hit2).all()
    _assert_bitwise(out2, spay[every], "gathered payload")


# ---------------------------------------------------------------------------
# segment_agg: kernel (interpret) vs jnp oracle
# ---------------------------------------------------------------------------


@pytest.mark.kernel_parity
@pytest.mark.parametrize("N", [0, 1, 100, 256, 257])
@pytest.mark.parametrize("S", [1, 4, 5])
def test_segment_agg_kernel_bitwise(N, S):
    """Masked segmented aggregate across ragged rows / non-multiple-of-block
    shapes / a single segment; ~1/3 of rows filtered out via the weight
    column. counts/sums/mins/maxs must all be bit-identical (±inf sentinels
    for empty segments included)."""
    rng = np.random.default_rng(N * 100 + S)
    vals = _dyadic(rng, (N, 3))
    w = (rng.random(N) > 1 / 3).astype(np.float32)
    sid = rng.integers(0, S, size=N).astype(np.int32)
    got = ops.segment_agg_op(
        jnp.asarray(vals), jnp.asarray(w), jnp.asarray(sid),
        num_segments=S, interpret=True,
    )
    want = ref.segment_agg_ref(
        jnp.asarray(vals), jnp.asarray(w), jnp.asarray(sid), num_segments=S
    )
    for g, x, what in zip(got, want, ("counts", "sums", "mins", "maxs")):
        _assert_bitwise(g, x, what)


@pytest.mark.kernel_parity
def test_segment_agg_all_rows_filtered():
    """w == 0 everywhere: zero counts/sums, ±inf extrema — in both paths."""
    rng = np.random.default_rng(9)
    N, S = 130, 3
    vals = _dyadic(rng, (N, 2))
    w = np.zeros(N, np.float32)
    sid = rng.integers(0, S, size=N).astype(np.int32)
    counts, sums, mins, maxs = ops.segment_agg_op(
        jnp.asarray(vals), jnp.asarray(w), jnp.asarray(sid),
        num_segments=S, interpret=True,
    )
    assert not np.asarray(counts).any()
    assert not np.asarray(sums).any()
    assert (np.asarray(mins) == np.inf).all()
    assert (np.asarray(maxs) == -np.inf).all()
    want = ref.segment_agg_ref(
        jnp.asarray(vals), jnp.asarray(w), jnp.asarray(sid), num_segments=S
    )
    for g, x, what in zip((counts, sums, mins, maxs), want,
                          ("counts", "sums", "mins", "maxs")):
        _assert_bitwise(g, x, what)


# ---------------------------------------------------------------------------
# Plan level: RAVEN_KERNELS on/off bit-compat, host oracle, fingerprints
# ---------------------------------------------------------------------------


def _star_tables(n=200, m=16, seed=3):
    """Star schema with dyadic-rational values: f32 sums are exact, so every
    execution path must agree bit-for-bit."""
    rng = np.random.default_rng(seed)
    dim = {
        "k": np.arange(m, dtype=np.int64),
        "v1": _dyadic(rng, m),
        "v2": _dyadic(rng, m),
    }
    fact = {
        # leave some keys unmatched so the join actually filters
        "fk": rng.integers(0, m + 4, size=n).astype(np.int64),
        "x": _dyadic(rng, n),
    }
    return {"f": fact, "d": dim}


def _relational_plan():
    from repro.relational.engine import Aggregate, Filter, Join, Scan
    from repro.relational.expr import Bin, Col, Const

    return Aggregate(
        Filter(
            Join(Scan("f", ["fk", "x"]), "d", "fk", "k", ["v1", "v2"]),
            Bin("gt", Col("x"), Const(0.0)),
        ),
        [
            ("n", "count", "x"), ("sum_x", "sum", "x"),
            ("avg_v1", "mean", "v1"), ("min_v1", "min", "v1"),
            ("max_v2", "max", "v2"),
        ],
    )


def _host_oracle(tables):
    """Careful-f32 numpy reference for the filter→join→group-by plan."""
    f, d = tables["f"], tables["d"]
    pos = np.searchsorted(d["k"], np.clip(f["fk"], d["k"][0], d["k"][-1]))
    pos = np.clip(pos, 0, len(d["k"]) - 1)
    hit = d["k"][pos] == f["fk"]
    mask = hit & (f["x"] > 0)
    x = f["x"][mask].astype(np.float32)
    v1 = d["v1"][pos][mask].astype(np.float32)
    v2 = d["v2"][pos][mask].astype(np.float32)
    n = np.float32(mask.sum())
    out = {
        "n": n,
        "sum_x": np.float32(x.astype(np.float64).sum()),  # dyadic: exact
        "avg_v1": np.float32(v1.astype(np.float64).sum()) / max(n, np.float32(1)),
        "min_v1": v1.min() if len(v1) else np.float32(0),
        "max_v2": v2.max() if len(v2) else np.float32(0),
    }
    return out


def _run_mode(tables, mode, monkeypatch, segments=None):
    from repro.relational.engine import clear_plan_cache, compile_plan

    monkeypatch.setenv("RAVEN_KERNELS", mode)
    clear_plan_cache()
    try:
        cp = compile_plan(_relational_plan(), cache=False)
        db = {t: {c: jnp.asarray(v) for c, v in cols.items()}
              for t, cols in tables.items()}
        res = cp.run(db, segments=segments)
        return {k: np.asarray(v) for k, v in
                res.table.to_numpy(compact=True).items()}
    finally:
        monkeypatch.delenv("RAVEN_KERNELS", raising=False)
        clear_plan_cache()


@pytest.mark.kernel_parity
def test_plan_level_kernels_on_off_bitwise_and_match_host(monkeypatch):
    tables = _star_tables()
    on = _run_mode(tables, "on", monkeypatch)
    off = _run_mode(tables, "off", monkeypatch)
    want = _host_oracle(tables)
    assert set(on) == set(off) == set(want)
    for k in want:
        _assert_bitwise(on[k].reshape(-1), off[k].reshape(-1),
                        f"on-vs-off {k}")
        _assert_bitwise(on[k].reshape(-1)[:1],
                        np.asarray(want[k], np.float32).reshape(-1),
                        f"kernel-vs-host {k}")


@pytest.mark.kernel_parity
def test_plan_level_segmented_on_off_bitwise(monkeypatch):
    """Coalesced serving shape: per-row request-segment ids thread a
    *segmented* aggregate through the kernel — on/off must stay bitwise
    equal per segment."""
    tables = _star_tables(n=150, seed=11)
    rng = np.random.default_rng(2)
    seg = np.sort(rng.integers(0, 6, size=150)).astype(np.int32)
    on = _run_mode(tables, "on", monkeypatch, segments=(seg, 6))
    off = _run_mode(tables, "off", monkeypatch, segments=(seg, 6))
    assert set(on) == set(off)
    for k in on:
        _assert_bitwise(on[k], off[k], f"segmented on-vs-off {k}")


def test_kernel_mode_forks_relational_fingerprints(monkeypatch):
    from repro.relational.engine import Scan, clear_plan_cache, plan_fingerprint

    plan = _relational_plan()
    monkeypatch.setenv("RAVEN_KERNELS", "on")
    clear_plan_cache()
    fp_on = plan_fingerprint(plan)
    monkeypatch.setenv("RAVEN_KERNELS", "off")
    clear_plan_cache()
    fp_off = plan_fingerprint(plan)
    assert fp_on != fp_off
    # plans with no Join/Aggregate must NOT fork on the knob
    scan = Scan("f", ["fk", "x"])
    monkeypatch.setenv("RAVEN_KERNELS", "on")
    s_on = plan_fingerprint(scan)
    monkeypatch.setenv("RAVEN_KERNELS", "off")
    s_off = plan_fingerprint(scan)
    assert s_on == s_off
    monkeypatch.delenv("RAVEN_KERNELS", raising=False)
    clear_plan_cache()


def test_baked_dim_order_eliminates_argsort():
    """Satellite fix: the Join stage must consume the stage-build-time baked
    sort order instead of re-sorting dim keys inside the traced fn — no
    sort op in the entry stage's StableHLO when the dimsort env entry is
    present (and one when it isn't, via the fallback path)."""
    from repro.exec.stages import DIMSORT_KEY, build_stage_graph
    from repro.relational.engine import Join, Scan, dimsort_entry

    tables = _star_tables()
    plan = Join(Scan("f", ["fk", "x"]), "d", "fk", "k", ["v1", "v2"])
    graph = build_stage_graph(plan)
    stage = graph.stages[0]
    env = {t: {c: jnp.asarray(v) for c, v in cols.items()}
           for t, cols in tables.items()}
    with_sorted = jax.jit(stage.fn).lower(
        {**env, DIMSORT_KEY: {"d": dimsort_entry(env["d"]["k"])}}
    ).as_text()
    without = jax.jit(stage.fn).lower(env).as_text()
    assert "stablehlo.sort" not in with_sorted
    assert "stablehlo.sort" in without


def test_dimsort_cache_is_content_keyed():
    """Two distinct jnp arrays with equal content share one cache entry;
    changed content gets a fresh one. Uniqueness marks the kernel-eligible
    entries."""
    from repro.relational.engine import dimsort_entry

    a = dimsort_entry(jnp.asarray(np.array([5, 1, 3], np.int64)))
    b = dimsort_entry(jnp.asarray(np.array([5, 1, 3], np.int64)))
    assert a is b
    c = dimsort_entry(jnp.asarray(np.array([5, 1, 4], np.int64)))
    assert c is not a
    assert "unique" in a
    dup = dimsort_entry(jnp.asarray(np.array([5, 1, 5], np.int64)))
    assert "unique" not in dup
    assert np.array_equal(np.asarray(a["keys"]), [1, 3, 5])
    # stable order: matches jnp.argsort on ties so the fallback gather and
    # the baked gather agree even with duplicate keys
    assert np.array_equal(
        np.asarray(dup["order"]), np.asarray(jnp.argsort(jnp.asarray([5, 1, 5])))
    )


# ---------------------------------------------------------------------------
# qualification: a Join the kernel cannot hold keeps the jnp gather, and
# EXPLAIN names the choice and its reason
# ---------------------------------------------------------------------------


def _join_explain(dim_rows: int, payload_dtype) -> str:
    import repro as raven
    from repro.ml import LogisticRegression, fit_pipeline

    rng = np.random.default_rng(5)
    dim = {"k": np.arange(dim_rows, dtype=np.int64),
           "v1": _dyadic(rng, dim_rows).astype(payload_dtype)}
    fact = {"fk": rng.integers(0, dim_rows, 256).astype(np.int64),
            "x": _dyadic(rng, 256)}
    pipe = fit_pipeline(
        {"x": fact["x"], "v1": dim["v1"][fact["fk"]].astype(np.float32)},
        (fact["x"] > 0).astype(np.int64), ["x", "v1"], [],
        LogisticRegression(n_iter=5),
    )
    db = raven.connect({"f": fact, "d": dim})
    db.models.publish("m", pipe)
    return db.sql(
        "SELECT COUNT(*) FROM PREDICT(model='m', data=f JOIN d ON fk = k) "
        "AS p WHERE x > 0"
    ).prepare(transform="sql").explain()


@pytest.mark.parametrize(
    "dim_rows,dtype,want",
    [
        (1024, np.float32, "tensor/kernel: gather_join"),
        (1024, np.int32, "tensor/jnp: argsort+searchsorted gather "
                         "(payload columns not all f32)"),
        (40_000, np.float32, "payload exceeds the kernel's VMEM budget"),
    ],
)
def test_explain_names_the_join_runtime_and_why(dim_rows, dtype, want):
    explain = _join_explain(dim_rows, dtype)
    assert "Join[d] on fk=k" in explain
    assert want in explain
    assert ("tensor/kernel: segment_agg, filter folded in as mask (groups of "
            "more than 8192 requests: jnp masked segment ops") in explain


def test_gather_join_block_n_respects_the_vmem_budget():
    from repro.kernels import VMEM_BUDGET_BYTES
    from repro.kernels.relational import (
        gather_join_block_n,
        gather_join_vmem_bytes,
    )

    assert gather_join_block_n(1024, 2) == 256
    bn = gather_join_block_n(16_384, 2)
    assert bn is not None and 8 <= bn < 256
    assert gather_join_vmem_bytes(bn, 16_384, 2) <= VMEM_BUDGET_BYTES
    assert gather_join_vmem_bytes(2 * bn, 16_384, 2) > VMEM_BUDGET_BYTES
    assert gather_join_block_n(100_000, 128) is None
    with pytest.raises(ValueError, match="cannot hold"):
        ops.gather_join_op(
            jnp.zeros((8,), jnp.int32), jnp.arange(100_000, dtype=jnp.int32),
            jnp.zeros((100_000, 128), jnp.float32), interpret=True,
        )


def test_segment_agg_block_n_respects_the_vmem_budget():
    from repro.kernels import VMEM_BUDGET_BYTES
    from repro.kernels.relational import (
        segment_agg_block_n,
        segment_agg_max_segments,
        segment_agg_vmem_bytes,
    )

    assert segment_agg_block_n(64, 5) == 256
    bn = segment_agg_block_n(8192, 5)
    assert bn is not None and 8 <= bn < 256
    assert segment_agg_vmem_bytes(bn, 8192, 5) <= VMEM_BUDGET_BYTES
    assert segment_agg_vmem_bytes(2 * bn, 8192, 5) > VMEM_BUDGET_BYTES
    assert segment_agg_block_n(16_384, 5) is None
    assert segment_agg_max_segments(5) == 8192
    with pytest.raises(ValueError, match="cannot hold"):
        ops.segment_agg_op(
            jnp.zeros((8, 5), jnp.float32), jnp.ones((8,), jnp.float32),
            jnp.zeros((8,), jnp.int32), num_segments=16_384, interpret=True,
        )


@pytest.mark.kernel_parity
def test_aggregate_beyond_the_kernel_keeps_the_jnp_segment_ops(monkeypatch):
    """A coalesced group with more slots than ``segment_agg`` holds runs the
    jnp segment ops — bitwise the same answers — and EXPLAIN states the
    bound."""
    import repro.kernels.relational as rel
    import repro.tensor.compile as tc

    emitted = []
    real_emit = tc.emit_aggregate_kernel
    monkeypatch.setattr(
        tc, "emit_aggregate_kernel",
        lambda *a: emitted.append(a[-1]) or real_emit(*a),
    )
    tables = _star_tables(n=150, seed=11)
    seg = np.sort(np.random.default_rng(2).integers(0, 6, size=150))
    seg = (seg.astype(np.int32), 6)
    kernel = _run_mode(tables, "on", monkeypatch, segments=seg)
    assert emitted == [8]  # the slot bucket of 6 requests
    monkeypatch.setattr(rel, "segment_agg_block_n", lambda S, C: None)
    emitted.clear()
    fallback = _run_mode(tables, "on", monkeypatch, segments=seg)
    assert emitted == []
    for k in kernel:
        _assert_bitwise(kernel[k], fallback[k], f"kernel-vs-fallback {k}")
    assert tc.aggregate_kernel_choice([("n", "count", "x")], 8) == (
        "8 segments exceed the kernel's VMEM budget"
    )
