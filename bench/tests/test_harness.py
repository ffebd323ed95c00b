"""CPU tests of the chip benchmark's harness, at a tiny size.

Run from the repository root: ``python -m pytest -q bench/tests``.
"""
from __future__ import annotations

import copy
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import data, model, reference, trace, traffic, work
from bench import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
DRIVE = os.path.join(BENCH, "tests", "drive.py")


def load(path):
    with open(path) as f:
        return json.load(f)


def tiny_config(name: str) -> dict:
    cfg = copy.deepcopy(load(os.path.join(BENCH, "configs", f"{name}.json")))
    cfg["name"] = f"tiny_{name}"
    cfg["fact_rows"] = 3000
    for t, spec in cfg["tables"].items():
        if "rows" in spec:
            spec["rows"] = 64
    cfg["model"]["n_estimators"] = 6
    cfg["model"]["max_depth"] = 3
    return cfg


TINY_BULK = {"loop": "closed", "clients": 2, "request_rows": [512, 512],
             "pool_rows": 4096, "distinct_requests": 4, "max_latency_ms": 2.0,
             "max_coalesce": 512, "check_requests": 2}
TINY_STREAM = {"loop": "open", "rate_per_s": 40, "request_rows": [64, 256],
               "pool_rows": 4096, "max_latency_ms": 2.0, "max_coalesce": 256,
               "check_requests": 10}


def env_cpu() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    return env


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout holding the harness, BENCHMARK.json and, added as files
    and entries only, a tiny configuration, traffic mix and metric."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    cfg = tiny_config("hospital_gb300")
    (root / "bench" / "configs" / "tiny_hospital.json").write_text(json.dumps(cfg))
    (root / "bench" / "configs" / "tiny_flights.json").write_text(
        json.dumps(tiny_config("flights_gb20")))
    (root / "bench" / "traffic" / "tiny_bulk.json").write_text(json.dumps(TINY_BULK))
    (root / "bench" / "traffic" / "tiny_stream.json").write_text(json.dumps(TINY_STREAM))
    (root / "bench" / "metrics" / "requests_done.py").write_text(
        "def read(ctx):\n    return float(len(ctx.completed()))\n")
    bench["configs"] += [
        {"name": "tiny_hospital", "source": "test", "file": "bench/configs/tiny_hospital.json",
         "reduced": [], "why": "test"},
        {"name": "tiny_flights", "source": "test", "file": "bench/configs/tiny_flights.json",
         "reduced": [], "why": "test"}]
    bench["workloads"] += [
        {"name": "tiny_bulk", "config": "tiny_hospital", "traffic": "tiny_bulk",
         "chips": 1, "why": "test"},
        {"name": "tiny_stream", "config": "tiny_flights", "traffic": "tiny_stream",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] += ["tiny_bulk"] if m["name"] == "rows_per_s" else ["tiny_stream"]
    bench["end_to_end"].append({"name": "requests_done", "unit": "requests",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock", "workloads": ["tiny_stream"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def drive(root, workload, seed, seconds=2.0, trace_on=0, fault=None) -> dict:
    cmd = [sys.executable, DRIVE, str(root), workload, str(seed), str(seconds),
           str(trace_on)] + ([fault] if fault else [])
    p = subprocess.run(cmd, cwd=root, env=env_cpu(), capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# the command
# ---------------------------------------------------------------------------


def test_run_exits_nonzero_without_an_accelerator():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hospital_gb300_bulk",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env_cpu(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "nothing was run" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "flights_gb20_stream",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_cell_config_traffic_and_metric_added_as_files_are_found(checkout):
    out = drive(checkout, "tiny_stream", 2**31 + 11)
    assert out["correct"] is True, out
    assert set(out["metrics"]) == {"p50_ms", "setup_s", "requests_done"}
    assert out["metrics"]["requests_done"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["score_gap"]["value"] <= bench_run.SCORE_GAP_LIMIT


@pytest.mark.parametrize("fault", ["alter_answer", "nan_answer", "drop_rows"])
def test_a_fault_in_the_timed_path_makes_the_run_incorrect(checkout, fault):
    out = drive(checkout, "tiny_bulk", 3_000_000_019, fault=fault)
    assert out["correct"] is False, out


# ---------------------------------------------------------------------------
# the yardstick
# ---------------------------------------------------------------------------


def test_tree_gemm_work_is_the_unpadded_hand_count():
    assert work.tree_gemm_flops(300, 59, 63, 64) == 300 * (2 * 59 * 63 + 2 * 63 * 64)
    # one tree of depth 1 over 2 features: x·A is 2x1, D·C is 1x2
    assert work.tree_gemm_flops(1, 2, 1, 2) == 2 * 2 * 1 + 2 * 1 * 2
    assert work.tree_gemm_bytes(59) == 4 * 59 + 4
    # hospital: 9 float32 numerics and 15 int32 codes in, 59 float32 out
    assert work.featurize_bytes(9, 15, 59) == 4 * 9 + 4 * 15 + 4 * 59


def test_counts_of_a_built_ensemble_ignore_padding():
    cfg = tiny_config("hospital_gb300")
    feat, forest = model.build(cfg, data.warehouse(cfg))
    F = len(np.unique(forest.feature))
    assert (forest.n_internal, forest.n_leaves) == (7, 8)
    assert work.tree_gemm_flops(forest.n_trees, F, forest.n_internal,
                                forest.n_leaves) == 6 * (2 * F * 7 + 2 * 7 * 8)


@pytest.mark.parametrize("key,value", [("kind", "random_forest"),
                                       ("post_transform", "none")])
def test_a_model_the_reference_does_not_score_is_refused(key, value):
    cfg = tiny_config("hospital_gb300")
    cfg["model"][key] = value
    with pytest.raises(ValueError, match="not supported"):
        model.build(cfg, data.warehouse(cfg))


def test_least_time_names_the_bound_that_binds():
    peak = work.peaks("TPU v5 lite")
    t, bound = work.least_time_s(197e12, 1.0, peak)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = work.least_time_s(1.0, 819e9, peak)
    assert bound == "memory" and t == pytest.approx(1.0)
    with pytest.raises(KeyError):
        work.peaks("TPU v99")


def test_traffic_gives_every_seed_the_same_work():
    mix = dict(TINY_STREAM, rate_per_s=100)
    a = traffic.sizes(mix, 500, traffic.rng_for(1, 3))
    b = traffic.sizes(mix, 500, traffic.rng_for(2**31 + 5, 3))
    assert sorted(a) == sorted(b) and list(a) != list(b)
    assert a.min() >= 64 and a.max() <= 256
    d = traffic.due_times(mix, 10.0, traffic.rng_for(9, 2))
    assert len(d) == 1000 and d[0] == 0.0 and d[-1] < 10.0
    assert np.all(np.diff(d) > 0)
    assert traffic.warm_sizes(TINY_STREAM) == [64, 128, 256]
    assert traffic.warm_sizes(TINY_BULK) == [512]


def _span(start, dur):
    return ["/host:CPU", "main", trace.WINDOW_SPAN, start, dur]


def _op(name, start, dur, plane="/device:TPU:0"):
    return [plane, trace.DEVICE_OPS_LINE, name, start, dur]


def test_trace_reduction_by_hand():
    events = [
        _span(100.0, 1000.0),
        _op("%fusion.1 = f32[8] fusion(%x)", 50.0, 100.0),  # clipped: [100, 150]
        _op("%tree_gemm.2 = f32[8,1] custom-call(%x)", 140.0, 200.0),  # union to 340
        _op("%featurize = f32[8,59] custom-call(%a)", 500.0, 100.0),
        # reads tree_gemm's output: not the kernel
        _op("%divide.3 = f32[8] divide(%tree_gemm.2)", 600.0, 0.0),
        _op("%tree_gemm.2 = f32[8,1] custom-call(%x)", 1050.0, 200.0),  # [1050, 1100]
        ["/host:CPU", "pump", "Execute", 340.0, 160.0],
    ]
    assert trace.window_s(events) == pytest.approx(1e-6)
    busy = (340 - 100) + 100 + 50
    assert trace.busy_s(events) == pytest.approx(busy * 1e-9)
    s, n = trace.kernel_s(events, "tree_gemm")
    assert n == 2 and s == pytest.approx((200 + 50) * 1e-9)
    assert trace.kernel_s(events, "featurize") == (pytest.approx(100e-9), 1)
    assert trace.kernel_s(events, "gather_join") == (0.0, 0)
    gaps = trace.idle_gaps(events)
    assert gaps[0][0] == "host: none | divide.3 -> tree_gemm.2"  # [600, 1050]
    assert gaps[0][1] == pytest.approx(450e-9)
    assert gaps[1] == ["host: Execute | tree_gemm.2 -> featurize",
                       pytest.approx(160e-9)]
    assert trace.top_ops(events)[0] == ["tree_gemm.2", pytest.approx(250e-9)]


def test_trace_reduction_on_a_recorded_trace():
    rec = load(os.path.join(BENCH, "testdata", "trace_events.json"))
    events, want = rec["events"], rec["expected"]
    assert trace.window_s(events) == pytest.approx(want["window_s"])
    assert trace.busy_s(events) == pytest.approx(want["busy_s"])
    for kernel, (seconds, count) in want["kernels"].items():
        s, n = trace.kernel_s(events, kernel)
        assert n == count and s == pytest.approx(seconds)
    # an independent count: the busy time is the measure of the union of
    # the clipped op intervals, rebuilt here on a 100 ns grid
    lo, hi = trace.window(events)
    grid = np.zeros(int((hi - lo) / 100) + 1, bool)
    for e in trace.device_ops(events)[want["plane"]]:
        a, b = max(e[3], lo), min(e[3] + e[4], hi)
        if b > a:
            grid[int(round((a - lo) / 100)):int(round((b - lo) / 100))] = True
    assert grid.sum() * 100e-9 == pytest.approx(trace.busy_s(events), rel=1e-3)
    # the kernel's time is the sum of its own events, clipped
    assert want["kernels"]["tree_gemm"][1] == 2
    assert 0 < want["kernels"]["tree_gemm"][0] <= trace.busy_s(events)


# ---------------------------------------------------------------------------
# the reference and its control
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["hospital_gb300", "flights_gb20"])
def test_reference_agrees_with_the_served_path(name):
    from bench import program

    cfg = tiny_config(name)
    tables = data.warehouse(cfg)
    feat, forest = model.build(cfg, tables)
    pool = data.fact_pool(cfg, 2**31 + 3, 700)
    served = program.Served(cfg, tables, feat, forest,
                            {"max_latency_ms": 2.0, "max_coalesce": 1024})
    try:
        got = served.submit(pool).wait(timeout=300)["score"]
    finally:
        served.close()
    ref = reference.scores(pool, tables, cfg["joins"], feat, forest)
    assert np.max(np.abs(np.asarray(got, np.float64) - ref)) <= bench_run.SCORE_GAP_LIMIT


@pytest.mark.parametrize("seed", [11, 2**31 + 7, 4_000_000_003])
def test_the_control_fails_the_limit(seed):
    """The reference at Precision.HIGH, in the program's place, reads a
    score gap over the limit: the lower precision is caught."""
    cfg = tiny_config("hospital_gb300")
    cfg["model"]["n_estimators"] = 40
    tables = data.warehouse(cfg)
    feat, forest = model.build(cfg, tables)
    pool = data.fact_pool(cfg, seed, 4000)
    ref = reference.scores(pool, tables, cfg["joins"], feat, forest)
    ctl = reference.scores(pool, tables, cfg["joins"], feat, forest, "high")
    assert np.max(np.abs(ctl - ref)) > 10 * bench_run.SCORE_GAP_LIMIT


def test_bf16_rounding_is_nearest_even():
    x = np.array([1.0, 1.0 + 2**-8, 1.0 + 2**-7 + 2**-8, -3.14159], np.float32)
    got = reference.bf16(x)
    assert got[0] == 1.0
    assert got[1] == 1.0                  # tie rounds to even
    assert got[2] == 1.0 + 2**-6          # tie rounds up to even
    assert abs(got[3] + 3.140625) < 1e-6
    x = np.float32(1.0 + 2**-9 + 2**-20 + 2**-22)
    # hi = 1.0 and lo = bf16(2**-9 + ...) = 2**-9: the bits below drop
    assert reference.high_pass(x) == np.float32(1.0 + 2**-9)
