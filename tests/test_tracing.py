"""Spans and counters of the serving path, and the benchmark readers that
turn them into per-layer metrics.

The served path opens ``raven.*`` spans (``repro.obs``) that land on the
profiler's clock; the scheduler counts each request's wait in its queue.
``bench/spans.py`` and the readers in ``bench/metrics/`` reduce both.
"""
from __future__ import annotations

import glob
import os
import time
from types import SimpleNamespace

import jax
import pytest

import repro as raven
from bench import spans, trace
from bench import run as bench_run
from repro.data.datasets import make_hospital
from repro.exec.scheduler import Scheduler

SQL = "SELECT * FROM PREDICT(model='m', data=patients) AS p WHERE score >= :t"
GROUP_STEPS = ["raven.h2d", "raven.stage", "raven.device_wait", "raven.d2h"]


def _batch(n, seed):
    return make_hospital(n, seed=seed).tables["patients"]


@pytest.fixture()
def served(hospital, hospital_dt):
    db = raven.connect(hospital.tables, stats="auto")
    db.register_model("m", hospital_dt)
    prep = db.sql(SQL).prepare(transform="dnn", params={"t": 0.5}).serve(
        options=raven.ServeOptions(max_latency_ms=2.0))
    prep.submit(_batch(100, seed=1)).wait(timeout=60)  # warm the bucket
    try:
        yield db, prep
    finally:
        db.close()


def _host_lines(log_dir: str) -> list[list[tuple]]:
    """Each host thread's events as ``(name, start_ns, end_ns, stats)``;
    unlike ``trace.extract`` this keeps the thread and the span ids."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            lines.append([(e.name, e.start_ns, e.start_ns + e.duration_ns,
                           dict(e.stats)) for e in line.events])
    return lines


def _serve_traced(db, prep, log_dir: str, seeds) -> list:
    """Serve one 100-row request per seed under the profiler; returns the
    requests once every group has left its span."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        reqs = [prep.submit(_batch(100, seed=s)) for s in seeds]
        for r in reqs:
            r.wait(timeout=60)
        # answers are out before the scheduler leaves the group's span
        deadline = time.monotonic() + 60
        while (db.cache_stats()["server"]["groups_inflight"]
               and time.monotonic() < deadline):
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    return reqs


def _group_spans(log_dir: str) -> list[tuple[dict, list[tuple]]]:
    """Each ``raven.group`` span's ids with the spans nested in it on its
    thread, in order."""
    out = []
    for line in _host_lines(log_dir):
        for name, a, b, stats in line:
            if name == "raven.group":
                inside = sorted((e for e in line if e[0] != "raven.group"
                                 and a <= e[1] and e[2] <= b),
                                key=lambda e: e[1])
                out.append((stats, inside))
    return out


def test_a_served_group_nests_its_steps_in_order_on_one_thread(served, tmp_path):
    db, prep = served
    reqs = _serve_traced(db, prep, str(tmp_path), (2, 3))

    # the benchmark's reduction keeps every span
    names = {e[2] for e in trace.extract(str(tmp_path))}
    assert {"raven.submit", "raven.group", *GROUP_STEPS} <= names

    groups = _group_spans(str(tmp_path))
    for stats, inside in groups:
        inside = [e for e in inside if e[0] in GROUP_STEPS]
        assert [e[0] for e in inside] == GROUP_STEPS
        assert {e[3]["group"] for e in inside} == {stats["group"]}
        assert stats["requests"] >= 1 and stats["rows"] >= 100
    assert len(groups) >= 1
    submits = [s for line in _host_lines(str(tmp_path))
               for name, _a, _b, s in line if name == "raven.submit"]
    assert sorted(s["rid"] for s in submits) == sorted(r.rid for r in reqs)
    assert all(r.group >= 1 for r in reqs)


def test_queue_wait_counters_advance_by_the_requests_served(served):
    db, prep = served
    before = db.cache_stats()["server"]
    reqs = [prep.submit(_batch(64, seed=s)) for s in (4, 5, 6)]
    for r in reqs:
        r.wait(timeout=60)
    after = db.cache_stats()["server"]
    assert after["queue_waits"] - before["queue_waits"] == 3
    # the oldest request of each group waits out the 2 ms deadline
    assert after["queue_wait_us"] - before["queue_wait_us"] >= 2000


class _Req:
    def __init__(self, rid, t_submit):
        self.rid = rid
        self.t_submit = t_submit


def test_pop_counts_queue_wait_and_stamps_a_fresh_dispatch_id():
    sch = Scheduler(lambda name, group: None)
    now = time.perf_counter()
    reqs = [_Req(0, now - 0.5), _Req(1, now - 0.25), _Req(2, now)]
    for r in reqs:
        sch.enqueue("q", r, 1)
    with sch._cv:
        first, _ = sch._pop_group(sch._queues["q"])
    assert sch.snapshot()["queue_waits"] == 3
    assert sch.snapshot()["queue_wait_us"] >= 750_000
    assert len({r.group for r in first}) == 1
    sch.enqueue("q", _Req(3, time.perf_counter()), 1)
    with sch._cv:
        second, _ = sch._pop_group(sch._queues["q"])
    assert second[0].group > first[0].group


# ---------------------------------------------------------------------------
# the readers, on hand-built traces (times in ns; a 10 ms window)
# ---------------------------------------------------------------------------

MS = 1e6
WINDOW = ["/host:CPU", "python", "bench.window", 0.0, 10 * MS]


def op(start_ms, dur_ms):
    return ["/device:TPU:0", "XLA Ops", "%fusion.1 = f32[8] fusion()",
            start_ms * MS, dur_ms * MS]


def host(name, start_ms, dur_ms):
    return ["/host:CPU", "python", name, start_ms * MS, dur_ms * MS]


def ctx_of(events, before=None, after=None):
    before, after = before or {}, after or {}
    return SimpleNamespace(
        events=events,
        stat_delta=lambda k: int(after.get(k, 0)) - int(before.get(k, 0)))


def read(metric, ctx):
    return bench_run.reader(metric)(ctx)


def test_a_d2h_span_over_half_the_idle_time_reads_half():
    events = [WINDOW, op(0, 4), host("raven.d2h", 4, 3)]
    assert read("idle_in_d2h_share.bulk", ctx_of(events)) == pytest.approx(0.5)
    assert read("idle_unattributed_share.bulk", ctx_of(events)) == pytest.approx(0.5)
    assert read("idle_in_h2d_share.bulk", ctx_of(events)) is None


def test_idle_shares_count_overlapping_spans_once_and_skip_busy_time():
    # idle: 2-4 and 6-10 (6 ms); the group span covers 1-9, of which 2-4
    # and 6-9 are idle; h2d (3-5) covers 1 ms of idle, d2h (8-12) 2 ms
    events = [WINDOW, op(0, 2), op(4, 2), host("raven.group", 1, 8),
              host("raven.h2d", 3, 2), host("raven.d2h", 8, 4),
              host("other", 9, 1)]
    c = ctx_of(events)
    assert read("idle_in_h2d_share.bulk", c) == pytest.approx(1 / 6)
    assert read("idle_in_d2h_share.bulk", c) == pytest.approx(2 / 6)
    assert read("idle_unattributed_share.bulk", c) == pytest.approx(0.0)


def test_step_medians_take_the_spans_that_start_in_the_window():
    events = [WINDOW, op(0, 1), host("raven.h2d", 1, 1), host("raven.h2d", 3, 2),
              host("raven.h2d", 6, 3), host("raven.h2d", 11, 9),
              host("raven.d2h", 2, 0.5), host("raven.device_wait", 5, 0.25)]
    c = ctx_of(events)
    assert read("h2d_ms.stream", c) == pytest.approx(2.0)
    assert read("d2h_ms.stream", c) == pytest.approx(0.5)
    assert read("device_wait_ms.stream", c) == pytest.approx(0.25)


def test_queue_wait_is_the_mean_of_the_window_deltas():
    c = ctx_of(None, {"queue_wait_us": 1000, "queue_waits": 2},
               {"queue_wait_us": 31000, "queue_waits": 5})
    assert read("queue_wait_ms.stream", c) == pytest.approx(10.0)


@pytest.mark.parametrize("metric", [
    "queue_wait_ms.stream", "h2d_ms.stream", "device_wait_ms.stream",
    "d2h_ms.stream", "idle_in_h2d_share.bulk", "idle_in_d2h_share.bulk",
    "idle_unattributed_share.bulk"])
def test_a_program_without_spans_or_counters_reads_nothing(metric):
    """A program that records no span or counter, as one from before they
    were added, gives no value and raises nothing."""
    assert read(metric, ctx_of([WINDOW, op(0, 4), host("DevicePut", 4, 3)])) is None
    assert read(metric, ctx_of(None)) is None


@pytest.mark.parametrize("x, y, want", [
    ([(0, 4)], [(2, 6)], 2),
    ([(0, 1), (2, 3), (4, 5)], [(0.5, 4.5)], 2),
    ([(0, 1)], [(1, 2)], 0),
    ([], [(0, 1)], 0),
])
def test_overlap_of_merged_intervals(x, y, want):
    assert spans.overlap(x, y) == pytest.approx(want)


def test_union_and_idle_intervals():
    assert spans.union([(3, 5), (0, 2), (1, 3), (7, 8)]) == [(0, 5), (7, 8)]
    assert spans.idle_intervals([(1, 2), (4, 6)], 0, 10) == [(0, 1), (2, 4), (6, 10)]
    assert spans.idle_intervals([(0, 10)], 0, 10) == []


def test_each_group_copies_once_each_way_and_counts_the_arrays(served, tmp_path):
    db, prep = served
    (reg,) = db.server.queries.values()
    reqs = _serve_traced(db, prep, str(tmp_path), (7, 8, 9))
    (result_columns,) = {len(r.result) for r in reqs}

    groups = _group_spans(str(tmp_path))
    assert groups
    for _stats, inside in groups:
        h2d = [e[3] for e in inside if e[0] == "raven.h2d"]
        d2h = [e[3] for e in inside if e[0] == "raven.d2h"]
        # one batched copy each way: every input column and the validity
        # in, every result column and the validity out
        assert [s["arrays"] for s in h2d] == [len(reg.scan_columns) + 1]
        assert [s["arrays"] for s in d2h] == [result_columns + 1]
