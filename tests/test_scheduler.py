"""The pipelined executor + multi-queue scheduler: concurrency stress,
backpressure, fairness, and the zero-warm-trace invariant.

The serving contract under test: threaded submitters against multiple
queues never lose or misroute a result; a bounded queue rejects (or blocks)
submits at ``max_pending``; a small latency-targeted query keeps a bounded
p99 while a large coalesced group is in flight; and pipelined execution
runs the *same* jit specializations as the serial path, so previously
served buckets never re-trace (asserted via ``db.cache_stats()``).
"""
from __future__ import annotations

import threading
import time

import numpy as np
import pytest

import repro as raven
from repro.data.datasets import make_hospital
from repro.errors import ServerOverloadedError
from repro.exec.scheduler import Scheduler
from repro.relational.engine import clear_plan_cache
from repro.serve import PredictionQueryServer

SQL = "SELECT * FROM PREDICT(model='m', data=patients) AS p WHERE score >= :t"


@pytest.fixture()
def db(hospital, hospital_dt):
    sess = raven.connect(hospital.tables, stats="auto")
    sess.register_model("m", hospital_dt)
    yield sess
    sess.close()


def _batch(n, seed):
    return make_hospital(n, seed=seed).tables["patients"]


# ---------------------------------------------------------------------------
# Scheduler unit behavior
# ---------------------------------------------------------------------------


class _Req:
    def __init__(self, rid, t_submit):
        self.rid = rid
        self.t_submit = t_submit


def _pop(sch: Scheduler, q):
    with sch._cv:  # _pop_group's contract: caller holds the scheduler lock
        group, _attempt = sch._pop_group(q)
        return group


def test_pop_group_respects_coalesce_cap():
    sch = Scheduler(lambda name, group: None, default_coalesce=100)
    now = time.perf_counter()
    for i, n in enumerate((40, 40, 40, 200, 10)):
        sch.enqueue("q", _Req(i, now), n)
    q = sch._queues["q"]
    # 40+40 fits, +40 would exceed 100
    assert [r.rid for r in _pop(sch, q)] == [0, 1]
    assert [r.rid for r in _pop(sch, q)] == [2]  # 40+200 > 100
    assert [r.rid for r in _pop(sch, q)] == [3]  # oversize pops alone
    assert [r.rid for r in _pop(sch, q)] == [4]


def test_edf_picks_tightest_deadline_and_rotates_overdue():
    sch = Scheduler(lambda name, group: None)
    sch.configure("bulk", max_latency_ms=50.0)
    sch.configure("fast", max_latency_ms=5.0)
    t0 = time.perf_counter()
    sch.enqueue("bulk", _Req(0, t0), 1)
    sch.enqueue("fast", _Req(1, t0 + 0.010), 1)
    # before anything is overdue: fast's 15ms deadline < bulk's 50ms
    assert sch._earliest(now=t0 + 0.012).name == "fast"
    # both long overdue: least-recently-served wins, and alternates
    far = t0 + 10.0
    first = sch._earliest(now=far)
    _pop(sch, first)
    sch.enqueue(first.name, _Req(2, t0), 1)
    assert sch._earliest(now=far).name != first.name


def test_backpressure_blocks_then_raises_on_timeout():
    sch = Scheduler(lambda name, group: None)
    sch.configure("q", max_pending=2)
    now = time.perf_counter()
    sch.enqueue("q", _Req(0, now), 1)
    sch.enqueue("q", _Req(1, now), 1)
    with pytest.raises(ServerOverloadedError, match="max_pending=2"):
        sch.enqueue("q", _Req(2, now), 1, block=False)
    t0 = time.perf_counter()
    with pytest.raises(ServerOverloadedError):
        sch.enqueue("q", _Req(2, now), 1, timeout=0.15)
    assert time.perf_counter() - t0 >= 0.1  # actually waited
    assert sch.overloads == 2 and sch.backpressure_waits == 1
    # a concurrent pop unblocks a waiting submitter
    unblocked = threading.Event()

    def submitter():
        sch.enqueue("q", _Req(3, time.perf_counter()), 1, timeout=5.0)
        unblocked.set()

    t = threading.Thread(target=submitter)
    t.start()
    time.sleep(0.05)
    _pop(sch, sch._queues["q"])
    t.join(5.0)
    assert unblocked.is_set()


def test_blocking_submit_without_pump_fails_fast_instead_of_deadlocking():
    # block=True + timeout=None + no pump thread: nothing can ever free the
    # queue (flush() is unreachable from the blocked caller) — must raise,
    # not hang
    sch = Scheduler(lambda name, group: None)
    sch.configure("q", max_pending=1)
    sch.enqueue("q", _Req(0, time.perf_counter()), 1)
    with pytest.raises(ServerOverloadedError, match="no pump thread"):
        sch.enqueue("q", _Req(1, time.perf_counter()), 1)


def test_drain_waits_for_groups_the_pump_already_took():
    # the pump pops a group and its (slow) dispatch is still in flight when
    # drain() runs on an empty queue: drain must wait for it, preserving
    # the "submit, flush, read the result" contract
    from concurrent.futures import Future

    done = threading.Event()

    def slow_dispatch(name, group):
        fut: Future = Future()

        def finish():
            time.sleep(0.2)
            for r in group:
                r.served = True
            done.set()
            fut.set_result(group)

        threading.Thread(target=finish, daemon=True).start()
        return fut

    sch = Scheduler(slow_dispatch, default_latency_ms=1.0)
    sch.start()
    try:
        req = _Req(0, time.perf_counter())
        req.served = False
        sch.enqueue("q", req, 1)
        # wait until the pump has popped it (queue empty, group in flight)
        deadline = time.time() + 5.0
        while sch.depths().get("q") and time.time() < deadline:
            time.sleep(0.005)
        sch.drain()
        assert req.served, "drain returned before the in-flight group settled"
        assert done.is_set()
    finally:
        sch.stop()


# ---------------------------------------------------------------------------
# Server-level backpressure
# ---------------------------------------------------------------------------


def test_submit_overload_raises_and_recovers(db):
    prep = db.sql(SQL).prepare(transform="sql", params={"t": 0.6}).serve(
        name="bounded", max_pending=2,
    )
    r1 = prep.submit(_batch(8, seed=1))
    r2 = prep.submit(_batch(8, seed=2))
    with pytest.raises(ServerOverloadedError, match="bounded"):
        prep.submit(_batch(8, seed=3), block=False)
    with pytest.raises(ServerOverloadedError):
        prep.submit(_batch(8, seed=3), timeout=0.05)
    db.flush()  # frees the queue
    assert r1.done and r2.done
    r3 = prep.submit(_batch(8, seed=3), block=False)
    db.flush()
    assert r3.done
    stats = db.cache_stats()["server"]
    assert stats["overloads"] >= 2
    assert stats["max_queue_depth"] >= 2


def test_blocked_submit_proceeds_when_pump_frees_space(db):
    prep = db.sql(SQL).prepare(transform="sql", params={"t": 0.6}).serve(
        name="bounded2", max_pending=1, max_latency_ms=5,
    )
    reqs = [prep.submit(_batch(16, seed=i), timeout=30.0) for i in range(6)]
    outs = [r.wait(timeout=30.0) for r in reqs]
    assert all(o is not None for o in outs)


# ---------------------------------------------------------------------------
# Concurrency stress: no lost or misrouted results across queries
# ---------------------------------------------------------------------------


def test_threaded_submitters_two_queries_no_lost_or_misrouted(db):
    # one pure query and one UDF (host-boundary) query served from the same
    # scheduler; 4 submitter threads interleave batches whose 'age' column
    # encodes (thread, sequence) so any misrouting/mixup is detectable
    pure = db.sql(SQL).prepare(transform="sql", params={"t": -1e9}).serve(
        name="pure_q", max_latency_ms=3,
    )
    udf = db.sql(SQL).prepare(transform="none", params={"t": -1e9}).serve(
        name="udf_q", max_latency_ms=3,
    )
    n_threads, n_per = 4, 6
    results: dict[tuple, tuple] = {}
    errors: list[BaseException] = []
    lock = threading.Lock()

    def submitter(tid):
        try:
            for i in range(n_per):
                n = 16 + 8 * ((tid + i) % 3)
                b = dict(_batch(n, seed=100 + tid * 31 + i))
                tag = float(1000 * tid + i)
                b["age"] = np.full(n, tag)
                prep = pure if (tid + i) % 2 == 0 else udf
                req = prep.submit(b)
                out = req.wait(timeout=60.0)
                with lock:
                    results[(tid, i)] = (tag, n, out)
        except BaseException as e:  # pragma: no cover - the assertion target
            with lock:
                errors.append(e)

    threads = [threading.Thread(target=submitter, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(results) == n_threads * n_per  # nothing lost
    for (tid, i), (tag, n, out) in results.items():
        # threshold -1e9 keeps every row, so each request must get exactly
        # its own rows back — its tag, all n of them, nobody else's
        assert len(out["age"]) == n, (tid, i)
        np.testing.assert_array_equal(np.unique(out["age"]), [tag])


def test_small_query_p99_bounded_while_bulk_group_in_flight(db):
    # a large coalesced UDF group occupies the boundary pool; the small
    # pure query must keep flowing on its own deadline instead of queueing
    # behind the bulk work (EDF + overdue rotation + pipelined dispatch)
    bulk = db.sql(SQL).prepare(transform="none", params={"t": 0.6}).serve(
        name="bulk", max_latency_ms=100, max_coalesce=1500,
    )
    small = db.sql(SQL).prepare(transform="sql", params={"t": 0.6}).serve(
        name="small", max_latency_ms=5,
    )
    bulk.submit(_batch(1500, seed=0)).wait(timeout=60)  # warm bulk bucket
    small.submit(_batch(32, seed=1)).wait(timeout=60)   # warm small bucket
    bulk_reqs = [bulk.submit(_batch(1500, seed=10 + i)) for i in range(4)]
    lats = []
    for i in range(10):
        r = small.submit(_batch(32, seed=50 + i))
        r.wait(timeout=60.0)
        lats.append(r.latency_s)
        time.sleep(0.005)
    for r in bulk_reqs:
        r.wait(timeout=120.0)
    stats = db.cache_stats()["server"]
    assert stats["pipeline"]["overlapped_groups"] >= 1
    # generous bound for loaded CI boxes: the serial pump would hold every
    # small behind a full bulk-group execution (hundreds of ms); pipelined
    # dispatch keeps the p99 within tens of ms of the 5 ms target
    p99 = sorted(lats)[-1]
    assert p99 < 0.5, f"small-query p99 {p99 * 1e3:.1f}ms — starved by bulk"


# ---------------------------------------------------------------------------
# Acceptance: pipelined serving preserves the zero-warm-trace invariant
# ---------------------------------------------------------------------------


def test_pipelined_execution_zero_new_traces_on_warm_buckets(db):
    clear_plan_cache()
    prep = db.sql(SQL).prepare(transform="none", params={"t": 0.6}).serve(
        name="warm_udf", max_latency_ms=3,
    )
    # warm through the pipelined pump path itself
    prep.submit(_batch(100, seed=1)).wait(timeout=60.0)
    warm = db.cache_stats()
    assert warm["traces"] >= 2
    for i, n in enumerate((65, 128, 80, 127)):  # all land in bucket 128
        # one request per group (like the serial warm test): a burst would
        # coalesce into a segmented group, which is a different — equally
        # cacheable, but separately warmed — program shape
        prep.submit(_batch(n, seed=30 + i)).wait(timeout=60.0)
    stats = db.cache_stats()
    assert stats["traces"] == warm["traces"], (
        "pipelined serving re-traced a previously-served bucket"
    )
    assert stats["stage_traces"] == warm["stage_traces"]
    assert stats["server"]["pipelined_groups"] >= 1


def test_serial_and_pipelined_results_identical(db):
    batches = [_batch(n, seed=60 + i) for i, n in enumerate((40, 90, 170))]
    outs = {}
    for mode in (False, True):
        srv = PredictionQueryServer(pipelined=mode)
        prep = db.sql(SQL).prepare(transform="none", params={"t": 0.6}).serve(
            name="ab", server=srv,
        )
        reqs = [prep.submit(b) for b in batches]
        srv.flush()
        outs[mode] = [r.result for r in reqs]
        srv.shutdown()
    for a, b in zip(outs[False], outs[True]):
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_allclose(a[k], b[k], rtol=1e-6)


def test_forced_donation_split_matches_plain(db, monkeypatch):
    """RAVEN_DONATE=1 exercises the donating volatile/resident jit split on
    CPU (jax warns the donation was unusable; results must be identical)."""
    import warnings

    ref_srv = PredictionQueryServer()
    db.sql(SQL).prepare(transform="sql", params={"t": 0.6}).serve(
        name="don_ref", server=ref_srv,
    )
    b = _batch(200, seed=9)
    ref = ref_srv.execute("don_ref", b)
    monkeypatch.setenv("RAVEN_DONATE", "1")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        don_srv = PredictionQueryServer()
        db.sql(SQL).prepare(transform="sql", params={"t": 0.6}).serve(
            name="don_on", server=don_srv,
        )
        got = don_srv.execute("don_on", b)
    assert set(ref) == set(got)
    for k in ref:
        np.testing.assert_allclose(got[k], ref[k], rtol=1e-6)


def test_aggregate_stage_does_not_donate(db, monkeypatch):
    """An aggregate folds the rows away, so no output can alias a donated
    row buffer: even with donation forced on, such a stage runs the plain
    program (XLA would otherwise warn the donation was unusable)."""
    import warnings

    monkeypatch.setenv("RAVEN_DONATE", "1")
    srv = PredictionQueryServer()
    db.sql(
        "SELECT COUNT(*), AVG(score) FROM PREDICT(model='m', data=patients) "
        "AS p WHERE score >= :t"
    ).prepare(transform="sql", params={"t": 0.6}).serve(
        name="agg_don", server=srv,
    )
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = srv.execute("agg_don", _batch(200, seed=4))
    assert out["count_rows"].shape == (1,)
    assert not [w for w in caught if "donated" in str(w.message)]
