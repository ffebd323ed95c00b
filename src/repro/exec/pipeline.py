"""Pipelined StageGraph execution: overlap host and device work across groups.

The serial runner (:func:`repro.exec.stages.run_graph`) blocks at every
stage, so a plan with an MLUdf host boundary leaves the device idle while
numpy churns through the interpreted pipeline — and leaves the host idle
while XLA runs the pure stages. :class:`PipelineExecutor` runs the *same*
stages (same jitted programs, same env structure, so warm buckets stay warm)
as a pipeline over request groups:

  * **pure (device) stages dispatch asynchronously** on the calling thread —
    JAX's async dispatch enqueues the XLA computation and returns
    immediately, so the scheduler thread spends microseconds per stage and
    moves on to the next group;
  * **host boundaries run on a dedicated boundary pool**: the only point
    that must synchronize with the device (``np.asarray`` of the upstream
    state) happens on a worker thread, so group B's entry stages run on
    device while group A sits in its MLUdf boundary — and two UDF-heavy
    groups can occupy two workers at once (numpy releases the GIL in the
    kernels that matter);
  * a graph whose remaining stages are all pure completes inline on the
    dispatching thread — its future resolves immediately and the caller's
    result conversion provides the synchronization. This keeps small
    latency-sensitive pure queries out of the boundary pool's queue, so a
    large host-bound group can never sit in front of them.

The executor also owns the pipelining gauges (groups in flight, groups
that began while another ran) surfaced through ``db.cache_stats()``; where
the time goes is read from the ``raven.*`` spans (:mod:`repro.obs`).
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Optional

from repro.exec.stages import (
    RunResult,
    StageGraph,
    State,
    call_pure,
    host_step,
    strip_consumed,
)
from repro.relational.table import Table


class PipelineExecutor:
    """Boundary thread pool + in-flight accounting for pipelined groups."""

    def __init__(self, workers: int = 2):
        self.workers = max(1, int(workers))
        self._pool: Optional[ThreadPoolExecutor] = None
        self._closed = False
        self._lock = threading.Lock()
        # gauges (all mutated under _lock)
        self.groups_in_flight = 0
        self.max_groups_in_flight = 0
        self.groups_started = 0
        self.overlapped_groups = 0  # groups that began while another ran

    @property
    def pool(self) -> ThreadPoolExecutor:
        """The boundary pool, created on first use.

        After :meth:`shutdown` the (shut-down) pool is returned as-is, so a
        straggling dispatch fails with the executor's RuntimeError instead
        of silently resurrecting a fresh pool nothing will ever shut down.
        """
        with self._lock:
            if self._pool is None and not self._closed:
                self._pool = ThreadPoolExecutor(
                    max_workers=self.workers,
                    thread_name_prefix="raven-boundary",
                )
            if self._pool is None:
                raise RuntimeError("PipelineExecutor is shut down")
            return self._pool

    def shutdown(self) -> None:
        with self._lock:
            self._closed = True
            pool = self._pool
        if pool is not None:
            pool.shutdown(wait=False)

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            return {
                "groups_in_flight": self.groups_in_flight,
                "max_groups_in_flight": self.max_groups_in_flight,
                "groups_started": self.groups_started,
                "overlapped_groups": self.overlapped_groups,
            }

    # -- in-flight accounting -------------------------------------------------

    def _enter_group(self) -> None:
        with self._lock:
            if self.groups_in_flight >= 1:
                self.overlapped_groups += 1
            self.groups_in_flight += 1
            self.groups_started += 1
            self.max_groups_in_flight = max(
                self.max_groups_in_flight, self.groups_in_flight
            )

    def _exit_group(self) -> None:
        with self._lock:
            self.groups_in_flight -= 1

    # -- the pipelined walk ---------------------------------------------------

    def run_graph_async(
        self,
        graph: StageGraph,
        env: dict[str, Any],
        *,
        bucketer: Optional[Callable[[int], int]] = None,
        on_mid_bucket: Optional[Callable[[int, int], None]] = None,
        donate: frozenset = frozenset(),
        group: int = 0,
    ) -> "Future[RunResult]":
        """Execute ``graph`` with host/device overlap; returns a future.

        Semantics are identical to :func:`repro.exec.stages.run_graph` — the
        same stage callables run over the same env structure — only the
        synchronization points move: pure stages are dispatched without
        waiting, and each host boundary (plus everything after it) runs on
        the boundary pool. ``group`` is the dispatch id the spans carry.
        """
        fut: Future = Future()
        self._enter_group()
        try:
            self._advance(graph, 0, None, env, bucketer, on_mid_bucket,
                          donate, group, fut)
        except BaseException as e:  # noqa: BLE001 — delivered via the future
            self._finish(fut, error=e)
        return fut

    def _advance(
        self,
        graph: StageGraph,
        start: int,
        state: Optional[State],
        env: dict[str, Any],
        bucketer,
        on_mid_bucket,
        donate: frozenset,
        group: int,
        fut: Future,
    ) -> None:
        """Run stages from ``start`` on the current thread until the next
        host boundary (handed to the pool) or the end of the graph."""
        for i in range(start, len(graph.stages)):
            stage = graph.stages[i]
            t0 = time.perf_counter()
            if stage.kind == "pure":
                state = call_pure(stage, env, donate, group)
                dt = time.perf_counter() - t0
                if stage.index == 0:
                    env = strip_consumed(env, donate)
                with self._lock:
                    # async dispatch has no meaningful per-stage wall time
                    # (the device work overlaps other groups), so only the
                    # dispatch-side accounting moves — calls/total_s stay
                    # the serial runner's blocking-wall measure
                    stage.async_calls += 1
                    stage.dispatch_s += dt
                continue

            # host boundary: everything from here on runs on the pool, and
            # the dispatching thread returns to its scheduler loop
            def boundary(
                _stage=stage, _state=state, _env=env, _i=i,
            ) -> None:
                t1 = time.perf_counter()
                try:
                    new_state, new_env = host_step(
                        _stage, _state, _env,
                        bucketer=bucketer, on_mid_bucket=on_mid_bucket,
                        group=group,
                    )
                except BaseException as e:  # noqa: BLE001
                    self._finish(fut, error=e)
                    return
                dt1 = time.perf_counter() - t1
                with self._lock:
                    _stage.calls += 1
                    _stage.total_s += dt1
                    _stage.async_calls += 1
                    _stage.dispatch_s += dt1
                try:
                    self._advance(graph, _i + 1, new_state, new_env,
                                  bucketer, on_mid_bucket, donate,
                                  group, fut)
                except BaseException as e:  # noqa: BLE001
                    self._finish(fut, error=e)

            self.pool.submit(boundary)
            return

        cols, valid, seg = state
        self._finish(fut, result=RunResult(
            table=Table(columns=cols, valid=valid), seg=seg
        ))

    def _finish(self, fut: Future, *, result=None, error=None) -> None:
        self._exit_group()
        if error is not None:
            fut.set_exception(error)
        else:
            fut.set_result(result)
