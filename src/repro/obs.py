"""Named spans of the serving path, on the profiler's clock.

``span(name, **ids)`` is a :class:`jax.profiler.TraceAnnotation`. With the
profiler on (``jax.profiler.trace`` or ``start_trace``) it records an event
named ``name`` on the host line of the thread that opened it, on the same
clock as the device's ``XLA Ops`` line, with ``ids`` as the event's stats.
With the profiler off, entering and leaving one costs about a microsecond,
so spans are opened once per request or per request group, never per
column or per row. The profiler is the only switch.

The spans, each with a fixed name (``group`` is the scheduler's dispatch id
and ``rid`` the request id, so one request's spans can be joined):

``raven.submit`` (rid, rows)
    dtype normalization and enqueue, on the submitting thread.
``raven.group`` (group, requests, rows)
    one popped group, from dispatch until the dispatch returns (for a
    fused graph that includes its completion), on the scheduler thread.
``raven.h2d`` (group, arrays)
    padding and the one batched copy of the group's input columns,
    validity and segment ids to the device; ``arrays`` counts what it moved.
``raven.stage`` (group, stage, fp)
    one pure stage's call: its dispatch, or its run where the caller blocks.
``raven.host_boundary`` (group, stage, fp)
    one MLUdf host boundary.
``raven.device_wait`` (group)
    waiting for the result's device buffers, before they are copied.
``raven.d2h`` (group, arrays)
    the one batched copy of the result to the host and its split per
    request; ``arrays`` counts the columns, validity and segment ids moved.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **ids) -> TraceAnnotation:
    """A context manager recording ``name`` with ``ids`` while the profiler
    traces; ``ids`` are ints or strings."""
    return TraceAnnotation(name, **ids)
