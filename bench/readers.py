"""Arithmetic shared by the metric readers in ``bench/metrics/``."""
from __future__ import annotations

import math

import numpy as np

from bench import trace, work


def latency_percentile(ctx, q: float):
    """Latency (ms) from due time to result over every request due in the
    window; a failed request counts as missing (infinitely late)."""
    lat = [(r.done - r.due) * 1e3 if r.ok else math.inf
           for r in ctx.due_in_window()]
    if not lat:
        return None
    v = float(np.percentile(np.asarray(lat), q))
    return v if math.isfinite(v) else None


def rows_served(ctx) -> int:
    return sum(r.n_rows for r in ctx.completed())


def roofline(ctx, kernel: str, flops_per_row: float, bytes_per_row: float):
    """The least time of the rows the window served over the kernel's summed
    device time, in %; None where the trace holds no such kernel."""
    if ctx.events is None:
        return None
    seconds, count = trace.kernel_s(ctx.events, kernel)
    rows = rows_served(ctx)
    if count == 0 or seconds <= 0 or rows == 0:
        return None
    least, _bound = work.least_time_s(rows * flops_per_row,
                                      rows * bytes_per_row, ctx.peak)
    return 100.0 * least / seconds


def busy_share(ctx):
    if ctx.events is None or not trace.device_ops(ctx.events):
        return None
    return trace.busy_s(ctx.events) / trace.window_s(ctx.events)


def compiles(ctx) -> int:
    """Plan traces plus XLA backend compiles inside the window."""
    return int(ctx.plan_traces + ctx.backend_compiles)


def ratio(num: float, den: float):
    return num / den if den else None
