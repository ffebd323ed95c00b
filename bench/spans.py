"""The program's own spans on the trace's clock.

The serving path opens ``raven.*`` spans (``repro.obs``) on the host threads
that do its work: ``raven.submit``, ``raven.group``, ``raven.h2d``,
``raven.stage``, ``raven.host_boundary``, ``raven.device_wait`` and
``raven.d2h``. ``trace.extract`` keeps every host line, so they arrive as
events ``[plane, line, name, start_ns, dur_ns]`` beside the device's ops.
Below: their durations inside the window, and how much of the device's idle
time (the window less the union of its ops) they cover. Each function
returns None where the trace holds none of the spans it reads, as from a
program that records none.
"""
from __future__ import annotations

import statistics

from bench import trace

PREFIX = "raven."


def host_spans(events: list[list], names) -> list[list]:
    """Host events whose name is in ``names`` (a collection of names, or
    ``PREFIX`` for every program span)."""
    if names == PREFIX:
        return [e for e in events if not e[0].startswith("/device:")
                and e[2].startswith(PREFIX)]
    return [e for e in events if not e[0].startswith("/device:")
            and e[2] in names]


def clip(spans: list[list], lo: float, hi: float) -> list[tuple]:
    """The spans' intervals cut to ``[lo, hi]``, empty ones dropped."""
    out = []
    for e in spans:
        a, b = max(e[3], lo), min(e[3] + e[4], hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: list[tuple]) -> list[tuple]:
    """Sorted, merged intervals."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [tuple(m) for m in merged]


def length(intervals: list[tuple]) -> float:
    return sum(b - a for a, b in intervals)


def overlap(x: list[tuple], y: list[tuple]) -> float:
    """Length of the intersection of two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if b > a:
            total += b - a
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_intervals(busy: list[tuple], lo: float, hi: float) -> list[tuple]:
    """``[lo, hi]`` less the merged ``busy`` intervals inside it."""
    out, prev = [], lo
    for a, b in busy:
        if a > prev:
            out.append((prev, a))
        prev = max(prev, b)
    if hi > prev:
        out.append((prev, hi))
    return out


def median_ms(events: list[list], name: str):
    """Median duration (ms) of the ``name`` spans that start in the window."""
    lo, hi = trace.window(events)
    durs = [e[4] / 1e6 for e in host_spans(events, (name,))
            if lo <= e[3] <= hi]
    return statistics.median(durs) if durs else None


def idle_covered_share(events: list[list], names):
    """Share of the device's idle time in the window that the union of the
    ``names`` spans covers, summed over the device planes."""
    lo, hi = trace.window(events)
    covered = union(clip(host_spans(events, names), lo, hi))
    planes = trace.device_ops(events)
    if not covered or not planes:
        return None
    idle = covered_idle = 0.0
    for ops in planes.values():
        gaps = idle_intervals(trace.busy_intervals(ops, lo, hi), lo, hi)
        idle += length(gaps)
        covered_idle += overlap(gaps, covered)
    return covered_idle / idle if idle else None
