"""From a profiler trace to device busy time, kernel time and idle gaps.

:func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain events; the reductions below work on those alone, so a small recorded
trace (``bench/testdata/``) checks them without a chip.

An event is ``[plane, line, name, start_ns, dur_ns]``. Device operations are
the events on the ``XLA Ops`` line of each ``/device:`` plane. The window is
the host span the harness opens around its measured loop (``WINDOW_SPAN``).
"""
from __future__ import annotations

import glob
import os

WINDOW_SPAN = "bench.window"
DEVICE_OPS_LINE = "XLA Ops"


def extract(log_dir: str) -> list[list]:
    """Every event of the newest trace under ``log_dir``: the device planes'
    ``XLA Ops`` lines and every host line."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != DEVICE_OPS_LINE:
                continue
            for e in line.events:
                events.append([plane.name, line.name, e.name,
                               float(e.start_ns), float(e.duration_ns)])
    return events


def op_name(e: list) -> str:
    """A device op's HLO instruction name: ``%tree_gemm.1 = f32[...] ...``
    gives ``tree_gemm.1``."""
    return e[2].split(" = ", 1)[0].lstrip("%")


def window(events: list[list]) -> tuple[float, float]:
    spans = [e for e in events if e[2] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    e = max(spans, key=lambda e: e[4])
    return e[3], e[3] + e[4]


def device_ops(events: list[list]) -> dict[str, list[list]]:
    """Device op events by device plane."""
    out: dict[str, list[list]] = {}
    for e in events:
        if e[0].startswith("/device:") and e[1] == DEVICE_OPS_LINE:
            out.setdefault(e[0], []).append(e)
    return out


def _clip(e: list, lo: float, hi: float) -> tuple[float, float]:
    return max(e[3], lo), min(e[3] + e[4], hi)


def busy_intervals(ops: list[list], lo: float, hi: float) -> list[tuple]:
    """The union of the ops' intervals inside ``[lo, hi]``, merged."""
    spans = sorted(s for s in (_clip(e, lo, hi) for e in ops) if s[1] > s[0])
    merged: list[list[float]] = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [tuple(m) for m in merged]


def busy_s(events: list[list]) -> float:
    """Seconds in which an op ran, averaged over the device planes."""
    lo, hi = window(events)
    planes = device_ops(events)
    if not planes:
        return 0.0
    total = sum(b - a for ops in planes.values()
                for a, b in busy_intervals(ops, lo, hi))
    return total / len(planes) / 1e9


def window_s(events: list[list]) -> float:
    lo, hi = window(events)
    return (hi - lo) / 1e9


def kernel_s(events: list[list], kernel: str) -> tuple[float, int]:
    """Summed device seconds and count of the ops that are the kernel
    itself (a Pallas kernel's instruction takes the kernel's name, so
    ``tree_gemm`` is ``tree_gemm`` or ``tree_gemm.<n>``; ops that merely
    read its output are not counted), inside the window."""
    lo, hi = window(events)
    total, count = 0.0, 0
    for ops in device_ops(events).values():
        for e in ops:
            name = op_name(e)
            if name == kernel or name.startswith(kernel + "."):
                a, b = _clip(e, lo, hi)
                if b > a:
                    total += b - a
                    count += 1
    return total / 1e9, count


def top_ops(events: list[list], k: int = 10) -> list[list]:
    """The ``k`` device ops (by instruction name) that took most time."""
    lo, hi = window(events)
    by: dict[str, float] = {}
    for ops in device_ops(events).values():
        for e in ops:
            a, b = _clip(e, lo, hi)
            if b > a:
                name = op_name(e)
                by[name] = by.get(name, 0.0) + (b - a) / 1e9
    return [[n, s] for n, s in sorted(by.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(events: list[list], k: int = 10) -> list[list]:
    """The ``k`` longest device-idle gaps in the window, each named by the
    host event that covers most of it (deepest first) and the device ops on
    either side: ``host: <event> | <op before> -> <op after>``."""
    lo, hi = window(events)
    planes = device_ops(events)
    if not planes:
        return []
    plane = sorted(planes)[0]
    busy = busy_intervals(planes[plane], lo, hi)
    gaps, prev = [], lo
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = b
    if hi > prev:
        gaps.append((prev, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    host = [e for e in events if not e[0].startswith("/device:")
            and e[2] != WINDOW_SPAN and e[4] > 0]
    ops = sorted(planes[plane], key=lambda e: e[3])
    out = []
    for a, b in gaps:
        best, best_cover = None, 0.0
        for e in host:
            cover = min(e[3] + e[4], b) - max(e[3], a)
            # the event that covers most of the gap; among those covering
            # alike, the shortest (innermost) one
            if cover > best_cover * 1.001 or (
                    best is not None and cover >= best_cover * 0.999
                    and e[4] < best[4]):
                best, best_cover = e, cover
        before = [e for e in ops if e[3] + e[4] <= a + 1]
        after = [e for e in ops if e[3] >= b - 1]
        name = "host: " + (best[2] if best is not None else "none")
        name += " | " + (op_name(before[-1]) if before else "start")
        name += " -> " + (op_name(after[0]) if after else "end")
        out.append([name, (b - a) / 1e9])
    return out
