"""The one traffic generator. A mix is a JSON file of parameters,
``bench/traffic/<name>.json``:

``loop``            ``"closed"`` (``clients`` callers, each sends its next
                    request when the last returns) or ``"open"`` (arrivals at
                    ``rate_per_s`` whatever the server does);
``request_rows``    ``[lo, hi]``: request sizes, log-uniform between them;
``pool_rows``       fresh fact rows drawn from the seed; requests are slices;
``distinct_requests`` (closed) batches prepared in set-up and cycled;
``max_latency_ms``, ``max_coalesce``  the serving queue's settings;
``check_requests``  answers compared with the reference after the window.

Every seed gets the same multiset of sizes and of inter-arrival gaps (the
quantiles of their distributions, one per request) in its own order, with
its own rows: the seed reorders the work and does not change its amount.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def sizes(mix: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` request sizes: the log-uniform quantiles, permuted."""
    lo, hi = (int(v) for v in mix["request_rows"])
    u = (np.arange(n) + 0.5) / n
    s = np.rint(np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo))))
    return rng.permutation(s.astype(np.int64))


def due_times(mix: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Arrival times in ``[0, seconds)`` of a Poisson stream at the mix's
    rate: the exponential gaps' quantiles, permuted, scaled to the window."""
    n = max(1, int(round(float(mix["rate_per_s"]) * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u))
    t = np.cumsum(gaps)
    return (t - t[0]) * (seconds / t[-1]) * (n - 1) / n


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def closed_batches(mix: dict, pool: dict, seed: int) -> list[dict]:
    """The distinct requests a closed loop cycles through."""
    rng = rng_for(seed, 1)
    n_rows = len(next(iter(pool.values())))
    out = []
    for n in sizes(mix, int(mix["distinct_requests"]), rng):
        start = int(rng.integers(0, n_rows - n + 1))
        out.append({c: v[start:start + n] for c, v in pool.items()})
    return out


def open_plan(mix: dict, pool: dict, seed: int, seconds: float):
    """``(due, batches)`` of an open loop over ``seconds``."""
    due = due_times(mix, seconds, rng_for(seed, 2))
    rng = rng_for(seed, 3)
    n_rows = len(next(iter(pool.values())))
    batches = []
    for n in sizes(mix, len(due), rng):
        start = int(rng.integers(0, n_rows - n + 1))
        batches.append({c: v[start:start + n] for c, v in pool.items()})
    return due, batches


def warm_sizes(mix: dict) -> list[int]:
    """Every row bucket the window can reach: the power-of-two buckets from
    64 up to the coalesce cap (closed loops of one fixed size use that size
    alone, since each group holds one request)."""
    lo, hi = (int(v) for v in mix["request_rows"])
    if mix["loop"] == "closed" and lo == hi and hi >= int(mix["max_coalesce"]):
        return [hi]
    top = int(mix["max_coalesce"])
    out, b = [], 64
    while b < lo:
        b *= 2
    while b <= top:
        out.append(b)
        b *= 2
    return out
