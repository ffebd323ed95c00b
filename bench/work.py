"""Operations and bytes the model needs, counted from its shape.

These count the work the model asks for, whatever implements it: a kernel
that pads its matrices or runs extra precision passes does more, and that
excess never counts as work. All counts are per served row.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's peaks from ``peaks.json``; a kind not listed is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (source: {table['source']})")
    return table["devices"][device_kind]


def tree_gemm_flops(T: int, F: int, I: int, L: int) -> float:
    """GEMM-strategy tree scoring: ``X·A`` (F x I) and ``D·C`` (I x L)
    per tree, two operations per multiply-add."""
    return float(T * (2 * F * I + 2 * I * L))


def tree_gemm_bytes(F: int) -> float:
    """The row's F float32 features in and its float32 score out."""
    return float(4 * F + 4)


def featurize_bytes(n_num: int, n_cat: int, F: int) -> float:
    """float32 numerics and int32 codes in, the F float32 features the trees
    read out."""
    return float(4 * n_num + 4 * n_cat + 4 * F)


def least_time_s(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The larger of the compute and the memory bound, and which binds."""
    tc = flops / float(peak["flops_per_s"])
    tm = nbytes / float(peak["hbm_bytes_per_s"])
    return (tc, "compute") if tc >= tm else (tm, "memory")
