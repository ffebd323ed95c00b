"""Tables drawn from a configuration's schema.

A configuration names its tables, their columns and their row counts. The
warehouse (dimension tables and the fact table the model was fit on) comes
from the configuration's fixed ``model_seed``, so every run serves the same
model and compiles the same programs. The fact rows a run serves come from
``--seed``: fresh rows of the same distributions, with keys into the fixed
dimension tables.

Numerics are float32, categorical codes and keys int32: the types the
tables are stored and served in.
"""
from __future__ import annotations

import numpy as np


def _numeric(rng: np.random.Generator, spec: list, n: int) -> np.ndarray:
    kind = spec[0]
    if kind == "normal":
        return rng.normal(spec[1], spec[2], n).astype(np.float32)
    if kind == "int":  # integers in [lo, hi), as recorded (e.g. age)
        return rng.integers(spec[1], spec[2], n).astype(np.float32)
    raise ValueError(f"unknown numeric distribution {kind!r}")


def table_rows(cfg: dict, name: str) -> int:
    return int(cfg["fact_rows"] if name == cfg["fact"] else cfg["tables"][name]["rows"])


def make_table(cfg: dict, name: str, rng: np.random.Generator, n: int) -> dict:
    spec = cfg["tables"][name]
    cols: dict[str, np.ndarray] = {}
    if "key" in spec:
        cols[spec["key"]] = np.arange(n, dtype=np.int32)
    for c, dist in spec["numeric"].items():
        cols[c] = _numeric(rng, dist, n)
    for c, card in spec["categorical"].items():
        cols[c] = rng.integers(0, int(card), n).astype(np.int32)
    for fk, dim in spec["keys"].items():
        cols[fk] = rng.integers(0, table_rows(cfg, dim), n).astype(np.int32)
    return cols


def warehouse(cfg: dict) -> dict[str, dict[str, np.ndarray]]:
    """Every table of the configuration, drawn from ``model_seed``."""
    rng = np.random.default_rng(int(cfg["model_seed"]))
    return {t: make_table(cfg, t, rng, table_rows(cfg, t)) for t in cfg["tables"]}


def fact_pool(cfg: dict, seed: int, n: int) -> dict[str, np.ndarray]:
    """``n`` fresh fact rows drawn from ``seed``."""
    rng = np.random.default_rng([int(seed), int(cfg["model_seed"])])
    return make_table(cfg, cfg["fact"], rng, n)


def model_inputs(cfg: dict) -> tuple[list[str], dict[str, int]]:
    """The model's input columns over the joined view: numerics (fact first,
    then each joined dimension) and categoricals with their cardinalities."""
    order = [cfg["fact"]] + [dim for _fk, dim, _dk in cfg["joins"]]
    numeric = [c for t in order for c in cfg["tables"][t]["numeric"]]
    cards = {c: int(k) for t in order
             for c, k in cfg["tables"][t]["categorical"].items()}
    return numeric, cards
