"""The served model, drawn from the configuration's ``model_seed``.

No model is trained. A gradient-boosted ensemble is drawn at its published
tree count and depth, with full trees:

* each split draws an input column uniformly; a numeric splits on its scaled
  value, a categorical on one of its one-hot columns;
* a numeric threshold is the featurized value of a warehouse row at a
  uniform quantile in [0.02, 0.98] (a histogram cut point, as hist-based
  boosting places them); a one-hot threshold is 0.5;
* leaves are normal(0, 1), each tree weighs ``1/sqrt(n_estimators)`` so the
  margin has unit spread, and the output is the logistic of the margin.

The featurizer is set from the warehouse: the scaler's mean and 1/std, and
the declared category domains. Rows go left where ``x <= threshold``.

This module and ``reference.py`` hold the model as the benchmark's own
arrays; ``program.py`` converts them into the program's model format.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from bench import data


@dataclass
class Featurizer:
    """Scaled numerics, then one one-hot block per categorical."""

    numeric: list[str]
    offset: np.ndarray  # (n_num,) float64 mean
    scale: np.ndarray  # (n_num,) float64 1/std
    categorical: list[str]
    cards: list[int]

    @property
    def width(self) -> int:
        return len(self.numeric) + sum(self.cards)

    def scaled(self, j: int, x: np.ndarray) -> np.ndarray:
        """Numeric ``j`` featurized in float32, as the scaler op states."""
        x = np.asarray(x, np.float32)
        return (x - np.float32(self.offset[j])) * np.float32(self.scale[j])


@dataclass
class Forest:
    """Full binary trees in breadth-first order: node ``k``'s children are
    ``2k+1`` and ``2k+2``; leaves follow the ``2**depth - 1`` internal
    nodes."""

    feature: np.ndarray  # (T, I) int64 featurized column of each split
    threshold: np.ndarray  # (T, I) float32
    leaf: np.ndarray  # (T, L) float64
    weight: float
    base: float
    depth: int

    @property
    def n_trees(self) -> int:
        return int(self.feature.shape[0])

    @property
    def n_internal(self) -> int:
        return int(self.feature.shape[1])

    @property
    def n_leaves(self) -> int:
        return int(self.leaf.shape[1])


def joined(cfg: dict, tables: dict) -> dict[str, np.ndarray]:
    """The fact table with its dimension columns (keys are row ids)."""
    out = dict(tables[cfg["fact"]])
    for fk, dim, dk in cfg["joins"]:
        pos = np.searchsorted(tables[dim][dk], out[fk])
        for c, v in tables[dim].items():
            if c != dk:
                out[c] = v[pos]
    return out


def build(cfg: dict, tables: dict) -> tuple[Featurizer, Forest]:
    numeric, cards = data.model_inputs(cfg)
    view = joined(cfg, tables)
    rows = len(next(iter(view.values())))
    raw = np.stack([view[c].astype(np.float64) for c in numeric], axis=1) \
        if numeric else np.zeros((rows, 0))
    std = raw.std(axis=0)
    feat = Featurizer(
        numeric=numeric, offset=raw.mean(axis=0),
        scale=1.0 / np.where(std == 0.0, 1.0, std),
        categorical=list(cards), cards=list(cards.values()),
    )
    m = cfg["model"]
    # the reference scores a logistic GB ensemble and nothing else: another
    # kind or output transform would be served and checked as this one
    if (m.get("kind"), m.get("post_transform")) != ("gradient_boosting",
                                                    "logistic"):
        raise ValueError(
            f"bench: model kind {m.get('kind')!r} with post_transform "
            f"{m.get('post_transform')!r} is not supported; only "
            "'gradient_boosting' with 'logistic'")
    T, depth = int(m["n_estimators"]), int(m["max_depth"])
    I, L = 2 ** depth - 1, 2 ** depth
    rng = np.random.default_rng(int(cfg["model_seed"]) + 1)
    n_in = len(numeric) + len(cards)
    col = rng.integers(0, n_in, size=(T, I))
    q = rng.uniform(0.02, 0.98, size=(T, I))
    pick = rng.random(size=(T, I))
    feature = np.zeros((T, I), np.int64)
    threshold = np.full((T, I), np.float32(0.5), np.float32)
    n = raw.shape[0]
    for j in range(len(numeric)):
        at = col == j
        cut = np.sort(feat.scaled(j, raw[:, j]))
        feature[at] = j
        threshold[at] = cut[(q[at] * n).astype(np.int64)]
    start = len(numeric)
    for k, card in enumerate(feat.cards):
        at = col == len(numeric) + k
        feature[at] = start + (pick[at] * card).astype(np.int64)
        start += card
    forest = Forest(
        feature=feature, threshold=threshold,
        leaf=rng.normal(0.0, 1.0, size=(T, L)),
        weight=1.0 / float(np.sqrt(T)), base=0.0, depth=depth,
    )
    return feat, forest
