"""The plain reference: join, featurize and score in numpy.

It follows the query's semantics and imports nothing of the program: a
foreign-key lookup for each join, the scaler in float32 and one-hot
indicators for the featurizer, and a walk down each full tree of the
benchmark's own :class:`~bench.model.Forest` (go left where
``x <= threshold``), the margin summed in float64 and passed through the
logistic.

``precision="high"`` is the control: the same reference with its split-
feature selection computed as the GEMM strategy's ``X·A`` would be at
``Precision.HIGH`` — three bfloat16 passes, which against a 0/1 selection
matrix return ``hi + lo``, the feature rounded to 16 significant bits. It
is the nearest precision below the ``HIGHEST`` the program states.
"""
from __future__ import annotations

import numpy as np

from bench.model import Featurizer, Forest

BLOCK_ROWS = 4096


def join(fact: dict, tables: dict, joins: list) -> dict[str, np.ndarray]:
    out = dict(fact)
    for fk, dim, dk in joins:
        keys = tables[dim][dk]
        order = np.argsort(keys, kind="stable")
        pos = np.searchsorted(keys, out[fk], sorter=order)
        pos = order[np.clip(pos, 0, len(keys) - 1)]
        if not np.array_equal(keys[pos], out[fk]):
            raise ValueError(f"reference: a {fk} has no row in {dim}")
        for c, v in tables[dim].items():
            if c != dk:
                out[c] = v[pos]
    return out


def bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to bfloat16 (nearest, ties to even), kept as float32."""
    u = np.asarray(x, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def high_pass(x: np.ndarray) -> np.ndarray:
    """``x`` as a three-pass bfloat16 product with 1.0 returns it."""
    x = np.asarray(x, np.float32)
    hi = bf16(x)
    return hi + bf16(x - hi)


def features(cols: dict, feat: Featurizer, used: np.ndarray) -> np.ndarray:
    """The featurized columns ``used`` (sorted featurized indices), float32."""
    n = len(next(iter(cols.values())))
    out = np.zeros((n, len(used)), np.float32)
    n_num = len(feat.numeric)
    starts = np.cumsum([n_num] + feat.cards[:-1]) if feat.cards else []
    for k, f in enumerate(used):
        if f < n_num:
            out[:, k] = feat.scaled(int(f), cols[feat.numeric[f]])
            continue
        c = int(np.searchsorted(starts, f, side="right")) - 1
        out[:, k] = cols[feat.categorical[c]] == f - starts[c]
    return out


def margins(X: np.ndarray, pos: np.ndarray, forest: Forest) -> np.ndarray:
    """float64 margin of each row; ``pos`` maps each split to a column of X.

    Every row walks each tree from the root, ``depth`` steps down: left
    (child ``2k+1``) where its feature is at most the split's threshold,
    else right (``2k+2``). Indices are flattened so each step is a few
    ``take`` calls over a block of rows."""
    T, I, K = forest.n_trees, forest.n_internal, X.shape[1]
    split_col = pos.reshape(-1).astype(np.int32)
    split_thr = forest.threshold.reshape(-1)
    leaves = forest.leaf.reshape(-1)
    tree_split = (np.arange(T, dtype=np.int32) * I)[None, :]
    tree_leaf = (np.arange(T, dtype=np.int32) * forest.n_leaves)[None, :]
    out = np.empty(len(X), np.float64)
    for s in range(0, len(X), BLOCK_ROWS):
        xb = np.ascontiguousarray(X[s:s + BLOCK_ROWS])
        row = (np.arange(len(xb), dtype=np.int32) * K)[:, None]
        node = np.zeros((len(xb), T), np.int32)
        for _ in range(forest.depth):
            k = tree_split + node
            x = xb.reshape(-1).take(row + split_col.take(k))
            node = 2 * node + 2 - (x <= split_thr.take(k))
        leaf = leaves.take(tree_leaf + node - I)
        out[s:s + BLOCK_ROWS] = forest.base + forest.weight * leaf.sum(axis=1)
    return out


def scores(fact: dict, tables: dict, joins: list, feat: Featurizer,
           forest: Forest, precision: str = "highest") -> np.ndarray:
    """Probability of each fact row, as ``SELECT score`` returns it."""
    cols = join(fact, tables, joins)
    used, pos = np.unique(forest.feature, return_inverse=True)
    X = features(cols, feat, used)
    if precision == "high":
        X = high_pass(X)
    elif precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    z = margins(X, pos.reshape(forest.feature.shape), forest)
    return 1.0 / (1.0 + np.exp(-z))
