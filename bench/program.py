"""The system under test, as a user drives it.

``raven.connect → db.sql → prepare(transform="dnn") → serve → submit/wait``.
This is the only module of the benchmark that imports the program. It hands
the program the benchmark's model in the program's own format and reads the
program's counters; it computes nothing that the comparison relies on.
"""
from __future__ import annotations

import time

import numpy as np

import repro as raven
from repro.ml.pipeline import InputSpec, PipelineNode, TrainedPipeline
from repro.ml.trees import LEAF, TreeEnsemble

from bench.model import Featurizer, Forest


def ensemble(forest: Forest, n_features: int) -> TreeEnsemble:
    """The forest as the program's flattened tree ensemble."""
    T, I, L = forest.n_trees, forest.n_internal, forest.n_leaves
    per = I + L
    off = np.arange(T + 1, dtype=np.int64) * per
    k = np.arange(per)
    internal = k < I
    base = off[:-1, None]
    feature = np.where(internal, 0, LEAF) + np.zeros((T, 1), np.int64)
    feature[:, :I] = forest.feature
    threshold = np.zeros((T, per))
    threshold[:, :I] = forest.threshold.astype(np.float64)
    leaf_value = np.zeros((T, per))
    leaf_value[:, I:] = forest.leaf
    node = base + k[None, :]
    left = np.where(internal, base + 2 * k + 1, node)
    right = np.where(internal, base + 2 * k + 2, node)
    return TreeEnsemble(
        feature=feature.reshape(-1), threshold=threshold.reshape(-1),
        left=left.reshape(-1), right=right.reshape(-1),
        leaf_value=leaf_value.reshape(-1), tree_offsets=off,
        tree_weight=np.full(T, forest.weight), base_score=forest.base,
        post_transform="logistic", n_features=int(n_features),
    )


def pipeline(feat: Featurizer, forest: Forest) -> TrainedPipeline:
    """Scaler over the numerics, one-hot per categorical, concat, trees."""
    nodes, parts, specs = [], [], []
    if feat.numeric:
        specs += [InputSpec(c, "numeric") for c in feat.numeric]
        nodes.append(PipelineNode("concat", list(feat.numeric), ["num_raw"]))
        nodes.append(PipelineNode(
            "scaler", ["num_raw"], ["num_scaled"],
            {"offset": feat.offset, "scale": feat.scale},
        ))
        parts.append("num_scaled")
    for c, card in zip(feat.categorical, feat.cards):
        specs.append(InputSpec(c, "categorical"))
        nodes.append(PipelineNode(
            "one_hot", [c], [f"{c}_oh"], {"categories": np.arange(card)}
        ))
        parts.append(f"{c}_oh")
    nodes.append(PipelineNode("concat", parts, ["features"]))
    nodes.append(PipelineNode(
        "tree_ensemble", ["features"], ["score", "label"],
        {"ensemble": ensemble(forest, feat.width)},
    ))
    pipe = TrainedPipeline(inputs=specs, outputs=["score", "label"], nodes=nodes)
    pipe.toposort()
    return pipe


class Served:
    """One prepared, served query over one session."""

    def __init__(self, cfg: dict, tables: dict, feat: Featurizer,
                 forest: Forest, mix: dict):
        # No artifact store: a program it loads runs through
        # ``Exported.call`` on every request, a different host path from the
        # live jit that serves a run whose store is still empty, so the
        # first run of a checkout would measure other code than the rest.
        # JAX's persistent compilation cache still serves every compile.
        self.phases: dict[str, float] = {}  # set-up seconds by step
        t = time.perf_counter()
        self.db = raven.connect(tables)
        try:
            t = self._phase("connect", t)
            self.db.models.publish("m", pipeline(feat, forest))
            t = self._phase("publish", t)
            self.prep = self.db.sql(cfg["query"]).prepare(transform="dnn")
            t = self._phase("prepare", t)
            self.prep.serve(options=raven.ServeOptions(
                max_latency_ms=float(mix["max_latency_ms"]),
                max_coalesce=int(mix["max_coalesce"]),
            ))
            self._phase("serve", t)
        except BaseException:
            self.db.close()
            raise

    def _phase(self, name: str, since: float) -> float:
        now = time.perf_counter()
        self.phases[name] = now - since
        return now

    def submit(self, batch: dict):
        return self.prep.submit(batch)

    def traces(self) -> int:
        """XLA traces of compiled plans so far (the plan cache's count)."""
        return int(self.db.cache_stats()["traces"])

    def server_stats(self) -> dict:
        return dict(self.db.cache_stats()["server"])

    def close(self) -> None:
        self.db.close()
