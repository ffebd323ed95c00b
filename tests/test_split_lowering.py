"""Partial MLtoDNN: pipeline-splitting lowering.

Property: executing the split — compiled tensor prefix, host residual,
compiled tensor suffix — matches host ``run_pipeline`` *bit-for-bit* on CPU
for elementwise-safe ops (scaler/concat/feature_extractor + a python_udf
residual), across every split shape: residual in the middle, residual first
(suffix-only), residual last (prefix-only), and no residual at all (the
fully-supported degenerate split). Plus: the end-to-end optimizer emits
``TensorOp → MLUdf → TensorOp`` instead of one monolithic MLUdf, cut
columns never leak into query output, ``explain()`` renders the placement,
and a split plan warm-starts with zero re-traces through the artifact store.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.rules.ml_to_dnn import (
    MLtoDNNUnsupported,
    compile_pipeline_to_dnn_partial,
)
from repro.ml.pipeline import (
    InputSpec,
    PipelineNode,
    TrainedPipeline,
    run_pipeline,
    split_pipeline,
)
from repro.tensor.compile import tensor_supported

try:  # the property test is hypothesis-driven when available ...
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # ... and a seeded deterministic sweep otherwise
    HAVE_HYPOTHESIS = False


def _udf(X):
    # deterministic, elementwise, f32-exact on both runtimes
    return (X.astype(np.float32) * np.float32(0.5)) + np.float32(0.25)


_udf.__fingerprint_token__ = "test-split-udf-v1"


def _build(k: int, offsets, scales, udf_pos: str) -> TrainedPipeline:
    """k numeric inputs -> concat -> scaler -> feature_extractor, with a
    python_udf inserted at ``udf_pos`` in {none, start, middle, end}."""
    xs = [f"x{i}" for i in range(k)]
    nodes: list[PipelineNode] = []
    off = np.asarray(offsets, dtype=np.float32)
    sc = np.asarray(scales, dtype=np.float32)
    idx = list(reversed(range(k)))

    if udf_pos == "start":
        # unsupported node first: no supported prefix exists (suffix-only)
        nodes.append(
            PipelineNode("python_udf", [xs[0]], ["h0"], {"fn": _udf})
        )
        concat_in = ["h0", *xs[1:]]
    else:
        concat_in = list(xs)
    nodes.append(PipelineNode("concat", concat_in, ["raw"]))
    if udf_pos == "middle":
        nodes.append(PipelineNode("python_udf", ["raw"], ["raw_h"], {"fn": _udf}))
        scaler_in = "raw_h"
    else:
        scaler_in = "raw"
    nodes.append(
        PipelineNode("scaler", [scaler_in], ["scaled"], {"offset": off, "scale": sc})
    )
    nodes.append(
        PipelineNode("feature_extractor", ["scaled"], ["feat"], {"indices": idx})
    )
    final = "feat"
    if udf_pos == "end":
        nodes.append(PipelineNode("python_udf", ["feat"], ["feat_h"], {"fn": _udf}))
        final = "feat_h"
    return TrainedPipeline(
        inputs=[InputSpec(x, "numeric") for x in xs],
        outputs=[final],
        nodes=nodes,
    )


def _run_split(pipe: TrainedPipeline, inputs: dict[str, np.ndarray]):
    """Execute prefix (tensor) -> residual (host) -> suffix (tensor),
    chaining through cut columns exactly as the plan does."""
    part = compile_pipeline_to_dnn_partial(pipe)
    cols: dict[str, np.ndarray] = dict(inputs)

    def tensor_seg(compiled):
        comp, seg = compiled
        out = comp.fn({n: jnp.asarray(cols[n]) for n in comp.input_names})
        for val, col in zip(seg.pipeline.outputs, seg.out_cols):
            cols[col] = np.asarray(out[val])

    if part.full is not None:
        out = part.full.fn({n: jnp.asarray(cols[n]) for n in part.full.input_names})
        return {o: np.asarray(out[o]) for o in pipe.outputs}, part
    if part.prefix is not None:
        tensor_seg(part.prefix)
    if part.residual is not None:
        seg = part.residual
        res = run_pipeline(
            seg.pipeline, {s.name: cols[s.name] for s in seg.pipeline.inputs}
        )
        for val, col in zip(seg.pipeline.outputs, seg.out_cols):
            cols[col] = res[val]
    if part.suffix is not None:
        tensor_seg(part.suffix)
    return {o: cols[o] for o in pipe.outputs}, part


def _check_split_matches_host(k, n, udf_pos, offsets, scales, arr):
    pipe = _build(k, offsets, scales, udf_pos)
    inputs = {f"x{i}": arr[:, i] for i in range(k)}

    host = run_pipeline(pipe, inputs)
    got, part = _run_split(pipe, inputs)

    # split shape is exactly what udf_pos dictates
    if udf_pos == "none":
        assert part.full is not None
    else:
        assert part.residual is not None
        assert (part.prefix is None) == (udf_pos == "start")
        assert (part.suffix is None) == (udf_pos == "end")

    def _2d(x):
        x = np.asarray(x, dtype=np.float32)
        return x.reshape(x.shape[0], 1) if x.ndim == 1 else x

    for o in pipe.outputs:
        want = _2d(host[o])
        have = _2d(got[o])
        assert want.shape == have.shape
        # bit-for-bit: elementwise f32 math must agree exactly on CPU
        assert np.array_equal(
            want.view(np.uint32), have.view(np.uint32)
        ), f"bitwise mismatch on {o}"


if HAVE_HYPOTHESIS:
    # XLA:CPU flushes subnormal operands and results to zero where numpy
    # keeps them, so values are 0 or at least 2**-10 in magnitude: every
    # difference, product and udf result of them is then 0 or normal
    finite_f32 = st.one_of(
        st.just(0.0),
        st.floats(min_value=2.0**-10, max_value=1e3, width=32),
        st.floats(min_value=-1e3, max_value=-(2.0**-10), width=32),
    )

    @settings(max_examples=25, deadline=None)
    @given(
        data=st.data(),
        k=st.integers(min_value=1, max_value=4),
        n=st.integers(min_value=0, max_value=37),
        udf_pos=st.sampled_from(["none", "start", "middle", "end"]),
    )
    def test_split_execution_matches_host_bitwise(data, k, n, udf_pos):
        offsets = data.draw(st.lists(finite_f32, min_size=k, max_size=k))
        scales = data.draw(st.lists(finite_f32, min_size=k, max_size=k))
        rows = data.draw(
            st.lists(
                st.lists(finite_f32, min_size=k, max_size=k),
                min_size=n,
                max_size=n,
            )
        )
        arr = np.asarray(rows, dtype=np.float32).reshape(n, k)
        _check_split_matches_host(k, n, udf_pos, offsets, scales, arr)

else:

    @pytest.mark.parametrize("udf_pos", ["none", "start", "middle", "end"])
    @pytest.mark.parametrize("k,n", [(1, 0), (1, 7), (3, 37), (4, 128)])
    def test_split_execution_matches_host_bitwise(k, n, udf_pos):
        rng = np.random.default_rng(hash((k, n, udf_pos)) % (2**32))
        offsets = rng.uniform(-1e3, 1e3, size=k).astype(np.float32)
        scales = rng.uniform(-1e3, 1e3, size=k).astype(np.float32)
        arr = rng.uniform(-1e3, 1e3, size=(n, k)).astype(np.float32)
        _check_split_matches_host(k, n, udf_pos, offsets, scales, arr)


def test_split_placement_covers_every_node():
    pipe = _build(3, [0.0, 1.0, 2.0], [1.0, 0.5, 2.0], "middle")
    split = split_pipeline(pipe, tensor_supported)
    assert [seg for _, seg in split.placement] == [
        "prefix", "residual", "suffix", "suffix"
    ]
    # every node appears exactly once, in topo order
    assert [lbl.split("[")[0] for lbl, _ in split.placement] == [
        "concat", "python_udf", "scaler", "feature_extractor"
    ]


def test_nothing_lowerable_raises_and_optimizer_falls_back():
    pipe = TrainedPipeline(
        inputs=[InputSpec("x0", "numeric")],
        outputs=["h"],
        nodes=[PipelineNode("python_udf", ["x0"], ["h"], {"fn": _udf})],
    )
    with pytest.raises(MLtoDNNUnsupported):
        compile_pipeline_to_dnn_partial(pipe)


# ---------------------------------------------------------------------------
# End-to-end: optimizer emits the split plan; serving warm-starts it
# ---------------------------------------------------------------------------


def _hospital_split_pipeline(hospital, train_pipeline_fn):
    pipe = train_pipeline_fn(hospital, "gb")
    nodes = list(pipe.nodes)
    mi = next(
        i for i, nd in enumerate(nodes) if nd.op in ("tree_ensemble", "linear")
    )
    udf = PipelineNode(
        "python_udf", [nodes[mi].inputs[0]], ["features_h"], {"fn": _udf}
    )
    model = dataclasses.replace(
        nodes[mi], inputs=["features_h", *nodes[mi].inputs[1:]]
    )
    return dataclasses.replace(
        pipe, nodes=[*nodes[:mi], udf, model, *nodes[mi + 1:]]
    )


@pytest.fixture()
def split_db(hospital):
    import repro as raven
    from tests.conftest import train_pipeline

    joined = hospital.joined_columns()
    db = raven.connect({"patients": joined})
    db.register_model("risk", _hospital_split_pipeline(hospital, train_pipeline))
    yield db, joined
    db.close()


def test_optimizer_emits_split_not_monolithic_udf(split_db):
    from repro.relational.engine import MLUdf, TensorOp, walk_plan

    db, joined = split_db
    prep = db.table("patients").predict("risk").prepare(transform="dnn")
    kinds = [
        type(s).__name__
        for s in walk_plan(prep.plan)
        if isinstance(s, (MLUdf, TensorOp))
    ]
    # innermost-first: prefix TensorOp, host residual, suffix TensorOp
    assert kinds == ["TensorOp", "MLUdf", "TensorOp"]
    udf = next(s for s in walk_plan(prep.plan) if isinstance(s, MLUdf))
    assert len(udf.pipeline.nodes) == 1  # minimal residual
    assert [s.kind for s in prep.compiled.graph.stages] == ["pure", "host", "pure"]

    # results equal the host path; cut columns never reach the output
    pipe = db.models["risk"]
    host = run_pipeline(pipe, {s.name: joined[s.name] for s in pipe.inputs})
    out = prep({k: joined[k] for k in joined})
    assert not [c for c in out if c.startswith("__pv_")]
    assert np.allclose(out["score"], host["score"], rtol=5e-3, atol=1e-5)

    text = prep.explain()
    assert "split across runtimes" in text
    assert "host/residual" in text and "tensor/prefix" in text
    assert "MLtoDNN split" in text


def test_split_plan_zero_warm_retraces(split_db, tmp_path):
    import repro as raven
    from repro.relational.engine import clear_plan_cache, set_artifact_store

    db, joined = split_db
    hospital_pipe = db.models["risk"]
    cache = str(tmp_path / "cache")

    def prepare_and_serve():
        d = raven.connect({"patients": joined}, cache_dir=cache)
        d.register_model("risk", hospital_pipe)
        p = d.table("patients").predict("risk").prepare(transform="dnn")
        p.serve("q")
        r = p.submit({k: joined[k][:200] for k in joined})
        d.flush()
        r.wait()
        d.artifact_store.drain()
        stats = d.cache_stats()
        d.close()
        return stats, np.sort(np.asarray(r.result["score"]))

    clear_plan_cache()
    set_artifact_store(None)
    cold, cold_scores = prepare_and_serve()
    assert cold["traces"] > 0
    # simulate a fresh process: drop the in-memory tier, keep the disk tier
    clear_plan_cache()
    set_artifact_store(None)
    warm, warm_scores = prepare_and_serve()
    # warm start re-traces nothing: every pure stage program — including the
    # split's prefix/suffix TensorOp stages — loads from disk
    assert warm["traces"] == 0, (cold, warm)
    assert warm["disk_hits"] > 0
    np.testing.assert_allclose(cold_scores, warm_scores, rtol=1e-6)
    clear_plan_cache()
    set_artifact_store(None)
