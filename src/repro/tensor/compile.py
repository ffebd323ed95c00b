"""Compile a TrainedPipeline into one fused jittable tensor program.

This is the MLtoDNN target (paper §5.1, via Hummingbird): featurizers become
vectorized jnp ops, tree ensembles become GEMM or gather-traversal programs
(strategy picked per-ensemble, Hummingbird-style: GEMM for shallow/wide on
the MXU, traversal for deep/narrow), and the whole thing is one closure that
XLA fuses — the "DNN runtime" execution of the model.

On TPU the tree-GEMM and featurize steps dispatch to the Pallas kernels in
:mod:`repro.kernels`; on CPU they run the pure-jnp oracles (same math).
Every f32 contraction runs at ``Precision.HIGHEST``: the TPU's default f32
matmul rounds its operands to bf16.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import _on_tpu
from repro.ml.pipeline import TrainedPipeline
from repro.ml.trees import TreeEnsemble
from repro.tensor.tree2tensor import (
    build_gemm_program,
    build_traversal_program,
    gemm_predict,
    traversal_predict,
)


@dataclass
class TensorCompilation:
    fn: Callable[[dict[str, jnp.ndarray]], dict[str, jnp.ndarray]]
    strategy: dict[str, str]  # model output name -> chosen tree strategy
    n_ops: int
    # columns the fused program consumes — surfaced so the StageGraph can
    # infer schema through an otherwise-opaque TensorOp closure
    input_names: tuple[str, ...] = ()
    # values produced by a scaler/one-hot/concat chain collapsed into the
    # fused Pallas featurize kernel (jnp oracle on CPU)
    fused: tuple[str, ...] = ()


# ---------------------------------------------------------------------------
# Coverage predicate (drives the pipeline-splitting partial lowering)
# ---------------------------------------------------------------------------

_SUPPORTED_OPS = frozenset(
    {
        "concat",
        "scaler",
        "one_hot",
        "label_encode",
        "feature_extractor",
        "constant",
        "normalizer",
        "tree_ensemble",
        "linear",
    }
)


def tensor_supported(node) -> bool:
    """Can this pipeline node run in the tensor runtime?

    Unknown ops (e.g. ``python_udf`` — an opaque host callable) are out, as
    are encoders over string/object categories: numpy compares strings fine
    on host, but a jnp program cannot hold them. These are exactly the nodes
    the split analysis routes to the host residual.
    """
    if node.op not in _SUPPORTED_OPS:
        return False
    if node.op == "one_hot":
        return np.asarray(node.attrs["categories"]).dtype.kind not in "OUSV"
    if node.op == "label_encode":
        return np.asarray(node.attrs["classes"]).dtype.kind not in "OUSV"
    return True


# ---------------------------------------------------------------------------
# Fused-featurize targeting: scaler/one-hot/concat chains -> Pallas kernel
# ---------------------------------------------------------------------------


def _detect_featurize_fusions(pipe: TrainedPipeline):
    """Find concat nodes whose whole input chain is the standard featurize
    pattern — ``concat(scaler(concat(numerics)), one_hot(c1), ...)`` over
    graph inputs — and describe each as one fused kernel call.

    Returns ``(fusions, swallowed)``: ``fusions`` maps the id() of each
    fusable final concat node to its kernel arguments; ``swallowed`` holds
    the ids of chain members replaced by the fused step. Intermediates must
    be single-consumer and not graph outputs, so fusing never orphans a
    value. The numeric part, when present, must be the concat's first input
    (the kernel emits numerics-first layout).
    """
    graph_inputs = {s.name for s in pipe.inputs}
    producer = {o: n for n in pipe.nodes for o in n.outputs}
    n_consumers: dict[str, int] = {}
    for n in pipe.nodes:
        for v in n.inputs:
            n_consumers[v] = n_consumers.get(v, 0) + 1
    out_set = set(pipe.outputs)

    def _single_use_intermediate(v: str) -> bool:
        return n_consumers.get(v, 0) == 1 and v not in out_set

    fusions: dict[int, dict] = {}
    swallowed: set[int] = set()
    for node in pipe.nodes:
        if node.op != "concat" or not node.inputs or id(node) in swallowed:
            continue
        numeric: list[str] = []
        offset = scale = None
        cat_cols: list[str] = []
        cat_vals: list[np.ndarray] = []
        segments: list[tuple[int, int]] = []
        members: list = []
        start = 0
        ok = True
        for pos, v in enumerate(node.inputs):
            p = producer.get(v)
            if p is None or not _single_use_intermediate(v):
                ok = False
                break
            if p.op == "scaler" and pos == 0 and not numeric:
                src = producer.get(p.inputs[0])
                if (
                    src is None
                    or src.op != "concat"
                    or not _single_use_intermediate(p.inputs[0])
                    or not src.inputs
                    or any(c not in graph_inputs or c in producer for c in src.inputs)
                ):
                    ok = False
                    break
                offset = np.asarray(p.attrs["offset"], np.float32).reshape(-1)
                scale = np.asarray(p.attrs["scale"], np.float32).reshape(-1)
                if offset.shape[0] != len(src.inputs):
                    ok = False
                    break
                numeric = list(src.inputs)
                members += [src, p]
            elif p.op == "one_hot":
                src_col = p.inputs[0]
                cats = np.asarray(p.attrs["categories"])
                if (
                    src_col not in graph_inputs
                    or src_col in producer
                    or cats.dtype.kind not in "iu"
                ):
                    ok = False
                    break
                segments.append((start, int(cats.shape[0])))
                start += int(cats.shape[0])
                cat_vals.append(cats.astype(np.int32))
                cat_cols.append(src_col)
                members.append(p)
            else:
                ok = False
                break
        if not ok or len(members) < 2:
            continue
        fusions[id(node)] = {
            "numeric": tuple(numeric),
            "offset": offset if offset is not None else np.zeros(0, np.float32),
            "scale": scale if scale is not None else np.zeros(0, np.float32),
            "categorical": tuple(cat_cols),
            "cat_values": (
                np.concatenate(cat_vals)
                if cat_vals
                else np.zeros(0, np.int32)
            ),
            "segments": tuple(segments),
            "out": node.outputs[0],
        }
        swallowed.update(id(m) for m in members)
    return fusions, swallowed


def _featurize_block_n(n_rows: int) -> int:
    """Row-block size for the fused kernel: the row count's power-of-two
    bucket (serving already pads batches to one), clamped to [8, 256] so the
    kernel never pads small batches up to a full 256-row block."""
    b = 1 << max(3, (max(n_rows, 1) - 1).bit_length())
    return min(b, 256)


def _choose_tree_strategy(ens: TreeEnsemble) -> str:
    """GEMM when padded matrices stay MXU-friendly; else gather traversal.

    Heuristic mirrors Hummingbird — and like Hummingbird's, it is
    hardware-specific: the GEMM strategy exists to feed matrix units
    (MXU/TensorCore); on a CPU backend its O(F·I + I·L) dense work loses to
    O(depth) gather-stepping, so CPU always picks traversal. The paper's
    §5.2 point — don't hard-code the crossover, learn it per hardware — is
    enforced by the strategy corpus measuring on the live backend either way.
    """
    if not _on_tpu():
        return "traversal"
    slices = ens.tree_slices()
    max_nodes = max(sl.stop - sl.start for sl in slices)
    max_internal = (max_nodes + 1) // 2
    return "gemm" if max_internal <= 128 else "traversal"


def tree_kernel_enabled(use_pallas: Optional[bool]) -> bool:
    """Does a GEMM-strategy ensemble run the ``tree_gemm`` Pallas kernel?
    ``use_pallas=None`` means yes on TPU, as for featurize and the
    relational kernels."""
    return bool(use_pallas) or (use_pallas is None and _on_tpu())


def tree_runtime(strategy: str, use_pallas: Optional[bool]) -> str:
    """How a tree ensemble compiled with ``strategy`` runs (for EXPLAIN)."""
    if strategy == "traversal":
        return "traversal (XLA gathers)"
    if tree_kernel_enabled(use_pallas):
        return "gemm (tree_gemm kernel)"
    return "gemm (XLA einsum)"


def compile_pipeline_tensor(
    pipe: TrainedPipeline, strategy: str = "auto", use_pallas: bool | None = None
) -> TensorCompilation:
    # eager coverage validation: reject unsupported pipelines at compile
    # time, not at first trace inside the closure — the partial-lowering
    # path relies on this to decide splits before any plan is built
    bad = sorted({n.op for n in pipe.nodes if not tensor_supported(n)})
    if bad:
        raise ValueError(f"unsupported for tensor lowering: {', '.join(bad)}")

    fusions, swallowed = _detect_featurize_fusions(pipe)
    steps: list[tuple] = []  # (kind, node) in topo order — closed over below
    chosen: dict[str, str] = {}
    fused_outs: list[str] = []
    for node in pipe.nodes:
        if id(node) in swallowed:
            continue
        if id(node) in fusions:
            steps.append(("featurize", node, fusions[id(node)]))
            fused_outs.append(node.outputs[0])
        elif node.op == "tree_ensemble":
            ens = node.attrs["ensemble"]
            strat = strategy if strategy != "auto" else _choose_tree_strategy(ens)
            chosen[node.outputs[0]] = strat
            prog = (
                build_gemm_program(ens)
                if strat == "gemm"
                else build_traversal_program(ens)
            )
            steps.append((strat, node, prog))
        else:
            steps.append((node.op, node, None))

    input_names = list(pipe.input_names())
    outputs = list(pipe.outputs)

    def fn(cols: dict[str, jnp.ndarray]) -> dict[str, jnp.ndarray]:
        vals: dict[str, jnp.ndarray] = {}
        for name in input_names:
            x = cols[name]
            vals[name] = x[:, None] if x.ndim == 1 else x
        n = next(iter(vals.values())).shape[0] if vals else 0
        for kind, node, prog in steps:
            a = node.attrs
            if kind == "featurize":
                from repro.kernels.ops import featurize_op

                info = prog
                num = (
                    jnp.concatenate(
                        [vals[c].astype(jnp.float32) for c in info["numeric"]],
                        axis=1,
                    )
                    if info["numeric"]
                    else jnp.zeros((n, 0), jnp.float32)
                )
                cat = (
                    jnp.concatenate(
                        [vals[c].astype(jnp.int32) for c in info["categorical"]],
                        axis=1,
                    )
                    if info["categorical"]
                    else jnp.zeros((n, 0), jnp.int32)
                )
                vals[info["out"]] = featurize_op(
                    num, cat,
                    jnp.asarray(info["offset"]), jnp.asarray(info["scale"]),
                    jnp.asarray(info["cat_values"]), info["segments"],
                    block_n=_featurize_block_n(num.shape[0]),
                    use_pallas=use_pallas,
                )
            elif kind == "concat":
                vals[node.outputs[0]] = jnp.concatenate(
                    [vals[i].astype(jnp.float32) for i in node.inputs], axis=1
                )
            elif kind == "scaler":
                x = vals[node.inputs[0]].astype(jnp.float32)
                vals[node.outputs[0]] = (
                    x - jnp.asarray(a["offset"], jnp.float32)
                ) * jnp.asarray(a["scale"], jnp.float32)
            elif kind == "one_hot":
                x = vals[node.inputs[0]].reshape(-1)
                cats = jnp.asarray(np.asarray(a["categories"]))
                vals[node.outputs[0]] = (
                    x[:, None] == cats[None, :]
                ).astype(jnp.float32)
            elif kind == "label_encode":
                x = vals[node.inputs[0]].reshape(-1)
                vals[node.outputs[0]] = jnp.searchsorted(
                    jnp.asarray(np.asarray(a["classes"])), x
                )[:, None]
            elif kind == "feature_extractor":
                idx = jnp.asarray(np.asarray(a["indices"], dtype=np.int32))
                vals[node.outputs[0]] = vals[node.inputs[0]][:, idx]
            elif kind == "constant":
                v = jnp.asarray(
                    np.atleast_1d(np.asarray(a["value"], np.float32))
                )[None, :]
                vals[node.outputs[0]] = jnp.broadcast_to(v, (n, v.shape[1]))
            elif kind == "normalizer":
                x = vals[node.inputs[0]].astype(jnp.float32)
                if a["norm"] == "l1":
                    d = jnp.abs(x).sum(axis=1, keepdims=True)
                elif a["norm"] == "l2":
                    d = jnp.sqrt((x * x).sum(axis=1, keepdims=True))
                else:
                    d = jnp.abs(x).max(axis=1, keepdims=True)
                vals[node.outputs[0]] = x / jnp.where(d == 0.0, 1.0, d)
            elif kind in ("gemm", "traversal"):
                X = vals[node.inputs[0]].astype(jnp.float32)
                if kind == "gemm":
                    if tree_kernel_enabled(use_pallas):
                        from repro.kernels.ops import pad_gemm_program, tree_gemm_op

                        A, B, C, D, V = pad_gemm_program(
                            prog.A, prog.B, prog.C, prog.Dcount, prog.V
                        )
                        raw = tree_gemm_op(
                            X, A, B, C, D, V, base=prog.base, use_pallas=True
                        )
                    else:
                        raw = gemm_predict(prog, X)
                else:
                    raw = traversal_predict(prog, X)
                score = (
                    1.0 / (1.0 + jnp.exp(-raw)) if prog.post == "logistic" else raw
                )
                thr = float(a.get("decision_threshold", 0.5))
                vals[node.outputs[0]] = score
                if len(node.outputs) > 1:
                    vals[node.outputs[1]] = (score >= thr).astype(jnp.int32)
            elif kind == "linear":
                X = vals[node.inputs[0]].astype(jnp.float32)
                w = jnp.asarray(np.asarray(a["weights"], np.float32))
                z = jnp.dot(X, w, precision=jax.lax.Precision.HIGHEST)
                z = z + jnp.float32(a["bias"])
                if a.get("post", "none") == "logistic":
                    z = 1.0 / (1.0 + jnp.exp(-z))
                thr = float(a.get("decision_threshold", 0.5))
                vals[node.outputs[0]] = z
                if len(node.outputs) > 1:
                    vals[node.outputs[1]] = (z >= thr).astype(jnp.int32)
            else:
                raise ValueError(kind)
        return {o: vals[o] for o in outputs}

    # canonical content token: the closure is a pure function of the
    # pipeline + compilation choices, so plans embedding it (TensorOp)
    # fingerprint stably across objects and processes instead of by id()
    from repro.core.fingerprint import fingerprint as _fingerprint

    fn.__fingerprint_token__ = _fingerprint(
        # "fz1" versions the fused-featurize emission so artifacts compiled
        # before chain fusion existed can never alias the new programs
        "tensor_compile", "fz1", pipe, strategy, use_pallas,
        sorted(chosen.items()), tuple(fused_outs),
    )
    fn.__input_names__ = tuple(input_names)
    return TensorCompilation(
        fn=fn, strategy=chosen, n_ops=len(steps),
        input_names=tuple(input_names), fused=tuple(fused_outs),
    )


# ---------------------------------------------------------------------------
# Relational kernel emission (targeted by the Join / Aggregate stage steps)
# ---------------------------------------------------------------------------
#
# The relational side of the kernel runtime lives here with the rest of the
# tensor-runtime codegen: the stage IR (exec/stages.py) decides *where* a
# Join or Filter→Aggregate chain sits in a pure stage, these helpers decide
# *how* it lowers — the Pallas gather-join / masked segmented-aggregate ops
# when shapes qualify, the legacy jnp composition otherwise. The upstream
# filter's validity mask is threaded in as the kernel mask, so Filter→Join
# and Filter→Aggregate chains fuse without materializing filtered rows.


def join_kernel_choice(plan, dim, fk, ds) -> Optional[str]:
    """Why this Join cannot lower to the gather-join kernel, or ``None``
    when it can. The kernel needs the engine's baked dim-sort entry with its
    uniqueness marker (the one-hot matmul gather needs unique dim keys),
    integer keys on both sides, f32 payload columns, at least one payload
    column to gather, and a payload small enough to stay resident in VMEM.
    ``dim`` maps column names to arrays (or anything with ``dtype`` and
    ``shape``), so EXPLAIN can ask the same question of the session tables
    that the traced Join step asks of the stage inputs."""
    from repro.kernels.relational import gather_join_block_n

    if ds is None or "unique" not in ds:
        return "duplicate dimension keys"
    if not plan.dim_columns:
        return "no payload columns"
    keys = dim[plan.dim_key]
    if not (
        jnp.issubdtype(keys.dtype, jnp.integer)
        and jnp.issubdtype(fk.dtype, jnp.integer)
    ):
        return "non-integer join keys"
    if not all(dim[c].dtype == jnp.float32 for c in plan.dim_columns):
        return "payload columns not all f32"
    m = int(keys.shape[0])
    if gather_join_block_n(m, len(plan.dim_columns)) is None:
        return (
            f"{m}x{len(plan.dim_columns)} payload exceeds the kernel's "
            "VMEM budget"
        )
    return None


def emit_join_kernel(plan, dim, fk, ds):
    """Emit the gather-join kernel call for a qualifying Join. Returns
    ``(brought, hit)``: the gathered dim columns (zero where the key
    missed) and the per-row hit mask to AND into row validity."""
    from repro.kernels.ops import gather_join_op

    order = ds["order"]
    spay = jnp.stack(
        [dim[c][order] for c in plan.dim_columns], axis=1
    ).astype(jnp.float32)
    gathered, hit = gather_join_op(
        fk.astype(jnp.int32), ds["keys"].astype(jnp.int32), spay
    )
    brought = {
        c: gathered[:, j] for j, c in enumerate(plan.dim_columns)
    }
    return brought, hit


def _agg_sources(aggs) -> list[str]:
    """The distinct value columns an Aggregate's aggs read, in order."""
    src: list[str] = []
    for _, op, col in aggs:
        if op != "count" and col not in src:
            src.append(col)
    return src


def aggregate_kernel_choice(aggs, num_segments: int) -> Optional[str]:
    """Why an Aggregate over ``num_segments`` output slots cannot lower to
    the segment-agg kernel, or ``None`` when it can: the kernel keeps every
    segment's accumulators resident in VMEM, so it holds a bounded number
    of segments (:func:`aggregate_kernel_max_segments`)."""
    from repro.kernels.relational import segment_agg_block_n

    if segment_agg_block_n(num_segments, len(_agg_sources(aggs))) is None:
        return f"{num_segments} segments exceed the kernel's VMEM budget"
    return None


def aggregate_kernel_max_segments(aggs) -> int:
    """The most request slots one segment-agg kernel call holds for these
    aggs; a coalesced group with more runs the jnp segment ops."""
    from repro.kernels.relational import segment_agg_max_segments

    return segment_agg_max_segments(len(_agg_sources(aggs)))


def emit_aggregate_kernel(aggs, cols, w, sid, num_segments):
    """Emit one masked segmented-aggregate kernel call covering every agg of
    an Aggregate op (sum/mean/count share a single one-hot matmul; min/max
    ride the same pass). ``w`` is the fused filter/validity mask."""
    from repro.kernels.ops import segment_agg_op

    src = _agg_sources(aggs)
    n = w.shape[0]
    if src:
        vals = jnp.stack([cols[c].astype(jnp.float32) for c in src], axis=1)
    else:
        vals = jnp.zeros((n, 0), jnp.float32)
    counts, sums, mins, maxs = segment_agg_op(
        vals, w, sid, num_segments=num_segments
    )
    idx = {c: j for j, c in enumerate(src)}
    out = {}
    for name, op, col in aggs:
        if op == "count":
            out[name] = counts
        elif op == "sum":
            out[name] = sums[:, idx[col]]
        elif op == "mean":
            out[name] = sums[:, idx[col]] / jnp.maximum(counts, 1.0)
        elif op == "min":
            out[name] = jnp.where(counts > 0, mins[:, idx[col]], 0.0)
        elif op == "max":
            out[name] = jnp.where(counts > 0, maxs[:, idx[col]], 0.0)
        else:
            raise ValueError(op)
    return out
