"""StageGraph: a first-class physical stage IR for the execution layer.

Lowering a physical plan used to produce an opaque list of Python closures;
every serving optimization (post-UDF bucketing, cross-request coalescing,
async flush, plan-cache persistence) dead-ended at that representation. This
module replaces it with a declarative graph of :class:`Stage` nodes, each
carrying:

  * its operator slice of the plan (maximal pure-jnp segment, or one MLUdf
    host boundary),
  * input/output column schema and the env tables it reads,
  * the ``:param`` slots its expressions consume,
  * a canonical per-stage content fingerprint (chained through upstream
    stages, so a stage's hash identifies *this stage of this plan*),
  * runtime accounting (XLA traces, calls, wall time).

Execution threads a three-part state ``(columns, valid, seg)`` through the
stages: ``valid`` is the row-validity mask that makes padded/bucketed serving
exact, and ``seg`` is an optional per-row request-segment id that lets
submits from different requests coalesce into one padded batch and be split
back apart after host boundaries compact rows (and lets aggregates fold
per-segment instead of per-batch).

The runner (:func:`run_graph`) accepts a ``bucketer`` so the serving layer
can re-pad rows to a power-of-two bucket at *every* host-boundary exit — not
just at query entry — which is what keeps post-UDF pure stages from
re-tracing on data-dependent shape churn.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import span
from repro.relational.expr import eval_expr, params_of
from repro.relational.table import Table

# -- execution-environment keys ---------------------------------------------
# (canonical home; repro.relational.engine re-exports the first two for
# backward compatibility)

# initial fact-spine validity mask (padded serving)
ROW_VALID_KEY = "__row_valid__"
# bound :param values (0-d arrays): runtime inputs, so re-binding never
# re-traces
PARAMS_KEY = "__params__"
# per-row request-segment ids (int32), present only under coalesced serving
ROW_SEG_KEY = "__row_seg__"
# baked dim-table sort data, injected once per execution by the engine:
# {dim_table: {"keys": sorted_keys, "order": argsort_perm[, "unique": ...]}}.
# Dim tables are frozen at registration, so the engine computes (and caches)
# the sorted order on the host instead of re-deriving it inside the traced
# stage on every call; the Join step falls back to an in-trace argsort when
# the entry is absent (abstract execution, sharded path).
DIMSORT_KEY = "__dimsort__"
# arange(num_segment_slots): its *static length* tells segmented aggregates
# their output width at trace time (slot count is power-of-two bucketed)
SEG_SLOTS_KEY = "__seg_slots__"
# runtime scalar: how many of the segment slots are real requests
SEG_COUNT_KEY = "__seg_count__"

# pseudo-table carrying a host boundary's output into the next pure stage
MID_TABLE = "__mid__"
MID_VALID = "__valid__"
MID_SEG = "__seg__"

# state threaded through stages: (columns, valid-mask, segment-ids-or-None)
State = tuple[dict[str, jnp.ndarray], jnp.ndarray, Optional[jnp.ndarray]]


def donation_enabled() -> bool:
    """Whether pure stages donate their entry buffers to XLA.

    Donation lets the compiler reuse the (single-use) padded fact-spine
    buffers in place instead of allocating fresh outputs. XLA:CPU does not
    implement input-output aliasing, so by default donation is on only for
    accelerator backends; ``RAVEN_DONATE=1``/``0`` forces it either way
    (the forced-on CPU path still computes correctly — jax just warns that
    the donated buffers were not usable).
    """
    flag = os.environ.get("RAVEN_DONATE")
    if flag is not None:
        return flag not in ("0", "false", "")
    return jax.default_backend() != "cpu"


# env keys that are per-execution (single-use) rather than database-resident:
# eligible for donation alongside the donated fact tables
VOLATILE_KEYS = (ROW_VALID_KEY, ROW_SEG_KEY, MID_TABLE)


def seg_bucket(k: int, min_bucket: int = 4) -> int:
    """Power-of-two segment-slot bucket for ``k`` coalesced requests.

    Bucketing the slot count (like row counts) bounds the number of traced
    segmented-aggregate programs at log2 of the max coalesce width.
    """
    b = max(int(min_bucket), 1)
    while b < k:
        b <<= 1
    return b


# ---------------------------------------------------------------------------
# Pure-operator steps (env -> State composition)
# ---------------------------------------------------------------------------


def pure_step(plan, inner: Optional[Callable[[dict], State]]) -> Callable[[dict], State]:
    """Compose one pure operator on top of ``inner`` (env -> state)."""
    from repro.relational.engine import (
        Aggregate,
        Filter,
        Join,
        Project,
        Scan,
        TensorOp,
    )

    if isinstance(plan, Scan):
        def fn(env, _plan=plan):
            cols = {c: env[_plan.table][c] for c in _plan.columns}
            n = next(iter(cols.values())).shape[0]
            # the serving layer pads batches to a shape bucket and marks the
            # pad rows invalid up front via ROW_VALID_KEY
            rv = env.get(ROW_VALID_KEY)
            valid = jnp.ones((n,), dtype=bool) if rv is None else rv.astype(bool)
            return cols, valid, env.get(ROW_SEG_KEY)
        return fn

    if isinstance(plan, Join):
        # relational-kernel mode is a codegen decision: captured once at
        # stage-build time, and folded into the stage fingerprint by
        # build_stage_graph so the two modes never alias compiled artifacts
        from repro.kernels.ops import kernels_enabled

        use_kernels = kernels_enabled()

        def fn(env, _plan=plan, _kern=use_kernels):
            from repro.tensor.compile import (
                emit_join_kernel,
                join_kernel_choice,
            )

            cols, valid, seg = inner(env)
            dim = env[_plan.dim_table]
            keys = dim[_plan.dim_key]
            fk = cols[_plan.fact_key]
            ds = env.get(DIMSORT_KEY, {}).get(_plan.dim_table)
            if _kern and join_kernel_choice(_plan, dim, fk, ds) is None:
                brought, hit = emit_join_kernel(_plan, dim, fk, ds)
                out = dict(cols)
                out.update(brought)
                return out, valid & hit, seg
            if ds is not None:  # baked at registration (satellite: no
                order = ds["order"]  # per-call argsort inside the trace)
                skeys = ds["keys"]
            else:
                order = jnp.argsort(keys)
                skeys = keys[order]
            pos = jnp.searchsorted(skeys, fk)
            pos = jnp.clip(pos, 0, skeys.shape[0] - 1)
            hit = skeys[pos] == fk
            gather = order[pos]
            out = dict(cols)
            for c in _plan.dim_columns:
                out[c] = dim[c][gather]
            return out, valid & hit, seg
        return fn

    if isinstance(plan, Filter):
        def fn(env, _plan=plan):
            cols, valid, seg = inner(env)
            keep = eval_expr(_plan.expr, cols, env.get(PARAMS_KEY))
            return cols, valid & keep.astype(bool), seg
        return fn

    if isinstance(plan, Project):
        def fn(env, _plan=plan):
            cols, valid, seg = inner(env)
            keep = _plan.keep if _plan.keep is not None else list(cols)
            out = {c: cols[c] for c in keep}
            for name, e in _plan.exprs.items():
                out[name] = eval_expr(e, cols, env.get(PARAMS_KEY))
            return out, valid, seg
        return fn

    if isinstance(plan, TensorOp):
        def fn(env, _plan=plan):
            cols, valid, seg = inner(env)
            out = dict(cols)
            out.update(_plan.fn(cols))
            for c in _plan.consumes:  # block columns ending here (split)
                out.pop(c, None)
            return out, valid, seg
        return fn

    if isinstance(plan, Aggregate):
        from repro.kernels.ops import kernels_enabled

        use_kernels = kernels_enabled()

        def fn(env, _plan=plan, _kern=use_kernels):
            from repro.tensor.compile import (
                aggregate_kernel_choice,
                emit_aggregate_kernel,
            )

            cols, valid, seg = inner(env)
            w = valid.astype(jnp.float32)
            if seg is None:
                # global fold: a single output row; the upstream filter is
                # already folded in as the validity weight
                if _kern:
                    sid = jnp.zeros_like(valid, dtype=jnp.int32)
                    out = emit_aggregate_kernel(_plan.aggs, cols, w, sid, 1)
                    return out, jnp.ones((1,), dtype=bool), None
                out = {}
                sid0 = jnp.zeros_like(valid, dtype=jnp.int32)
                nvalid = jnp.sum(w)
                for name, op, col in _plan.aggs:
                    if op == "count":
                        out[name] = nvalid[None]
                    elif op == "sum":
                        out[name] = jnp.sum(cols[col] * w)[None]
                    elif op == "mean":
                        out[name] = (
                            jnp.sum(cols[col] * w) / jnp.maximum(nvalid, 1.0)
                        )[None]
                    elif op in ("min", "max"):
                        out[name] = _masked_extremum(
                            op, cols[col], valid, nvalid[None], sid0, 1
                        )
                    else:
                        raise ValueError(op)
                return out, jnp.ones((1,), dtype=bool), None
            # segmented fold: one output row per request slot. Invalid/pad
            # rows carry weight 0, so routing them to slot 0 is harmless;
            # slot count is static (len of SEG_SLOTS_KEY), the number of
            # *real* segments is a runtime scalar.
            slots = env[SEG_SLOTS_KEY]
            ns = slots.shape[0]
            k = env[SEG_COUNT_KEY]
            sid = jnp.where(valid, seg, 0)
            if _kern and aggregate_kernel_choice(_plan.aggs, ns) is None:
                out = emit_aggregate_kernel(_plan.aggs, cols, w, sid, ns)
                return out, slots < k, slots
            counts = jax.ops.segment_sum(w, sid, num_segments=ns)
            out = {}
            for name, op, col in _plan.aggs:
                if op == "count":
                    out[name] = counts
                elif op == "sum":
                    out[name] = jax.ops.segment_sum(
                        cols[col] * w, sid, num_segments=ns
                    )
                elif op == "mean":
                    s = jax.ops.segment_sum(cols[col] * w, sid, num_segments=ns)
                    out[name] = s / jnp.maximum(counts, 1.0)
                elif op in ("min", "max"):
                    out[name] = _masked_extremum(
                        op, cols[col], valid, counts, sid, ns
                    )
                else:
                    raise ValueError(op)
            return out, slots < k, slots
        return fn

    raise TypeError(type(plan))


def _masked_extremum(op, values, valid, counts, sid, ns):
    """Segment min/max over valid rows only; empty segments yield 0.0 (the
    same convention in the jnp fallback, the CPU oracle, and the Pallas
    kernel, so every dispatch path agrees)."""
    v = values.astype(jnp.float32)
    if op == "min":
        m = jax.ops.segment_min(
            jnp.where(valid, v, jnp.inf), sid, num_segments=ns
        )
    else:
        m = jax.ops.segment_max(
            jnp.where(valid, v, -jnp.inf), sid, num_segments=ns
        )
    return jnp.where(counts > 0, m, 0.0)


def _from_mid(env) -> State:
    """Stage entry for operators sitting on top of a host boundary: the
    boundary's output arrives re-wrapped as the ``__mid__`` pseudo-table."""
    cols = dict(env[MID_TABLE])
    valid = cols.pop(MID_VALID)
    seg = cols.pop(MID_SEG, None)
    return cols, valid, seg


# ---------------------------------------------------------------------------
# Stage / StageGraph
# ---------------------------------------------------------------------------


@dataclass
class Stage:
    """One node of the stage graph.

    ``kind == "pure"`` stages own a maximal pure-jnp operator segment and are
    jitted into a single XLA program (``runner``); ``kind == "host"`` stages
    own one MLUdf boundary and run interpreted on host. ``fingerprint`` is a
    canonical content hash of this stage's operators chained through every
    upstream stage's hash.
    """

    index: int
    kind: str  # "pure" | "host"
    ops: list  # plan-node slice, innermost first
    fingerprint: str
    reads: dict[str, tuple[str, ...]]  # env tables consumed -> columns
    in_columns: Optional[tuple[str, ...]]  # upstream-stage columns consumed
    out_columns: tuple[str, ...]
    params: frozenset[str] = frozenset()
    fn: Optional[Callable[[dict], State]] = None  # pure: raw env -> state
    runner: Optional[Callable[[dict], State]] = None  # pure: jitted fn
    udf: Any = None  # host: the MLUdf plan node
    # False when the chained fingerprint involves an identity-hashed (id())
    # component — valid only while those objects live in THIS process, so
    # the persistent artifact store must never key an entry on it
    content_stable: bool = True
    # runtime accounting (mutated by the jit trace hook and the runner)
    traces: int = 0
    calls: int = 0
    total_s: float = 0.0
    # pipelined-execution accounting: async_calls counts executions where a
    # pure stage was *dispatched* without waiting for the device (dispatch_s
    # is that enqueue cost; the device time overlaps other stages), and for
    # host stages the wall time spent off the dispatch thread on the
    # boundary pool
    async_calls: int = 0
    dispatch_s: float = 0.0
    # bucket programs served from the persistent artifact store instead of
    # being traced in this process (warm-start preloads + lazy disk hits)
    disk_loads: int = 0

    @property
    def label(self) -> str:
        """Compact operator chain, e.g. ``Scan[patients]→Project``."""
        return "→".join(_op_label(op) for op in self.ops)

    def describe(self) -> str:
        avg = f"{1e3 * self.total_s / self.calls:.2f}ms" if self.calls else "-"
        out = ", ".join(self.out_columns)
        pin = f" params=({', '.join(sorted(self.params))})" if self.params else ""
        disk = f" disk_loads={self.disk_loads}" if self.disk_loads else ""
        pipe = ""
        if self.async_calls:
            d = 1e3 * self.dispatch_s / self.async_calls
            word = "overlap" if self.kind == "host" else "dispatch"
            pipe = f" pipelined={self.async_calls} {word}={d:.2f}ms"
        return (
            f"[{self.index}] {self.kind:<4} {self.label}  "
            f"fp={self.fingerprint[:12]}…  out=({out}){pin}  "
            f"traces={self.traces} calls={self.calls} avg={avg}{pipe}{disk}"
        )


@dataclass
class StageGraph:
    """The lowered physical plan: a linear chain of stages."""

    plan: Any  # the PhysicalPlan this graph was lowered from
    stages: list[Stage]

    @property
    def is_pure(self) -> bool:
        """One jitted XLA program, no host boundary (MLtoSQL/MLtoDNN output)."""
        return all(s.kind == "pure" for s in self.stages)

    @property
    def n_host_boundaries(self) -> int:
        return sum(1 for s in self.stages if s.kind == "host")

    @property
    def has_aggregate(self) -> bool:
        from repro.relational.engine import Aggregate

        return any(
            isinstance(op, Aggregate) for s in self.stages for op in s.ops
        )

    @property
    def needs_segments(self) -> bool:
        """True when per-request splitting of a coalesced batch requires
        segment ids: row alignment with the input spine is lost at host
        boundaries (compaction) and at aggregates (folding)."""
        return not self.is_pure or self.has_aggregate

    @property
    def traces(self) -> int:
        return sum(s.traces for s in self.stages)

    def describe(self) -> str:
        head = (
            f"stage graph: {len(self.stages)} stage(s), "
            f"{self.n_host_boundaries} host boundary(ies)"
        )
        return "\n".join([head] + [s.describe() for s in self.stages])


# ---------------------------------------------------------------------------
# Plan segmentation + schema inference
# ---------------------------------------------------------------------------


def _linearize(plan) -> list:
    """Plan nodes innermost (Scan) first. Plans are linear chains."""
    from repro.relational.engine import walk_plan

    return list(walk_plan(plan))[::-1]


def plan_segments(plan) -> list[tuple[str, list]]:
    """Split a plan into maximal pure segments and host-boundary segments.

    Returns ``[(kind, ops), ...]`` with ops innermost-first — the shared
    segmentation logic used by lowering (fn building), the optimizer's
    stage-boundary annotation, and EXPLAIN.
    """
    from repro.relational.engine import MLUdf

    segments: list[tuple[str, list]] = []
    for op in _linearize(plan):
        if isinstance(op, MLUdf):
            segments.append(("host", [op]))
        elif segments and segments[-1][0] == "pure":
            segments[-1][1].append(op)
        else:
            segments.append(("pure", [op]))
    return segments


def _op_label(op) -> str:
    """One operator's display label (shared by Stage.label and the
    optimizer's stage-boundary annotation)."""
    name = type(op).__name__
    if name == "Scan":
        return f"Scan[{op.table}]"
    if name == "Join":
        return f"Join[{op.dim_table}]"
    if name == "MLUdf":
        return f"MLUdf[{op.pipeline.n_ops()}-op]"
    if name == "TensorOp":
        # the fused closure is opaque; the tensor compiler stamps the
        # columns it consumes (see TensorCompilation.input_names)
        ins = getattr(op.fn, "__input_names__", None)
        arity = f"{len(ins)}→{len(op.output_names)}" if ins is not None else (
            f"→{len(op.output_names)}"
        )
        return f"TensorOp[{arity}]"
    return name


def describe_segments(plan) -> list[str]:
    """Human-readable stage-boundary annotation (one line per stage), used by
    the optimizer's report at lowering time."""
    return [
        f"{kind}: " + "→".join(_op_label(op) for op in ops)
        for kind, ops in plan_segments(plan)
    ]


def _segment_out_cols(ops, in_cols: Optional[list[str]]) -> list[str]:
    """Fold output-column inference over one segment's operator slice."""
    from repro.relational.engine import (
        Aggregate,
        Filter,
        Join,
        MLUdf,
        Project,
        Scan,
        TensorOp,
    )

    cur = list(in_cols or [])
    for op in ops:
        if isinstance(op, Scan):
            cur = list(op.columns)
        elif isinstance(op, Join):
            cur = cur + list(op.dim_columns)
        elif isinstance(op, Filter):
            pass
        elif isinstance(op, Project):
            base = list(op.keep) if op.keep is not None else cur
            cur = base + [c for c in op.exprs if c not in base]
        elif isinstance(op, (MLUdf, TensorOp)):
            cur = [c for c in cur if c not in op.consumes]
            cur = cur + [c for c in op.output_names if c not in cur]
        elif isinstance(op, Aggregate):
            cur = [a[0] for a in op.aggs]
        else:
            raise TypeError(type(op))
    return cur


def _segment_reads(ops) -> dict[str, tuple[str, ...]]:
    """Env tables (and their columns) this segment reads directly."""
    from repro.relational.engine import Join, Scan

    reads: dict[str, list[str]] = {}
    for op in ops:
        if isinstance(op, Scan):
            reads.setdefault(op.table, []).extend(op.columns)
        elif isinstance(op, Join):
            cols = reads.setdefault(op.dim_table, [])
            for c in [op.dim_key, *op.dim_columns]:
                if c not in cols:
                    cols.append(c)
    return {t: tuple(cs) for t, cs in reads.items()}


def _segment_params(ops) -> frozenset[str]:
    from repro.relational.engine import Filter, Project

    names: set[str] = set()
    for op in ops:
        if isinstance(op, Filter):
            names |= params_of(op.expr)
        elif isinstance(op, Project):
            for e in op.exprs.values():
                names |= params_of(e)
    return frozenset(names)


def build_stage_graph(plan, pins: Optional[list] = None) -> StageGraph:
    """Lower a physical plan into its :class:`StageGraph`.

    Pure segments get an ``env -> state`` callable composed from
    :func:`pure_step` (jitted later by the engine, which installs ``runner``
    and the trace-accounting hook); host segments carry their MLUdf node.
    Per-stage fingerprints chain: ``fp[i] = H(fp[i-1], ops[i])`` with each
    operator hashed shallowly (child pointers excluded — the chain itself
    encodes upstream structure). A stage whose chain involved an
    identity-hashed component (anything landing in ``pins``) is marked
    ``content_stable=False`` — downstream stages inherit the mark, since
    their chained hash embeds the unstable prefix.
    """
    from repro.core.fingerprint import fingerprint, node_fingerprint
    from repro.kernels.ops import kernel_mode_token
    from repro.relational.engine import Aggregate, Join

    pins = pins if pins is not None else []
    stages: list[Stage] = []
    prev_fp = ""
    prev_out: Optional[list[str]] = None
    prev_stable = True
    for idx, (kind, ops) in enumerate(plan_segments(plan)):
        stage_pins: list = []
        tokens = [node_fingerprint(op, pins=stage_pins) for op in ops]
        # the RAVEN_KERNELS mode changes the program emitted for Join /
        # Aggregate stages, so it must fork their fingerprints (and only
        # theirs — other stages keep their historical hashes)
        extra = (
            [kernel_mode_token()]
            if any(isinstance(op, (Join, Aggregate)) for op in ops)
            else []
        )
        fp = fingerprint("stage", kind, prev_fp, tokens, *extra, pins=stage_pins)
        stable = prev_stable and not stage_pins
        pins.extend(stage_pins)
        out_cols = _segment_out_cols(ops, prev_out)
        if kind == "pure":
            fn: Optional[Callable] = None if idx == 0 else _from_mid
            for op in ops:
                fn = pure_step(op, fn)
            in_cols = tuple(prev_out) if prev_out is not None else None
            stage = Stage(
                index=idx, kind=kind, ops=ops, fingerprint=fp,
                content_stable=stable,
                reads=_segment_reads(ops), in_columns=in_cols,
                out_columns=tuple(out_cols), params=_segment_params(ops),
                fn=fn,
            )
        else:
            udf = ops[0]
            stage = Stage(
                index=idx, kind=kind, ops=ops, fingerprint=fp,
                content_stable=stable,
                reads={}, in_columns=tuple(udf.pipeline.input_names()),
                out_columns=tuple(out_cols), udf=udf,
            )
        stages.append(stage)
        prev_fp = fp
        prev_out = out_cols
        prev_stable = stable
    return StageGraph(plan=plan, stages=stages)


# ---------------------------------------------------------------------------
# Host-boundary (MLUdf) execution
# ---------------------------------------------------------------------------


def run_udf(udf, cols: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    """Batch-at-a-time interpreted pipeline execution (host)."""
    from repro.ml.pipeline import run_pipeline

    n = len(next(iter(cols.values())))
    in_names = udf.pipeline.input_names()
    outs: dict[str, list[np.ndarray]] = {o: [] for o in udf.pipeline.outputs}
    bs = udf.batch_size
    for s in range(0, max(n, 1), bs):
        batch = {k: cols[k][s : s + bs] for k in in_names}
        if len(next(iter(batch.values()))) == 0:
            continue
        res = run_pipeline(udf.pipeline, batch)
        for o in udf.pipeline.outputs:
            outs[o].append(np.asarray(res[o]))
    if n == 0:
        # run the pipeline over the zero-row slice anyway: outputs must keep
        # their true trailing shape (split-lowering block columns are 2-D),
        # or the downstream pure stage would trace against the wrong rank
        res = run_pipeline(udf.pipeline, {k: cols[k][:0] for k in in_names})
        for o in udf.pipeline.outputs:
            outs[o].append(np.asarray(res[o]))
    result = dict(cols)
    for o, name in zip(udf.pipeline.outputs, udf.output_names):
        result[name] = np.concatenate(outs[o])
    for c in udf.consumes:  # block columns ending at this boundary (split)
        if c not in udf.output_names:
            result.pop(c, None)
    return result


# ---------------------------------------------------------------------------
# The runner
# ---------------------------------------------------------------------------


@dataclass
class RunResult:
    """One graph execution: the result table and the per-row segment ids it
    carried (None outside coalesced serving)."""

    table: Table
    seg: Optional[jnp.ndarray]


def call_pure(stage: Stage, env: dict[str, Any],
              donate: frozenset = frozenset(), group: int = 0) -> State:
    """Invoke one pure stage — the jitted runner when the engine installed
    one (it understands the donation set), else the raw composed fn —
    inside a ``raven.stage`` span; ``group`` is the serving dispatch id
    (0 outside serving)."""
    with span("raven.stage", group=group, stage=stage.index,
              fp=stage.fingerprint[:12]):
        if stage.runner is not None:
            return stage.runner(env, donate=donate)
        return stage.fn(env)


def strip_consumed(env: dict[str, Any], donate: frozenset) -> dict[str, Any]:
    """Drop the entry stage's single-use inputs from the env once consumed.

    Under donation the entry stage aliased the padded fact spine (and the
    row-validity/segment vectors) into its outputs, so later stages must not
    see those now-invalid buffers; without donation this is a no-op so the
    env pytree structure — and therefore every warm jit specialization and
    on-disk artifact digest — is unchanged from the serial, non-donating
    layout.
    """
    if not donate or not donation_enabled():
        return env
    drop = set(donate) | {ROW_VALID_KEY, ROW_SEG_KEY}
    return {k: v for k, v in env.items() if k not in drop}


def host_step(
    stage: Stage,
    state: State,
    env: dict[str, Any],
    *,
    bucketer: Optional[Callable[[int], int]] = None,
    on_mid_bucket: Optional[Callable[[int, int], None]] = None,
    group: int = 0,
) -> tuple[State, dict[str, Any]]:
    """Run one MLUdf host boundary inside a ``raven.host_boundary`` span:
    synchronize the upstream device state, compact to valid rows, run the
    interpreted pipeline, re-pad the output to a shape bucket, and re-wrap
    it as the ``__mid__`` pseudo-table.

    This is the graph's only synchronization point — ``np.asarray`` blocks
    on the device work the upstream pure stages dispatched — which is what
    lets the pipelined executor run it on a boundary worker thread while
    the dispatch thread keeps feeding the device. Returns the new state and
    the env (with ``__mid__`` installed) for the downstream stages.
    """
    from repro.exec.faults import maybe_inject

    with span("raven.host_boundary", group=group, stage=stage.index,
              fp=stage.fingerprint[:12]):
        # "udf" fault site: the interpreted ML runtime raises at the host
        # boundary (the Spark→Python-UDF failure mode), before any device sync
        maybe_inject("udf", token=stage.fingerprint)
        cols, valid, seg = state
        np_cols = {k: np.asarray(v) for k, v in cols.items()}
        mask = np.asarray(valid)
        np_cols = {k: v[mask] for k, v in np_cols.items()}  # compact
        np_seg = np.asarray(seg)[mask] if seg is not None else None
        out = run_udf(stage.udf, np_cols)
        n = len(next(iter(out.values()))) if out else 0
        b = bucketer(n) if bucketer is not None else n
        if b > n:
            out = {
                k: np.concatenate([v, np.zeros((b - n,) + v.shape[1:], dtype=v.dtype)])
                for k, v in out.items()
            }
            if np_seg is not None:
                np_seg = np.concatenate(
                    [np_seg, np.zeros(b - n, dtype=np_seg.dtype)]
                )
        if on_mid_bucket is not None:
            on_mid_bucket(stage.index, b)
        mid = {k: jnp.asarray(v) for k, v in out.items()}
        mid[MID_VALID] = jnp.asarray(np.arange(b) < n)
        if np_seg is not None:
            mid[MID_SEG] = jnp.asarray(np_seg, dtype=jnp.int32)
        env = dict(env)
        env[MID_TABLE] = mid
        return _from_mid(env), env


def run_graph(
    graph: StageGraph,
    env: dict[str, Any],
    *,
    bucketer: Optional[Callable[[int], int]] = None,
    on_mid_bucket: Optional[Callable[[int, int], None]] = None,
    donate: frozenset = frozenset(),
    group: int = 0,
) -> RunResult:
    """Execute a stage graph over an environment, one stage at a time.

    ``bucketer`` (serving layer) maps a host boundary's compacted row count
    to a padded bucket, so the *next* pure stage sees power-of-two shapes
    instead of data-dependent churn; ``on_mid_bucket(stage_index, bucket)``
    lets the caller account mid-graph bucket hits/misses. Without a
    ``bucketer`` the boundary output runs at its exact compacted shape (the
    one-shot ``execute_plan`` path). ``donate`` names env tables whose
    buffers are single-use (the serving layer's freshly padded fact spine)
    and may be aliased into stage outputs on accelerator backends.
    ``group`` is the serving dispatch id the stage spans carry.

    This serial runner blocks at every stage; the pipelined executor in
    :mod:`repro.exec.pipeline` runs the same stages — same jitted programs,
    same env structure — with device dispatch overlapped across request
    groups.
    """
    state: Optional[State] = None
    for stage in graph.stages:
        t0 = time.perf_counter()
        if stage.kind == "pure":
            state = call_pure(stage, env, donate, group)
            jax.block_until_ready(state[:2])
            if stage.index == 0:
                env = strip_consumed(env, donate)
        else:
            state, env = host_step(
                stage, state, env,
                bucketer=bucketer, on_mid_bucket=on_mid_bucket, group=group,
            )
        stage.calls += 1
        stage.total_s += time.perf_counter() - t0
    cols, valid, seg = state
    return RunResult(table=Table(columns=cols, valid=valid), seg=seg)
