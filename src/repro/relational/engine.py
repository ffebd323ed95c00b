"""Physical plans + execution for the columnar JAX data engine.

A plan is a tree of operators over a database (dict of named column-dicts).
Lowering splits the plan at host boundaries (``MLUdf``) into a
:class:`~repro.exec.stages.StageGraph`: maximal pure-jnp segments are jitted
as single XLA programs (so an MLtoSQL-compiled model fuses with the
scans/joins/filters around it — the whole point of the optimization), while
MLUdf stages run interpreted numpy on host with batch-at-a-time dispatch (the
Spark→Python-UDF→ML-runtime boundary, including its conversion and per-batch
overheads). The stage graph is a first-class IR — declarative,
schema-carrying, per-stage fingerprinted — built by :mod:`repro.exec.stages`;
this module owns the plan-node definitions, the jit/trace accounting, and the
fingerprint-keyed compiled-plan cache on top of it.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.exec.faults import maybe_inject
from repro.relational.expr import Expr
from repro.relational.table import Table

# ---------------------------------------------------------------------------
# Plan nodes
# ---------------------------------------------------------------------------


@dataclass
class Scan:
    table: str
    columns: list[str]  # columns actually read (projection pushdown target)


@dataclass
class Join:
    """Foreign-key join: gather dim columns onto the fact spine."""

    child: "PhysicalPlan"
    dim_table: str
    fact_key: str
    dim_key: str
    dim_columns: list[str]  # dim columns to bring in (pushdown target)


@dataclass
class Filter:
    child: "PhysicalPlan"
    expr: Expr


@dataclass
class Project:
    child: "PhysicalPlan"
    keep: Optional[list[str]]  # None -> pass all child columns through
    exprs: dict[str, Expr] = field(default_factory=dict)


@dataclass
class MLUdf:
    """Host-boundary pipeline invocation (interpreted 'ML runtime')."""

    child: "PhysicalPlan"
    pipeline: Any  # TrainedPipeline
    output_names: list[str]  # graph outputs -> column names
    batch_size: int = 10_000
    # upstream block columns (split-lowering cut values) this node is the
    # last consumer of — dropped from its output schema
    consumes: tuple[str, ...] = ()


@dataclass
class TensorOp:
    """Fused tensor program (MLtoDNN output). ``fn(cols)->cols`` is jittable."""

    child: "PhysicalPlan"
    fn: Callable[[dict[str, jnp.ndarray]], dict[str, jnp.ndarray]]
    output_names: list[str]
    # upstream block columns this node is the last consumer of (see MLUdf)
    consumes: tuple[str, ...] = ()


@dataclass
class Aggregate:
    child: "PhysicalPlan"
    aggs: list[tuple[str, str, str]]  # (out_name, op{sum,count,mean,min,max}, col)


PhysicalPlan = Union[Scan, Join, Filter, Project, MLUdf, TensorOp, Aggregate]


def plan_children(p: PhysicalPlan) -> list[PhysicalPlan]:
    return [] if isinstance(p, Scan) else [p.child]


def walk_plan(p: PhysicalPlan):
    yield p
    for c in plan_children(p):
        yield from walk_plan(c)


# ---------------------------------------------------------------------------
# Lowering: plan -> StageGraph (repro.exec.stages)
# ---------------------------------------------------------------------------

from repro.exec.stages import (  # noqa: E402  (plan nodes must exist first)
    DIMSORT_KEY,
    PARAMS_KEY,
    ROW_SEG_KEY,
    ROW_VALID_KEY,
    SEG_COUNT_KEY,
    SEG_SLOTS_KEY,
    VOLATILE_KEYS,
    RunResult,
    StageGraph,
    build_stage_graph,
    donation_enabled,
    run_graph,
    seg_bucket,
)


def plan_fingerprint(plan: PhysicalPlan, pins: Optional[list] = None) -> str:
    """Canonical content hash of a physical plan.

    Structurally identical plans (same operators, expressions, pipeline
    weights) hash equal, so compiled artifacts are reusable across plan
    objects. Opaque callables (``TensorOp.fn``) hash by identity and are
    reported via ``pins``; the compiled-plan cache keeps those alive so a
    fingerprint can never alias a dead closure's recycled id.

    Plans containing Join/Aggregate ops additionally fold in the
    ``RAVEN_KERNELS`` mode token: the mode changes the stage programs those
    plans lower to, so a CompiledPlan cached under one mode must never be
    served under the other.
    """
    from repro.core.fingerprint import fingerprint
    from repro.kernels.ops import kernel_mode_token

    extra = (
        [kernel_mode_token()]
        if any(isinstance(p, (Join, Aggregate)) for p in walk_plan(plan))
        else []
    )
    return fingerprint(plan, *extra, pins=pins)


@dataclass
class CacheStats:
    """Module-level compiled-plan cache accounting.

    ``traces`` counts XLA stage tracings across all entries; ``stage_traces``
    breaks the same count down per stage fingerprint, so callers (and
    ``db.cache_stats()`` on the session) can assert zero-retrace warm paths
    for a *specific* stage — e.g. the post-UDF pure stage of a host-boundary
    plan — without reaching into compiled-plan internals.
    """

    hits: int = 0
    misses: int = 0
    evictions: int = 0
    traces: int = 0  # XLA (re)compiles: stage tracings across all entries
    stage_traces: dict[str, int] = field(default_factory=dict)
    disk_hits: int = 0    # artifact-store loads that skipped work: a persisted
    disk_misses: int = 0  # plan or an AOT-exported stage program (vs not found)

    def snapshot(self) -> dict[str, Any]:
        return {
            "hits": self.hits, "misses": self.misses,
            "evictions": self.evictions, "traces": self.traces,
            "stage_traces": dict(self.stage_traces),
            "disk_hits": self.disk_hits, "disk_misses": self.disk_misses,
        }


PLAN_CACHE_STATS = CacheStats()
_PLAN_CACHE: "dict[str, CompiledPlan]" = {}  # insertion-ordered: LRU via re-insert
PLAN_CACHE_CAPACITY = 64


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()
    _DIMSORT_CACHE.clear()
    PLAN_CACHE_STATS.hits = PLAN_CACHE_STATS.misses = 0
    PLAN_CACHE_STATS.evictions = PLAN_CACHE_STATS.traces = 0
    PLAN_CACHE_STATS.disk_hits = PLAN_CACHE_STATS.disk_misses = 0
    PLAN_CACHE_STATS.stage_traces.clear()


# -- baked dim-table sort orders ---------------------------------------------
# Dim tables are frozen at registration, so the Join stage's sorted key
# order is a pure function of the key column's *content*. Baking it here (on
# the host, once per distinct key column) removes the per-call argsort from
# the traced stage; the cache is content-keyed — array identity is useless
# because callers re-wrap numpy tables into fresh jnp arrays per call — and
# bounded. Entries carry a zero-length "unique" marker array when the keys
# are duplicate-free: its *presence in the pytree structure* is what lets
# the traced Join step decide at trace time that the one-hot-matmul kernel
# gather is exact (see tensor.compile.join_kernel_choice).

_DIMSORT_CACHE: dict[tuple, dict[str, jnp.ndarray]] = {}
_DIMSORT_CAPACITY = 128


def dimsort_entry(keys) -> dict[str, jnp.ndarray]:
    """Baked sort data for one dim-key column: ``keys`` sorted, the stable
    argsort permutation (matching ``jnp.argsort``'s stable order, so the
    baked and in-trace fallback paths gather identical rows even with
    duplicate keys), and the uniqueness marker."""
    import hashlib

    nk = np.ascontiguousarray(np.asarray(keys))
    key = (str(nk.dtype), nk.shape, hashlib.sha1(nk.tobytes()).hexdigest())
    hit = _DIMSORT_CACHE.get(key)
    if hit is not None:
        return hit
    order = np.argsort(nk, kind="stable")
    sk = nk[order]
    entry = {
        "keys": jnp.asarray(sk),
        "order": jnp.asarray(order.astype(np.int32)),
    }
    if sk.size == 0 or not np.any(sk[1:] == sk[:-1]):
        entry["unique"] = jnp.zeros((0,), jnp.int32)
    if len(_DIMSORT_CACHE) >= _DIMSORT_CAPACITY:
        _DIMSORT_CACHE.pop(next(iter(_DIMSORT_CACHE)))
    _DIMSORT_CACHE[key] = entry
    return entry


# The process-wide artifact store (disk tier under the in-memory LRU above).
# ``raven.connect(cache_dir=...)`` installs one; stage runners consult it at
# bucket-compile time, so even CompiledPlans already resident in the LRU pick
# up (or populate) the disk tier of whichever store is active.
_ARTIFACT_STORE: Optional[Any] = None


def set_artifact_store(store: Optional[Any]) -> Optional[Any]:
    """Install (or clear, with None) the process-wide artifact store;
    returns the previous one."""
    global _ARTIFACT_STORE
    prev, _ARTIFACT_STORE = _ARTIFACT_STORE, store
    return prev


def get_artifact_store() -> Optional[Any]:
    return _ARTIFACT_STORE


@dataclass
class CompiledPlan:
    """Reusable compiled artifact for one physical plan.

    Wraps the lowered :class:`~repro.exec.stages.StageGraph`: pure stages
    carry jitted executables (jit specializes per input shape bucket
    internally; ``traces`` counts those specializations — i.e. actual XLA
    compiles). ``pins`` keeps identity-hashed plan components alive while
    this entry can be looked up.
    """

    fingerprint: str
    graph: StageGraph
    pins: list = field(default_factory=list)

    @property
    def stages(self) -> list:
        return self.graph.stages

    @property
    def n_stages(self) -> int:
        return len(self.graph.stages)

    @property
    def is_pure(self) -> bool:
        """One jitted XLA program, no host boundary (MLtoSQL/MLtoDNN output)."""
        return self.graph.is_pure

    @property
    def traces(self) -> int:
        """XLA stage tracings attributable to this compiled plan."""
        return self.graph.traces

    @property
    def specializations(self) -> int:
        """Distinct per-stage bucket programs this plan holds, however they
        arrived (fresh XLA traces *or* AOT disk loads). ``traces`` alone
        undercounts warm coverage when the artifact store preloaded shapes;
        the registry's warm gate compares this before/after a cutover."""
        return sum(
            st.traces + st.disk_loads for st in self.graph.stages
            if st.kind == "pure"
        )

    def warm_start(self, store: Optional[Any] = None) -> int:
        """Preload every on-disk exported program for this plan's stages.

        Enumerates the active artifact store's entries under each pure
        stage's chained fingerprint and deserializes them eagerly, so the
        first request landing on a previously-served bucket shape runs the
        AOT artifact instead of tracing. Returns the number of bucket
        programs loaded.
        """
        store = store if store is not None else get_artifact_store()
        if store is None:
            return 0
        n = 0
        for stage in self.graph.stages:
            if isinstance(stage.runner, _StageRunner):
                n += stage.runner.preload(store)
        return n

    def _env(
        self,
        database: dict[str, dict[str, jnp.ndarray]],
        row_valid: Optional[jnp.ndarray],
        params: Optional[dict[str, Any]],
        segments: Optional[tuple[np.ndarray, int]],
    ) -> dict[str, Any]:
        """Build the execution environment shared by the serial runner and
        the pipelined executor — one construction path, so both execute the
        exact same jit specializations."""
        env: dict[str, Any] = dict(database)
        if row_valid is not None:
            env[ROW_VALID_KEY] = jnp.asarray(row_valid, dtype=bool)
        if params:
            # float32 0-d arrays: a fresh bound value is a same-shape input
            # to the jitted stages, so re-binding never re-traces
            env[PARAMS_KEY] = {
                k: jnp.asarray(v, dtype=jnp.float32) for k, v in params.items()
            }
        if segments is not None:
            seg_ids, count = segments
            # slot count is power-of-two bucketed so segmented aggregates
            # trace per bucket, not per coalesce width; the real request
            # count rides in as a runtime scalar
            ns = seg_bucket(count)
            env[ROW_SEG_KEY] = jnp.asarray(seg_ids, dtype=jnp.int32)
            env[SEG_SLOTS_KEY] = jnp.arange(ns, dtype=jnp.int32)
            env[SEG_COUNT_KEY] = jnp.asarray(count, dtype=jnp.int32)
        ds: dict[str, dict[str, jnp.ndarray]] = {}
        for p in walk_plan(self.graph.plan):
            if isinstance(p, Join):
                tab = database.get(p.dim_table)
                if tab is not None and p.dim_key in tab:
                    ds[p.dim_table] = dimsort_entry(tab[p.dim_key])
        if ds:
            env[DIMSORT_KEY] = ds
        return env

    def lower_entry(
        self,
        database: dict[str, dict[str, jnp.ndarray]],
        params: Optional[dict[str, Any]] = None,
    ):
        """Lower the entry stage's program for these inputs without running
        it or counting a trace; ``.as_text()`` shows what the program holds,
        e.g. the Pallas kernels (``tpu_custom_call``, ``kernel_name``)."""
        stage = self.graph.stages[0]
        if stage.kind != "pure":
            raise ValueError("the entry stage is a host boundary")
        return jax.jit(stage.fn).lower(self._env(database, None, params, None))

    def run(
        self,
        database: dict[str, dict[str, jnp.ndarray]],
        row_valid: Optional[jnp.ndarray] = None,
        params: Optional[dict[str, Any]] = None,
        segments: Optional[tuple[np.ndarray, int]] = None,
        bucketer: Optional[Callable[[int], int]] = None,
        on_mid_bucket: Optional[Callable[[int, int], None]] = None,
        donate: frozenset = frozenset(),
        group: int = 0,
    ) -> RunResult:
        """Execute the stage graph; the full-fidelity serving entry point.

        ``segments=(seg_ids, n_requests)`` threads per-row request-segment
        ids through the graph (coalesced serving); ``bucketer`` re-pads host
        boundary outputs to shape buckets so post-UDF stages stay warm;
        ``donate`` names fact tables whose (single-use, freshly padded)
        buffers the entry stage may alias into its outputs on accelerator
        backends; ``group`` is the serving dispatch id the stage spans carry.
        """
        env = self._env(database, row_valid, params, segments)
        return run_graph(
            self.graph, env, bucketer=bucketer, on_mid_bucket=on_mid_bucket,
            donate=frozenset(donate), group=group,
        )

    def run_async(
        self,
        database: dict[str, dict[str, jnp.ndarray]],
        *,
        executor: Any,
        row_valid: Optional[jnp.ndarray] = None,
        params: Optional[dict[str, Any]] = None,
        segments: Optional[tuple[np.ndarray, int]] = None,
        bucketer: Optional[Callable[[int], int]] = None,
        on_mid_bucket: Optional[Callable[[int, int], None]] = None,
        donate: frozenset = frozenset(),
        group: int = 0,
    ):
        """Pipelined execution: returns a ``Future[RunResult]``.

        Pure stages dispatch asynchronously on the calling thread and host
        boundaries run on ``executor``'s boundary pool (see
        :class:`repro.exec.pipeline.PipelineExecutor`), so one request
        group's host work overlaps another's device work. Runs the same
        stage programs over the same env structure as :meth:`run` — a
        bucket warmed by either path stays warm for both.
        """
        env = self._env(database, row_valid, params, segments)
        return executor.run_graph_async(
            self.graph, env, bucketer=bucketer, on_mid_bucket=on_mid_bucket,
            donate=frozenset(donate), group=group,
        )

    def __call__(
        self,
        database: dict[str, dict[str, jnp.ndarray]],
        row_valid: Optional[jnp.ndarray] = None,
        params: Optional[dict[str, Any]] = None,
    ) -> Table:
        return self.run(database, row_valid=row_valid, params=params).table


class _StageRunner:
    """Per-stage executable: disk tier under jit's in-process specialization.

    Without an active artifact store this is exactly ``jax.jit(traced)``.
    With one, each new env shape/dtype structure (= one jit specialization =
    one bucket variant) first consults the store under the stage's chained
    content fingerprint: a hit deserializes the AOT-exported program and
    runs it (zero traces, ever); a miss traces live and then hands the
    freshly-specialized program to the store's background writer so the
    *next* process warm-starts without this request paying the export cost.
    The per-digest outcome is memoized, so steady-state calls never touch
    disk.

    On accelerator backends (or under ``RAVEN_DONATE=1``) a call carrying a
    non-empty ``donate`` set to a stage without an Aggregate (whose outputs
    could alias a row buffer) runs through a second jit specialization whose
    first argument — the single-use serving inputs: donated fact tables,
    the row-validity/segment vectors, the ``__mid__`` pseudo-table — is
    donated to XLA, letting the compiler alias the padded entry buffers
    into stage outputs instead of allocating fresh ones.
    """

    def __init__(self, stage):
        self.stage = stage

        def traced(env, _fn=stage.fn, _stage=stage):
            # python side effects run at trace time only: this counts
            # actual XLA compiles (one per new env shape/dtype structure),
            # attributed both globally and to this specific stage — and is
            # exactly where a "compile" fault fires (a failure that only
            # occurs when specializing, never on a warm call)
            maybe_inject("compile", token=_stage.fingerprint)
            _stage.traces += 1
            PLAN_CACHE_STATS.traces += 1
            PLAN_CACHE_STATS.stage_traces[_stage.fingerprint] = (
                PLAN_CACHE_STATS.stage_traces.get(_stage.fingerprint, 0) + 1
            )
            return _fn(env)

        self.jitted = jax.jit(traced)
        self._jitted_donating: Optional[Callable] = None  # built on demand
        # an aggregate folds the rows away: no output can alias a donated
        # row buffer, so donating would only earn XLA's "not usable" warning
        self._aliasable = not any(isinstance(op, Aggregate) for op in stage.ops)
        # env digest -> deserialized exported call, or None (= run live)
        self._known: dict[str, Optional[Callable]] = {}

    def _run_live(self, env, donate: frozenset):
        if not donate or not self._aliasable or not donation_enabled():
            return self.jitted(env)
        if self._jitted_donating is None:
            def traced2(volatile, resident, _fn=self.stage.fn,
                        _stage=self.stage):
                maybe_inject("compile", token=_stage.fingerprint)
                _stage.traces += 1
                PLAN_CACHE_STATS.traces += 1
                PLAN_CACHE_STATS.stage_traces[_stage.fingerprint] = (
                    PLAN_CACHE_STATS.stage_traces.get(_stage.fingerprint, 0)
                    + 1
                )
                return _fn({**resident, **volatile})

            self._jitted_donating = jax.jit(traced2, donate_argnums=(0,))
        volatile = {
            k: v for k, v in env.items()
            if k in donate or k in VOLATILE_KEYS
        }
        resident = {k: v for k, v in env.items() if k not in volatile}
        return self._jitted_donating(volatile, resident)

    def __call__(self, env, donate: frozenset = frozenset()):
        # fault sites: "latency" stalls the stage (slow-stage spike),
        # "stage" raises at call time; tokens carry the stage fingerprint
        # so a plan can target one stage (e.g. only the kernel-mode fork)
        maybe_inject("latency", token=self.stage.fingerprint)
        maybe_inject("stage", token=self.stage.fingerprint)
        store = get_artifact_store()
        if store is None or not self.stage.content_stable:
            # identity-hashed fingerprint components are meaningless in any
            # other process (and a recycled id could alias a different
            # stage), so an unstable stage never touches the disk tier
            return self._run_live(env, donate)
        from repro.exec.artifact_store import env_digest

        digest = env_digest(env)
        if digest in self._known:
            fn = self._known[digest]
            return self._run_live(env, donate) if fn is None else fn(env)
        fn = store.load_stage(self.stage.fingerprint, digest)
        if fn is not None:
            PLAN_CACHE_STATS.disk_hits += 1
            self.stage.disk_loads += 1
            self._known[digest] = fn
            return fn(env)
        PLAN_CACHE_STATS.disk_misses += 1
        self._known[digest] = None
        # snapshot the env's structure (shapes/dtypes only) *before* running:
        # under donation the live call invalidates the volatile buffers, and
        # the background writer must not pin real device arrays anyway
        from repro.exec.artifact_store import abstract_env

        abstract = abstract_env(env)
        out = self._run_live(env, donate)  # live trace for this structure
        # export the raw stage fn (not ``traced``: the export's own trace
        # must not inflate retrace accounting); the store's writer thread
        # serializes off the request path
        store.save_stage_async(
            self.stage.fingerprint, digest, self.stage.fn, abstract
        )
        return out

    def preload(self, store) -> int:
        """Deserialize every on-disk bucket program for this stage."""
        if not self.stage.content_stable:
            return 0
        n = 0
        for digest in store.stage_digests(self.stage.fingerprint):
            if digest in self._known:
                # already resolved in this process — including digests this
                # process traced live and then saved itself: re-loading
                # those would fabricate "disk warm start" stats for work
                # that never crossed a process boundary
                continue
            fn = store.load_stage(self.stage.fingerprint, digest)
            if fn is not None:
                PLAN_CACHE_STATS.disk_hits += 1
                self.stage.disk_loads += 1
                self._known[digest] = fn
                n += 1
        return n


def _build_compiled(plan: PhysicalPlan, fingerprint: str, pins: list) -> CompiledPlan:
    graph = build_stage_graph(plan, pins=pins)
    for stage in graph.stages:
        if stage.kind == "pure":
            stage.runner = _StageRunner(stage)
    return CompiledPlan(fingerprint=fingerprint, graph=graph, pins=pins)


def compile_plan(plan: PhysicalPlan, cache: bool = True) -> CompiledPlan:
    """Compile a plan into a reusable executable over a database dict.

    Pure stages are jitted (one XLA program each — a fully-MLtoSQL'd query is
    exactly ONE program); UDF stages run on host between them. Compiled
    artifacts are cached in a module-level LRU keyed by plan fingerprint, so
    repeated compile/execute of an identical plan reuses both the lowered
    stages and jit's shape-specialized XLA programs instead of re-jitting
    per call. ``cache=False`` forces a fresh compile (the pre-cache,
    compile-per-call behavior — kept for benchmarks and tests).
    """
    if not cache:
        pins: list = []
        return _build_compiled(plan, plan_fingerprint(plan, pins=pins), pins)
    pins = []
    fp = plan_fingerprint(plan, pins=pins)
    entry = _PLAN_CACHE.get(fp)
    if entry is not None:
        PLAN_CACHE_STATS.hits += 1
        _PLAN_CACHE.pop(fp)  # LRU: re-insert as most recent
        _PLAN_CACHE[fp] = entry
        return entry
    PLAN_CACHE_STATS.misses += 1
    entry = _build_compiled(plan, fp, pins)
    _PLAN_CACHE[fp] = entry
    while len(_PLAN_CACHE) > PLAN_CACHE_CAPACITY:
        _PLAN_CACHE.pop(next(iter(_PLAN_CACHE)))
        PLAN_CACHE_STATS.evictions += 1
    return entry


def execute_plan(
    plan: PhysicalPlan,
    database: dict[str, dict[str, np.ndarray]],
    row_valid: Optional[np.ndarray] = None,
    params: Optional[dict[str, Any]] = None,
) -> Table:
    db = {
        t: {c: jnp.asarray(v) for c, v in cols.items()}
        for t, cols in database.items()
    }
    return compile_plan(plan)(db, row_valid=row_valid, params=params)


def plan_params(plan: PhysicalPlan) -> set[str]:
    """Names of every :class:`~repro.relational.expr.Param` the plan reads."""
    from repro.relational.expr import params_of

    names: set[str] = set()
    for p in walk_plan(plan):
        if isinstance(p, Filter):
            names |= params_of(p.expr)
        elif isinstance(p, Project):
            for e in p.exprs.values():
                names |= params_of(e)
    return names


# ---------------------------------------------------------------------------
# Data-parallel execution (shard_map over the 'data' mesh axis)
# ---------------------------------------------------------------------------


def compile_plan_sharded(
    plan: PhysicalPlan,
    mesh: jax.sharding.Mesh,
    fact_table: str,
    axis: str = "data",
) -> Callable[[dict], Table]:
    """Shard the fact table's rows over ``axis``; replicate dimension tables.

    Only valid for fully-pure plans (MLtoSQL / MLtoDNN output). Aggregates
    become partial-per-shard + psum.
    """
    from jax.sharding import PartitionSpec as P

    graph = build_stage_graph(plan)
    assert len(graph.stages) == 1 and graph.is_pure, (
        "sharded execution requires a host-boundary-free plan"
    )
    fn = graph.stages[0].fn
    has_agg = any(isinstance(p, Aggregate) for p in walk_plan(plan))

    def body(env):
        cols, valid, _seg = fn(env)
        if has_agg:
            cols = {k: jax.lax.psum(v, axis) for k, v in cols.items()}
            # counts/sums compose additively; mean needs sum/count form —
            # callers use sum+count and divide outside.
        return cols, valid

    def specs_for(env):
        in_specs = {}
        for t, cols in env.items():
            spec = P(axis) if t == fact_table else P()
            in_specs[t] = {c: spec for c in cols}
        return in_specs

    def run(database):
        env = {
            t: {c: jnp.asarray(v) for c, v in cols.items()}
            for t, cols in database.items()
        }
        in_specs = (specs_for(env),)
        out_specs = (
            ({k: P() for k in _out_cols(plan)}, P())
            if has_agg
            else ({k: P(axis) for k in _out_cols(plan)}, P(axis))
        )
        sharded = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        cols, valid = jax.jit(sharded)(env)
        return Table(columns=cols, valid=valid)

    return run


def _out_cols(plan: PhysicalPlan) -> list[str]:
    """Static output-column inference for out_specs."""
    if isinstance(plan, Scan):
        return list(plan.columns)
    if isinstance(plan, Join):
        return _out_cols(plan.child) + list(plan.dim_columns)
    if isinstance(plan, Filter):
        return _out_cols(plan.child)
    if isinstance(plan, Project):
        base = _out_cols(plan.child) if plan.keep is None else list(plan.keep)
        return base + list(plan.exprs)
    if isinstance(plan, (MLUdf, TensorOp)):
        base = [c for c in _out_cols(plan.child) if c not in plan.consumes]
        return base + [c for c in plan.output_names if c not in base]
    if isinstance(plan, Aggregate):
        return [a[0] for a in plan.aggs]
    raise TypeError(type(plan))
