"""Run one cell of ``BENCHMARK.json`` on the accelerator this process holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``): draw the warehouse and the model from the
configuration's ``model_seed`` and the served rows from ``--seed``, open a
session, prepare and serve the query, and run every row bucket the window
can reach. Then the measured window: ``--seconds`` of the cell's traffic,
with the profiler off (``--trace 0``: the end-to-end metrics) or on
(``--trace 1``: the per-layer metrics). After the window closes, the
server is shut down and a sample of the answers drawn from the seed is
compared with the numpy reference; the numbers compared are printed with
their limits as the last lines on standard error and under ``"checks"``
in the result. The result is the last line on standard output.

Exits non-zero, printing no result, when JAX finds no accelerator or fewer
chips than the cell asks for, or when the program is not beside it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(ROOT, ".bench_cache")
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

# Limits of the numbers that decide ``correct`` (see PERF.md, "How correct
# is decided"): the widest gap between a served score and the reference's,
# and the answers that never came or came with the wrong row count.
SCORE_GAP_LIMIT = 1e-4
MISSING_LIMIT = 0
WAIT_AFTER_CLOSE_S = 60.0


class NoAccelerator(RuntimeError):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(name: str) -> dict:
    """The cell's entry of ``BENCHMARK.json`` with its metric lists."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {**cells[name], "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def reader(metric: str):
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Record:
    """One request as the client saw it (host clock, perf_counter)."""

    index: int
    n_rows: int
    due: float
    sent: float = math.nan  # open loop: when the generator sent it
    done: float = math.nan
    ok: bool = False
    batch: int = 0
    score: Optional[np.ndarray] = None


@dataclass
class Context:
    """What a metric reader reads."""

    cell: dict
    cfg: dict
    mix: dict
    seconds: float
    setup_s: float
    t0: float = 0.0
    t1: float = 0.0
    records: list = field(default_factory=list)
    server_before: dict = field(default_factory=dict)
    server_after: dict = field(default_factory=dict)
    plan_traces: int = 0
    backend_compiles: int = 0
    events: Optional[list] = None
    work: dict = field(default_factory=dict)
    peak: dict = field(default_factory=dict)
    chips: int = 1
    late_s: list = field(default_factory=list)

    def completed(self) -> list:
        """Requests that returned inside the window."""
        return [r for r in self.records if r.ok and self.t0 <= r.done <= self.t1]

    def due_in_window(self) -> list:
        return [r for r in self.records if self.t0 <= r.due <= self.t1]

    def stat_delta(self, key: str) -> int:
        return int(self.server_after.get(key, 0)) - int(self.server_before.get(key, 0))


class CompileCounter:
    """Counts XLA backend compiles while ``active``."""

    def __init__(self):
        self.active = False
        self.count = 0
        self._lock = threading.Lock()

    def __call__(self, event: str, *args, **kwargs) -> None:
        if self.active and "backend_compile" in event:
            with self._lock:
                self.count += 1


def accelerator(chips: int, require: bool = True):
    """JAX's devices, after pointing its compile cache into the checkout."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    import jax

    jax.config.update("jax_compilation_cache_dir", os.path.join(CACHE, "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no eviction: JAX's evicting cache is not safe across the threads that
    # compile here (an entry's access-time file can be read before it is
    # written), and a failed write recompiles that program in every run
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    if require and (devices[0].platform == "cpu" or len(devices) < chips):
        raise NoAccelerator(
            f"JAX found {len(devices)} {devices[0].platform} device(s); the "
            f"cell needs {chips} accelerator chip(s)")
    return devices


# ---------------------------------------------------------------------------
# the loops
# ---------------------------------------------------------------------------


def _settle(rec: Record, req, deadline: float) -> None:
    """Wait for the answer until ``deadline``, a minute past the window's
    close; one that has not come by then counts as failed."""
    try:
        out = req.wait(timeout=max(0.0, deadline - time.perf_counter()))
    except Exception as e:  # noqa: BLE001 - a failed answer is counted
        rec.ok = False
        print(f"request {rec.index} failed: {e}", file=sys.stderr)
        return
    rec.done = req.t_done
    rec.ok = True
    rec.score = np.asarray(out["score"]).reshape(-1)


def closed_loop(ctx: Context, served, batches: list) -> list:
    """Start ``clients`` callers; each sends its next request when the last
    returned, cycling through the prepared batches, until the window
    closes. Returns the callers' threads."""
    clients = int(ctx.mix["clients"])
    lock = threading.Lock()
    counter = iter(range(1 << 62))

    def client(c: int) -> None:
        k = c
        while True:
            now = time.perf_counter()
            if now >= ctx.t1:
                return
            with lock:
                i = next(counter)
                rec = Record(i, len(next(iter(batches[k].values()))),
                             due=now, batch=k)
                ctx.records.append(rec)
            _settle(rec, served.submit(batches[k]),
                    ctx.t1 + WAIT_AFTER_CLOSE_S)
            k = (k + clients) % len(batches)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    return threads


def open_loop(ctx: Context, served, due: np.ndarray, batches: list) -> list:
    """Send each request at its due time. A collector takes each answer as it
    comes, as a client does, keeping only its scores, so that answers are not
    held until the window closes; returns the collector's thread, which ends
    once the last answer is in."""
    todo: queue.SimpleQueue = queue.SimpleQueue()

    def collect() -> None:
        while (item := todo.get()) is not None:
            _settle(*item, ctx.t1 + WAIT_AFTER_CLOSE_S)

    collector = threading.Thread(target=collect, daemon=True)
    collector.start()
    for i, (d, b) in enumerate(zip(due, batches)):
        t_due = ctx.t0 + float(d)
        while True:
            wait = t_due - time.perf_counter()
            if wait <= 0:
                break
            time.sleep(wait if wait < 0.002 else wait - 0.001)
        rec = Record(i, len(next(iter(b.values()))), due=t_due, batch=i)
        rec.sent = time.perf_counter()
        ctx.late_s.append(rec.sent - t_due)
        ctx.records.append(rec)
        todo.put((rec, served.submit(b)))
    todo.put(None)
    return [collector]


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def check_sample(ctx: Context, seed: int) -> list:
    """The requests due in the window whose answers are compared: a sample
    drawn from the seed, with the longest request in it."""
    due = ctx.due_in_window()
    k = min(int(ctx.mix["check_requests"]), len(due))
    rng = np.random.default_rng([int(seed), 7])
    picked = {int(i) for i in rng.choice(len(due), size=k, replace=False)}
    if due:
        picked.add(max(range(len(due)), key=lambda i: due[i].n_rows))
    return [due[i] for i in sorted(picked)]


def compare(ctx: Context, sample: list, batches: list, tables, feat, forest,
            precision: str = "highest") -> dict:
    """The sampled answers against the reference. With ``precision``
    ``"high"`` the control, the reference at the precision below the
    program's, is put in the program's place."""
    from bench import reference

    gap, missing, rows = 0.0, 0, 0
    for rec in sample:
        batch = batches[rec.batch]
        ref = reference.scores(batch, tables, ctx.cfg["joins"], feat, forest)
        got = rec.score if rec.ok else None
        if precision != "highest":
            got = reference.scores(batch, tables, ctx.cfg["joins"], feat,
                                   forest, precision=precision)
        if got is None or len(got) != len(ref):
            missing += 1
            continue
        d = np.abs(got.astype(np.float64) - ref)
        if len(d):
            # scores are probabilities: a non-finite one reads as the widest gap
            gap = max(gap, float(d.max()) if np.isfinite(d).all() else 1.0)
        rows += len(ref)
    return {"score_gap": gap, "answers_missing": missing, "rows_compared": rows}


def checks_of(result: dict) -> dict:
    return {
        "score_gap": {"value": result["score_gap"], "limit": SCORE_GAP_LIMIT},
        "answers_missing": {"value": result["answers_missing"],
                            "limit": MISSING_LIMIT},
    }


def is_correct(checks: dict, rows_compared: int) -> bool:
    return rows_compared > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())


# ---------------------------------------------------------------------------


class Cell:
    """One cell, set up: its model served and every bucket warm. Windows of
    traffic then run against it, one per seed."""

    def __init__(self, name: str, require_accelerator: bool = True):
        from bench import data, model, traffic, work

        t = time.perf_counter()
        self.spec = cell_spec(name)
        self.chips = int(self.spec["chips"])
        self.devices = accelerator(self.chips, require_accelerator)
        dev = self.devices[0]
        self.peak = work.peaks(dev.device_kind) if require_accelerator else {}
        self.cfg = load_json(os.path.join(HERE, "configs",
                                          f"{self.spec['config']}.json"))
        self.mix = traffic.load(self.spec["traffic"])
        from bench import program

        devices_s = time.perf_counter() - t
        t = time.perf_counter()
        self.tables = data.warehouse(self.cfg)
        self.feat, self.forest = model.build(self.cfg, self.tables)
        used = np.unique(self.forest.feature)
        f = self.forest
        self.work = {
            "tree_gemm_flops_per_row": work.tree_gemm_flops(
                f.n_trees, len(used), f.n_internal, f.n_leaves),
            "tree_gemm_bytes_per_row": work.tree_gemm_bytes(len(used)),
            "featurize_bytes_per_row": work.featurize_bytes(
                len(self.feat.numeric), len(self.feat.categorical), len(used)),
        }
        data_s = time.perf_counter() - t
        self.served = program.Served(self.cfg, self.tables, self.feat,
                                     self.forest, self.mix)
        self.phases = {"jax_and_program": devices_s, "data_and_model": data_s,
                       **self.served.phases}
        try:
            fact = self.tables[self.cfg["fact"]]
            for round_ in ("warm_first", "warm_second"):
                # the second round finds every program compiled
                t = time.perf_counter()
                for n in traffic.warm_sizes(self.mix):
                    batch = {c: np.resize(v, n) for c, v in fact.items()}
                    self.served.submit(batch).wait(timeout=1200)
                self.phases[round_] = time.perf_counter() - t
        except BaseException:
            self.served.close()
            raise

    def plan(self, seed: int, seconds: float):
        """``(due, batches)``: the seed's requests (``due`` is None for a
        closed loop, whose callers cycle through the batches)."""
        from bench import data, traffic

        pool = data.fact_pool(self.cfg, seed, int(self.mix["pool_rows"]))
        if self.mix["loop"] == "closed":
            return None, traffic.closed_batches(self.mix, pool, seed)
        return traffic.open_plan(self.mix, pool, seed, seconds)

    def window(self, due, batches, seconds: float, trace: bool,
               setup_s: float) -> Context:
        """Run the traffic for ``seconds``; the answers are in the records."""
        import jax

        ctx = Context(cell=self.spec, cfg=self.cfg, mix=self.mix,
                      seconds=seconds, setup_s=setup_s, peak=self.peak,
                      chips=self.chips, work=self.work)
        counter = CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(counter)
        traces0 = self.served.traces()
        ctx.server_before = self.served.server_stats()
        trace_dir = os.path.join(CACHE, "trace", self.spec["name"])
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # runtime spans only: cheap
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        counter.active = True
        ctx.t0 = time.perf_counter()
        ctx.t1 = ctx.t0 + seconds
        span = jax.profiler.TraceAnnotation("bench.window") if trace else None
        if span is not None:
            span.__enter__()
        if self.mix["loop"] == "closed":
            callers = closed_loop(ctx, self.served, batches)
        else:
            callers = open_loop(ctx, self.served, due, batches)
        while time.perf_counter() < ctx.t1:
            time.sleep(min(0.01, max(0.0, ctx.t1 - time.perf_counter())))
        if span is not None:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        for t in callers:
            t.join()
        counter.active = False
        jax.monitoring.unregister_event_duration_listener(counter)
        ctx.backend_compiles = counter.count
        ctx.plan_traces = self.served.traces() - traces0
        ctx.server_after = self.served.server_stats()
        if trace:
            from bench import trace as tr

            ctx.events = tr.extract(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
        return ctx

    def memory_peak_bytes(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices[:self.chips])

    def close(self) -> None:
        self.served.close()


def verify(cell: Cell, ctx: Context, batches: list, seed: int,
           precision: str = "highest") -> tuple[dict, dict]:
    """``(checks, result)`` of the seed's sample of answers."""
    sample = check_sample(ctx, seed)
    result = compare(ctx, sample, batches, cell.tables, cell.feat,
                     cell.forest, precision)
    result["requests_compared"] = len(sample)
    return checks_of(result), result


def quarter_medians(ctx: Context) -> tuple[float, float]:
    """Median latency (ms) of the requests due in the window's first and in
    its last quarter: a backlog that grows shows as the second far above
    the first. A failed request counts as infinitely late."""
    q = ctx.seconds / 4
    out = []
    for lo, hi in ((0.0, q), (3 * q, ctx.seconds)):
        lat = [(r.done - r.due) * 1e3 if r.ok else math.inf
               for r in ctx.due_in_window() if lo <= r.due - ctx.t0 < hi]
        out.append(float(np.median(lat)) if lat else math.nan)
    return out[0], out[1]


def run(args, require_accelerator: bool = True) -> dict:
    started_s = time.perf_counter() - T_START
    cell = Cell(args.workload, require_accelerator)
    try:
        due, batches = cell.plan(args.seed, args.seconds)
        # What set-up left (JAX, the program, the session, the plan) is
        # long-lived: freeze it out of the collector, as a server does once
        # started, so that a full collection walks only the window's own
        # objects instead of stopping every thread for a walk of the heap.
        gc.collect()
        gc.freeze()
        setup_s = time.perf_counter() - T_START
        dev = cell.devices[0]
        print(f"set-up {setup_s:.3f} s on {dev.platform} {dev.device_kind} "
              f"x{len(cell.devices)}: start {started_s:.3f}, " + ", ".join(
                  f"{k} {v:.3f}" for k, v in cell.phases.items()),
              file=sys.stderr, flush=True)
        ctx = cell.window(due, batches, args.seconds, bool(args.trace), setup_s)
        peak_bytes = cell.memory_peak_bytes()
    finally:
        cell.close()
    # correctness, once the window has closed and the server is gone
    checks, result = verify(cell, ctx, batches, args.seed)
    for rec in ctx.records:
        rec.score = None
    failed = sum(1 for r in ctx.records if not r.ok)
    metrics = {}
    names = cell.spec["per_layer"] if args.trace else cell.spec["end_to_end"]
    for m in names:
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(cell.devices), "memory_peak_bytes": peak_bytes}
    out = {"correct": is_correct(checks, result["rows_compared"]) and failed == 0,
           "attempted": len(ctx.records), "failed": failed,
           "metrics": metrics, "device": device}
    if args.trace:
        from bench import trace

        device["busy_s"] = trace.busy_s(ctx.events)
        device["window_s"] = trace.window_s(ctx.events)
        out["breakdown"] = {"device_ops": trace.top_ops(ctx.events),
                            "idle_gaps": trace.idle_gaps(ctx.events)}
    if ctx.late_s:
        late = np.asarray(ctx.late_s) * 1e3
        print(f"generator lateness ms: p50 {np.percentile(late, 50):.4f} "
              f"p95 {np.percentile(late, 95):.4f} max {late.max():.4f}",
              file=sys.stderr)
    if ctx.mix["loop"] == "open":
        first, last = quarter_medians(ctx)
        print(f"backlog: first quarter median {first:.4f} ms, last quarter "
              f"median {last:.4f} ms", file=sys.stderr)
    print(f"rows compared {result['rows_compared']} over "
          f"{result['requests_compared']} requests; compiles in window: plan "
          f"{ctx.plan_traces}, backend {ctx.backend_compiles}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args)
    except NoAccelerator as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
