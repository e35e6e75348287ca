"""One run of one cell: set-up, the measured window, the check, the result.

The system under test is the service the configuration names (its
``service`` key; without one, ``GraphService(state)`` on one chip), with
the program's defaults and no telemetry, behind ``AsyncGraphService``.
Queries enter through ``query_async``, updates through ``submit``; nothing
else of the program is driven in the window.  Set-up warms every program
the window runs, so that nothing compiles inside it (the count is printed).
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from . import check, graphs, reference, spans, workload
from . import trace as trace_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")
METRICS_DIR = os.path.join(ROOT, "bench", "metrics")
#: a reply that takes longer than this counts as failed
REPLY_TIMEOUT_S = 120.0
#: sampled replies compared per (kind, rung), with the slowest and lanes
#: that shared a dispatch among them
SAMPLE_PER_STRATUM = 6
KINDS = ("bfs", "sssp", "bc")
#: the service of a configuration without a ``service`` key
LOCAL = {"kind": "local", "chips": 1}


def log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def load_benchmark() -> dict:
    with open(BENCHMARK) as f:
        return json.load(f)


def cell_spec(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, cell: str, traced: bool) -> List[dict]:
    """The cell's end-to-end metrics (untraced) or per-layer ones."""
    pool = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in pool if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``, or for a
    name split by cell (``query_p90_ms.hot``) the reader of its stem."""
    path = os.path.join(METRICS_DIR, f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(METRICS_DIR, f"{name.split('.')[0]}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------
# Compile accounting (JAX's own monitoring events)
# --------------------------------------------------------------------------

class CompileClock:
    """Seconds spent tracing, lowering and compiling or loading compiled
    programs from the persistent cache, and counts of each."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.seconds = 0.0
        self.traces = self.compiles = self.hits = self.misses = 0
        self._lock = threading.Lock()
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def close(self) -> None:
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._duration)
        monitoring.unregister_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self._DURATIONS:
            with self._lock:
                self.seconds += duration
                if event.endswith("jaxpr_trace_duration"):
                    self.traces += 1
                elif event.endswith("backend_compile_duration"):
                    self.compiles += 1

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def snapshot(self) -> dict:
        with self._lock:
            return {"seconds": self.seconds, "traces": self.traces,
                    "compiles": self.compiles, "cache_hits": self.hits,
                    "cache_misses": self.misses}


def _quantiles(xs) -> Optional[list]:
    """min, median, 90th and 95th percentiles and max, in milliseconds."""
    if not xs:
        return None
    return [1e3 * float(np.percentile(xs, p)) for p in (0, 50, 90, 95, 100)]


def _since(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


# --------------------------------------------------------------------------
# What the window records
# --------------------------------------------------------------------------

@dataclass
class QueryRec:
    kind: str
    src: int
    t_send: float
    t_done: float
    failed: bool
    version: int = -1
    mode: str = ""
    group: Optional[int] = None   # ServeStats.dispatches when it resolved
    result: object = None


@dataclass
class CommitRec:
    t_call: float                 # the submit call that filled the batch
    t_ret: float                  # ...returned: the version is the latest
    version: int
    ops_committed: int
    dues: List[float]             # due time of each op of the batch


@dataclass
class Run:
    """Everything a metric reader may read (``bench/metrics/*.py``)."""

    cell: str
    seconds: float
    setup_s: float
    t0: float = 0.0               # the window's start
    queries: List[QueryRec] = field(default_factory=list)
    commits: List[CommitRec] = field(default_factory=list)
    # the commit that was running at the window's close, if one was
    closing: Optional[CommitRec] = None
    counters: Dict[str, int] = field(default_factory=dict)
    trace: Optional[trace_mod.Reduced] = None
    #: the program's own ``repro.`` spans in the traced window
    spans: Optional[spans.SpanReduced] = None


def _annotate(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def _counters(svc, srv) -> Dict[str, int]:
    st, fe, sc = svc.stats, srv.stats, svc.scheduler.stats
    return {"unchanged": int(st.unchanged), "delta": int(st.delta),
            "full": int(st.full), "errors": int(st.errors),
            "degraded": int(st.degraded), "retries": int(st.retries),
            "dispatches": int(fe.dispatches),
            "batched_dispatches": int(fe.batched_dispatches),
            "fallbacks": int(fe.fallbacks),
            "picked": int(fe.picked),
            "deadline_expired": int(fe.deadline_expired),
            "max_lanes": int(fe.max_batch_seen),
            "ops_committed": int(sc.ops_committed),
            "batches_committed": int(sc.batches_committed)}


class Window:
    """Drives the clients and the updater for ``seconds`` from ``t0``."""

    def __init__(self, srv, svc, plan: workload.Plan, first_op: int,
                 seconds: float, traced: bool):
        self.srv, self.svc, self.plan = srv, svc, plan
        self.first_op, self.seconds, self.traced = first_op, seconds, traced
        self.stop = threading.Event()
        self.queries: List[QueryRec] = []
        self.commits: List[CommitRec] = []
        self.lateness: List[tuple] = []
        self.errors: List[BaseException] = []
        self._lock = threading.Lock()

    def _client(self, kind: str, sources: list) -> None:
        import jax
        srv = self.srv
        for src in sources:
            if self.stop.is_set():
                return
            group = {}

            def note(_f, group=group):
                if threading.current_thread().name == "serve-dispatcher":
                    group["id"] = srv.stats.dispatches

            with _annotate(self.traced, "bench.client.send"):
                t_send = time.perf_counter()
                fut = srv.query_async(kind, src)
            fut.add_done_callback(note)
            rec = QueryRec(kind, src, t_send, 0.0, True)
            with _annotate(self.traced, "bench.client.wait"):
                try:
                    reply = fut.result(timeout=REPLY_TIMEOUT_S)
                    jax.block_until_ready(reply.result)
                    rec.failed = bool(reply.degraded)
                    rec.version, rec.mode = reply.version, reply.mode
                    rec.result = reply.result
                except Exception as exc:       # counted in ``failed``
                    rec.failed = True
                    with self._lock:
                        self.errors.append(exc)
            rec.t_done = time.perf_counter()
            rec.group = group.get("id")
            with self._lock:
                self.queries.append(rec)

    def _updater(self) -> None:
        """Open loop at the plan's rate, or closed loop when it is 0."""
        ops = self.plan.updates[self.first_op:]
        rate = self.plan.open_loop_rate
        svc, srv = self.svc, self.srv
        dues: List[float] = []
        for i, op in enumerate(ops):
            now = time.perf_counter()
            due = self.t0 + i / rate if rate else now
            if due >= self.t1 or self.stop.is_set():
                return
            if due > now:
                with _annotate(self.traced, "bench.updater.sleep"):
                    time.sleep(due - now)
            t_call = time.perf_counter()
            self.lateness.append((due, t_call - due))
            dues.append(due)
            before = svc.version
            with _annotate(self.traced, "bench.updater.submit"):
                srv.submit(op)
            if svc.version != before:
                self.commits.append(CommitRec(
                    t_call, time.perf_counter(), svc.version,
                    int(svc.scheduler.stats.ops_committed), dues))
                dues = []

    def run(self) -> Dict[str, Dict[str, int]]:
        """The window; returns the counters at its start and end."""
        threads = [threading.Thread(target=self._client, args=c,
                                    name=f"client-{c[0]}-{i}", daemon=True)
                   for i, c in enumerate(self.plan.clients)]
        if self.plan.updates[self.first_op:]:
            threads.append(threading.Thread(target=self._updater,
                                            name="updater", daemon=True))
        before = _counters(self.svc, self.srv)
        with _annotate(self.traced, "bench.window"):
            self.t0 = time.perf_counter()
            self.t1 = self.t0 + self.seconds
            for t in threads:
                t.start()
            time.sleep(max(0.0, self.t1 - time.perf_counter()))
            self.stop.set()
            after = _counters(self.svc, self.srv)
        self.threads = threads
        return {"start": before, "end": after}

    def join(self) -> None:
        for t in self.threads:
            t.join(timeout=REPLY_TIMEOUT_S + 60)
            if t.is_alive():
                raise RuntimeError(f"thread {t.name} did not finish")


# --------------------------------------------------------------------------
# Set-up
# --------------------------------------------------------------------------

def service_spec(config: dict) -> dict:
    """The configuration's ``service``: ``{"kind": "local", "chips": 1}``
    without the key, or ``{"kind": "sharded", "chips": n, "bc_mode": m}``
    for ``ShardedGraphService`` over ``n`` chips."""
    spec = dict(config.get("service", LOCAL))
    if spec.get("kind") == "local" and spec.get("chips", 1) == 1:
        return spec
    if spec.get("kind") == "sharded" and int(spec.get("chips", 0)) >= 1 \
            and spec.get("bc_mode"):
        return spec
    raise ValueError(f"unknown service {spec!r} in configuration "
                     f"{config.get('name')!r}")


def build_service(config: dict, state):
    """The configuration's service over ``state``, with the program's
    defaults, and the devices it holds."""
    import jax
    from jax.sharding import Mesh

    from repro.engine import GraphService
    from repro.launch.mesh import make_graph_mesh
    from repro.shard import ShardedGraphService

    spec = service_spec(config)
    if spec["kind"] == "local":
        return GraphService(state), jax.devices()[:1]
    devices = jax.devices()[:spec["chips"]]
    if len(devices) < spec["chips"]:
        raise RuntimeError(f"the service shards over {spec['chips']} chips, "
                           f"JAX sees {len(devices)}")
    mesh = make_graph_mesh(Mesh(np.asarray(devices), ("chips",)))
    return ShardedGraphService(state, mesh, bc_mode=spec["bc_mode"]), devices


def _lanes_warmup(svc, state, kinds, max_lanes, rungs, sources, dirty):
    """Run every rung program of ``rungs`` at every lane count up to
    ``max_lanes`` for each kind, through the dispatcher's own batching
    (``serve.batch.dispatch_local_group``), so the window finds all of
    them compiled, with the eager stacking and slicing of each count."""
    import jax
    import jax.numpy as jnp

    from repro.core import queries
    from repro.serve.batch import Lane, dispatch_local_group

    for kind in kinds:
        srcs = sources[kind]
        priors = None
        for n in range(1, max_lanes + 1):
            lanes = [Lane(i, srcs[i % len(srcs)], "full") for i in range(n)]
            out, _ = dispatch_local_group(svc, kind, state, lanes)
            jax.block_until_ready(out)
            priors = priors or out
        if "delta" not in rungs:
            continue
        for n in range(1, max_lanes + 1):
            lanes = []
            for i in range(n):
                prior = priors[0]
                if kind == "bc":
                    cut = jnp.maximum(queries.bc_level_cut(
                        prior.level, dirty, state.alive), 1)
                    lanes.append(Lane(i, srcs[0], "delta", prior=prior,
                                      cut=cut))
                else:
                    lanes.append(Lane(i, srcs[0], "delta", prior=prior,
                                      dirty=dirty))
            out, _ = dispatch_local_group(svc, kind, state, lanes)
            jax.block_until_ready(out)


def _sharded_warmup(svc, kinds, sources, commit, commits: int,
                    growth: int) -> None:
    """Run every program that the dedup front end
    (``AsyncGraphService._dispatch_dedup``) and its fallback drive for the
    sharded service.  Each collect is of one source: per kind, a full
    collect after the first of ``commits`` commits and a collect through
    the ladder after each later one; the delta rung on a full and on a delta
    prior, whichever rung the ladder took; the ladder's test for a revived
    source; the resilient path a fallback takes (``service.query``); and
    the tile view's row refresh at every window width and row bucket the
    run's commits can form.  Leaves the result cache empty, as the local
    warm-up does."""
    import jax

    priors = {}
    for i in range(commits):
        commit()
        for kind in kinds:
            src = sources[kind][0]
            _, res, mode = svc._traced_collect(kind, src, svc._key(kind, src))
            jax.block_until_ready(res)
            if i == 0:
                if mode != "full":
                    raise RuntimeError(f"warm-up: {kind} took the {mode} "
                                       "rung on an empty cache")
                priors[kind] = (svc.version, res)
    state = svc.ring.latest.state
    for kind in kinds:
        src = sources[kind][0]
        version, prior = priors[kind]
        dirty = svc.ring.dirty_between(version, svc.version)
        for _ in range(2):
            prior = svc._delta_collect(kind, prior, dirty, src, state)
            jax.block_until_ready(prior)
        jax.block_until_ready(svc._revived_source(prior, src, state))
        jax.block_until_ready(svc.query(kind, src).result)
    _refresh_warmup(svc, growth)
    with svc._cache_lock:
        svc._cache.clear()


def _refresh_warmup(svc, growth: int) -> None:
    """Run the sharded tile view's row refresh (``_rows_refresh_fn``) at
    every (window width, row bucket) a commit can form: the widths of the
    tile rows' edge counts at the latest version, give or take ``growth``
    edges, and every bucket up to ``REFRESH_BATCH``, with padding rows that
    write nothing back (``refresh_sharded_view``'s own call)."""
    import jax
    import jax.numpy as jnp

    from repro.shard.tile_shard import (REFRESH_BATCH, ShardedTileView,
                                        _rows_refresh_fn)

    view = svc.view()
    state = svc.ring.latest.state
    esrc = np.asarray(jax.device_get(state.esrc))
    rows = np.searchsorted(esrc, np.arange(view.n_tiles + 1) * view.tile)
    counts = np.diff(rows)

    def width(n: int) -> int:            # ``core.tiles.dirty_row_windows``
        w = 64
        while w < n:
            w *= 2
        return min(w, state.ecap)

    widths, w = [], width(max(0, int(counts.min()) - growth))
    while w <= width(int(counts.max()) + growth):
        widths.append(w)
        if w == state.ecap:
            break
        w = min(2 * w, state.ecap)
    buckets = [1 << i for i in range(REFRESH_BATCH.bit_length())]
    vw, occ = view.w, view.occ
    for w in widths:
        for b in buckets:
            vw, occ = _rows_refresh_fn(view.mesh, view.tile, w, b)(
                vw, occ, state.esrc, state.edst, state.ew, state.alive,
                jnp.asarray(np.full(b, -1, np.int32)),
                jnp.asarray(np.zeros(b, np.int32)))
    jax.block_until_ready((vw, occ))
    svc._view = ShardedTileView(vw, occ, view.mesh, view.tile)


def _query_all(srv, pairs) -> list:
    import jax
    futs = [srv.query_async(kind, s) for kind, s in pairs]
    replies = [f.result(timeout=600) for f in futs]
    jax.block_until_ready([r.result for r in replies])
    return replies


# --------------------------------------------------------------------------
# The check
# --------------------------------------------------------------------------

def _dispatch_sizes(queries: List[QueryRec]) -> Dict[tuple, int]:
    """Lanes per compiled dispatch, keyed by (dispatch, kind, version,
    rung), of the delta and full replies whose dispatch is known."""
    sizes: Dict[tuple, int] = {}
    for q in queries:
        if q.group is not None and q.mode in ("delta", "full"):
            key = (q.group, q.kind, q.version, q.mode)
            sizes[key] = sizes.get(key, 0) + 1
    return sizes


def _sample(queries: List[QueryRec], seed: int) -> List[QueryRec]:
    """Per (kind, rung): the slowest reply, up to two lanes that shared a
    compiled dispatch, the rest drawn from the seed."""
    rng = graphs.rng_for(seed, 3)
    strata: Dict[tuple, list] = {}
    for q in queries:
        if not q.failed:
            strata.setdefault((q.kind, q.mode), []).append(q)
    shared = _dispatch_sizes(queries)
    out = []
    for key in sorted(strata):
        cand = strata[key]
        pick = {id(max(cand, key=lambda q: q.t_done - q.t_send)): None}
        multi = [q for q in cand if shared.get(
            (q.group, q.kind, q.version, q.mode), 0) > 1]
        for i in rng.permutation(len(multi))[:2]:
            pick[id(multi[i])] = None
        for i in rng.permutation(len(cand)):
            if len(pick) >= SAMPLE_PER_STRATUM:
                break
            pick[id(cand[i])] = None
        out += [q for q in cand if id(q) in pick]
    return out


def _host_result(res) -> dict:
    """A reply's result on the host.  A sharded result holds one row per
    source of its collect (``[S, V]``, ``ok[S]``) and ``agree``; the dedup
    front end collects one source at a time, so its row is the first."""
    import jax
    out = {k: np.asarray(v) for k, v in jax.device_get(res)._asdict().items()}
    if "agree" not in out:
        return out
    if out["ok"].shape != (1,):
        raise ValueError(f"a sharded reply of {out['ok'].shape[0]} sources")
    return {k: v[0] if v.ndim and v.shape[0] == 1 else v
            for k, v in out.items()}


def _state_arrays(state):
    """(sorted live edge keys, weights, liveness) of a device version."""
    import jax
    alive, esrc, edst, ew = jax.device_get(
        (state.alive, state.esrc, state.edst, state.ew))
    n = alive.shape[0]
    live = (ew < np.inf) & (esrc >= 0) & (esrc < n)
    keys = esrc[live].astype(np.int64) * n + edst[live].astype(np.int64)
    order = np.argsort(keys, kind="stable")
    return keys[order], ew[live][order], alive


def _check_replies(got, hosts, control):
    """Numbers compared over the sampled replies, and the control's over
    the same replies when ``control`` (a rounding) is given."""
    wrong, err, c_wrong, c_err = 0, 0.0, 0, 0.0
    for q, res in got:
        a, b = check.compare_reply(q.kind, hosts[q.version], q.src, res)
        wrong, err = wrong + a, max(err, b)
        if control is not None:
            a, b = check.control_reply(q.kind, hosts[q.version], q.src,
                                       control)
            c_wrong, c_err = c_wrong + a, max(c_err, b)
    ctrl = (None if control is None
            else {"wrong_entries": c_wrong, "bc_rel_err": c_err})
    return {"wrong_entries": wrong, "bc_rel_err": err}, ctrl


def _check_states(states, hosts, stale):
    """Numbers compared over the device versions ``states``; with
    ``stale(v)`` (the reference of version ``v`` without its last
    acknowledged batch) the control's too, on the latest version."""
    e_wrong, a_wrong = 0, 0
    for v, (keys, ws, alive) in states.items():
        a, b = check.compare_state(keys, ws, alive, hosts[v])
        e_wrong, a_wrong = e_wrong + a, a_wrong + b
    ctrl = None
    if stale is not None:
        v = max(states)
        old = stale(v)
        a, b = check.compare_state(old.keys, old.w, old.alive, hosts[v])
        ctrl = {"edges_wrong": a, "alive_wrong": b}
    return {"edges_wrong": e_wrong, "alive_wrong": a_wrong}, ctrl


def _versions(base, ops, counts, batch_size, wanted):
    """Host graphs of the ``wanted`` versions (``counts[v]`` ops each)."""
    return {v: reference.replay(base, ops, counts[v], batch_size)
            for v in sorted(wanted)}


# --------------------------------------------------------------------------
# The run
# --------------------------------------------------------------------------

def run_cell(cell: str, seed: int, seconds: float, traced: bool, *,
             t_process: float, config: Optional[dict] = None,
             traffic: Optional[dict] = None, bench: Optional[dict] = None,
             control=None) -> dict:
    """One run of ``cell``; returns the result object.  ``config`` and
    ``traffic`` replace the cell's files (tests run them small);
    ``control`` (a rounding function) also returns the control's readings
    over the same sampled replies, under ``"control"``."""
    import jax

    from repro.core import updates as prog_updates
    from repro.runtime.compile_cache import enable_compile_cache

    for code in ("PUTV", "REMV", "PUTE", "REME"):
        if getattr(prog_updates, code) != getattr(reference, code):
            raise RuntimeError(f"op code {code} differs from the program's")
    bench = bench or load_benchmark()
    spec = cell_spec(bench, cell)
    config = config or graphs.load_config(spec["config"])
    traffic = traffic or workload.load_traffic(spec["traffic"])
    cache_dir = enable_compile_cache()
    # every program in the persistent cache, however short its compile,
    # so that a run's set-up loads and never compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()
    try:
        return _run(cell, seed, seconds, traced, t_process, config, traffic,
                    bench, control, clock, cache_dir)
    finally:
        clock.close()


def _run(cell, seed, seconds, traced, t_process, config, traffic, bench,
         control, clock, cache_dir) -> dict:
    import jax

    from repro.core.graph_state import from_edge_list
    from repro.serve import AsyncGraphService

    dev = jax.devices()[0]
    split = {}
    t = time.perf_counter()
    n, ecap, src, dst, w, labels = graphs.generate(config, seed)
    base = reference.HostGraph.from_edges(n, src, dst, w)
    split["generate_s"] = time.perf_counter() - t

    t = time.perf_counter()
    c0 = clock.snapshot()
    state = from_edge_list(n, ecap, src, dst, w)
    jax.block_until_ready(state)
    del src, dst, w
    split["load_s"] = time.perf_counter() - t

    svc, devices = build_service(config, state)
    sharded = service_spec(config)["kind"] == "sharded"
    batch = svc.scheduler.batch_size
    warm_commits = 2
    kinds = [k for k in KINDS if traffic.get("clients", {}).get(k)]
    max_lanes = max([traffic["clients"][k] for k in kinds] or [0])
    plan = workload.plan(traffic, base, seed, seconds, config["weight_max"],
                         extra_ops=(warm_commits + 1) * batch, labels=labels,
                         structure=graphs.structure_seed(config, seed),
                         directed=config["directed"])
    log("setup", cell=cell, seed=seed, vcap=n, ecap=ecap,
        edges=int(base.keys.size), alive=int(base.alive.sum()),
        clients=len(plan.clients), update_ops_planned=len(plan.updates),
        cache_dir=cache_dir)

    counts = {0: 0}          # version -> ops committed
    srv = AsyncGraphService(svc)
    srv.start()
    try:
        t = time.perf_counter()
        first_op = 0

        def commit():
            nonlocal first_op
            srv.submit_many(plan.updates[first_op:first_op + batch])
            first_op += batch
            counts[svc.version] = first_op

        # pool sources, or fresh ones from the far end of the clients'
        # sequences, which no client reaches in a window
        sources = {k: plan.pools.get(k) or [s for kk, ss in plan.clients
                                            if kk == k for s in ss[-2:]]
                   for k in kinds}
        if sharded:
            _sharded_warmup(svc, kinds, sources, commit, warm_commits,
                            len(plan.updates))
        else:
            for _ in range(warm_commits):
                commit()
            if kinds:
                dirty = svc.ring.dirty_between(0, svc.version)
                _lanes_warmup(svc, svc.ring.latest.state, kinds, max_lanes,
                              traffic.get("warm_rungs", ["full"]), sources,
                              dirty)
        split["warmup_s"] = time.perf_counter() - t
        t = time.perf_counter()
        if plan.pools:
            # as many of a kind at once as its clients can have in flight
            pool = max(len(p) for p in plan.pools.values())
            for i in range(0, pool, max_lanes):
                _query_all(srv, [(k, s) for k in kinds
                                 for s in plan.pools[k][i:i + max_lanes]])
            # one more commit, then each kind once more through the
            # ladder's classification against it
            commit()
            _query_all(srv, [(k, plan.pools[k][0]) for k in kinds])
        split["prefill_s"] = time.perf_counter() - t
        if not srv.drain(timeout=600):
            raise RuntimeError("set-up queries still in flight")
        c1 = clock.snapshot()
        setup = {"compile": _since(c0, c1), **{k: round(v, 3) for k, v in
                                               split.items()}}

        logdir = None
        if traced:
            logdir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(logdir, profiler_options=opts)
        win = Window(srv, svc, plan, first_op, seconds, traced)
        t_window = time.perf_counter()
        setup_s = t_window - t_process
        counters = win.run()
        c2 = clock.snapshot()
        if traced:
            jax.profiler.stop_trace()
        win.join()
        if not srv.drain(timeout=REPLY_TIMEOUT_S):
            raise RuntimeError("queries still in flight after the window")
        for c in win.commits:
            counts[c.version] = c.ops_committed
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devices]
        peak = max(peaks) if None not in peaks else None
        run = Run(cell, seconds, setup_s, win.t0,
                  queries=[q for q in win.queries if q.t_done <= win.t1],
                  commits=[c for c in win.commits if c.t_ret <= win.t1],
                  closing=next((c for c in win.commits if c.t_ret > win.t1),
                               None),
                  counters={**_since(counters["start"], counters["end"]),
                            "max_lanes": counters["end"]["max_lanes"]})
        in_window = _since(c1, c2)
        lat = np.array([x for _, x in win.lateness]) if win.lateness else None
        edge = max(1, len(win.lateness) // 10) if win.lateness else 0
        log("window", seconds=seconds, setup_s=round(setup_s, 3), **setup,
            compiles_in_window=in_window["compiles"] + in_window[
                "cache_hits"], window_compile=in_window,
            queries=len(run.queries), commits=len(run.commits),
            ops_committed=sum(len(c.dues) for c in run.commits),
            counters=run.counters,
            lateness_start_ms=(None if lat is None else
                               1e3 * float(np.median(lat[:edge]))),
            lateness_end_ms=(None if lat is None else
                             1e3 * float(np.median(lat[-edge:]))),
            commit_ms=_quantiles([c.t_ret - c.t_call for c in run.commits]),
            latency_ms=_quantiles([q.t_done - q.t_send for q in run.queries]),
            fresh_ms=_quantiles([c.t_ret - due for c in run.commits
                                 for due in c.dues]),
            peak_bytes=peak, errors=[repr(e) for e in win.errors[:3]])

        # what the check needs from the device, then let the program go
        sample = _sample(run.queries, seed) if kinds else []
        got = [(q, _host_result(q.result)) for q in sample]
        states = {}
        if not kinds:
            ring = svc.ring
            for v in (ring.latest.version, ring.oldest_version):
                states[v] = _state_arrays(ring.get(v))
        final_version = svc.version
        for q in win.queries:
            q.result = None
    finally:
        srv.stop()
    del svc, srv, state

    t = time.perf_counter()
    shared = _dispatch_sizes(win.queries)
    if kinds:
        answered = {q.kind for q in run.queries if not q.failed}
        hosts = _versions(base, plan.updates, counts, batch,
                          {q.version for q, _ in got})
        numbers, ctrl = _check_replies(got, hosts, control)
        numbers = {"kinds_unanswered": len(set(kinds) - answered), **numbers}
    else:
        hosts = _versions(base, plan.updates, counts, batch, states)
        numbers, ctrl = _check_states(states, hosts, control and (
            lambda v: reference.replay(base, plan.updates,
                                       counts[v] - batch, batch)))
        numbers = {"versions_wrong": abs(final_version - (len(counts) - 1)),
                   **numbers}
    checks = check.verdict(numbers)
    log("check", replies_checked=len(got),
        rungs_checked=sorted({f"{q.kind}/{q.mode}" for q, _ in got}),
        shared_lanes_checked=sum(1 for q, _ in got if shared.get(
            (q.group, q.kind, q.version, q.mode), 0) > 1),
        versions_checked=sorted(hosts), seconds=round(
            time.perf_counter() - t, 3), control=ctrl)

    if traced:
        try:
            st = spans.from_profile(trace_mod.read_profile(logdir))
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        run.trace = trace_mod.reduce(st.base)
        run.spans = spans.reduce(st)
        log("trace", idle_by_device=run.trace.idle_by_device,
            collectives=vars(run.trace.collectives),
            span_seconds=run.spans.self_s, span_counts=run.spans.count)
    metrics = {}
    for m in metrics_for(bench, cell, traced):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    failed = (sum(q.failed for q in win.queries)
              + run.counters["fallbacks"] + run.counters["errors"])
    out = {"correct": check.passed(checks),
           "attempted": len(win.queries) + len(win.lateness),
           "failed": int(failed), "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": peak}}
    if len(devices) > 1:
        out["device"]["memory_peak_bytes_per_chip"] = peaks
    if traced:
        out["device"]["busy_s"] = run.trace.busy_s
        out["device"]["window_s"] = run.trace.window_s
        top = sorted(run.trace.programs.items(), key=lambda kv: -kv[1])[:10]
        out["breakdown"] = {"device_ops": [list(kv) for kv in top],
                            "idle_gaps": [list(kv) for kv in
                                          run.trace.idle_by_label]}
    if ctrl is not None:
        out["control"] = ctrl
    out["checks"] = checks
    return out


def print_result(out: dict) -> None:
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
