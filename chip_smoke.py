#!/usr/bin/env python3
"""Smoke test of the served dynamic-graph path on a TPU.

    python chip_smoke.py             # one chip: served path + compiled kernels
    python chip_smoke.py --chips 4   # four chips: the sharded service only

One chip (the default) runs two phases:

  * ``serve``: a Graph500 Kronecker graph (scale 20, edge factor 16) behind
    ``AsyncGraphService(GraphService(...))``; client threads send concurrent
    BFS / SSSP / BC queries while an updater thread commits skewed churn,
    so the unchanged, delta and full rungs of the ladder all answer;
  * ``kernels``: the dense tile-semiring queries at vcap 16384 with the six
    Pallas kernels compiled (``tpu_custom_call`` in the HLO), compared with
    the jnp path, plus one all-source ``GraphService.bc_scores``.

``--chips 4`` runs ``ShardedGraphService`` (ring BC) at vcap 65536, whose
16 GiB dense grid no single chip holds, with churn, and nothing else.

Every sampled answer is compared with a plain host reference (scipy
Dijkstra for BFS hops and SSSP, a numpy level-synchronous Brandes for BC)
over a host copy of the graph that applies the same ops itself.  Any
mismatch, fallback, error, retry or degraded reply fails the run.  The
script refuses to run (exit 2, no result line) unless JAX's first device
is a TPU.  Its last stdout line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from scipy.sparse import csr_matrix  # noqa: E402
from scipy.sparse.csgraph import dijkstra  # noqa: E402

from repro.core import queries  # noqa: E402
from repro.core.tiles import dense_views_from_tiles  # noqa: E402
from repro.core.updates import PUTE, REME  # noqa: E402
from repro.data import load_rmat_graph, rmat_edges  # noqa: E402
from repro.engine import GraphService  # noqa: E402
from repro.launch.mesh import make_graph_mesh  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve import AsyncGraphService  # noqa: E402
from repro.shard import ShardedGraphService  # noqa: E402

#: Graph500 Kronecker initiator (graph500.org specification, section 3).
GRAPH500 = (0.57, 0.19, 0.19, 0.05)
EDGE_FACTOR = 16
SLACK = 1.5
#: sources per query kind per round: the async dispatcher's lane count
#: (one power-of-two bucket, so each rung compiles one batch width).
LANES = 4
#: BC delta is a sum of O(vcap) f32 terms in order-dependent rounding;
#: sqrt(vcap) * eps(f32) is ~1e-4 at vcap 2^20, so 1e-3 leaves margin.
DELTA_RTOL = 1e-3


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def require(ok, what: str) -> None:
    """Fail the run unless ``ok`` (not ``assert``: ``python -O`` would
    strip every check and let a wrong run print its result line)."""
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


# --------------------------------------------------------------------------
# Compile accounting: what JAX reports spending on trace/lower/compile.
# --------------------------------------------------------------------------

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_retrieval_time_sec")


class CompileClock:
    """Seconds spent tracing, lowering, compiling (or reading compiled
    programs back from the persistent cache), from JAX's own monitoring
    events, plus persistent-cache hit/miss counts."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            with self._lock:
                self.seconds += duration

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# --------------------------------------------------------------------------
# Host reference: an independent copy of the graph and plain algorithms.
# --------------------------------------------------------------------------

class HostGraph:
    """The graph as host arrays: sorted unique ``u * V + v`` keys with their
    weights, plus vertex liveness.  Applies ops with the ADT's sequential
    semantics on its own (no code under test involved)."""

    def __init__(self, n: int, keys: np.ndarray, w: np.ndarray,
                 alive: np.ndarray):
        self.n, self.keys, self.w, self.alive = n, keys, w, alive
        self._csr = None

    @classmethod
    def from_edges(cls, n: int, src, dst, w) -> "HostGraph":
        keys = src.astype(np.int64) * n + dst.astype(np.int64)
        # duplicate keys keep the last weight (the bulk load's rule)
        rev_keys, first = np.unique(keys[::-1], return_index=True)
        weights = w[::-1][first].astype(np.float32)
        alive = np.zeros(n, bool)
        alive[src] = True
        alive[dst] = True
        return cls(n, rev_keys, weights, alive)

    def out_neighbors(self, u: int) -> np.ndarray:
        lo, hi = np.searchsorted(self.keys, [u * self.n, (u + 1) * self.n])
        return (self.keys[lo:hi] - u * self.n).astype(np.int64)

    def apply(self, ops) -> "HostGraph":
        """A new version: PUTE inserts or re-weights, REME removes; an op on
        a dead endpoint does nothing."""
        final = {}
        for op in ops:
            kind, u, v = op[0], int(op[1]), int(op[2])
            if not (self.alive[u] and self.alive[v]):
                continue
            key = u * self.n + v
            if kind == PUTE:
                final[key] = float(op[3])
            elif kind == REME:
                final[key] = None
        touched = np.fromiter(sorted(final), np.int64, len(final))
        idx = np.searchsorted(self.keys, touched)
        present = (idx < self.keys.size) & (
            self.keys[np.minimum(idx, self.keys.size - 1)] == touched)
        keep = np.ones(self.keys.size, bool)
        w = self.w.copy()
        new_k, new_w = [], []
        for key, i, pres in zip(touched, idx, present):
            val = final[int(key)]
            if pres and val is None:
                keep[i] = False
            elif pres:
                w[i] = val
            elif val is not None:
                new_k.append(key)
                new_w.append(val)
        keys = np.concatenate([self.keys[keep], np.asarray(new_k, np.int64)])
        w = np.concatenate([w[keep], np.asarray(new_w, np.float32)])
        order = np.argsort(keys, kind="stable")
        return HostGraph(self.n, keys[order], w[order], self.alive)

    def csr(self) -> csr_matrix:
        if self._csr is None:
            u = self.keys // self.n
            indptr = np.searchsorted(u, np.arange(self.n + 1))
            self._csr = csr_matrix(
                (self.w.astype(np.float64), (self.keys % self.n).astype(
                    np.int64), indptr), shape=(self.n, self.n))
        return self._csr


def ref_hops(g: HostGraph, s: int) -> np.ndarray:
    d = dijkstra(g.csr(), directed=True, indices=s, unweighted=True)
    return np.where(np.isinf(d), -1, d).astype(np.int64)


def ref_sssp(g: HostGraph, s: int) -> np.ndarray:
    return dijkstra(g.csr(), directed=True, indices=s)


def ref_brandes(g: HostGraph, s: int):
    """Level-synchronous Brandes from ``s``: (level, sigma, delta)."""
    a = g.csr()
    n = g.n
    level = np.full(n, -1, np.int64)
    sigma = np.zeros(n, np.float64)
    level[s], sigma[s] = 0, 1.0
    layers = []                       # per level: (edge src, edge dst)
    front = np.array([s], np.int64)
    d = 0
    while front.size:
        counts = a.indptr[front + 1] - a.indptr[front]
        us = np.repeat(front, counts)
        starts = np.repeat(a.indptr[front] - np.cumsum(counts) + counts,
                           counts)
        vs = a.indices[starts + np.arange(us.size)].astype(np.int64)
        fresh = vs[level[vs] < 0]
        level[fresh] = d + 1
        tree = level[vs] == d + 1
        us, vs = us[tree], vs[tree]
        sigma += np.bincount(vs, weights=sigma[us], minlength=n)
        layers.append((us, vs))
        front = np.unique(fresh)
        d += 1
    delta = np.zeros(n, np.float64)
    for us, vs in reversed(layers):
        delta += np.bincount(us, weights=sigma[us] / sigma[vs]
                             * (1.0 + delta[vs]), minlength=n)
    delta[s] = 0.0
    return level, sigma, delta


def check_answer(kind: str, g: HostGraph, s: int, dist=None, level=None,
                 sigma=None, delta=None) -> None:
    """Assert one device answer equals the host reference (dist, level
    and sigma exactly; BC delta to ``DELTA_RTOL``)."""
    if kind == "bfs":
        want = ref_hops(g, s)
        require(np.array_equal(np.asarray(dist, np.int64), want),
                f"bfs from {s}: hop distances differ from the reference")
    elif kind == "sssp":
        want = ref_sssp(g, s)
        require(np.array_equal(np.asarray(dist, np.float64), want),
                f"sssp from {s}: distances differ from the reference")
    else:
        lv, sg, dl = ref_brandes(g, s)
        require(np.array_equal(np.asarray(level, np.int64), lv),
                f"bc from {s}: levels differ from the reference")
        require(np.array_equal(np.asarray(sigma, np.float64), sg),
                f"bc from {s}: path counts differ from the reference")
        require(np.allclose(np.asarray(delta, np.float64), dl,
                            rtol=DELTA_RTOL, atol=DELTA_RTOL),
                f"bc from {s}: dependencies differ from the reference")


# --------------------------------------------------------------------------
# Workload: Graph500 graph, sources, skewed churn.
# --------------------------------------------------------------------------

def graph500(scale: int, seed: int):
    """(device GraphState, HostGraph) of one Kronecker graph; the device
    side goes through the package's loader, the host side dedups the same
    generated edge list on its own."""
    n = 1 << scale
    m = EDGE_FACTOR * n
    state = load_rmat_graph(n, m, slack=SLACK, seed=seed,
                            initiator=GRAPH500)
    src, dst, w = rmat_edges(n, m, *GRAPH500, seed=seed)
    return state, HostGraph.from_edges(n, src, dst, w)


def pick_sources(g: HostGraph, rng, n_hubs: int):
    """``n_hubs`` alive sources of high out-degree plus one alive source
    with no out-edge (a query churn never reaches: its rung stays
    unchanged)."""
    outdeg = np.bincount(g.keys // g.n, minlength=g.n)
    hubs = np.argsort(-outdeg, kind="stable")[:64 * n_hubs]
    hubs = rng.choice(hubs, n_hubs, replace=False)
    quiet = np.flatnonzero(g.alive & (outdeg == 0))
    return [int(x) for x in hubs] + [int(rng.choice(quiet))]


def hot_set(movers: np.ndarray, r: int, size: int = 64) -> np.ndarray:
    """Round ``r``'s hot vertices: the next ``size`` of a fixed random
    order, so the hot set moves every batch."""
    return np.take(movers, np.arange(r * size, (r + 1) * size), mode="wrap")


def churn(g: HostGraph, rng, size: int, hot: np.ndarray, max_w: int):
    """One batch of skewed edge churn: hot vertices drawn Zipf(1.1) from
    ``hot``; half the ops insert an edge to a random alive vertex, a
    quarter delete one of the vertex's edges, a quarter re-weight one."""
    alive = np.flatnonzero(g.alive)
    p = 1.0 / np.arange(1, hot.size + 1) ** 1.1
    us = hot[rng.choice(hot.size, size, p=p / p.sum())]
    ops = []
    for u in us:
        nbrs = g.out_neighbors(int(u))
        r = rng.random()
        if r < 0.5 or nbrs.size == 0:
            ops.append((PUTE, int(u), int(rng.choice(alive)),
                        float(rng.integers(1, max_w + 1))))
        elif r < 0.75:
            ops.append((REME, int(u), int(rng.choice(nbrs))))
        else:
            ops.append((PUTE, int(u), int(rng.choice(nbrs)),
                        float(rng.integers(1, max_w + 1))))
    return ops


def _require_clean(service, frontend=None) -> dict:
    """Fallbacks, errors, retries and degraded replies all fail the run."""
    st = service.stats
    counts = {"errors": int(st.errors), "retries": int(st.retries),
              "degraded": int(st.degraded),
              "fallbacks": int(frontend.stats.fallbacks) if frontend else 0}
    require(not any(counts.values()),
            f"hidden failure path taken: {counts}")
    return counts


# --------------------------------------------------------------------------
# Phase 1: the served path on one chip.
# --------------------------------------------------------------------------

def serve_phase(scale: int = 20, rounds: int = 3, batch_ops: int = 256,
                seed: int = 0, checks_per_rung: int = 2,
                clock: CompileClock | None = None) -> dict:
    """Graph500 graph behind ``AsyncGraphService(GraphService(...))``:
    ``rounds`` rounds of concurrent client queries, each overlapped with
    one committed churn batch, then one quiet round.  Returns the counts
    it printed."""
    t0 = time.perf_counter()
    c0 = clock.seconds if clock else 0.0
    rng = np.random.default_rng(seed)
    state, g0 = graph500(scale, seed)
    jax.block_until_ready(state)
    log("serve", step="graph", vcap=state.vcap, ecap=state.ecap,
        edges=int(g0.keys.size), alive=int(g0.alive.sum()),
        seconds=round(time.perf_counter() - t0, 3))
    versions = [g0]
    srcs = {k: pick_sources(g0, rng, LANES - 1) for k in ("bfs", "sssp",
                                                          "bc")}
    outdeg = np.bincount(g0.keys // g0.n, minlength=g0.n)
    movers = rng.permutation(np.flatnonzero(outdeg > 0))
    svc = GraphService(state, batch_size=batch_ops)
    replies = []                           # (kind, src, reply)

    def client(kind):
        futs = [(s, srv.query_async(kind, s)) for s in srcs[kind]]
        for s, f in futs:
            replies.append((kind, s, f.result()))

    with AsyncGraphService(svc, max_batch=3 * LANES) as srv:
        for r in range(rounds + 1):
            threads = [threading.Thread(target=client, args=(k,))
                       for k in srcs]
            if r < rounds:
                ops = churn(versions[-1], rng, batch_ops,
                            hot_set(movers, r), scale)
                versions.append(versions[-1].apply(ops))
                threads.append(threading.Thread(target=srv.submit_many,
                                                args=(ops,)))
            tr = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            require(srv.drain(timeout=600), "queries still in flight")
            require(svc.version == len(versions) - 1,
                    f"committed version {svc.version} != {len(versions) - 1}")
            log("serve", step="round", round=r, version=svc.version,
                replies=len(replies),
                seconds=round(time.perf_counter() - tr, 3))
        clean = _require_clean(svc, srv)
    # a client thread that raised left its replies out
    require(len(replies) == (rounds + 1) * sum(map(len, srcs.values())),
            f"only {len(replies)} queries answered")
    st = svc.stats
    rungs = {"unchanged": int(st.unchanged), "delta": int(st.delta),
             "full": int(st.full)}
    run_s = time.perf_counter() - t0
    log("serve", step="served", queries=len(replies), rungs=rungs,
        ops_committed=int(svc.scheduler.stats.ops_committed),
        versions=svc.version, batches_dispatched=srv.stats.dispatches,
        max_lanes=srv.stats.max_batch_seen, **clean)
    require(all(rungs.values()), f"a ladder rung never answered: {rungs}")

    t1 = time.perf_counter()
    checked, seen = {}, set()
    for kind, s, rep in replies:
        key = (kind, rep.mode)
        if (checked.get(key, 0) >= checks_per_rung
                or (kind, s, rep.version) in seen):
            continue
        res = jax.device_get(rep.result)
        require(bool(res.ok), f"{kind} from alive source {s} not ok")
        g = versions[rep.version]
        if kind == "bc":
            check_answer(kind, g, s, level=res.level, sigma=res.sigma,
                         delta=res.delta)
        else:
            check_answer(kind, g, s, dist=res.dist)
        seen.add((kind, s, rep.version))
        checked[key] = checked.get(key, 0) + 1
    out = {"vcap": state.vcap, "rungs": rungs,
           "references_matched": sum(checked.values()),
           "compile_s": round((clock.seconds if clock else 0.0) - c0, 3),
           "wall_s": round(run_s, 3),
           "reference_s": round(time.perf_counter() - t1, 3),
           "peak_bytes": peak_bytes(jax.devices()[0])}
    log("serve", step="checked",
        matched={f"{k}/{m}": n for (k, m), n in sorted(checked.items())},
        **out)
    return out


# --------------------------------------------------------------------------
# Phase 2: the six Pallas kernels compiled, against the jnp path.
# --------------------------------------------------------------------------

def kernel_phase(scale: int = 14, n_srcs: int = 128, src_chunk: int = 2048,
                 seed: int = 1, clock: CompileClock | None = None) -> dict:
    """Dense tile-semiring BFS/SSSP/BC, dense and tile-skipping, kernels
    vs jnp; then one all-source ``bc_scores`` on the kernel path.  On a
    TPU every kernel program must hold a compiled Pallas kernel (other
    backends run the kernel bodies in interpret mode)."""
    t0 = time.perf_counter()
    c0 = clock.seconds if clock else 0.0
    rng = np.random.default_rng(seed)
    state, g = graph500(scale, seed)
    svc = GraphService(state)
    view = svc.tile_view()
    adj, w, alive = dense_views_from_tiles(state, view)
    srcs = jnp.asarray(rng.choice(np.flatnonzero(g.alive), n_srcs,
                                  replace=False), jnp.int32)
    fns = {"bfs": (queries.bfs_batched_dense, adj),
           "sssp": (queries.sssp_batched_dense, w),
           "bc": (queries.bc_batched_dense, adj)}
    kernels = {"bfs": "bool_mm", "sssp": "minplus_mm", "bc": "count_mm"}
    matched = []
    for kind, (fn, operand) in fns.items():
        for amask in (None, view.occ):
            name = kernels[kind] + ("_masked" if amask is not None else "")
            compiled = fn.lower(operand, srcs, alive, use_kernel=True,
                                amask=amask).compile()
            if jax.default_backend() == "tpu":
                require("tpu_custom_call" in compiled.as_text(),
                        f"{name}: no Pallas kernel in the compiled program")
            got = jax.device_get(compiled(operand, srcs, alive,
                                          amask=amask))
            want = jax.device_get(fn(operand, srcs, alive, use_kernel=False,
                                     amask=amask))
            if kind == "bc":
                require(np.array_equal(got[1], want[1]), f"{name}: sigma")
                require(np.array_equal(got[2], want[2]), f"{name}: level")
                require(np.allclose(got[0], want[0], rtol=1e-5, atol=1e-5),
                        f"{name}: delta")
            else:
                for a, b in zip(jax.tree_util.tree_leaves(got),
                                jax.tree_util.tree_leaves(want)):
                    require(np.array_equal(a, b), f"{name}: differs from jnp")
            matched.append(name)
    # One sampled source through the host reference, too.
    s = int(srcs[0])
    bc = jax.device_get(queries.bc_batched_dense(
        adj, srcs[:1], alive, use_kernel=True, amask=view.occ))
    check_answer("bc", g, s, level=bc[2][0], sigma=bc[1][0], delta=bc[0][0])
    scores, _ = svc.bc_scores(use_kernel=True, src_chunk=src_chunk)
    scores = np.asarray(jax.device_get(scores))
    require(np.isfinite(scores[g.alive]).all(),
            "bc_scores: non-finite score")
    require(np.isnan(scores[~g.alive]).all(), "bc_scores: dead slot scored")
    out = {"vcap": state.vcap, "sources": n_srcs,
           "kernels_matched": matched,
           "tiles_active": int((np.asarray(view.occ) > 0).sum()),
           "tiles_total": int(view.occ.size),
           "bc_scores_sources": int(g.alive.sum()),
           "compile_s": round((clock.seconds if clock else 0.0) - c0, 3),
           "wall_s": round(time.perf_counter() - t0, 3),
           "peak_bytes": peak_bytes(jax.devices()[0])}
    log("kernels", **out)
    return out


# --------------------------------------------------------------------------
# Phase 3 (--chips 4): the sharded service across four chips.
# --------------------------------------------------------------------------

def sharded_phase(scale: int = 16, rounds: int = 2, batch_ops: int = 256,
                  n_srcs: int = 4, seed: int = 2,
                  clock: CompileClock | None = None) -> dict:
    """``ShardedGraphService`` (ring BC) with churn: BFS/SSSP/BC at every
    version, each answer row checked against the host reference."""
    t0 = time.perf_counter()
    c0 = clock.seconds if clock else 0.0
    rng = np.random.default_rng(seed)
    devices = jax.devices()
    state, g0 = graph500(scale, seed)
    mesh = make_graph_mesh()
    svc = ShardedGraphService(state, mesh, bc_mode="ring",
                              batch_size=batch_ops)
    view = svc.view()
    bands = sorted((s.device.id, s.index[0].start, s.index[0].stop)
                   for s in view.w.addressable_shards)
    log("sharded", step="view", vcap=state.vcap, vp=view.vp,
        edges=int(g0.keys.size), bands=bands,
        band_bytes=int(view.w.addressable_shards[0].data.nbytes))
    require(len({b[0] for b in bands}) == len(devices) == mesh.devices.size,
            f"bands not on {len(devices)} distinct devices: {bands}")
    srcs = pick_sources(g0, rng, n_srcs - 1)
    outdeg = np.bincount(g0.keys // g0.n, minlength=g0.n)
    movers = rng.permutation(np.flatnonzero(outdeg > 0))
    versions = [g0]
    matched = 0
    for r in range(rounds + 1):
        g = versions[-1]
        for kind in ("bfs", "sssp", "bc"):
            rep = svc.query(kind, srcs)
            require(rep.version == len(versions) - 1,
                    f"{kind}: answered at {rep.version}, not the latest")
            res = jax.device_get(rep.result)
            require(bool(res.agree), f"{kind}: shards saw different versions")
            for i, s in enumerate(srcs):
                if kind == "bc":
                    check_answer(kind, g, s, level=res.level[i],
                                 sigma=res.sigma[i], delta=res.delta[i])
                else:
                    check_answer(kind, g, s, dist=res.dist[i])
                matched += 1
        if r < rounds:
            ops = churn(g, rng, batch_ops, hot_set(movers, r), scale)
            versions.append(g.apply(ops))
            svc.submit_many(ops)
    clean = _require_clean(svc)
    st = svc.stats
    out = {"vcap": state.vcap, "devices": len(devices),
           "rungs": {"unchanged": int(st.unchanged), "delta": int(st.delta),
                     "full": int(st.full)},
           "references_matched": matched,
           "ops_committed": int(svc.scheduler.stats.ops_committed),
           "compile_s": round((clock.seconds if clock else 0.0) - c0, 3),
           "wall_s": round(time.perf_counter() - t0, 3),
           "peak_bytes": {d.id: peak_bytes(d) for d in devices}, **clean}
    log("sharded", step="checked", **out)
    return out


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded service across four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    clock = CompileClock()
    log("start", platform=dev.platform, kind=dev.device_kind,
        devices=len(jax.devices()), jax=jax.__version__, cache_dir=cache)
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(seed=args.seed + 2, clock=clock)
    else:
        serve_phase(seed=args.seed, clock=clock)
        kernel_phase(seed=args.seed + 1, clock=clock)
    log("end", wall_s=round(time.perf_counter() - t0, 3),
        compile_s=round(clock.seconds, 3), cache_hits=clock.hits,
        cache_misses=clock.misses)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
