"""ShardedGraphService: the streaming front end on a device mesh.

Shares :class:`repro.engine.service.BaseGraphService` with the local
``GraphService`` — updates enter through the
:class:`~repro.engine.scheduler.StreamScheduler` and commit into a
:class:`~repro.engine.version_ring.VersionRing`; queries are answered from
the ring with per-``(kind, sources)`` caches, the PG-Icn / PG-Cn collect
loops, the LRU cache pruning, and the mode counters all written once in
the base — but every collect here runs distributed ``shard_map`` programs
over the sharded tile grid, and the grid itself is maintained
incrementally per shard (``refresh_sharded_view`` re-derives only the
dirty tile rows named by the ring's dirty sets).

Each collect climbs the same *unchanged → delta → full* ladder as the
local engine:

  * **unchanged** — churn since the cached answer never touched its
    reached region: the cached result stands with zero device work;
  * **delta** — the engine's poison + re-relax path on the mesh
    (``shard.queries.delta_*_sharded``): the poison pointer-doubling runs
    unsharded over the replicated prior parent arrays, the re-relax
    warm-starts the sharded level loop from the keep set, and BC resumes
    its per-source level-cut warm start from the cached forward trees.
    Guarded like ``engine.incremental``'s ``_prior_usable``: the prior
    must match the current vertex table (and be negative-cycle-free for
    SSSP), the dirty span must be within the ring window and under
    ``dirty_threshold``; a delta SSSP that detects a new negative cycle
    re-runs the full query for the canonical answer;
  * **full** — the distributed fixed point.

Consistency modes match the paper at batch granularity: ``"icn"`` single
collect; ``"cn"`` double collect across ring versions until two answers
match.  Each collect additionally carries the psum-validated cross-shard
version agreement (``result.agree``) — the intra-query half of the
paper's double-collect check, spanning shards instead of time.

One version per collect: a collect reads and pins the ring's latest entry
once, refreshes the tile view to exactly that version and runs its rungs
on it, so the answer is exact at the version the reply names however many
commits land meanwhile.  Refreshing donates the previous view's buffers,
so the read, the refresh and the launches of one collect hold the
collect lock against other collecting threads; commits never take it.
"""
from __future__ import annotations

import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.graph_state import GraphState
from repro.core.tiles import TILE
from repro.engine.incremental import _dirty_stats
from repro.engine.service import BaseGraphService, QueryReply  # noqa: F401
from repro.engine.service import ServiceStats  # noqa: F401  (re-export)
from repro.engine.service import ThresholdSpec
from repro.engine.version_ring import RingEntry
from repro.obs import Telemetry
from repro.obs.trace import annotate as _trace_annotate
from repro.obs.trace import maybe_span
from repro.resil.faults import P_COLLECT_DELTA, P_COLLECT_DISPATCH, \
    InjectedCrash, inject
from repro.resil.policy import ResiliencePolicy

from . import queries as shard_queries
from .tile_shard import (
    ShardedTileView,
    as_graph_mesh,
    refresh_sharded_view,
    refresh_stats,
)

_QUERIES = {"bfs": shard_queries.bfs, "sssp": shard_queries.sssp,
            "bc": shard_queries.bc_batched}
_DELTA = {"bfs": shard_queries.delta_bfs_sharded,
          "sssp": shard_queries.delta_sssp_sharded,
          "bc": shard_queries.delta_bc_sharded}


def _reached_union(kind: str, result) -> jax.Array:
    """bool[vcap]: union over sources of the query's reached region."""
    if kind == "bfs":
        return (result.dist >= 0).any(axis=0)
    if kind == "sssp":
        return (result.dist < jnp.inf).any(axis=0)
    return (result.level >= 0).any(axis=0)


class ShardedGraphService(BaseGraphService):
    """submit()/query() front end over the sharded tile grid.

    ``bc_mode`` picks the adjacency strategy of every BC collect (full and
    delta): ``"gather"`` all-gathers the row bands per query (O(Vp^2)
    per-shard memory, kept as the oracle path), ``"ring"`` SUMMA-rotates
    the O(Vp^2/n) bands with ``lax.ppermute`` — see
    ``shard.queries.bc_batched``.  Levels/sigma (hence the delta ladder's
    cuts) are bit-identical across modes; scores agree to f32 summation
    order.
    """

    _kinds = ("bfs", "sssp", "bc")
    _service_name = "sharded"

    def __init__(self, initial_state: GraphState, mesh: Mesh, *,
                 tile: int = TILE, use_kernel: bool = False,
                 src_chunk: Optional[int] = None, bc_mode: str = "gather",
                 ring_depth: int = 8, batch_size: int = 32,
                 dirty_threshold: ThresholdSpec = None,
                 strict_order: bool = False,
                 coalesce: bool = False, max_collects: int = 16,
                 max_cached: int = 128,
                 telemetry: Optional[Telemetry] = None,
                 policy: Optional[ResiliencePolicy] = None,
                 journal=None, monitor=None, adaptive=None, breaker=None,
                 compact_every: Optional[int] = None):
        shard_queries._bc_kind(bc_mode, delta=False)  # validate up front
        self.mesh = as_graph_mesh(mesh)
        self.tile = tile
        self.use_kernel = use_kernel
        self.src_chunk = src_chunk
        self.bc_mode = bc_mode
        self._init_service(
            initial_state, ring_depth=ring_depth, batch_size=batch_size,
            dirty_threshold=dirty_threshold, strict_order=strict_order,
            coalesce=coalesce, max_collects=max_collects,
            max_cached=max_cached, telemetry=telemetry, policy=policy,
            journal=journal, monitor=monitor, adaptive=adaptive,
            breaker=breaker, compact_every=compact_every)
        self._view: Optional[ShardedTileView] = None
        self._view_version: int = -1
        self._collect_lock = threading.RLock()

    # ------------------------------- view --------------------------------

    def view(self, entry: Optional[RingEntry] = None) -> ShardedTileView:
        """The sharded tile grid at ``entry``'s version (default: the
        latest), refreshed per shard from the ring's dirty sets (full
        rebuild on resize, window loss, or a view ahead of ``entry``).
        Its version is noted as the current collect's ``pinned``
        (``_traced_collect``)."""
        with self._collect_lock:
            if entry is None:
                entry = self.ring.latest
            if self._view is None or self._view_version != entry.version:
                self._refresh(entry)
            self._query_tls.pinned = self._view_version
            return self._view

    def _refresh(self, entry: RingEntry) -> None:
        dirty = None
        if self._view is not None and self._view_version < entry.version:
            dirty = self.ring.dirty_between(self._view_version, entry.version)
        tracer = self.telemetry.tracer if self.telemetry is not None else None
        rows0, disp0 = refresh_stats.rows, refresh_stats.dispatches
        with maybe_span(tracer, "tile_refresh", service=self._service_name,
                        full=(self._view is None or dirty is None)) as sp:
            self._view = refresh_sharded_view(entry.state, self._view, dirty,
                                              mesh=self.mesh, tile=self.tile)
            sp.set(version=entry.version,
                   rows=refresh_stats.rows - rows0,
                   dispatches=refresh_stats.dispatches - disp0)
        self._view_version = entry.version

    # ------------------------------ queries ------------------------------

    def _key(self, kind: str, srcs) -> Tuple[str, tuple]:
        if srcs is None:
            return kind, ("all",)
        arr = np.atleast_1d(np.asarray(srcs))
        return kind, tuple(int(s) for s in arr)

    def _check_srcs(self, kind: str, srcs) -> None:
        if srcs is None and kind != "bc":
            raise ValueError(f"{kind!r} needs explicit sources")

    def _icn_validated(self, result) -> bool:
        return bool(result.agree)

    def _delta_usable(self, kind: str, prior, state: GraphState) -> bool:
        """The sharded ``_prior_usable``: same-vcap prior whose cached
        payload the delta path can certify (SSSP additionally: converged,
        i.e. no prior negative cycle).  Per-source ``ok`` flips are fine —
        a source that died poisons its whole tree, one that was dead
        re-relaxes cold, and a BC source that turned suspect restarts at
        cut 0."""
        if kind == "bc":
            return prior.level.shape[1] == state.vcap
        if prior.dist.shape[1] != state.vcap:
            return False
        return kind == "bfs" or not bool(prior.negcycle.any())

    def _revived_source(self, prior, srcs, state: GraphState) -> bool:
        """True when a source that was NOT ok at prior time is alive now.

        Such a source's cached row is empty, so no dirty vertex can
        intersect it — invisible to both the unchanged test and the level
        cut — yet the row must be recomputed (the delta paths restart it
        cold once this forces them past the unchanged shortcut).
        Conservative for SSSP, where ``ok`` also folds in the negative-
        cycle flag: a cached negcycle answer is re-collected every time.
        """
        idx = (jnp.arange(prior.ok.shape[0], dtype=jnp.int32) if srcs is None
               else jnp.atleast_1d(jnp.asarray(srcs, jnp.int32)))
        alive_now = (state.alive[jnp.clip(idx, 0, state.vcap - 1)]
                     & (idx >= 0) & (idx < state.vcap))
        return bool((~prior.ok & alive_now).any())

    def _collect(self, kind: str, srcs, key, ladder: bool = True):
        """One collect at the latest ring version, read and pinned once,
        climbing the unchanged → delta → full ladder on the tile view of
        that version (see module docstring).

        ``ladder=False`` (a resilience retry) dispatches the full
        distributed query directly — no cache read, no dirty-set math — so
        a failed delta path cannot poison the retry."""
        with self._collect_lock, self.ring.pin() as pin:
            entry = self.ring.get_entry(pin.version)
            if ladder:
                mode, res = self._ladder(kind, srcs, key, entry)
            else:
                mode, res = "full", self._full_collect(
                    kind, srcs, entry.state, self.view(entry))
            self._cache_store(key, entry.version, res)
            return entry, res, mode

    def _ladder(self, kind: str, srcs, key, entry: RingEntry):
        """The ladder's rung for ``key`` at ``entry`` -> (mode, result)."""
        state = entry.state
        slot = self._cache.get(key)
        mode, res = "full", None
        # A tripped breaker quarantines the cached prior: no unchanged
        # shortcut, no dirty-set math, no delta dispatch — the clean
        # full path answers until half-open probes succeed.
        use_prior = slot is not None and self._breaker_allows(kind)
        try:
            if use_prior:
                prior = slot.result
                if slot.version == entry.version:
                    mode, res = "unchanged", prior
                else:
                    dirty = self.ring.dirty_between(slot.version,
                                                    entry.version)
                    union = _reached_union(kind, prior)
                    if dirty is not None and union.shape[0] == state.vcap:
                        n_dirty, touched = (int(x) for x in
                                            _dirty_stats(union, dirty))
                        frac = n_dirty / state.vcap
                        _trace_annotate(dirty=n_dirty,
                                        dirty_frac=round(frac, 6))
                        self._note_dirty_frac(frac)
                        if not touched and self._revived_source(prior, srcs,
                                                                state):
                            touched = True
                        if not touched:
                            mode, res = "unchanged", prior
                        elif (frac <= self._threshold(kind)
                              and self._delta_usable(kind, prior, state)):
                            mode, res = "delta", self._delta_collect(
                                kind, prior, dirty, srcs, state,
                                self.view(entry))
                            if res is None:  # new negcycle: canonical full
                                mode, res = "full", None
            if res is None:
                res = self._full_collect(kind, srcs, state,
                                         self.view(entry))
        except InjectedCrash:
            raise
        except Exception:
            # conservative attribution: any failure while a usable prior
            # was in play counts against the kind's delta path
            if use_prior:
                self._breaker_failure(kind)
            raise
        if use_prior:
            self._breaker_success(kind, mode)
        return mode, res

    def _full_collect(self, kind: str, srcs, state: GraphState,
                      view: ShardedTileView):
        """Dispatch the full distributed query (the ladder's bottom rung) on
        ``view``, the tile view of ``state``."""
        inject(P_COLLECT_DISPATCH)
        acct = self._acct_begin()
        res = _QUERIES[kind](
            view, state, srcs,
            **(self._bc_kwargs() if kind == "bc" else {}),
            use_kernel=self.use_kernel, accountant=acct)
        self._acct_charge(acct)
        return res

    def _bc_kwargs(self) -> dict:
        return {"src_chunk": self.src_chunk, "bc_mode": self.bc_mode}

    def _delta_collect(self, kind: str, prior, dirty, srcs,
                       state: GraphState,
                       view: Optional[ShardedTileView] = None):
        """Run the distributed delta query on ``view``, the tile view of
        ``state`` (default: the view at the latest version, which ``state``
        must then be); ``None`` = fall back to full (delta SSSP surfaced a
        negative cycle born since the prior)."""
        inject(P_COLLECT_DELTA)
        if view is None:
            view = self.view()
        acct = self._acct_begin()
        if kind == "bc":
            res = _DELTA[kind](view, state, prior, dirty, srcs,
                               use_kernel=self.use_kernel, accountant=acct,
                               **self._bc_kwargs())
            self._acct_charge(acct)
            return res
        res = _DELTA[kind](view, state, prior, dirty, srcs,
                           use_kernel=self.use_kernel, accountant=acct)
        self._acct_charge(acct)
        if kind == "sssp" and bool(res.negcycle.any()):
            return None
        return res

    # --------------------------- batched analytics ------------------------

    def bc_scores(self):
        """Exact all-vertex betweenness centrality at the latest version via
        the distributed batched-Brandes path; dead slots are NaN.  Cached
        through the regular query cache (kind ``"bc"``, all sources), so a
        localized commit pays only the level-cut delta sweep."""
        reply = self.query("bc", None)
        state = self.ring.latest.state
        scores = jnp.where(state.alive, reply.result.scores, jnp.nan)
        return scores, reply.version
