"""Delta-driven BFS / SSSP recomputation from a prior result + dirty set.

Static analytics recompute the whole fixed point on every change; the
paper's point (Figs 12/13) is that a versioned structure knows *what moved*
and should pay only for that.  Given a prior ``BFSResult``/``SSSPResult``
and the dirty-vertex set accumulated since it was computed (see
``engine.version_ring``), the delta queries here:

  1. **Poison** the stale region: a vertex's cached distance is invalid iff
     some edge on its cached shortest path may have changed.  Every edge
     mutation bumps ``ecnt`` at the edge's *source*, so the path through
     ``v`` is suspect exactly when some ancestor of ``v`` in the prior
     traversal tree has a dirty parent-edge source (or the vertex itself
     died).  Poison propagates down the parent tree by pointer doubling —
     ``ceil(log2 vcap)`` gathers, not a per-level walk.
  2. **Re-relax** from the surviving frontier: clean distances are genuine
     path lengths in the *new* graph (their whole parent chain is
     untouched), i.e. admissible upper bounds, so the standard
     label-correcting fixed point under ``lax.while_loop`` converges to the
     exact answer in ~(affected-region diameter) passes instead of
     ~(graph diameter).
  3. **Fall back** to full recompute when the dirty region is too large for
     the delta to win (``dirty_threshold``), when the cached result is
     unusable (dead source, grown vertex table, negative cycle), or when
     the caller has no dirty info at all.

The host wrappers also expose the cheap *unchanged* test — no dirty vertex
intersects the prior reached region — which returns the prior result with
zero relax passes; that selectivity is where most of the paper's win lives.

``validate_incremental`` is the ``cmp_tree``-style check that a delta answer
is bit-identical to a fresh collect on the same snapshot.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.graph_state import (
    INF,
    NOKEY,
    GraphState,
    find_edge_slots,
)
from repro.core.queries import (
    BCResult,
    BFSResult,
    EdgeView,
    SSSPResult,
    _bc_coo_sweep,
    _bfs_parent,
    _source_ok,
    _sssp_parent,
    bc_dependencies,
    bc_level_cut,
    bfs,
    edge_view,
    relax_fixpoint,
    sssp,
)
from repro.obs.hlo import account_jit
from repro.obs.trace import annotate as _trace_annotate


@dataclass
class IncrementalStats:
    """How one incremental query was answered."""

    mode: str               # "unchanged" | "delta" | "full"
    dirty_count: int = 0
    dirty_fraction: float = 0.0


def _poison(state: GraphState, prior_parent: jax.Array,
            prior_reached: jax.Array, prior_distf: jax.Array,
            dirty: jax.Array, check_weight: bool) -> jax.Array:
    """bool[vcap]: vertices whose cached distance can no longer be trusted.

    Seeds: reached vertices that died, and vertices whose parent edge is
    actually gone.  A dirty parent only *suspects* the edge — ``ecnt`` says
    the parent's out-list changed, not which edge — so we re-probe the new
    state (one vectorized binary search): if edge ``(parent[v], v)`` is
    still live with the same weight (``prior.dist[v] - prior.dist[parent]``;
    weight ignored for BFS), the cached path survives and ``v`` stays
    clean.  Poison then closes downward over the prior tree by pointer
    doubling (after step k, a vertex is poisoned iff any of its 2^k nearest
    ancestors, itself included, is a seed).
    """
    vcap = prior_parent.shape[0]
    alive = state.alive
    parc = jnp.clip(prior_parent, 0, vcap - 1)
    has_par = (prior_parent != NOKEY) & prior_reached
    suspect = has_par & dirty[parc]
    self_id = jnp.arange(vcap, dtype=jnp.int32)
    qu = jnp.where(suspect, parc, NOKEY)
    qv = jnp.where(suspect, self_id, NOKEY)
    slot, _, edge_live = find_edge_slots(state, qu, qv)
    edge_ok = edge_live
    if check_weight:
        edge_ok = edge_ok & (state.ew[slot] == prior_distf - prior_distf[parc])
    seed = (prior_reached & ~alive) | (suspect & ~edge_ok)
    # Ancestor pointer: parent where one exists, else self (fixed point).
    anc = jnp.where(has_par, parc, self_id)
    steps = max(1, int(math.ceil(math.log2(max(vcap, 2)))))

    def body(_, carry):
        poison, anc = carry
        return poison | poison[anc], anc[anc]

    poison, anc_fin = lax.fori_loop(0, steps, body, (seed, anc))
    # With zero-weight edges the tight-edge parent "tree" can contain
    # cycles (dist does not strictly decrease along a zero-weight parent
    # link), and poison propagated along parents never escapes a cycle —
    # the entry edge that actually fed the cycle its distance is invisible
    # to the chain walk.  Such chains never reach a root: after >= vcap
    # doublings a tree vertex's ancestor is its (parentless) root, while a
    # cycle-bound chain lands on a vertex that still has a parent.  Their
    # cached distances are unverifiable, so poison them outright.
    return poison | has_par[anc_fin]


@jax.jit
def _dirty_stats(prior_reached: jax.Array, dirty: jax.Array):
    """(dirty count, query touched) in one device round trip.

    ``touched``: any dirty vertex intersects the prior reached region.
    Every mutation that can change the query's answer dirties a *reached*
    vertex: edge changes dirty the edge's source (irrelevant unless the
    source was reached), and liveness changes dirty the vertex itself
    (irrelevant unless it was reached — a vertex entering the region needs
    a new edge out of a reached, hence dirty, source).
    """
    return (jnp.sum(dirty.astype(jnp.int32)),
            (dirty & prior_reached).any())


# --------------------------------- BFS -----------------------------------

def delta_bfs_on(state: GraphState, view: EdgeView, prior: BFSResult,
                 dirty: jax.Array, src) -> BFSResult:
    """``delta_bfs`` over a prebuilt ``view`` of ``state``."""
    src = jnp.asarray(src, jnp.int32)
    vcap = state.vcap
    order = view.by_dst
    ok = _source_ok(state, src)

    priorf = prior.dist.astype(jnp.float32)
    poison = _poison(state, prior.parent, prior.reached, priorf, dirty,
                     check_weight=False)
    keep = prior.reached & ~poison
    dist0 = jnp.where(keep, priorf, INF)
    dist0 = dist0.at[src].set(jnp.where(ok, 0.0, INF), mode="drop")

    distf, _, _ = relax_fixpoint(dist0, order, 1.0, vcap)

    reached = distf < INF
    dist = jnp.where(reached, distf, -1.0).astype(jnp.int32)
    # Parent reconstruction matches queries.bfs exactly (see
    # bfs_tree_parents — shared with the sharded delta path).
    parent = _bfs_parent(order, dist, src)
    return BFSResult(ok, reached, dist, parent)


@jax.jit
def delta_bfs(state: GraphState, prior: BFSResult, dirty: jax.Array,
              src) -> BFSResult:
    """Recompute BFS on ``state`` reusing ``prior`` (computed <= dirty ago).

    Bit-identical to ``queries.bfs(state, src)`` for any dirty set that
    covers the actual changes (a too-large dirty set only costs time).
    """
    return delta_bfs_on(state, edge_view(state), prior, dirty, src)


# --------------------------------- SSSP ----------------------------------

def delta_sssp_on(state: GraphState, view: EdgeView, prior: SSSPResult,
                  dirty: jax.Array, src) -> SSSPResult:
    """``delta_sssp`` over a prebuilt ``view`` of ``state``."""
    src = jnp.asarray(src, jnp.int32)
    vcap = state.vcap
    order = view.by_dst
    ok_src = _source_ok(state, src)

    prior_reached = prior.dist < INF
    poison = _poison(state, prior.parent, prior_reached, prior.dist, dirty,
                     check_weight=True)
    keep = prior_reached & ~poison
    dist0 = jnp.where(keep, prior.dist, INF)
    dist0 = dist0.at[src].set(jnp.where(ok_src, 0.0, INF), mode="drop")

    dist, changed, _ = relax_fixpoint(dist0, order, order.w, vcap)

    # Same free CHECKNEGCYCLE as queries.sssp: from *any* admissible upper
    # bound, Bellman-Ford converges within vcap-1 passes absent a negative
    # cycle, so exiting the loop still-changed == negative cycle.
    negcycle = changed

    parent = _sssp_parent(order, dist, src)
    return SSSPResult(ok_src & ~negcycle, negcycle, dist, parent)


@jax.jit
def delta_sssp(state: GraphState, prior: SSSPResult, dirty: jax.Array,
               src) -> SSSPResult:
    """Delta Bellman-Ford; bit-identical to ``queries.sssp`` absent negative
    cycles (on detection the wrapper re-runs the full query, whose
    partially-relaxed distances are iteration-order-dependent)."""
    return delta_sssp_on(state, edge_view(state), prior, dirty, src)


# ---------------------------------- BC -----------------------------------

def delta_bc(state: GraphState, prior: BCResult, dirty: jax.Array,
             src) -> BCResult:
    """Level-cut delta Brandes: recompute BC dependencies reusing ``prior``.

    BC needs a different poison than BFS/SSSP: ``sigma`` counts *all*
    shortest paths, so even an edge insertion that moves no distance (a new
    tight edge into an existing level) changes downstream counts — per-edge
    chain probing cannot clear it.  But level sets are built level-by-level
    from the out-edge lists of the previous level's (clean) vertices, so
    everything strictly above the shallowest dirty level is untouched
    (``bc_level_cut``): reuse the cached forward levels/sigma there, resume
    the forward sweep from the cut's frontier, and re-run the backward
    sweep in full (dependency flow crosses the cut upward, so it cannot be
    truncated).  Bit-identical to ``bc_dependencies(state, src)`` — the
    warm forward state at the resume pass equals the cold run's.

    Callers gate on ``cut >= 1`` (a cut of 0 means the source itself is
    suspect; ``incremental_bc`` falls back to the full query there — the
    same gate, via ``prior.ok``, excludes priors whose source was dead).
    """
    cut = bc_level_cut(prior.level, dirty, state.alive)
    return _delta_bc_at_cut(state, prior, cut, src)


def delta_bc_on(state: GraphState, view: EdgeView, prior: BCResult, cut,
                src) -> BCResult:
    """``_delta_bc_at_cut`` over a prebuilt ``view`` of ``state``."""
    src = jnp.asarray(src, jnp.int32)
    vcap = state.vcap
    ok = _source_ok(state, src)

    cut = jnp.asarray(cut, jnp.int32)
    keep = (prior.level >= 0) & (prior.level < cut)
    level0 = jnp.where(keep, prior.level, -1)
    sigma0 = jnp.where(keep, prior.sigma, 0.0)
    front0 = level0 == cut - 1

    level, sigma, delta = _bc_coo_sweep(
        view, vcap, level0, sigma0, front0, cut - 1)
    return BCResult(ok, delta, sigma, level)


@jax.jit
def _delta_bc_at_cut(state: GraphState, prior: BCResult, cut,
                     src) -> BCResult:
    """``delta_bc`` with the cut already computed (``incremental_bc``
    evaluates it once for its host-side gate and passes the device scalar
    through rather than re-deriving it under jit)."""
    return delta_bc_on(state, edge_view(state), prior, cut, src)


# ----------------------------- host wrappers ------------------------------

def _prior_usable(state: GraphState, prior, prior_ok) -> bool:
    return (prior is not None
            and bool(prior_ok)
            and prior.dist.shape[0] == state.vcap)


def _acct_key(kind: str, state: GraphState) -> tuple:
    """Program signature of a local jitted query: ``src`` is a traced
    scalar, so the compiled program depends only on the table capacities."""
    return ("local", kind, state.vcap, state.ecap)


def incremental_bfs(state: GraphState, prior: Optional[BFSResult],
                    dirty: Optional[jax.Array], src, *,
                    dirty_threshold: float = 0.25, accountant=None):
    """BFS on ``state`` reusing ``prior`` where possible.

    Returns ``(BFSResult, IncrementalStats)``; the result is always exactly
    what ``queries.bfs(state, src)`` would return.  With an ``accountant``
    (``repro.obs.hlo``), the cost dict of whichever compiled program
    produced the answer is deposited in ``accountant.last`` — the
    *unchanged* shortcut runs no program and deposits nothing.
    """
    if dirty is None or not _prior_usable(state, prior, prior.ok if prior else False):
        account_jit(accountant, _acct_key("bfs", state), bfs, state, src)
        return bfs(state, src), IncrementalStats("full")
    n_dirty, touched = (int(x) for x in _dirty_stats(prior.reached, dirty))
    frac = n_dirty / state.vcap
    _trace_annotate(dirty=n_dirty, dirty_frac=round(frac, 6))
    stats = IncrementalStats("delta", n_dirty, frac)
    # Unchanged beats the threshold check: churn confined outside the
    # query's reached region leaves the cached answer valid no matter how
    # large the dirty set is.
    if not touched:
        stats.mode = "unchanged"
        return prior, stats
    if frac > dirty_threshold:
        stats.mode = "full"
        account_jit(accountant, _acct_key("bfs", state), bfs, state, src)
        return bfs(state, src), stats
    account_jit(accountant, _acct_key("bfs_delta", state), delta_bfs,
                state, prior, dirty, src)
    return delta_bfs(state, prior, dirty, src), stats


def incremental_sssp(state: GraphState, prior: Optional[SSSPResult],
                     dirty: Optional[jax.Array], src, *,
                     dirty_threshold: float = 0.25, accountant=None):
    """SSSP analogue of ``incremental_bfs``."""
    if dirty is None or not _prior_usable(state, prior, prior.ok if prior else False):
        account_jit(accountant, _acct_key("sssp", state), sssp, state, src)
        return sssp(state, src), IncrementalStats("full")
    n_dirty, touched = (int(x) for x in _dirty_stats(prior.dist < jnp.inf,
                                                     dirty))
    frac = n_dirty / state.vcap
    _trace_annotate(dirty=n_dirty, dirty_frac=round(frac, 6))
    stats = IncrementalStats("delta", n_dirty, frac)
    if not touched:
        stats.mode = "unchanged"
        return prior, stats
    if frac > dirty_threshold:
        stats.mode = "full"
        account_jit(accountant, _acct_key("sssp", state), sssp, state, src)
        return sssp(state, src), stats
    res = delta_sssp(state, prior, dirty, src)
    if bool(res.negcycle):
        # Negative cycle: the full query's non-converged distances depend on
        # relaxation order; rerun it so callers see the canonical answer.
        stats.mode = "full"
        account_jit(accountant, _acct_key("sssp", state), sssp, state, src)
        return sssp(state, src), stats
    account_jit(accountant, _acct_key("sssp_delta", state), delta_sssp,
                state, prior, dirty, src)
    return res, stats


def incremental_bc(state: GraphState, prior: Optional[BCResult],
                   dirty: Optional[jax.Array], src, *,
                   dirty_threshold: float = 0.25, accountant=None):
    """BC dependencies with the engine's unchanged → delta → full ladder.

    Same *unchanged* shortcut as BFS/SSSP — churn that never touches the
    prior forward-traversal region (``level >= 0``) cannot move any
    shortest path from ``src``, so the cached dependencies stand.  A
    touched region runs the level-cut delta (``delta_bc``) when the
    shallowest suspect level is below the source (``cut >= 1``) and the
    dirty fraction is within ``dirty_threshold``; otherwise full recompute.
    """
    usable = (prior is not None and bool(prior.ok)
              and prior.level.shape[0] == state.vcap)
    if dirty is None or not usable:
        account_jit(accountant, _acct_key("bc", state), bc_dependencies,
                    state, src)
        return bc_dependencies(state, src), IncrementalStats("full")
    n_dirty, touched = (int(x) for x in _dirty_stats(prior.level >= 0, dirty))
    frac = n_dirty / state.vcap
    _trace_annotate(dirty=n_dirty, dirty_frac=round(frac, 6))
    stats = IncrementalStats("delta", n_dirty, frac)
    if not touched:
        stats.mode = "unchanged"
        return prior, stats
    if frac > dirty_threshold:
        stats.mode = "full"
        account_jit(accountant, _acct_key("bc", state), bc_dependencies,
                    state, src)
        return bc_dependencies(state, src), stats
    cut = bc_level_cut(prior.level, dirty, state.alive)
    if int(cut) < 1:
        stats.mode = "full"
        account_jit(accountant, _acct_key("bc", state), bc_dependencies,
                    state, src)
        return bc_dependencies(state, src), stats
    account_jit(accountant, _acct_key("bc_delta", state), _delta_bc_at_cut,
                state, prior, cut, src)
    return _delta_bc_at_cut(state, prior, cut, src), stats


# ------------------------------ validation --------------------------------

def results_equal(a, b) -> bool:
    """CMPTREE over result tuples: region, tree, and payload all bit-equal."""
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


def validate_incremental(state: GraphState, src, result, kind: str) -> bool:
    """``cmp_tree``-style check: does ``result`` match a fresh collect?

    Compares the reached region, the traversal tree, and the payload of the
    incremental answer against ``queries.bfs``/``sssp``/``bc_dependencies``
    run from scratch on the same snapshot — the engine's analogue of the
    paper's CMPTREE validation of a SCAN.
    """
    fresh = {"bfs": bfs, "sssp": sssp, "bc": bc_dependencies}[kind](state, src)
    return results_equal(result, fresh)
