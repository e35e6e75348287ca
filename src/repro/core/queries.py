"""The graph query operations: BFS, SSSP (+negative-cycle check), BC.

Non-recursive traversals (the paper's queue/stack machinery) become
*edge-parallel frontier fixed points* under ``lax.while_loop``:

  * BFS      -- boolean-semiring frontier expansion (one segmented min over
                the in-arcs of every vertex per level);
  * SSSP     -- Bellman-Ford relax to fixed point, plus the paper's
                CHECKNEGCYCLE: one extra relax pass; any improvement implies a
                negative cycle reachable from the source;
  * BC       -- Brandes: forward level/sigma counting, backward dependency
                accumulation per level.

Every per-level reduction from edges to vertices is a *segmented*
reduction over a sorted edge order (``EdgeView``), never a scatter over
the ``ecap`` slots: dst-keyed ones (BFS hits and parents, relax minima,
sigma counts, tree parents) run over a dst-major copy of the edge table,
sorted once per program and shared by every lane of a batched rung;
src-keyed ones (BC's backward sums) run over the table itself, which is
kept sorted by ``(src, dst)``.  A level then costs one edge-long gather of
a vertex vector, one segmented scan and a ``vcap``-long read-out.

Each query also has a *dense batched* variant (vmap over sources becomes a
semiring matmul on the MXU -- see ``semiring.py`` / ``repro.kernels``), which
is both the Ligra-style static baseline and the TPU-native path the paper's
CPU design could not exploit.

All functions are pure and jitted; masks/dists are fixed-shape ``[vcap]``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .graph_state import INF, NOKEY, GraphState, densify, live_edge_mask
from . import semiring


class BFSResult(NamedTuple):
    ok: jax.Array        # bool[]  source was alive
    reached: jax.Array   # bool[vcap]
    dist: jax.Array      # int32[vcap]  (-1 = unreached)
    parent: jax.Array    # int32[vcap]  (NOKEY = none; BFS-tree edges)


class SSSPResult(NamedTuple):
    ok: jax.Array        # bool[]  source alive and no negative cycle
    negcycle: jax.Array  # bool[]
    dist: jax.Array      # f32[vcap]  (+inf = unreachable)
    parent: jax.Array    # int32[vcap]


class BCResult(NamedTuple):
    ok: jax.Array        # bool[]
    delta: jax.Array     # f32[vcap]  dependencies delta(s|v) of source s
    sigma: jax.Array     # f32[vcap]  shortest-path counts from s
    level: jax.Array     # int32[vcap]


# ----------------------- sorted edge orders -------------------------------

class EdgeOrder(NamedTuple):
    """The edge table sorted by a key vertex, laid out for segmented scans.

    The slots of one key vertex form a contiguous segment.  The sorted
    slots, padded to ``rows * cols``, are laid out column-major as
    ``[rows, cols]``: column ``c`` holds sorted slots ``c * rows`` to
    ``(c + 1) * rows - 1`` top to bottom, so a scan runs down the rows of
    every column at once and only the column tails carry across columns.
    Slots that are not live (tombstones, dead endpoints, ``NOKEY`` tail
    and padding) keep their place and are masked by value.
    """

    src: jax.Array    # int32[rows, cols]  source id (0 where not live)
    dst: jax.Array    # int32[rows, cols]  destination id (0 where not live)
    live: jax.Array   # bool[rows, cols]
    w: jax.Array      # f32[rows, cols]    weight (+inf where not live)
    run: jax.Array    # int32[rows, cols]  slots of its segment above it, in its column
    crun: jax.Array   # int32[cols]  columns since the last one a segment starts in
    last: jax.Array   # int32[vcap]  row * cols + col of the vertex's last slot
    col: jax.Array    # int32[vcap]  that slot's column
    carry: jax.Array  # bool[vcap]   the vertex's segment starts in an earlier column
    has: jax.Array    # bool[vcap]   the vertex has a slot in this order


class EdgeView(NamedTuple):
    """Both orders a query reduces over: ``by_dst`` (dst-major, the
    in-arcs of each vertex contiguous) and ``by_src`` (the table's own
    ``(src, dst)`` order, the out-arcs contiguous)."""

    by_dst: EdgeOrder
    by_src: EdgeOrder


def _layout_cols(ecap: int) -> int:
    """Columns of an order's layout: a multiple of 128 (the lane width)
    near ``sqrt(ecap)``, so the scan down the rows and the carry across
    the columns both take about ``log2(ecap) / 2`` steps."""
    return 128 * max(1, round(math.sqrt(ecap) / 128))


def _edge_order(key, esrc, edst, w, vcap: int) -> EdgeOrder:
    """Lay out an edge table already sorted by ``key`` (``NOKEY`` last);
    ``w`` is +inf exactly on the slots that are not live."""
    ecap = key.shape[0]
    cols = _layout_cols(ecap)
    rows = -(-ecap // cols)
    size = rows * cols

    def pad(a, fill):
        return jnp.concatenate([a, jnp.full((size - ecap,), fill, a.dtype)])

    def grid(a):
        return a.reshape(cols, rows).T

    key, esrc, edst, w = (pad(key, NOKEY), pad(esrc, NOKEY),
                          pad(edst, NOKEY), pad(w, INF))
    live = w < INF
    ids = jnp.arange(vcap, dtype=jnp.int32)
    first = jnp.searchsorted(key, ids, side="left").astype(jnp.int32)
    end = jnp.searchsorted(key, ids, side="right").astype(jnp.int32)
    last = jnp.maximum(end - 1, 0)
    col = last // rows
    pos = jnp.arange(size, dtype=jnp.int32)
    seg0 = jnp.where(key < vcap, first[jnp.clip(key, 0, vcap - 1)], pos)
    run = pos - jnp.maximum(seg0, (pos // rows) * rows)
    starts = (seg0 == pos).reshape(cols, rows).any(axis=1)
    cids = jnp.arange(cols, dtype=jnp.int32)
    crun = cids - lax.cummax(jnp.where(starts, cids, 0))
    return EdgeOrder(
        src=grid(jnp.where(live, esrc, 0)), dst=grid(jnp.where(live, edst, 0)),
        live=grid(live), w=grid(w), run=grid(run), crun=crun,
        last=(last % rows) * cols + col, col=col, carry=first < col * rows,
        has=end > first)


def edge_view(state: GraphState) -> EdgeView:
    """The state's two edge orders.  The dst-major one costs the one
    edge-long sort of a program, so a batched rung builds the view once
    and hands it to every lane; XLA drops an order no query reads."""
    w = jnp.where(live_edge_mask(state), state.ew, INF)
    # A stable sort by dst alone keeps each dst's slots in the table's src
    # order, i.e. the (dst, src) order; two operands and one key compile
    # several times faster on the TPU than sorting the fields as keys.
    dkey, perm = lax.sort(
        (state.edst, jnp.arange(state.ecap, dtype=jnp.int32)), num_keys=1,
        is_stable=True)
    return EdgeView(
        by_dst=_edge_order(dkey, state.esrc[perm], dkey, w[perm], state.vcap),
        by_src=_edge_order(state.esrc, state.esrc, state.edst, w,
                           state.vcap))


def _seg_scan(x, run, op, ident):
    """Inclusive segmented scan down axis 0: entry ``i`` becomes ``op``
    over itself and the ``run[i]`` entries of its segment above it."""
    n = x.shape[0]
    d = 1
    while d < n:
        above = jnp.concatenate(
            [jnp.full((d,) + x.shape[1:], ident, x.dtype), x[:-d]])
        x = jnp.where(run >= d, op(above, x), x)
        d *= 2
    return x


def seg_reduce(order: EdgeOrder, x, op, ident):
    """``op`` over each vertex's segment of ``x`` (one value per slot of
    ``order``, laid out as it is): ``f[vcap]``, ``ident`` where the
    vertex has no slot.  ``x`` holds ``ident`` wherever a slot must not
    count."""
    y = _seg_scan(x, order.run, op, ident)
    tails = _seg_scan(y[-1], order.crun, op, ident)
    carry_in = jnp.concatenate([jnp.full((1,), ident, y.dtype), tails[:-1]])
    out = y.reshape(-1)[order.last]
    out = jnp.where(order.carry, op(carry_in[order.col], out), out)
    return jnp.where(order.has, out, ident)


def _source_ok(state: GraphState, src):
    vcap = state.vcap
    return state.alive[jnp.clip(src, 0, vcap - 1)] & (src >= 0) & (src < vcap)


# --------------------------------- BFS -----------------------------------

def bfs_on(state: GraphState, view: EdgeView, src) -> BFSResult:
    """``bfs`` over a prebuilt ``view`` of ``state``."""
    src = jnp.asarray(src, jnp.int32)
    vcap = state.vcap
    order = view.by_dst
    ok = _source_ok(state, src)

    reached0 = jnp.zeros((vcap,), jnp.bool_).at[src].set(ok, mode="drop")
    dist0 = jnp.where(reached0, 0, -1).astype(jnp.int32)
    parent0 = jnp.full((vcap,), NOKEY, jnp.int32)

    def cond(carry):
        _, _, _, frontier, lvl = carry
        return frontier.any() & (lvl < vcap)

    def body(carry):
        reached, dist, parent, frontier, lvl = carry
        act = order.live & frontier[order.src]
        # min source over the active in-arcs: the candidate parent, and
        # NOKEY exactly where no in-arc is active (no hit)
        cand_par = seg_reduce(order, jnp.where(act, order.src, NOKEY),
                              jnp.minimum, NOKEY)
        newly = (cand_par != NOKEY) & ~reached
        parent = jnp.where(newly, cand_par, parent)
        dist = jnp.where(newly, lvl + 1, dist)
        return reached | newly, dist, parent, newly, lvl + 1

    reached, dist, parent, _, _ = lax.while_loop(
        cond, body, (reached0, dist0, parent0, reached0, jnp.int32(0)))
    return BFSResult(ok, reached, dist, parent)


@jax.jit
def bfs(state: GraphState, src) -> BFSResult:
    return bfs_on(state, edge_view(state), src)


# --------------------------------- SSSP ----------------------------------

def _relax_once(dist, order: EdgeOrder, w):
    cand = seg_reduce(order, jnp.where(order.live, dist[order.src] + w, INF),
                      jnp.minimum, INF)
    return jnp.minimum(dist, cand)


def relax_fixpoint(dist0, order: EdgeOrder, w, vcap):
    """Bellman-Ford label-correcting fixed point from admissible upper bounds.

    Each pass is one segmented min over the dst-major ``order`` (``w``:
    the arc weights laid out as ``order``, or a scalar).  Returns
    ``(dist, changed-at-exit, iterations)``.  Shared by ``sssp`` and the
    engine's delta queries (``repro.engine.incremental``) so the two paths
    cannot drift apart — their bit-identical guarantee rests on running
    the exact same relax pass.
    """

    def cond(carry):
        _, changed, it = carry
        return changed & (it < vcap)

    def body(carry):
        dist, _, it = carry
        nd = _relax_once(dist, order, w)
        return nd, (nd < dist).any(), it + 1

    return lax.while_loop(cond, body, (dist0, jnp.bool_(True), jnp.int32(0)))


def sssp_on(state: GraphState, view: EdgeView, src) -> SSSPResult:
    """``sssp`` over a prebuilt ``view`` of ``state``."""
    src = jnp.asarray(src, jnp.int32)
    vcap = state.vcap
    order = view.by_dst
    ok_src = _source_ok(state, src)

    dist0 = jnp.full((vcap,), INF).at[src].set(
        jnp.where(ok_src, 0.0, INF), mode="drop")

    dist, changed, _ = relax_fixpoint(dist0, order, order.w, vcap)

    # The paper's CHECKNEGCYCLE for free: the fixed-point loop only exits
    # with ``changed`` still True when the vcap-th pass improved something,
    # which (shortest simple paths having < vcap edges) happens iff a
    # negative cycle is reachable — the extra relax pass it would otherwise
    # take to prove convergence is the loop's own final no-change pass.
    negcycle = changed
    parent = _sssp_parent(order, dist, src)
    return SSSPResult(ok_src & ~negcycle, negcycle, dist, parent)


@jax.jit
def sssp(state: GraphState, src) -> SSSPResult:
    return sssp_on(state, edge_view(state), src)


# ---------------------------------- BC -----------------------------------

def _bc_coo_sweep(view: EdgeView, vcap, level0, sigma0, front0, lvl0):
    """Brandes forward + backward over COO edges from a (possibly warm) start.

    The shared body of ``bc_dependencies`` (cold start: source frontier at
    level 0) and the engine's level-cut ``delta_bc`` (warm start: the prior
    forward tree above the cut, frontier at ``cut - 1``).  Warm starts
    produce bit-identical results because the loop state at pass ``lvl0``
    equals the cold run's state at that pass — the levels below ``lvl0``
    are required to be exactly what a cold run would have computed.

    A forward level is one segmented sum over the dst-major order: the
    sigma of each vertex's active in-neighbours.  Every frontier vertex
    has sigma >= 1 (the source 1, later ones a sum of such), so the sum
    is positive exactly where an in-arc is active — it is the hit test
    too.  A backward level is one segmented sum over the table's own
    src order; the per-arc level test and sigma ratio are fixed once the
    forward sweep ends, so they are computed once, before it.
    """
    fwd, bwd = view.by_dst, view.by_src

    # Forward phase: levels + shortest-path counts.
    def fcond(carry):
        _, _, frontier, lvl = carry
        return frontier.any() & (lvl < vcap)

    def fbody(carry):
        level, sigma, frontier, lvl = carry
        paths = jnp.where(frontier, sigma, 0.0)[fwd.src]
        adds = seg_reduce(fwd, jnp.where(fwd.live, paths, 0.0), jnp.add, 0.0)
        newly = (adds > 0) & (level < 0)
        sigma = jnp.where(newly, adds, sigma)
        level = jnp.where(newly, lvl + 1, level)
        return level, sigma, newly, lvl + 1

    level, sigma, _, _ = lax.while_loop(
        fcond, fbody, (level0, sigma0, front0, jnp.asarray(lvl0, jnp.int32)))

    # Backward phase: delta[u] += sum over tree edges (u,w) at level l->l+1
    # of sigma[u]/sigma[w] * (1 + delta[w]), from the deepest level down
    # (max(level) == deepest reached level; -1 when nothing is reached).
    lvl_src = level[bwd.src]
    tree_lvl = jnp.where(bwd.live & (level[bwd.dst] == lvl_src + 1),
                         lvl_src, -1)
    sig_dst = sigma[bwd.dst]
    ratio = sigma[bwd.src] / jnp.where(sig_dst > 0, sig_dst, 1.0)

    def bcond(carry):
        _, l = carry
        return l >= 0

    def bbody(carry):
        delta, l = carry
        contrib = jnp.where(tree_lvl == l, ratio * (1.0 + delta[bwd.dst]),
                            0.0)
        return delta + seg_reduce(bwd, contrib, jnp.add, 0.0), l - 1

    delta, _ = lax.while_loop(
        bcond, bbody, (jnp.zeros((vcap,), jnp.float32), jnp.max(level)))
    delta = jnp.where(level == 0, 0.0, delta)  # source contributes nothing
    return level, sigma, delta


def bc_dependencies_on(state: GraphState, view: EdgeView, src) -> BCResult:
    """``bc_dependencies`` over a prebuilt ``view`` of ``state``."""
    src = jnp.asarray(src, jnp.int32)
    vcap = state.vcap
    ok = _source_ok(state, src)

    level0 = jnp.full((vcap,), -1, jnp.int32).at[src].set(
        jnp.where(ok, 0, -1), mode="drop")
    sigma0 = jnp.zeros((vcap,), jnp.float32).at[src].set(
        jnp.where(ok, 1.0, 0.0), mode="drop")
    front0 = level0 == 0

    level, sigma, delta = _bc_coo_sweep(
        view, vcap, level0, sigma0, front0, jnp.int32(0))
    return BCResult(ok, delta, sigma, level)


@jax.jit
def bc_dependencies(state: GraphState, src) -> BCResult:
    """Brandes single-source dependency accumulation delta(src | .)."""
    return bc_dependencies_on(state, edge_view(state), src)


@jax.jit
def bc_level_cut(prior_level, dirty, alive):
    """Shallowest forward level a dirty set can have poisoned, per source.

    ``prior_level`` is ``int32[vcap]`` (one source) or ``int32[S, vcap]``
    (batched; ``dirty``/``alive`` broadcast over sources).  Levels strictly
    below the returned cut are guaranteed untouched: BFS level sets are
    determined level-by-level by the out-edge lists of the previous level's
    vertices, every edge mutation dirties the edge's *source*, and a
    liveness flip dirties the vertex itself — so a dirty vertex at prior
    level ``l`` can disturb levels ``>= l + 1`` through its out-edges, or
    level ``l`` itself only by dying.  Sources untouched by the dirty set
    get a cut past every level (pure reuse); a cut of 0 means the source
    itself is suspect and the caller must recompute that source cold.
    """
    reached = prior_level >= 0
    d = dirty & reached
    died = d & ~alive
    big = jnp.int32(prior_level.shape[-1] + 1)  # deeper than any level
    c1 = jnp.min(jnp.where(died, prior_level, big), axis=-1)
    c2 = jnp.min(jnp.where(d, prior_level + 1, big), axis=-1)
    return jnp.minimum(c1, c2)


# ------------------------ traversal-tree parents ---------------------------

def _bfs_parent(order: EdgeOrder, d, s):
    """Min source over the tree in-arcs ``dist[u] + 1 == dist[v]``."""
    vcap = d.shape[0]
    distf = jnp.where(d >= 0, d.astype(jnp.float32), INF)
    du = distf[order.src]
    tree = order.live & (du + 1.0 == distf[order.dst]) & (du < INF)
    parent = seg_reduce(order, jnp.where(tree, order.src, NOKEY),
                        jnp.minimum, NOKEY)
    parent = jnp.where(d >= 0, parent, NOKEY)
    return parent.at[jnp.clip(s, 0, vcap - 1)].set(NOKEY)


def _sssp_parent(order: EdgeOrder, d, s):
    """Min source over the tight in-arcs ``dist[v] == dist[u] + w(u, v)``."""
    vcap = d.shape[0]
    du = d[order.src]
    tight = order.live & (d[order.dst] == du + order.w) & (du < INF)
    parent = seg_reduce(order, jnp.where(tight, order.src, NOKEY),
                        jnp.minimum, NOKEY)
    return parent.at[jnp.clip(s, 0, vcap - 1)].set(NOKEY)


@jax.jit
def bfs_tree_parents(state: GraphState, dist: jax.Array,
                     srcs: jax.Array) -> jax.Array:
    """Canonical BFS-tree parents from final distances, batched over sources.

    ``dist`` is ``int32[S, vcap]`` (-1 unreached); returns
    ``int32[S, vcap]`` parents identical to per-source ``queries.bfs``: the
    frontier at level ``l`` is exactly ``{u : dist[u] == l}``, so the
    min-source over tree edges ``dist[u] + 1 == dist[v]`` reproduces the
    per-level min-source candidate.  Shared by the engine's ``delta_bfs``
    and the sharded queries (``repro.shard.queries``) so every path derives
    parents from one definition.
    """
    return jax.vmap(partial(_bfs_parent, edge_view(state).by_dst))(dist, srcs)


@jax.jit
def sssp_tree_parents(state: GraphState, dist: jax.Array,
                      srcs: jax.Array) -> jax.Array:
    """Tight-edge parents from final distances, batched over sources.

    ``dist`` is ``f32[S, vcap]`` (+inf unreachable); identical to
    per-source ``queries.sssp``: any tight edge
    ``dist[v] == dist[u] + w(u, v)``, min source id as tie-break.
    """
    return jax.vmap(partial(_sssp_parent, edge_view(state).by_dst))(dist,
                                                                    srcs)


def bc_map(state: GraphState, v, sources) -> jax.Array:
    """Per-source Brandes baseline: ``lax.map`` of ``bc_dependencies``.

    Kept as the oracle/benchmark baseline for ``bc``'s batched path.
    """
    v = jnp.asarray(v, jnp.int32)
    view = edge_view(state)

    def one(s):
        r = bc_dependencies_on(state, view, s)
        return jnp.where(r.ok, r.delta[jnp.clip(v, 0, state.vcap - 1)], 0.0)

    return jnp.sum(lax.map(one, jnp.asarray(sources, jnp.int32)))


def bc(state: GraphState, v, sources=None, *, method: str = "batched",
       use_kernel: bool = False, tile_view=None,
       src_chunk: int | None = None) -> jax.Array:
    """Betweenness centrality of ``v``: sum_s delta(s|v).

    ``sources`` defaults to every vertex slot (dead sources contribute 0 —
    exact Brandes over the alive set).  The default ``method="batched"``
    runs every source at once as level-synchronous semiring matmuls
    (``bc_batched_dense``); ``method="map"`` is the per-source ``lax.map``
    baseline.  ``tile_view`` (see ``repro.core.tiles``) supplies the dense
    weights plus the tile-occupancy mask so the semiring products skip
    empty tiles.  ``src_chunk`` bounds the batched path's S x V scratch
    (see ``bc_batched_dense``).
    """
    v = jnp.asarray(v, jnp.int32)
    if sources is None:
        sources = jnp.arange(state.vcap, dtype=jnp.int32)
    sources = jnp.asarray(sources, jnp.int32)
    ok = state.alive[jnp.clip(v, 0, state.vcap - 1)]
    if method == "map":
        total = bc_map(state, v, sources)
        return jnp.where(ok, total, jnp.nan)
    if method != "batched":
        raise ValueError(f"unknown bc method {method!r}")
    tile = 128
    if tile_view is not None:
        from .tiles import dense_views_from_tiles
        adj_mask, _, alive = dense_views_from_tiles(state, tile_view)
        amask, tile = tile_view.occ, tile_view.tile
    else:
        adj_mask, _, alive = dense_views(state)
        amask = None
    delta, _, _, src_ok = bc_batched_dense(
        adj_mask, sources, alive, use_kernel=use_kernel, amask=amask,
        tile=tile, src_chunk=src_chunk)
    vals = jnp.where(src_ok, delta[:, jnp.clip(v, 0, state.vcap - 1)], 0.0)
    return jnp.where(ok, jnp.sum(vals), jnp.nan)


# ------------------------ dense batched variants --------------------------
# vmap-over-sources == semiring matmuls: the MXU path (and the "static
# parallel analytics" baseline corresponding to Ligra in the paper's study).

@partial(jax.jit, static_argnames=("use_kernel", "tile"))
def bfs_batched_dense(adj_mask: jax.Array, srcs: jax.Array,
                      alive: jax.Array, use_kernel: bool = False,
                      amask: jax.Array | None = None, tile: int = 128):
    """Multi-source BFS over a dense adjacency mask.  Returns dist[S, V].

    ``amask``: optional tile-occupancy grid of the adjacency (see
    ``repro.core.tiles``) — empty tiles are skipped by the semiring product.
    """
    V = adj_mask.shape[0]
    a = (adj_mask & alive[:, None] & alive[None, :]).astype(jnp.float32)
    ok = alive[jnp.clip(srcs, 0, V - 1)]
    front0 = jax.nn.one_hot(srcs, V, dtype=jnp.float32) * ok[:, None]
    dist0 = jnp.where(front0 > 0, 0, -1).astype(jnp.int32)

    def cond(c):
        _, front, lvl = c
        return (front > 0).any() & (lvl < V)

    def body(c):
        dist, front, lvl = c
        nxt = semiring.bool_mm(front, a, use_kernel=use_kernel,
                               amask=amask, tile=tile)
        newly = (nxt > 0) & (dist < 0)
        dist = jnp.where(newly, lvl + 1, dist)
        return dist, newly.astype(jnp.float32), lvl + 1

    dist, _, _ = lax.while_loop(cond, body, (dist0, front0, jnp.int32(0)))
    return dist


@partial(jax.jit, static_argnames=("use_kernel", "tile"))
def sssp_batched_dense(w_dense: jax.Array, srcs: jax.Array,
                       alive: jax.Array, use_kernel: bool = False,
                       amask: jax.Array | None = None, tile: int = 128):
    """Multi-source Bellman-Ford over dense weights.  Returns (dist[S,V], negcycle[S])."""
    V = w_dense.shape[0]
    S = srcs.shape[0]
    big = jnp.where(alive[:, None] & alive[None, :], w_dense, INF)
    ok = alive[jnp.clip(srcs, 0, V - 1)]
    dist0 = jnp.where(
        jax.nn.one_hot(srcs, V, dtype=jnp.float32) * ok[:, None] > 0, 0.0, INF)

    def cond(c):
        _, changed, it = c
        return changed.any() & (it < V)

    def body(c):
        dist, _, it = c
        nd = jnp.minimum(dist, semiring.minplus_mm(dist, big,
                                                   use_kernel=use_kernel,
                                                   amask=amask, tile=tile))
        return nd, (nd < dist).any(axis=1), it + 1

    # The paper's CHECKNEGCYCLE from the loop's own exit state (PR 1 applied
    # this to the COO path): row s of the per-source changed vector is still
    # True at exit only when the V-th relax pass improved that source's
    # distances, which — shortest simple paths having < V edges — happens
    # iff a negative cycle is reachable from s.  No extra relax pass needed.
    dist, changed, _ = lax.while_loop(
        cond, body, (dist0, jnp.ones((S,), jnp.bool_), jnp.int32(0)))
    return dist, changed


def dense_views(state: GraphState):
    """Snapshot -> (adjacency mask, dense weights, alive) for batched queries."""
    w = densify(state)
    return w < INF, w, state.alive


# ------------------------- batched Brandes (BC) ---------------------------

def bc_sweep_ops(fwd_mm, bwd_mm, srcs: jax.Array, alive: jax.Array, V: int,
                 prior_level=None, prior_sigma=None, cut=None,
                 sync_any=None, sync_max=None):
    """One forward+backward Brandes sweep over *abstract* semiring products.

    The sweep never touches the adjacency itself — it only calls

      * ``fwd_mm(x)``  with the frontier-masked sigma ``x: f32[S, V]`` and
        expects the counting product ``x @ A``  (``f32[S, V]``);
      * ``bwd_mm(g)``  with the dependency flow ``g: f32[S, V]`` and
        expects ``g @ A^T`` (``f32[S, V]``)

    — which is what lets one sweep body serve both the dense chunked path
    (``bc_batched_dense``: one ``count_mm`` against the full matrix) and
    the sharded SUMMA-style ring path (``repro.shard.queries``: the
    products are assembled from O(V^2/n) bands rotated around the mesh
    with ``lax.ppermute``, no adjacency ever materialised per shard).
    Levels and sigma are bit-identical across providers: sigma counts are
    exact integers in f32 (< 2^24), so the band summation order cannot
    change them; only the backward ``delta`` sees f32 reassociation.

    ``sync_any``/``sync_max`` (default: identity) merge the loop-control
    predicates across whatever the products span.  A provider whose
    ``fwd_mm``/``bwd_mm`` contain collectives (the ring) MUST run its
    level loops in lock-step on every shard — a shard that exited early
    would abandon the rotation mid-ring — so the ring passes ``pmax``
    reductions here; extra lock-step iterations are exact no-ops (empty
    frontiers add zeros).

    ``prior_level``/``prior_sigma``/``cut`` warm-start the forward sweep
    per source (the level-cut delta-BC path): levels strictly below
    ``cut[s]`` are reused from the prior forward tree and source ``s``
    resumes expanding from its frontier at level ``cut[s] - 1``; a cut of
    0 (source itself suspect) restarts that source cold, and a cut past
    every level (untouched source) reuses its whole tree with zero forward
    passes.  The per-source level counter makes rows independent, so mixed
    cuts share one loop; each row's state at its resume pass equals the
    cold run's state at that pass, hence levels/sigma stay bit-identical
    and the (full) backward sweep reproduces delta bit-identically too.
    """
    if sync_any is None:
        sync_any = lambda p: p  # noqa: E731
    if sync_max is None:
        sync_max = lambda x: x  # noqa: E731
    S = srcs.shape[0]
    ok = alive[jnp.clip(srcs, 0, V - 1)] & (srcs >= 0) & (srcs < V)
    cold_front = jax.nn.one_hot(srcs, V, dtype=jnp.float32) * ok[:, None]
    level0 = jnp.where(cold_front > 0, 0, -1).astype(jnp.int32)
    sigma0 = cold_front
    lvl0 = jnp.zeros((S,), jnp.int32)
    if prior_level is not None:
        cut = jnp.broadcast_to(jnp.asarray(cut, jnp.int32), (S,))
        # A now-ok source whose prior tree is EMPTY (it was dead when the
        # prior was computed and has been resurrected since) looks
        # untouched to the level cut — its row has no reached levels for
        # the dirty set to intersect — but must restart cold.
        rows = jnp.arange(S, dtype=jnp.int32)
        revived = ok & (prior_level[rows, jnp.clip(srcs, 0, V - 1)] < 0)
        cut = jnp.where(revived, 0, cut)
        warm = (cut >= 1)[:, None]
        keep = warm & (prior_level >= 0) & (prior_level < cut[:, None])
        level0 = jnp.where(warm, jnp.where(keep, prior_level, -1), level0)
        sigma0 = jnp.where(warm, jnp.where(keep, prior_sigma, 0.0), sigma0)
        lvl0 = jnp.maximum(cut - 1, 0)
    front0 = (level0 == lvl0[:, None]).astype(jnp.float32)

    # Forward phase: levels + shortest-path counts.  The continue flag is
    # computed in the body and carried (rather than derived in the cond)
    # so a collective sync_any stays legal — while-loop conds must be
    # collective-free.
    def _more(front, lvl):
        return sync_any((front > 0).any() & (lvl < V).any())

    def fcond(c):
        return c[4]

    def fbody(c):
        level, sigma, front, lvl, _ = c
        # One counting product per level does both jobs: frontier sigma is
        # >= 1 on every frontier vertex and counts are exact integers in
        # f32 (below 2^24), so adds > 0 is precisely the bool_mm frontier
        # hit — no separate boolean product needed.
        adds = fwd_mm(jnp.where(front > 0, sigma, 0.0))
        newly = (adds > 0) & (level < 0)
        sigma = jnp.where(newly, adds, sigma)
        level = jnp.where(newly, lvl[:, None] + 1, level)
        front = newly.astype(jnp.float32)
        return level, sigma, front, lvl + 1, _more(front, lvl + 1)

    level, sigma, _, _, _ = lax.while_loop(
        fcond, fbody, (level0, sigma0, front0, lvl0, _more(front0, lvl0)))

    # Backward phase, deepest level first.  g carries the per-vertex
    # dependency flow of the level below; pulling it across edges is a
    # counting product against A^T.
    sig_safe = jnp.where(sigma > 0, sigma, 1.0)

    def bcond(c):
        _, l = c
        return l >= 0

    def bbody(c):
        delta, l = c
        g = jnp.where(level == l + 1, (1.0 + delta) / sig_safe, 0.0)
        pulled = bwd_mm(g)
        delta = delta + jnp.where(level == l, sigma * pulled, 0.0)
        return delta, l - 1

    # The deepest *edge* layer is (max level - 1) -> (max level); with
    # per-source resume passes the loop counter no longer bounds the depth,
    # so take it off the levels themselves.  sync_max keeps lock-step
    # providers iterating to the deepest level of ANY shard's chunk — the
    # extra iterations pull zero flow.
    delta, _ = lax.while_loop(
        bcond, bbody, (jnp.zeros_like(sigma), sync_max(jnp.max(level)) - 1))
    delta = jnp.where(level == 0, 0.0, delta)  # sources contribute nothing
    return delta, sigma, level, ok


@partial(jax.jit, static_argnames=("use_kernel", "tile", "src_chunk"))
def bc_batched_dense(adj_mask: jax.Array, srcs: jax.Array, alive: jax.Array,
                     use_kernel: bool = False,
                     amask: jax.Array | None = None, tile: int = 128,
                     src_chunk: int | None = None,
                     prior_level: jax.Array | None = None,
                     prior_sigma: jax.Array | None = None,
                     cut: jax.Array | None = None):
    """Multi-source Brandes as level-synchronous semiring matmuls.

    Forward sweep: bool_mm expands the per-source frontier (levels) while
    count_mm accumulates sigma, the number of shortest paths (integers in
    f32 — exact below 2^24).  Backward sweep: per level ``l`` (deepest
    first) the dependency flow  delta[u] += sigma[u] * sum_w A[u,w] *
    [level[w] = l+1] * (1 + delta[w]) / sigma[w]  is one count_mm against
    the transposed adjacency.  Levels and sigma match per-source
    ``bc_dependencies`` bit-exactly; delta agrees up to float summation
    order (the COO path's segmented sums vs the MXU dot).

    Returns ``(delta[S,V], sigma[S,V], level[S,V], ok[S])``.

    ``amask``: optional tile-occupancy grid of the adjacency — both sweeps
    skip empty tiles (the transposed sweep uses the transposed grid).

    ``src_chunk``: process the source axis in chunks of this size (the
    tail chunk may be ragged), one full forward+backward sweep per chunk
    with the chunk's forward levels reused by its backward sweep.  Peak
    scratch drops from 4 x S x V to 4 x src_chunk x V f32, which is what
    lets all-source BC run past vcap ~ 16k; per-source results are
    independent of the chunking (levels/sigma bit-exact; the matmul k
    reduction is unchanged, so delta only sees the padding's exact +0.0
    terms).

    ``prior_level``/``prior_sigma`` (``[S, V]``, a prior call's forward
    tree on the same sources) + ``cut`` (``int32[S]`` or scalar, from
    ``bc_level_cut``) select the level-cut delta path: each source reuses
    its cached levels/sigma strictly below its cut and re-runs the forward
    only from there (the backward sweep always runs in full — dependency
    flow crosses the cut upward).  Results are bit-identical to the cold
    call on every source (see ``bc_sweep_ops``).
    """
    a = (adj_mask & alive[:, None] & alive[None, :]).astype(jnp.float32)
    at = a.T
    amask_t = None if amask is None else amask.T

    def fwd_mm(x):
        return semiring.count_mm(x, a, use_kernel=use_kernel, amask=amask,
                                 tile=tile)

    def bwd_mm(g):
        return semiring.count_mm(g, at, use_kernel=use_kernel, amask=amask_t,
                                 tile=tile)

    return bc_batched_ops(fwd_mm, bwd_mm, srcs, alive, a.shape[0],
                          src_chunk=src_chunk, prior_level=prior_level,
                          prior_sigma=prior_sigma, cut=cut)


def bc_batched_ops(fwd_mm, bwd_mm, srcs: jax.Array, alive: jax.Array, V: int,
                   *, src_chunk: int | None = None,
                   prior_level: jax.Array | None = None,
                   prior_sigma: jax.Array | None = None,
                   cut: jax.Array | None = None,
                   sync_any=None, sync_max=None):
    """The chunked batched-Brandes driver over abstract semiring products.

    Exactly ``bc_batched_dense``'s source-chunking loop (one full
    forward+backward ``bc_sweep_ops`` per chunk, tail chunk ragged, warm
    state sliced per chunk) but consuming ``fwd_mm``/``bwd_mm`` providers
    instead of a materialised adjacency — the hook the sharded ring BC
    uses to run the identical per-chunk sweep over rotated O(V^2/n) bands.
    ``sync_any``/``sync_max`` are forwarded to every chunk's sweep (see
    ``bc_sweep_ops``).
    """
    S = srcs.shape[0]
    warm = prior_level is not None
    if warm:
        if prior_sigma is None or cut is None:
            raise ValueError("warm start needs prior_level, prior_sigma "
                             "and cut together")
        cut = jnp.broadcast_to(jnp.asarray(cut, jnp.int32), (S,))
    if src_chunk is None or src_chunk >= S:
        return bc_sweep_ops(fwd_mm, bwd_mm, srcs, alive, V,
                            prior_level, prior_sigma, cut,
                            sync_any, sync_max)
    if src_chunk < 1:
        raise ValueError(f"src_chunk must be >= 1, got {src_chunk}")
    parts = [bc_sweep_ops(fwd_mm, bwd_mm, srcs[lo:lo + src_chunk], alive, V,
                          prior_level[lo:lo + src_chunk] if warm else None,
                          prior_sigma[lo:lo + src_chunk] if warm else None,
                          cut[lo:lo + src_chunk] if warm else None,
                          sync_any, sync_max)
             for lo in range(0, S, src_chunk)]
    return tuple(jnp.concatenate([p[i] for p in parts], axis=0)
                 for i in range(4))
