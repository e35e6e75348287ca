"""Distributed tile-sparse queries: BFS / SSSP / BC over the sharded grid.

Each query is one ``shard_map`` program over the 1-D graph mesh axis.  Per
level a shard does **local** tile-skipping semiring work against its band
of the :class:`~repro.shard.tile_shard.ShardedTileView` — the very same
``bool_mm`` / ``minplus_mm`` / ``count_mm`` products (Pallas kernels or
jnp fallbacks) the single-device path runs, with the band's occupancy grid
as ``amask`` — followed by ONE vcap-sized collective merging the partial
frontiers:

  * BFS   — int8 ``pmax`` of the per-band frontier hits
            (S x Vp bytes per level);
  * SSSP  — f32 min-merge (``-pmax(-x)``) of the per-band relax candidates
            (4 x S x Vp bytes per level);
  * BC    — the **source axis** is sharded instead, each shard running the
            chunked batched-Brandes sweep over its own S/n sources (S/n x
            Vp level/sigma/delta state — the "BC at larger scale"
            decomposition) with one final psum merging the per-vertex
            scores.  How a shard sees the adjacency is the ``bc_mode``
            knob: ``"gather"`` all-gathers the row bands once per query
            (full O(Vp^2) grid per shard, zero per-level collectives — the
            oracle path), ``"ring"`` keeps only the shard's own O(Vp^2/n)
            band and SUMMA-style rotates bands around the mesh with
            ``lax.ppermute``, one revolution per level step, partial
            products accumulating (forward) / assembling (backward)
            between hops (``_ring_mms``) — per-shard memory stays
            O(Vp^2/n) at the cost of O(Vp^2/n) permute bytes per rotation.

Collective bytes per level are O(S x vcap), independent of E — exactly the
paper's property that queries validate against vertex metadata, not edges.
Cross-shard snapshot agreement is psum-validated the same way: every query
returns ``agree``, true iff all shards computed from the same committed
``version`` (the double-collect version check of ``ShardedGraphService``
then spans commits).

Results are bit-identical to the single-device ``core.queries`` batched
path on the same snapshot: BFS levels are exact integers; the SSSP min-plus
merge is order-free; BC runs the identical per-chunk sweep on the gathered
operands (levels/sigma exact, delta exact per source — only the final
score sum reassociates across shards).

**Delta queries** (``delta_bfs_sharded`` / ``delta_sssp_sharded`` /
``delta_bc_sharded``) port the engine's churn-proportional path to the
mesh.  The split follows what replicates vs what shards: the *stale-region
analysis* runs unsharded on replicated vertex-sized arrays (it is
per-vertex work with no collective), while the *recompute* warm-starts the
usual sharded level loop — local band products, ONE vcap-sized collective
per level, exactly as the full queries.  Per kind:

  * SSSP  — the engine's poison (``engine.incremental._poison``, the very
            function the local delta runs: pointer doubling over the prior
            parent tree + one weight-checked edge re-probe) certifies the
            keep set, whose distances seed the min-plus re-relax loop;
  * BFS   — the level cut (``bc_level_cut``): the poison's finer keep set
            is only consumable by a min-plus re-relax (distances can
            shrink through inserted shortcuts), which would forfeit the
            boolean sgemm/MXU loop — so delta BFS reuses levels above the
            shallowest dirty level and RESUMES ``_bfs_body``'s bool/pmax
            loop from the cut frontier, with per-source resume counters;
  * BC    — the same per-source level cut over the cached forward trees,
            threaded through ``bc_batched_dense(prior_level=, ...)``,
            sharded along the source axis like the full BC.

Every delta result is bit-identical to the full sharded recompute AND to
the local engine's delta path (distances are unique; parents come from
the shared tree reconstruction; warm sweeps replay the cold op sequence).
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import semiring
from repro.core.graph_state import INF, GraphState
from repro.core.queries import (
    bc_batched_dense,
    bc_batched_ops,
    bc_level_cut,
    bfs_tree_parents,
    edge_view,
    seg_reduce,
    sssp_tree_parents,
)

from .tile_shard import ShardedTileView, _axis


class ShardedBFSResult(NamedTuple):
    ok: jax.Array        # bool[S]      source was alive
    dist: jax.Array      # int32[S, V]  (-1 = unreached)
    parent: jax.Array    # int32[S, V]  (NOKEY = none; == queries.bfs parents)
    val_ecnt: jax.Array  # int32[V]     validation vector (reached ecnt)
    agree: jax.Array     # bool[]       all shards saw the same version


class ShardedSSSPResult(NamedTuple):
    ok: jax.Array        # bool[S]  source alive and no negative cycle
    negcycle: jax.Array  # bool[S]
    dist: jax.Array      # f32[S, V]  (+inf = unreachable)
    parent: jax.Array    # int32[S, V]  (NOKEY = none; == queries.sssp parents)
    val_ecnt: jax.Array  # int32[V]
    agree: jax.Array     # bool[]


class ShardedBCResult(NamedTuple):
    ok: jax.Array        # bool[S]
    delta: jax.Array     # f32[S, V]   dependencies, sharded over sources
    sigma: jax.Array     # f32[S, V]
    level: jax.Array     # int32[S, V]
    scores: jax.Array    # f32[V]      sum_s delta[s, v] over ok sources
    val_ecnt: jax.Array  # int32[V]
    agree: jax.Array     # bool[]


def _version_agree(version, ax):
    v = jnp.asarray(version, jnp.int32)
    same = (v == lax.pmax(v, ax)).astype(jnp.int32)
    return lax.psum(same, ax) == lax.psum(1, ax)


def _band_views(w_local, alive, ax):
    """Per-shard operand prep: padded alive, the band's row slice, and the
    band's alive-masked adjacency/weights."""
    band, vp = w_local.shape
    alivep = jnp.pad(alive, (0, vp - alive.shape[0]))
    lo = lax.axis_index(ax) * band
    alive_rows = lax.dynamic_slice_in_dim(alivep, lo, band)
    edge = (w_local < INF) & alive_rows[:, None] & alivep[None, :]
    return alivep, lo, edge


# ------------------------------ BFS / SSSP ---------------------------------

def _cold_srcs(alive, srcs, vp, vcap):
    """Per-shard source prep shared by the cold bodies: ``ok`` flags and
    the one-hot source positions (as an int mask)."""
    alivep = jnp.pad(alive, (0, vp - vcap))
    ok = alivep[jnp.clip(srcs, 0, vp - 1)] & (srcs >= 0) & (srcs < vcap)
    at_src = (jnp.arange(vp, dtype=jnp.int32)[None, :] == srcs[:, None])
    return ok, at_src & ok[:, None]


def _bfs_body(w_local, occ_local, alive, ecnt, srcs, version, *,
              ax, tile, use_kernel):
    """Cold BFS == the warm loop started from the one-hot source frontier
    at pass 0 (exactly how ``bc_dependencies`` reuses ``_bc_coo_sweep``),
    so the full and delta paths cannot drift apart."""
    vp = w_local.shape[1]
    vcap = alive.shape[0]
    _, src_hot = _cold_srcs(alive, srcs, vp, vcap)
    dist0 = jnp.where(src_hot, 0, -1)
    lvl0 = jnp.zeros(srcs.shape, jnp.int32)
    return _bfs_delta_body(w_local, occ_local, alive, ecnt, srcs, version,
                           dist0, lvl0, ax=ax, tile=tile,
                           use_kernel=use_kernel)


def _sssp_body(w_local, occ_local, alive, ecnt, srcs, version, *,
               ax, tile, use_kernel):
    """Cold Bellman-Ford == the warm re-relax from the one-hot sources.

    The pass-0 activity seed is the finite rows of ``dist0`` — only a
    source vertex can relax anything on the first pass, so bands holding
    no source skip their product until relaxation reaches them (the
    band-level frontier the activity tracking then maintains).
    """
    vp = w_local.shape[1]
    vcap = alive.shape[0]
    _, src_hot = _cold_srcs(alive, srcs, vp, vcap)
    dist0 = jnp.where(src_hot, 0.0, INF)
    ok, changed, dist, val_ecnt, agree = _sssp_delta_body(
        w_local, occ_local, alive, ecnt, srcs, version, dist0,
        (dist0 < INF).any(axis=0),
        ax=ax, tile=tile, use_kernel=use_kernel)
    return ok & ~changed, changed, dist, val_ecnt, agree


# ----------------------------- delta re-relax -------------------------------

def _bfs_delta_body(w_local, occ_local, alive, ecnt, srcs, version, dist0,
                    lvl0, *, ax, tile, use_kernel):
    """Warm-started BFS: the EXISTING bool/pmax level loop resumed mid-way.

    ``dist0`` (replicated int32[S, Vp]) carries each source's prior levels
    strictly above its level cut (-1 elsewhere) and ``lvl0[S]`` the resume
    pass (``cut - 1``; 0 for cold rows, ``vcap`` for untouched rows, which
    therefore run zero passes).  Per-source counters keep rows independent,
    so mixed cuts share one loop; each warm row's state at its resume pass
    equals the cold run's, hence distances are bit-identical to the full
    query.  Same band bool products and ONE int8 pmax per level as
    ``_bfs_body`` — staying on the boolean formulation (sgemm/MXU) is the
    whole point of cutting by level instead of re-relaxing min-plus.

    Per-shard early-exit: a shard whose band rows hold NO frontier vertex
    this level skips the band product entirely (its bool product of a zero
    frontier is exactly zero) but still joins the per-level pmax — the
    common case when a deep level cut confines the resumed frontier to a
    few shards' bands.
    """
    vp = w_local.shape[1]
    vcap = alive.shape[0]
    S = srcs.shape[0]
    alivep, lo, edge = _band_views(w_local, alive, ax)
    a_local = edge.astype(jnp.float32)
    band = w_local.shape[0]

    ok = alivep[jnp.clip(srcs, 0, vp - 1)] & (srcs >= 0) & (srcs < vcap)
    front0 = (dist0 == lvl0[:, None]).astype(jnp.float32)

    def cond(c):
        _, front, lvl = c
        return (front > 0).any() & (lvl < vcap).any()

    def body(c):
        dist, front, lvl = c
        fk = lax.dynamic_slice_in_dim(front, lo, band, axis=1)
        part = lax.cond(
            (fk > 0).any(),
            lambda: semiring.bool_mm(fk, a_local, use_kernel=use_kernel,
                                     amask=occ_local, tile=tile),
            lambda: jnp.zeros((S, vp), jnp.float32))
        hit = lax.pmax(part.astype(jnp.int8), ax) > 0  # one int8 pmax / level
        newly = hit & (dist < 0)
        dist = jnp.where(newly, lvl[:, None] + 1, dist)
        return dist, newly.astype(jnp.float32), lvl + 1

    dist, _, _ = lax.while_loop(cond, body, (dist0, front0, lvl0))
    reached_any = (dist[:, :vcap] >= 0).any(axis=0)
    val_ecnt = jnp.where(reached_any, ecnt, 0)
    return ok, dist, val_ecnt, _version_agree(version, ax)


def _sssp_delta_body(w_local, occ_local, alive, ecnt, srcs, version, dist0,
                     active0, *, ax, tile, use_kernel):
    """Warm-started min-plus fixed point: delta SSSP's re-relax.

    ``dist0`` (replicated f32[S, Vp]) carries the poison step's keep-set
    distances — genuine path lengths in the new graph, hence admissible
    upper bounds — so the standard label-correcting loop converges in
    ~(affected-region diameter) passes instead of ~(graph diameter).  Same
    band products and ONE f32 min-merge per level as the full
    ``_sssp_body`` loop.

    Per-shard early-exit via ``active0`` (replicated bool[Vp], the
    suspect-row seed — see ``_sssp_delta_dist0``): a band whose rows hold
    no active vertex contributes ``INF`` without running its product, but
    still joins the min-merge collective.  Sound because a row's
    contribution can only differ from what ``dist`` already absorbed when
    the row's distance changed since the pass that produced it (weights
    are fixed within a query) — so after pass 0, activity is exactly the
    vertices the previous min-merge improved, which every shard derives
    identically from the replicated post-collective distances.  Skipped
    bands therefore never change ``dist``, the pass count, or the
    exit-changed negative-cycle flag: results stay bit-identical.
    """
    band, vp = w_local.shape
    vcap = alive.shape[0]
    S = srcs.shape[0]
    alivep, lo, edge = _band_views(w_local, alive, ax)
    big_local = jnp.where(edge, w_local, INF)

    ok = alivep[jnp.clip(srcs, 0, vp - 1)] & (srcs >= 0) & (srcs < vcap)

    def cond(c):
        _, changed, _, it = c
        return changed.any() & (it < vcap)

    def body(c):
        dist, _, act, it = c
        dk = lax.dynamic_slice_in_dim(dist, lo, band, axis=1)
        cand = lax.cond(
            lax.dynamic_slice_in_dim(act, lo, band).any(),
            lambda: semiring.minplus_mm(dk, big_local, use_kernel=use_kernel,
                                        amask=occ_local, tile=tile),
            lambda: jnp.full((S, vp), INF))
        cand = -lax.pmax(-cand, ax)  # one f32 min-merge / level
        nd = jnp.minimum(dist, cand)
        improved = nd < dist
        return nd, improved.any(axis=1), improved.any(axis=0), it + 1

    # Exit-changed == negative cycle, exactly as in _sssp_body.
    dist, changed, _, _ = lax.while_loop(
        cond, body, (dist0, jnp.ones((S,), jnp.bool_), active0,
                     jnp.int32(0)))
    reached_any = (dist[:, :vcap] < INF).any(axis=0)
    val_ecnt = jnp.where(reached_any, ecnt, 0)
    return ok, changed, dist, val_ecnt, _version_agree(version, ax)


# ---------------------------------- BC -------------------------------------

def _bc_operands(w_local, occ_local, alive, ax):
    """All-gather the row bands once per query: O(Vp^2/n x 4B) per shard,
    vs O(levels x S x Vp) had the adjacency stayed sharded through both
    sweeps — and it keeps the per-chunk sweep bit-identical to the
    single-device path."""
    vp = w_local.shape[1]
    alivep = jnp.pad(alive, (0, vp - alive.shape[0]))
    w_full = lax.all_gather(w_local, ax, axis=0, tiled=True)
    occ_full = lax.all_gather(occ_local, ax, axis=0, tiled=True)
    return alivep, w_full, occ_full


def _bc_finish(level, delta, ok, ecnt, vcap, ax):
    part = jnp.sum(jnp.where(ok[:, None], delta, 0.0), axis=0)
    scores = lax.psum(part, ax)[:vcap]
    reached_any = lax.psum((level[:, :vcap] >= 0).any(axis=0)
                           .astype(jnp.int32), ax) > 0
    val_ecnt = jnp.where(reached_any, ecnt, 0)
    return scores, val_ecnt


def _bc_body(w_local, occ_local, alive, ecnt, srcs_local, version, *,
             ax, tile, use_kernel, src_chunk):
    vp = w_local.shape[1]
    vcap = alive.shape[0]
    alivep, w_full, occ_full = _bc_operands(w_local, occ_local, alive, ax)
    delta, sigma, level, ok = bc_batched_dense(
        w_full < INF, srcs_local, alivep, use_kernel=use_kernel,
        amask=occ_full, tile=tile, src_chunk=src_chunk)
    scores, val_ecnt = _bc_finish(level, delta, ok, ecnt, vcap, ax)
    return ok, delta, sigma, level, scores, val_ecnt, _version_agree(version, ax)


def _bc_delta_body(w_local, occ_local, alive, ecnt, srcs_local, version,
                   dirty, prior_level, prior_sigma, *,
                   ax, tile, use_kernel, src_chunk):
    """Level-cut delta BC, source axis sharded like the full ``_bc_body``.

    Each shard derives the cuts for ITS sources from the replicated dirty
    set (``bc_level_cut`` — no collective needed: a source's forward tree
    is entirely local state) and warm-starts the chunked batched-Brandes
    sweep from its cached trees; only the score psum and the validation
    reduction cross shards, exactly as in the full query.
    """
    vp = w_local.shape[1]
    vcap = alive.shape[0]
    alivep, w_full, occ_full = _bc_operands(w_local, occ_local, alive, ax)
    dirtyp = jnp.pad(dirty, (0, vp - vcap))
    cut = bc_level_cut(prior_level, dirtyp, alivep)
    delta, sigma, level, ok = bc_batched_dense(
        w_full < INF, srcs_local, alivep, use_kernel=use_kernel,
        amask=occ_full, tile=tile, src_chunk=src_chunk,
        prior_level=prior_level, prior_sigma=prior_sigma, cut=cut)
    scores, val_ecnt = _bc_finish(level, delta, ok, ecnt, vcap, ax)
    return ok, delta, sigma, level, scores, val_ecnt, _version_agree(version, ax)


# ------------------------------- BC: ring ----------------------------------

def _ring_mms(a_local, occ_local, *, ax, tile, use_kernel):
    """SUMMA-style semiring-product providers over a rotating band ring.

    The gather-mode BC materialises the full ``Vp x Vp`` adjacency per
    shard; here each shard ever holds only its own ``O(Vp^2/n)`` band plus
    the one in-flight band a ``lax.ppermute`` hop is delivering.  Per
    product the ring makes one revolution — ``n`` partial products with
    ``n - 1`` hops, each step computing the held band's tile-skipping
    partial and then passing the band (and its occupancy grid, the
    kernels' ``amask``) to the next shard; the last partial is peeled out
    of the loop so no hop is spent returning bands home (every product
    restarts from the shard's own closed-over band):

      * ``fwd_mm(x)``: holding band ``b`` (rows ``[b*band, (b+1)*band)``),
        the contribution to ``x @ A`` is ``x[:, rows(b)] @ A[rows(b), :]``
        — partials ACCUMULATE across rotations (the k axis is sharded).
        The sum is exact for sigma (integer counts in f32), so the ring's
        band-major summation order is invisible to levels/sigma.
      * ``bwd_mm(g)``: the contribution to ``g @ A^T`` is the full-k
        product ``g @ A[rows(b), :].T`` covering output columns
        ``rows(b)`` — partials ASSEMBLE by column block, each an intact
        dot against the transposed band (occupancy grid transposed too).

    Collective bytes per rotation: ``band x Vp`` (int8 {0,1} band)
    ``+ rows x nt x 4`` (int32 occupancy band) = O(Vp^2/n) — the figure
    the collective-byte regression test pins against the compiled HLO.

    Both providers contain collectives, so every shard must call them the
    same number of times: the callers run their level loops in lock-step
    via ``bc_sweep_ops``'s ``sync_any``/``sync_max`` hooks
    (``_ring_sync``).
    """
    band, vp = a_local.shape
    n = vp // band
    perm = [(j, (j + 1) % n) for j in range(n)]
    i = lax.axis_index(ax)

    def rotate(ab, ob):
        return lax.ppermute(ab, ax, perm), lax.ppermute(ob, ax, perm)

    def _revolve(combine, init):
        """n partials, n - 1 hops: loop over the first n - 1 held bands
        (combine, then rotate), then combine the last held band with no
        hop — the loop-carried bands are discarded, so a homing rotation
        would be pure wasted ICI traffic."""

        def step(t, c):
            ab, ob, acc = c
            acc = combine(t, ab, ob, acc)
            ab, ob = rotate(ab, ob)
            return ab, ob, acc

        ab, ob, acc = lax.fori_loop(0, n - 1, step,
                                    (a_local, occ_local, init))
        return combine(n - 1, ab, ob, acc)

    def fwd_mm(x):
        def combine(t, ab, ob, acc):
            b = (i - t) % n  # the band this shard holds at step t
            xk = lax.dynamic_slice_in_dim(x, b * band, band, axis=1)
            return acc + semiring.count_mm(xk, ab, use_kernel=use_kernel,
                                           amask=ob, tile=tile)

        return _revolve(combine, jnp.zeros((x.shape[0], vp), jnp.float32))

    def bwd_mm(g):
        def combine(t, ab, ob, out):
            b = (i - t) % n
            part = semiring.count_mm(g, ab.T, use_kernel=use_kernel,
                                     amask=ob.T, tile=tile)
            return lax.dynamic_update_slice(out, part, (0, b * band))

        return _revolve(combine, jnp.zeros((g.shape[0], vp), jnp.float32))

    return fwd_mm, bwd_mm


def _ring_sync(ax):
    """Lock-step hooks for ``bc_sweep_ops`` (see ``_ring_mms``): the level
    loops continue until EVERY shard's source chunk is done — one int8
    pmax per forward level, one int32 pmax per chunk for the backward
    start — and a shard's extra iterations are exact no-ops."""
    return dict(
        sync_any=lambda p: lax.pmax(p.astype(jnp.int8), ax) > 0,
        sync_max=lambda x: lax.pmax(x, ax))


def _bc_ring_prep(w_local, occ_local, alive, ax, tile, use_kernel):
    alivep, _, edge = _band_views(w_local, alive, ax)
    # int8 holds {0,1} exactly and quarters the band the ring keeps,
    # carries and permutes: at vcap 65536 on four 16 GB chips, f32 bands
    # (held, in flight, and the weights band) needed 16.0 GB per chip.
    fwd_mm, bwd_mm = _ring_mms(edge.astype(jnp.int8), occ_local,
                               ax=ax, tile=tile, use_kernel=use_kernel)
    return alivep, fwd_mm, bwd_mm


def _bc_ring_body(w_local, occ_local, alive, ecnt, srcs_local, version, *,
                  ax, tile, use_kernel, src_chunk):
    """Ring-mode ``_bc_body``: the identical chunked batched-Brandes sweep
    (``bc_batched_ops`` == ``bc_batched_dense``'s driver) fed by rotated
    bands instead of a gathered matrix.  Levels/sigma bit-identical to the
    gather mode; per-shard adjacency memory O(Vp^2/n) instead of O(Vp^2).
    """
    vp = w_local.shape[1]
    vcap = alive.shape[0]
    alivep, fwd_mm, bwd_mm = _bc_ring_prep(w_local, occ_local, alive, ax,
                                           tile, use_kernel)
    delta, sigma, level, ok = bc_batched_ops(
        fwd_mm, bwd_mm, srcs_local, alivep, vp, src_chunk=src_chunk,
        **_ring_sync(ax))
    scores, val_ecnt = _bc_finish(level, delta, ok, ecnt, vcap, ax)
    return ok, delta, sigma, level, scores, val_ecnt, _version_agree(version, ax)


def _bc_delta_ring_body(w_local, occ_local, alive, ecnt, srcs_local, version,
                        dirty, prior_level, prior_sigma, *,
                        ax, tile, use_kernel, src_chunk):
    """Ring-mode ``_bc_delta_body``: the same per-shard level cuts
    (replicated dirty set against the shard's own cached forward trees —
    levels/sigma are bit-identical across modes, so the cuts and the
    per-source resume counters are too), warm-starting the ring sweep.
    """
    vp = w_local.shape[1]
    vcap = alive.shape[0]
    alivep, fwd_mm, bwd_mm = _bc_ring_prep(w_local, occ_local, alive, ax,
                                           tile, use_kernel)
    dirtyp = jnp.pad(dirty, (0, vp - vcap))
    cut = bc_level_cut(prior_level, dirtyp, alivep)
    delta, sigma, level, ok = bc_batched_ops(
        fwd_mm, bwd_mm, srcs_local, alivep, vp, src_chunk=src_chunk,
        prior_level=prior_level, prior_sigma=prior_sigma, cut=cut,
        **_ring_sync(ax))
    scores, val_ecnt = _bc_finish(level, delta, ok, ecnt, vcap, ax)
    return ok, delta, sigma, level, scores, val_ecnt, _version_agree(version, ax)


# ------------------------------ entry points -------------------------------

_KINDS = ("bfs", "sssp", "bc", "bc_ring", "bfs_delta", "sssp_delta",
          "bc_delta", "bc_delta_ring")

#: ``bc_mode`` knob -> the (full, delta) shard_map kinds it selects.
BC_MODES = {"gather": ("bc", "bc_delta"),
            "ring": ("bc_ring", "bc_delta_ring")}


def _bc_kind(bc_mode: str, delta: bool) -> str:
    if bc_mode not in BC_MODES:
        raise ValueError(f"unknown bc_mode {bc_mode!r}; "
                         f"supported modes: {', '.join(sorted(BC_MODES))}")
    return BC_MODES[bc_mode][1 if delta else 0]


@lru_cache(maxsize=None)
def query_fn(mesh: Mesh, kind: str, tile: int, use_kernel: bool = False,
             src_chunk: int | None = None):
    """The jitted shard_map program for ``kind`` on ``mesh``.

    Signature: ``fn(w, occ, alive, ecnt, srcs, version, *extras)`` over
    GLOBAL arrays — ``w``/``occ`` sharded ``P(axis, None)`` (a
    ``ShardedTileView``), vertex arrays replicated, ``srcs`` replicated for
    bfs/sssp and sharded ``P(axis)`` for the bc kinds (length must divide
    the axis size; the host wrappers pad with -1).  The ``*_ring`` bc
    kinds share the bc signatures and differ only in how the adjacency
    reaches each shard (band rotation vs all-gather).  The delta kinds
    take extras: ``bfs_delta`` a replicated warm-start ``dist0[S, Vp]``
    plus resume passes ``lvl0[S]``; ``sssp_delta`` the replicated
    ``dist0[S, Vp]`` plus the band-activity seed ``active0[Vp]``;
    ``bc_delta(_ring)`` the replicated dirty mask plus the source-sharded
    prior ``level``/``sigma``.  Cached per (mesh, kind, tile, use_kernel,
    src_chunk).
    """
    ax = _axis(mesh)
    vspec, rspec, sspec = P(ax, None), P(), P(ax)
    kw = dict(ax=ax, tile=tile, use_kernel=use_kernel)
    extra_specs = ()
    if kind == "bfs":
        body = partial(_bfs_body, **kw)
        src_spec = rspec
        out_specs = (rspec, rspec, rspec, rspec)
    elif kind == "sssp":
        body = partial(_sssp_body, **kw)
        src_spec = rspec
        out_specs = (rspec, rspec, rspec, rspec, rspec)
    elif kind == "bfs_delta":
        body = partial(_bfs_delta_body, **kw)
        src_spec = rspec
        extra_specs = (rspec, rspec)                 # dist0, lvl0
        out_specs = (rspec, rspec, rspec, rspec)
    elif kind == "sssp_delta":
        body = partial(_sssp_delta_body, **kw)
        src_spec = rspec
        extra_specs = (rspec, rspec)                 # dist0, active0
        out_specs = (rspec, rspec, rspec, rspec, rspec)
    elif kind in ("bc", "bc_ring"):
        bodies = {"bc": _bc_body, "bc_ring": _bc_ring_body}
        body = partial(bodies[kind], src_chunk=src_chunk, **kw)
        src_spec = sspec
        out_specs = (sspec, vspec, vspec, vspec, rspec, rspec, rspec)
    elif kind in ("bc_delta", "bc_delta_ring"):
        bodies = {"bc_delta": _bc_delta_body,
                  "bc_delta_ring": _bc_delta_ring_body}
        body = partial(bodies[kind], src_chunk=src_chunk, **kw)
        src_spec = sspec
        extra_specs = (rspec, vspec, vspec)          # dirty, level, sigma
        out_specs = (sspec, vspec, vspec, vspec, rspec, rspec, rspec)
    else:
        raise ValueError(f"unknown query kind {kind!r}; "
                         f"supported kinds: {', '.join(_KINDS)}")
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(vspec, vspec, rspec, rspec, src_spec, rspec) + extra_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(fn)


def query_shardings(mesh: Mesh, kind: str):
    """(in_shardings, out_shardings) matching ``query_fn`` — what an AOT
    ``jit(fn, in_shardings=...).lower`` (``launch/dryrun.py``) needs."""
    ax = _axis(mesh)
    v = NamedSharding(mesh, P(ax, None))
    r = NamedSharding(mesh, P())
    s = NamedSharding(mesh, P(ax))
    if kind in ("bc", "bc_ring"):
        return (v, v, r, r, s, r), (s, v, v, v, r, r, r)
    if kind in ("bc_delta", "bc_delta_ring"):
        return (v, v, r, r, s, r, r, v, v), (s, v, v, v, r, r, r)
    if kind == "bfs_delta":
        return (v, v, r, r, r, r, r, r), (r,) * 4
    if kind == "sssp_delta":
        return (v, v, r, r, r, r, r, r), (r,) * 5
    if kind not in ("bfs", "sssp"):
        raise ValueError(f"unknown query kind {kind!r}; "
                         f"supported kinds: {', '.join(_KINDS)}")
    return (v, v, r, r, r, r), (r,) * (4 if kind == "bfs" else 5)


def _srcs_array(srcs, n_shards: int = 1, pad_to_shards: bool = False):
    srcs = jnp.atleast_1d(jnp.asarray(srcs, jnp.int32))
    if pad_to_shards:
        rem = (-srcs.shape[0]) % n_shards
        if rem:
            srcs = jnp.concatenate(
                [srcs, jnp.full((rem,), -1, jnp.int32)])
    return srcs


def _host_local(view: ShardedTileView, x: jax.Array) -> jax.Array:
    """Pull a small replicated array onto ONE device of the mesh.

    The unsharded helper math (tree-parent reconstruction, the delta
    poison/cut prep) consumes the replicated per-source outputs of the
    shard_map programs; left replicated, those jitted helpers execute once
    per mesh device — pure waste on host-platform meshes where every
    placeholder device shares one CPU, and duplicated work off the
    critical path on a real mesh.  The arrays are S x vcap-sized, so the
    transfer is noise next to the O(Vp^2/n) bands.
    """
    return jax.device_put(x, view.mesh.devices.reshape(-1)[0])


def _mesh_replicated(view: ShardedTileView, x: jax.Array) -> jax.Array:
    """The inverse hop: broadcast a device-local helper output back to a
    replicated mesh array so it can enter a shard_map program (jit refuses
    to mix single-device and mesh-committed operands)."""
    return jax.device_put(x, NamedSharding(view.mesh, P()))


def _account(accountant, kind: str, view: ShardedTileView, fn, args,
             use_kernel: bool, src_chunk: int | None = None) -> None:
    """Deposit the compiled program's HLO cost with the accountant.

    Cached per program signature — (kind, mesh shape, tile, flags, operand
    shapes) — so only the FIRST query of a given shape pays one extra
    lower+compile of the very ``query_fn`` program it just ran; every
    later query reads the cached dict (collective bytes, temp memory,
    flops) that the service attributes to its trace record.  The result
    lands in ``accountant.last`` (see ``repro.obs.hlo``); return types
    stay untouched.
    """
    if accountant is None:
        return
    key = ("shard_query", kind, view.mesh.shape_tuple, view.tile,
           use_kernel, src_chunk) + tuple(
        (tuple(a.shape), str(a.dtype))
        for a in args if hasattr(a, "shape"))
    accountant.account(key, lambda: fn.lower(*args).compile())


def bfs(view: ShardedTileView, state: GraphState, srcs, *,
        use_kernel: bool = False, accountant=None) -> ShardedBFSResult:
    """Distributed multi-source BFS; ``dist`` is sliced back to ``vcap``.

    ``parent`` is reconstructed from the final distances on the replicated
    COO edge table (``bfs_tree_parents`` — O(S x ecap) per-vertex work, no
    collective), identical to per-source ``queries.bfs`` and the array the
    delta path's poison step walks.
    """
    srcs = _srcs_array(srcs)
    fn = query_fn(view.mesh, "bfs", view.tile, use_kernel)
    args = (view.w, view.occ, state.alive, state.ecnt, srcs, state.version)
    ok, dist, val_ecnt, agree = fn(*args)
    _account(accountant, "bfs", view, fn, args, use_kernel)
    dist = _host_local(view, dist)[:, :state.vcap]
    parent = bfs_tree_parents(state, dist, srcs)
    return ShardedBFSResult(ok, dist, parent, val_ecnt, agree)


def sssp(view: ShardedTileView, state: GraphState, srcs, *,
         use_kernel: bool = False, accountant=None) -> ShardedSSSPResult:
    """Distributed multi-source Bellman-Ford with negative-cycle flags.

    ``parent`` follows ``queries.sssp`` (tight edges, min-source tie-break)
    via the shared ``sssp_tree_parents`` reconstruction.
    """
    srcs = _srcs_array(srcs)
    fn = query_fn(view.mesh, "sssp", view.tile, use_kernel)
    args = (view.w, view.occ, state.alive, state.ecnt, srcs, state.version)
    ok, neg, dist, val_ecnt, agree = fn(*args)
    _account(accountant, "sssp", view, fn, args, use_kernel)
    dist = _host_local(view, dist)[:, :state.vcap]
    parent = sssp_tree_parents(state, dist, srcs)
    return ShardedSSSPResult(ok, neg, dist, parent, val_ecnt, agree)


def bc_batched(view: ShardedTileView, state: GraphState, srcs=None, *,
               use_kernel: bool = False, src_chunk: int | None = None,
               bc_mode: str = "gather", accountant=None) -> ShardedBCResult:
    """Distributed batched Brandes, source axis sharded over the mesh.

    ``srcs`` defaults to every vertex slot (exact all-sources BC); it is
    padded with -1 up to a multiple of the shard count (dead padding
    contributes nothing) and the padding is sliced back off the returned
    per-source arrays, which stay sharded ``P(axis, None)``.

    ``bc_mode`` picks how each shard sees the adjacency: ``"gather"``
    (one ``all_gather`` of the row bands per query — O(Vp^2) per-shard
    memory, zero per-level collectives; the oracle path) or ``"ring"``
    (SUMMA-style ``lax.ppermute`` band rotation — O(Vp^2/n) per-shard
    memory, one ring revolution per level step; see ``_ring_mms``).
    Levels/sigma are bit-identical across modes; delta/scores agree to
    f32 summation order.
    """
    if srcs is None:
        srcs = jnp.arange(state.vcap, dtype=jnp.int32)
    n_srcs = jnp.atleast_1d(jnp.asarray(srcs)).shape[0]
    srcs = _srcs_array(srcs, view.n_shards, pad_to_shards=True)
    fn = query_fn(view.mesh, _bc_kind(bc_mode, delta=False), view.tile,
                  use_kernel, src_chunk)
    args = (view.w, view.occ, state.alive, state.ecnt, srcs, state.version)
    ok, delta, sigma, level, scores, val_ecnt, agree = fn(*args)
    _account(accountant, _bc_kind(bc_mode, delta=False), view, fn, args,
             use_kernel, src_chunk)
    vcap = state.vcap
    return ShardedBCResult(ok[:n_srcs], delta[:n_srcs, :vcap],
                           sigma[:n_srcs, :vcap], level[:n_srcs, :vcap],
                           scores, val_ecnt, agree)


# ------------------------------ delta queries -------------------------------

@partial(jax.jit, static_argnames=("vp",))
def _sssp_delta_dist0(state: GraphState, prior_dist, prior_parent, dirty,
                      srcs, vp: int):
    """The poison step of the sharded delta SSSP, batched over sources.

    Runs the engine's ``_poison`` (pointer doubling over the prior parent
    tree + one vectorized edge re-probe, weight-checked) per source on
    REPLICATED arrays — the parent walk is per-vertex, so nothing here
    needs the mesh — and returns the warm-start ``dist0[S, vp]``:
    surviving prior distances (admissible upper bounds in the new graph),
    +inf elsewhere, source re-pinned to 0.  Identical seeding to the
    engine's ``delta_sssp``.

    Also derives ``active0[vp]``, the pass-0 band-activity seed of the
    re-relax loop's per-shard early-exit: the rows that can possibly
    improve anything on the first pass.  A kept, clean vertex relaxing a
    kept neighbour reproves what prior convergence already guarantees —
    only (a) DIRTY rows (their out-edge set or weights changed) and
    (b) rows with a live out-edge into the poisoned/unreached region
    (``dist0 == INF``) can tighten a bound, and either way only where the
    row is finite for some source.  Later passes reseed activity from the
    vertices the previous min-merge improved (see ``_sssp_delta_body``).
    """
    from repro.engine.incremental import _poison

    vcap = state.vcap

    def one(dist, parent, src):
        reached = dist < INF
        poison = _poison(state, parent, reached, dist, dirty,
                         check_weight=True)
        keep = reached & ~poison
        d0 = jnp.where(keep, dist, INF)
        ok = (state.alive[jnp.clip(src, 0, vcap - 1)]
              & (src >= 0) & (src < vcap))
        return d0.at[src].set(jnp.where(ok, 0.0, INF), mode="drop")

    dist0 = jax.vmap(one)(prior_dist, prior_parent, srcs)

    out_arcs = edge_view(state).by_src

    def gap_rows(d):
        gap = (out_arcs.live & (d[out_arcs.src] < INF)
               & (d[out_arcs.dst] == INF))
        return seg_reduce(out_arcs, gap, jnp.logical_or, False)

    finite_any = (dist0 < INF).any(axis=0)
    active0 = (dirty & finite_any) | jax.vmap(gap_rows)(dist0).any(axis=0)
    return (jnp.pad(dist0, ((0, 0), (0, vp - vcap)), constant_values=INF),
            jnp.pad(active0, (0, vp - vcap)))


@partial(jax.jit, static_argnames=("vp",))
def _bfs_delta_state0(state: GraphState, prior_dist, dirty, srcs, vp: int):
    """The cut step of the sharded delta BFS, batched over sources.

    BFS levels ARE a forward tree, so the delta reuses exactly the
    level-cut reasoning of delta-BC (``bc_level_cut``): everything
    strictly above a source's shallowest dirty level is certainly
    unchanged, everything below is suspect.  The parent-tree poison walk
    would certify MORE survivors (it re-probes individual edges), but its
    keep set is only usable by a min-plus re-relax — distances can shrink
    through inserted shortcut edges — which would forfeit the boolean
    (sgemm/MXU) formulation the sharded BFS loop is built on; the level
    cut keeps every pass on the int8-pmax loop.  Returns the warm level
    array and per-source resume pass (``cut - 1``; cold restart for
    suspect sources, ``vcap`` = zero passes for untouched ones).
    """
    vcap = state.vcap
    cut = bc_level_cut(prior_dist, dirty, state.alive)
    ok = (state.alive[jnp.clip(srcs, 0, vcap - 1)]
          & (srcs >= 0) & (srcs < vcap))
    # A now-ok source with an EMPTY prior row (dead at prior time,
    # resurrected since) is invisible to the level cut — nothing in its
    # row is reached — but must restart cold, not reuse the empty tree.
    rows = jnp.arange(srcs.shape[0], dtype=jnp.int32)
    revived = ok & (prior_dist[rows, jnp.clip(srcs, 0, vcap - 1)] < 0)
    cut = jnp.where(revived, 0, cut)
    ids = jnp.arange(vcap, dtype=jnp.int32)
    cold = jnp.where((ids[None, :] == srcs[:, None]) & ok[:, None], 0, -1)
    usable = cut >= 1
    keep = usable[:, None] & (prior_dist >= 0) & (prior_dist < cut[:, None])
    dist0 = jnp.where(usable[:, None], jnp.where(keep, prior_dist, -1), cold)
    lvl0 = jnp.where(usable, jnp.minimum(cut - 1, vcap), 0)
    dist0 = jnp.pad(dist0, ((0, 0), (0, vp - vcap)), constant_values=-1)
    return dist0.astype(jnp.int32), lvl0.astype(jnp.int32)


def delta_bfs_sharded(view: ShardedTileView, state: GraphState,
                      prior: ShardedBFSResult, dirty, srcs, *,
                      use_kernel: bool = False,
                      accountant=None) -> ShardedBFSResult:
    """Distributed delta BFS: level cut unsharded, warm loop on the mesh.

    ``prior`` must be a result for the SAME ``srcs`` at an earlier version
    whose accumulated dirty set is ``dirty`` (a superset only costs time).
    Bit-identical to both the full sharded ``bfs`` on this snapshot and
    the engine's per-source ``delta_bfs`` (BFS distances are unique and
    the parents come from the shared reconstruction); the cost is the
    passes BELOW each source's cut — churn deep in the traversal skips the
    shallow levels entirely, untouched sources run zero passes.
    """
    srcs = _srcs_array(srcs)
    dist0, lvl0 = _bfs_delta_state0(state, prior.dist, dirty, srcs,
                                    vp=view.vp)
    dist0, lvl0 = (_mesh_replicated(view, x) for x in (dist0, lvl0))
    fn = query_fn(view.mesh, "bfs_delta", view.tile, use_kernel)
    args = (view.w, view.occ, state.alive, state.ecnt, srcs, state.version,
            dist0, lvl0)
    ok, dist, val_ecnt, agree = fn(*args)
    _account(accountant, "bfs_delta", view, fn, args, use_kernel)
    dist = _host_local(view, dist)[:, :state.vcap]
    parent = bfs_tree_parents(state, dist, srcs)
    return ShardedBFSResult(ok, dist, parent, val_ecnt, agree)


def delta_sssp_sharded(view: ShardedTileView, state: GraphState,
                       prior: ShardedSSSPResult, dirty, srcs, *,
                       use_kernel: bool = False,
                       accountant=None) -> ShardedSSSPResult:
    """Distributed delta Bellman-Ford: poison unsharded, re-relax sharded.

    The prior must be negative-cycle-free (its distances must be converged
    path lengths for the poison chain walk to certify them); on detection
    in the NEW graph the caller should re-run the full query, whose
    partially-relaxed distances are the canonical answer — exactly the
    ``incremental_sssp`` contract.  Bit-identical to the full sharded
    ``sssp`` and to the engine's ``delta_sssp`` (the re-relax is the same
    fixed point, merged with an order-free f32 min per level).
    """
    srcs = _srcs_array(srcs)
    dist0, active0 = (_mesh_replicated(view, x) for x in _sssp_delta_dist0(
        state, prior.dist, prior.parent, dirty, srcs, vp=view.vp))
    fn = query_fn(view.mesh, "sssp_delta", view.tile, use_kernel)
    args = (view.w, view.occ, state.alive, state.ecnt, srcs, state.version,
            dist0, active0)
    ok, changed, dist, val_ecnt, agree = fn(*args)
    _account(accountant, "sssp_delta", view, fn, args, use_kernel)
    dist = _host_local(view, dist)[:, :state.vcap]
    parent = sssp_tree_parents(state, dist, srcs)
    return ShardedSSSPResult(ok & ~changed, changed, dist, parent,
                             val_ecnt, agree)


def delta_bc_sharded(view: ShardedTileView, state: GraphState,
                     prior: ShardedBCResult, dirty, srcs=None, *,
                     use_kernel: bool = False, src_chunk: int | None = None,
                     bc_mode: str = "gather",
                     accountant=None) -> ShardedBCResult:
    """Distributed level-cut delta BC, source axis sharded as in ``bc_batched``.

    Each shard cuts its own sources' cached forward trees at the shallowest
    dirty level (``bc_level_cut`` on the replicated dirty mask — sources
    the churn cannot have touched reuse their whole tree with zero forward
    passes; a source that is itself suspect restarts cold) and resumes the
    chunked batched-Brandes sweep.  Bit-identical to the full sharded
    ``bc_batched`` on this snapshot, scores included.  ``bc_mode`` as in
    ``bc_batched``; the prior's forward trees are mode-independent
    (level/sigma bit-identical), so the cuts and per-source resume
    counters cannot drift across modes either.
    """
    if srcs is None:
        srcs = jnp.arange(state.vcap, dtype=jnp.int32)
    n_srcs = jnp.atleast_1d(jnp.asarray(srcs)).shape[0]
    srcs = _srcs_array(srcs, view.n_shards, pad_to_shards=True)
    vcap = state.vcap
    S, vp = srcs.shape[0], view.vp
    # Re-pad the cached (sliced-back) prior to the program's [S, Vp] shape:
    # padding sources carry an empty tree and padding columns are never
    # reached, matching what the full program computes for them.
    level = jnp.full((S, vp), -1, jnp.int32).at[:n_srcs, :vcap].set(
        prior.level)
    sigma = jnp.zeros((S, vp), jnp.float32).at[:n_srcs, :vcap].set(
        prior.sigma)
    dirty = _mesh_replicated(view, dirty)
    fn = query_fn(view.mesh, _bc_kind(bc_mode, delta=True), view.tile,
                  use_kernel, src_chunk)
    args = (view.w, view.occ, state.alive, state.ecnt, srcs, state.version,
            dirty, level, sigma)
    ok, delta, sigma, level, scores, val_ecnt, agree = fn(*args)
    _account(accountant, _bc_kind(bc_mode, delta=True), view, fn, args,
             use_kernel, src_chunk)
    return ShardedBCResult(ok[:n_srcs], delta[:n_srcs, :vcap],
                           sigma[:n_srcs, :vcap], level[:n_srcs, :vcap],
                           scores, val_ecnt, agree)


def validate_incremental_sharded(view: ShardedTileView, state: GraphState,
                                 srcs, result, kind: str, *,
                                 use_kernel: bool = False,
                                 src_chunk: int | None = None,
                                 bc_mode: str = "gather") -> bool:
    """``cmp_tree``-style check for the sharded delta paths: bit-equality
    of every result field against a fresh full distributed collect on the
    same snapshot (the sharded analogue of
    ``engine.incremental.validate_incremental`` — delta BC included, since
    the warm-started sweep replays the cold op sequence exactly; a ring
    delta validates against a ring full collect so the comparison stays
    within one summation order)."""
    from repro.engine.incremental import results_equal

    if kind == "bc":
        fresh = bc_batched(view, state, srcs, use_kernel=use_kernel,
                           src_chunk=src_chunk, bc_mode=bc_mode)
    else:
        fresh = {"bfs": bfs, "sssp": sssp}[kind](view, state, srcs,
                                                 use_kernel=use_kernel)
    return results_equal(result, fresh)
