"""Sharded tile grid: the ``TileView`` partitioned over a 1-D device mesh.

The paper's scalability story is "more workers, same consistent snapshot";
the mesh analogue shards the blocked adjacency of ``repro.core.tiles`` by
**tile rows**: device ``i`` of an ``n``-device graph axis owns a contiguous
band of source vertices — ``Vp/n`` rows of the padded dense weights plus
the matching ``nt/n`` rows of the occupancy grid.  Row sharding is the
natural cut for level-synchronous semiring queries: a frontier product
against the band is entirely local (the band's occupancy grid is exactly
the ``amask`` the tile-skipping kernels and jnp fallbacks already accept),
and one vcap-sized collective per level merges the partial frontiers
(``repro.shard.queries``).

Both arrays are **global jax.Arrays** carrying a ``NamedSharding`` of
``P(axis, None)`` — the GSPMD layout: host code addresses them like any
``TileView`` while every jit/shard_map consumer sees only its local band.

``build_sharded_view`` derives the view from a snapshot (padding ``vcap``
up to a multiple of ``n * tile`` so whole tile rows land on each shard).
``refresh_sharded_view`` is the incremental path: the version ring's
dirty-vertex sets name the disturbed tile rows, and each dirty row is
re-derived by ONE owning shard under ``shard_map`` (every other shard
rewrites its current contents) — a small commit costs O(row), never an
O(Vp^2) rebuild or a cross-shard reshard.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.graph_state import INF, GraphState
from repro.core.tiles import (
    TILE,
    TileView,
    dirty_row_windows,
    row_window_slab,
)
from repro.obs import CounterStruct

GRAPH_AXIS = "graph"


def as_graph_mesh(mesh: Mesh | None = None, axis_name: str = GRAPH_AXIS) -> Mesh:
    """A 1-D logical graph mesh over every device of ``mesh`` (flattening a
    multi-axis production mesh), or over all local devices when ``None``."""
    if mesh is not None and tuple(mesh.axis_names) == (axis_name,):
        return mesh
    devices = (mesh.devices.reshape(-1) if mesh is not None
               else np.asarray(jax.devices()))
    return Mesh(devices, (axis_name,))


def _axis(mesh: Mesh) -> str:
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"sharded tile grid needs a 1-D mesh, got axes {mesh.axis_names}; "
            "flatten with as_graph_mesh(mesh) first")
    return mesh.axis_names[0]


def _padded_dim(vcap: int, tile: int, n_shards: int) -> int:
    chunk = tile * n_shards
    return -(-vcap // chunk) * chunk


@dataclass(frozen=True)
class ShardedTileView:
    """Row-sharded blocked adjacency snapshot.

    ``w``/``occ`` are global arrays sharded ``P(axis, None)`` over ``mesh``:
    shard ``i`` holds rows ``[i * vp/n, (i+1) * vp/n)`` of the padded dense
    weights and rows ``[i * nt/n, (i+1) * nt/n)`` of the occupancy grid.
    """

    w: jax.Array    # f32[Vp, Vp]   +inf = no edge, Vp % (n * tile) == 0
    occ: jax.Array  # int32[nt, nt] live-edge count per tile
    mesh: Mesh
    tile: int

    @property
    def vp(self) -> int:
        return self.w.shape[0]

    @property
    def n_tiles(self) -> int:
        return self.occ.shape[0]

    @property
    def n_shards(self) -> int:
        return int(self.mesh.devices.size)

    @property
    def band(self) -> int:
        """Rows of ``w`` owned by one shard."""
        return self.vp // self.n_shards

    @property
    def rows_per_shard(self) -> int:
        """Tile rows owned by one shard."""
        return self.n_tiles // self.n_shards


def sharded_occupancy_stats(view: ShardedTileView) -> dict:
    """Host-side summary incl. the per-shard tile-skip rates the kernels
    realise on each device's band."""
    occ = np.asarray(jax.device_get(view.occ))
    total = int(occ.size)
    active = int((occ > 0).sum())
    rows = view.rows_per_shard
    per_shard = []
    for i in range(view.n_shards):
        band = occ[i * rows:(i + 1) * rows]
        per_shard.append(round(float((band == 0).mean()) if band.size else 0.0,
                               4))
    return {
        "tile": view.tile,
        "grid": [view.n_tiles, view.n_tiles],
        "n_shards": view.n_shards,
        "tiles_total": total,
        "tiles_active": active,
        "tile_skip_rate": (total - active) / total if total else 0.0,
        "per_shard_tile_skip_rate": per_shard,
        "live_edges": int(occ.sum()),
    }


def gather_view(view: ShardedTileView) -> TileView:
    """Materialise the sharded view as a host-resident ``TileView`` (test
    oracle / debugging; O(Vp^2) transfer)."""
    return TileView(jnp.asarray(jax.device_get(view.w)),
                    jnp.asarray(jax.device_get(view.occ)))


# ------------------------------- build ------------------------------------

def _build_padded(state: GraphState, vp: int, tile: int):
    from repro.core.graph_state import live_edge_mask
    nt = vp // tile
    live = live_edge_mask(state)
    srcc = jnp.where(live, state.esrc, 0)
    dstc = jnp.where(live, state.edst, 0)
    w = jnp.full((vp, vp), INF, jnp.float32)
    w = w.at[srcc, dstc].min(jnp.where(live, state.ew, INF), mode="drop")
    occ = jnp.zeros((nt, nt), jnp.int32).at[srcc // tile, dstc // tile].add(
        live.astype(jnp.int32), mode="drop")
    return w, occ


@lru_cache(maxsize=None)
def _build_fn(mesh: Mesh, vp: int, tile: int):
    sh = NamedSharding(mesh, P(_axis(mesh), None))
    return jax.jit(partial(_build_padded, vp=vp, tile=tile),
                   out_shardings=(sh, sh))


def build_sharded_view(state: GraphState, mesh: Mesh,
                       tile: int = TILE) -> ShardedTileView:
    """Full O(vcap^2 + ecap) derivation, laid out row-sharded over ``mesh``."""
    ax = _axis(mesh)  # validates the mesh shape up front
    del ax
    n = int(mesh.devices.size)
    vp = _padded_dim(state.vcap, tile, n)
    w, occ = _build_fn(mesh, vp, tile)(state)
    return ShardedTileView(w, occ, mesh, tile)


# ------------------------------ refresh -----------------------------------

REFRESH_BATCH = 8  # max dirty tile rows fused into one shard_map dispatch


class RefreshStats(CounterStruct):
    """Per-process tallies of ``refresh_sharded_view``'s dispatch behavior
    (benchmarks read the deltas around a call): ``rows`` dirty tile rows
    refreshed, in ``dispatches`` shard_map program launches (the
    pre-batching cost was one launch per row == ``rows``).  Since PR 6 the
    values are ``shard_refresh_*`` counters in a
    :class:`repro.obs.MetricsRegistry`; the attribute surface (and the
    ``refresh_stats`` module global that benches delta around calls) is
    unchanged."""

    _FIELDS = ("rows", "dispatches", "rebuilds")
    _PREFIX = "shard_refresh_"


refresh_stats = RefreshStats()


@lru_cache(maxsize=None)
def _rows_refresh_fn(mesh: Mesh, tile: int, width: int, nrows: int):
    """Batched dirty-tile-row refresh as ONE shard_map program.

    Every shard receives the (replicated) edge windows of up to ``nrows``
    dirty rows and rebuilds all their slabs at once (``vmap`` over the row
    axis of the shared ``row_window_slab`` derivation), then writes each
    row back in place — only the OWNER of global tile row ``r`` keeps the
    new slab, every other shard (and every padding row, ``r == -1``)
    rewrites its current contents, so the donated buffers never move
    across shards.  Cached per (mesh, tile, window width, row-count
    bucket): under heavy churn a commit's same-width rows amortize to
    ``ceil(rows / REFRESH_BATCH)`` dispatches instead of one per row.
    """
    ax = _axis(mesh)

    def body(w_local, occ_local, esrc, edst, ew, alive, rs, los):
        vp = w_local.shape[1]
        nt = occ_local.shape[1]
        rows_per_shard = occ_local.shape[0]
        i = lax.axis_index(ax)
        slabs, occ_rows = jax.vmap(
            lambda r, lo: row_window_slab(esrc, edst, ew, alive, r, lo,
                                          tile=tile, width=width, vp=vp,
                                          nt=nt))(rs, los)

        def write(k, carry):
            w, occ = carry
            r = rs[k]
            own = (r >= 0) & ((r // rows_per_shard) == i)
            lr = jnp.where(own, r % rows_per_shard, 0)
            zero = jnp.int32(0)
            cur_w = lax.dynamic_slice(w, (lr * tile, zero), (tile, vp))
            cur_occ = lax.dynamic_slice(occ, (lr, zero), (1, nt))
            slab = jnp.where(own, slabs[k], cur_w)
            occ_row = jnp.where(own, occ_rows[k], cur_occ)
            return (lax.dynamic_update_slice(w, slab, (lr * tile, zero)),
                    lax.dynamic_update_slice(occ, occ_row, (lr, zero)))

        return lax.fori_loop(0, nrows, write, (w_local, occ_local))

    vspec, sspec = P(_axis(mesh), None), P()
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(vspec, vspec, sspec, sspec, sspec, sspec, sspec, sspec),
        out_specs=(vspec, vspec),
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=(0, 1))


def _batched_plan(plan):
    """Group the (row, lo, width) windows into dispatch batches: same-width
    rows fuse into chunks of up to ``REFRESH_BATCH``, each chunk padded up
    to the next power of two (padding rows are ``r = -1`` no-ops) so a
    handful of (width, bucket) program shapes cover every commit."""
    by_width: dict = {}
    for r, lo, width in plan:
        by_width.setdefault(width, []).append((r, lo))
    batches = []
    for width, rows in sorted(by_width.items()):
        for i in range(0, len(rows), REFRESH_BATCH):
            chunk = rows[i:i + REFRESH_BATCH]
            bucket = 1
            while bucket < len(chunk):
                bucket *= 2
            chunk = chunk + [(-1, 0)] * (bucket - len(chunk))
            rs = np.asarray([c[0] for c in chunk], np.int32)
            los = np.asarray([c[1] for c in chunk], np.int32)
            batches.append((width, bucket, rs, los))
    return batches


def refresh_sharded_view(state: GraphState, prev: ShardedTileView | None,
                         dirty: jax.Array | None, *,
                         mesh: Mesh | None = None,
                         tile: int | None = None) -> ShardedTileView:
    """Incremental rebuild from a dirty-vertex set (full rebuild fallback).

    Same host-side strategy pick as ``core.tiles.refresh_tile_view``: no
    dirty tile row returns ``prev``; a few dirty rows re-derive only those
    rows (same-width rows batched into one shard_map program each, writing
    in place on the owning shards); more than half the rows moved — or a
    resize / mesh change / no dirty info — rebuilds from scratch.
    ``prev``'s buffers are DONATED on the row path: treat the call as
    consuming ``prev``.  Dispatch tallies accumulate in ``refresh_stats``.
    """
    if prev is not None:
        mesh = mesh or prev.mesh
        tile = tile or prev.tile
    if mesh is None:
        raise ValueError("refresh_sharded_view needs a mesh when prev is None")
    tile = tile or TILE
    n = int(mesh.devices.size)
    if (prev is None or dirty is None
            or prev.mesh != mesh
            or prev.tile != tile
            or prev.vp != _padded_dim(state.vcap, tile, n)
            or dirty.shape[0] != state.vcap):
        refresh_stats.rebuilds += 1
        return build_sharded_view(state, mesh, tile)
    plan = dirty_row_windows(state, dirty, prev.n_tiles, tile)
    if plan is None:
        refresh_stats.rebuilds += 1
        return build_sharded_view(state, mesh, tile)
    if not plan:
        return prev
    w, occ = prev.w, prev.occ
    for width, bucket, rs, los in _batched_plan(plan):
        w, occ = _rows_refresh_fn(mesh, tile, width, bucket)(
            w, occ, state.esrc, state.edst, state.ew, state.alive,
            jnp.asarray(rs), jnp.asarray(los))
        refresh_stats.dispatches += 1
    refresh_stats.rows += len(plan)
    return ShardedTileView(w, occ, mesh, tile)
