"""LEGACY distributed graph engine: round-robin *edge* sharding (pre-PR-3).

Superseded by the sharded tile-grid subsystem (``repro.shard``, fronted by
``core.partition.make_distributed_query``), which shards the ``TileView``
tile rows instead and reuses the tile-skipping semiring path per shard.
This module is retained as the independent cross-implementation ORACLE for
the distributed tests (two decompositions agreeing on the same snapshot is
a far stronger check than either alone) — do not grow new features here.

The paper's 56 CPU threads become mesh devices.  The decomposition:

  * **edges are sharded** round-robin over a 1-D ``graph`` axis (we flatten
    the production mesh's ``data`` x ``model`` axes, and ``pod`` too in the
    multi-pod case): each shard owns ``ecap / n`` contiguous slots of the
    sorted edge array (a contiguous key range, like one Ligra partition);
  * **vertex arrays are replicated** (bool/int32 of size vcap -- tiny next to
    the edge table) so every shard validates liveness locally;
  * each BFS/SSSP level does local edge-parallel work then ONE ``psum`` of a
    vcap-sized vector to merge frontiers/distances -- the only collective.
    Collective bytes per query = O(levels * vcap * 4B), independent of E:
    exactly the paper's property that queries touch each vertex's metadata,
    not each edge, when validating.
  * the double-collect validation vector (reached/parent/ecnt) is computed on
    the merged arrays, identically on every shard -- cross-shard snapshot
    agreement for free (deterministic SPMD), with the version psum-checked.

``distributed_*`` functions take *already sharded* edge arrays and are meant
to be called under ``shard_map`` -- see ``make_distributed_query`` which
builds the pjit'd entry point for a given mesh, and is also what
``launch/dryrun.py`` lowers for the graph-engine dry-run cells.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .graph_state import INF, NOKEY, GraphState


GRAPH_AXES = ("data", "model")  # flattened into one logical graph axis


def _psum(x, axes):
    for ax in axes:
        x = lax.psum(x, ax)
    return x


def _pmax(x, axes):
    for ax in axes:
        x = lax.pmax(x, ax)
    return x


# Shard-local bodies (run under shard_map; edge arrays are per-shard slices).

def _bfs_sharded(alive, ecnt, esrc, edst, ew, src, axes):
    """Distributed BFS, collective-lean form (Perf §graph iter 1-2).

    Per level the ONLY collective is a pmax of an int8[vcap] hit mask
    (131 KB at the 131072-vertex Table-1 scale).  The BFS-tree parents are
    reconstructed AFTER the fixed point with one edge-parallel pass + one
    int32 merge — the paper's per-visit parent bookkeeping moved out of the
    critical path (8x less ICI volume per level than merging parents every
    level; measured in EXPERIMENTS.md §Perf).
    """
    vcap = alive.shape[0]
    live = (esrc != NOKEY) & (ew < INF)
    srcc = jnp.where(live, jnp.clip(esrc, 0, vcap - 1), 0)
    dstc = jnp.where(live, jnp.clip(edst, 0, vcap - 1), 0)
    live = live & alive[srcc] & alive[dstc]

    ok = alive[jnp.clip(src, 0, vcap - 1)] & (src >= 0) & (src < vcap)
    reached0 = jnp.zeros((vcap,), jnp.bool_).at[src].set(ok, mode="drop")
    dist0 = jnp.where(reached0, 0, -1).astype(jnp.int32)

    def cond(c):
        _, _, frontier, lvl = c
        return frontier.any() & (lvl < vcap)

    def body(c):
        reached, dist, frontier, lvl = c
        act = live & frontier[srcc]
        hit_local = jnp.zeros((vcap,), jnp.int8).at[dstc].max(
            act.astype(jnp.int8), mode="drop")
        hit = _pmax(hit_local, axes) > 0           # one int8 pmax / level
        newly = hit & ~reached
        dist = jnp.where(newly, lvl + 1, dist)
        return reached | newly, dist, newly, lvl + 1

    reached, dist, _, _ = lax.while_loop(
        cond, body, (reached0, dist0, reached0, jnp.int32(0)))

    # parent reconstruction: any tree edge dist[dst] == dist[src] + 1,
    # deterministic min-src tie-break; one int32 merge for the whole tree.
    tree_e = live & (dist[dstc] == dist[srcc] + 1) & (dist[srcc] >= 0)
    par_local = jnp.full((vcap,), NOKEY, jnp.int32).at[dstc].min(
        jnp.where(tree_e, srcc, NOKEY), mode="drop")
    parent = -_pmax(-par_local, axes)
    parent = jnp.where(reached & (dist > 0), parent, NOKEY)

    # validation vector (identical on all shards by construction)
    val_ecnt = jnp.where(reached, ecnt, 0)
    return reached, dist, parent, val_ecnt


def _sssp_sharded(alive, ecnt, esrc, edst, ew, src, axes):
    vcap = alive.shape[0]
    live = (esrc != NOKEY) & (ew < INF)
    srcc = jnp.where(live, jnp.clip(esrc, 0, vcap - 1), 0)
    dstc = jnp.where(live, jnp.clip(edst, 0, vcap - 1), 0)
    live = live & alive[srcc] & alive[dstc]
    w = jnp.where(live, ew, INF)

    ok = alive[jnp.clip(src, 0, vcap - 1)] & (src >= 0) & (src < vcap)
    dist0 = jnp.full((vcap,), INF).at[src].set(
        jnp.where(ok, 0.0, INF), mode="drop")

    def relax(dist):
        cand_local = jnp.full((vcap,), INF).at[dstc].min(
            jnp.where(live, dist[srcc] + w, INF), mode="drop")
        cand = -_pmax(-cand_local, axes)  # global min-merge
        return jnp.minimum(dist, cand)

    def cond(c):
        _, changed, it = c
        return changed & (it < vcap)

    def body(c):
        dist, _, it = c
        nd = relax(dist)
        return nd, (nd < dist).any(), it + 1

    dist, _, _ = lax.while_loop(cond, body, (dist0, jnp.bool_(True), jnp.int32(0)))
    negcycle = (relax(dist) < dist).any()
    reached = dist < INF
    val_ecnt = jnp.where(reached, ecnt, 0)
    return reached, dist, negcycle, val_ecnt


def shard_edges(state: GraphState, n_shards: int) -> GraphState:
    """Pad the edge table so ``ecap`` divides evenly across shards."""
    rem = (-state.ecap) % n_shards
    if rem == 0:
        return state
    return state._replace(
        esrc=jnp.concatenate([state.esrc, jnp.full((rem,), NOKEY, jnp.int32)]),
        edst=jnp.concatenate([state.edst, jnp.full((rem,), NOKEY, jnp.int32)]),
        ew=jnp.concatenate([state.ew, jnp.full((rem,), INF, jnp.float32)]),
    )


def make_distributed_query(mesh: Mesh, query: str = "bfs"):
    """Build the pjit'd distributed query for ``mesh``.

    Edge arrays sharded over every mesh axis (flattened); vertex arrays
    replicated.  Returns ``(fn, in_shardings, out_shardings)`` where
    ``fn(alive, ecnt, esrc, edst, ew, src)``.
    """
    axes = tuple(mesh.axis_names)
    espec = P(axes)          # edge arrays: fully sharded over all axes
    vspec = P()              # vertex arrays: replicated
    body = {"bfs": _bfs_sharded, "sssp": _sssp_sharded}[query]

    fn = jax.shard_map(
        partial(body, axes=axes),
        mesh=mesh,
        in_specs=(vspec, vspec, espec, espec, espec, vspec),
        out_specs=vspec,
        check_vma=False,
    )
    in_sh = (
        NamedSharding(mesh, vspec), NamedSharding(mesh, vspec),
        NamedSharding(mesh, espec), NamedSharding(mesh, espec),
        NamedSharding(mesh, espec), NamedSharding(mesh, vspec),
    )
    out_sh = NamedSharding(mesh, vspec)
    return fn, in_sh, out_sh


def distributed_query_specs(vcap: int, ecap: int, mesh: Mesh):
    """ShapeDtypeStructs for the dry-run (no allocation)."""
    n = mesh.devices.size
    ecap_p = ecap + ((-ecap) % n)
    sds = jax.ShapeDtypeStruct
    return (
        sds((vcap,), jnp.bool_),
        sds((vcap,), jnp.int32),
        sds((ecap_p,), jnp.int32),
        sds((ecap_p,), jnp.int32),
        sds((ecap_p,), jnp.float32),
        sds((), jnp.int32),
    )
