"""The dense batched query forms agree with the COO queries and the oracle.

``bfs_batched_dense``, ``sssp_batched_dense`` and ``bc_batched_dense`` are
what ``bc_scores`` and ``repro.shard`` run; the COO ``bfs`` / ``sssp`` /
``bc_dependencies`` are what the local service runs.  Each named case
below is a graph on which the two forms are most likely to part: deep
level loops, one wide frontier, unreachable vertices, dead vertices with
their arcs still in the table, a dead source, reweighted arcs and path
ties, a negative cycle, undirected arcs, and an edge table with no free
slot.  Every case has the same shapes, so each program compiles once; the
kernel setting runs the Pallas masked kernels (interpret mode off the
chip) against the tile view's occupancy grid, as ``bc_scores`` does.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    PUTE, REMV, bc_batched_dense, bc_dependencies, bfs, bfs_batched_dense,
    dense_views, sssp, sssp_batched_dense,
)
from repro.core.graph_state import NOKEY
from repro.core.tiles import build_tile_view, dense_views_from_tiles
from oracle import apply_pair, build_pair

INF = float("inf")
VCAP, ECAP, TILE = 32, 128, 16
BATCH = VCAP + ECAP        # every commit pads to one batch shape
SOURCES = (0, 7, 31)
CASES = ["empty", "chain", "star", "two_components", "dead_vertices",
         "dead_source", "reweighted_ties", "negative_cycle", "undirected",
         "full_table"]


def _both_ways(edges):
    return edges + [(v, u, w) for u, v, w in edges]


def _case_ops(case):
    """``(edges, later)``: the arcs committed with all ``VCAP`` vertices,
    then the op batches committed after them, one commit each."""
    chain = [(i, i + 1, 1.0) for i in range(VCAP - 1)]
    if case == "empty":
        return [], []
    if case == "chain":
        return [(i, i + 1, float(1 + i % 3)) for i in range(VCAP - 1)], []
    if case == "star":
        return ([(0, i, float(1 + i % 4)) for i in range(1, VCAP)]
                + [(i, 0, 2.0) for i in range(1, VCAP)]), []
    if case == "two_components":
        left = _both_ways([(i, i + 1, 1.0) for i in range(15)]
                          + [(0, 8, 3.0)])
        right = [(i, i + 1, 2.0) for i in range(16, 31)] + [(31, 16, 2.0)]
        return left + right, []
    if case == "dead_vertices":
        # 0 -> 16 -> 31 is the shortest path until 16 goes
        return (chain + [(0, 16, 1.0), (16, 31, 1.0), (7, 16, 1.0),
                         (15, 17, 5.0)]), [[(REMV, 16)]]
    if case == "dead_source":
        ring = [(i, (i + 1) % VCAP, float(1 + i % 2)) for i in range(VCAP)]
        return _both_ways(ring), [[(REMV, 7)]]
    if case == "reweighted_ties":
        # two diamonds: four shortest 0 -> 6 paths; the overwrite of 1 -> 3
        # moves SSSP's route to 3 from 1 onto 2
        diamonds = [(0, 1, 1.0), (0, 2, 1.0), (1, 3, 1.0), (2, 3, 2.0),
                    (3, 4, 1.0), (3, 5, 1.0), (4, 6, 1.0), (5, 6, 1.0)]
        tail = [(i, i + 1, 1.0) for i in range(6, VCAP - 1)] + [(31, 0, 1.0)]
        return diamonds + tail, [[(PUTE, 1, 3, 5.0)]]
    if case == "negative_cycle":
        # 4 -> 5 -> 4 weighs -2: reachable from 0, not from 7 or 31
        return ([(i, i + 1, 1.0) for i in range(10)]
                + [(5, 4, -3.0), (31, 30, 1.0), (30, 29, 1.0)]), []
    if case == "undirected":
        arcs = {}
        for i in range(VCAP):
            for k in (1, 5):
                u, v = sorted((i, (i * k + 3) % VCAP))
                if u != v:
                    arcs[(u, v)] = float(1 + (u + v) % 3)
        return _both_ways([(u, v, w) for (u, v), w in arcs.items()]), []
    if case == "full_table":
        return [(i, (i + k) % VCAP, float(1 + (i + k) % 4))
                for i in range(VCAP) for k in (1, 2, 5, 11)], []
    raise ValueError(case)


@functools.lru_cache(maxsize=None)
def _case(case):
    """The committed graph, its oracle, and both dense views of it."""
    edges, later = _case_ops(case)
    g, o = build_pair(VCAP, edges, vcap=VCAP, ecap=ECAP, batch_size=BATCH)
    if case == "reweighted_ties":
        before = o.sssp(0)[0][3]
    for ops in later:
        g, o = apply_pair(g, o, ops, batch_size=BATCH)
    # each case holds the shape it is named for
    assert g.ecap == ECAP
    if case == "full_table":
        assert not np.any(np.asarray(g.esrc) == NOKEY)
    if case == "dead_source":
        assert not o.get_v(7)
    if case == "reweighted_ties":
        assert o.sssp(0)[0][3] != before
    view = build_tile_view(g, tile=TILE)
    return g, o, dense_views(g), (dense_views_from_tiles(g, view), view.occ)


def _dense_args(case, kernel):
    g, o, plain, (tiled, occ) = _case(case)
    if kernel:
        return g, o, tiled, dict(use_kernel=True, amask=occ, tile=TILE)
    return g, o, plain, {}


def _oracle_levels(o, s):
    dist = o.bfs(s) or {}
    return np.array([dist.get(v, -1) for v in range(VCAP)], np.int32)


def _check_bfs(g, o, views, kw, srcs):
    adj, _, alive = views
    dist = np.asarray(bfs_batched_dense(adj, srcs, alive, **kw))
    for i, s in enumerate(SOURCES):
        np.testing.assert_array_equal(dist[i], np.asarray(bfs(g, s).dist))
        np.testing.assert_array_equal(dist[i], _oracle_levels(o, s))


def _check_sssp(g, o, views, kw, srcs, case):
    _, w, alive = views
    dist, neg = sssp_batched_dense(w, srcs, alive, **kw)
    dist, neg = np.asarray(dist), np.asarray(neg)
    for i, s in enumerate(SOURCES):
        coo = sssp(g, s)
        exp, exp_neg = o.sssp(s)
        assert bool(neg[i]) == bool(coo.negcycle) == exp_neg, s
        if case == "negative_cycle":
            continue  # distances are undefined behind a negative cycle
        np.testing.assert_array_equal(dist[i], np.asarray(coo.dist))
        exp = exp or {}
        np.testing.assert_array_equal(
            dist[i], np.array([exp.get(v, INF) for v in range(VCAP)],
                              np.float32))
    if case == "negative_cycle":
        assert neg.any() and not neg.all()


def _check_bc(g, o, views, kw, srcs, case):
    adj, _, alive = views
    delta, sigma, level, ok = (np.asarray(x) for x in bc_batched_dense(
        adj, srcs, alive, **kw))
    for i, s in enumerate(SOURCES):
        coo = bc_dependencies(g, s)
        assert bool(ok[i]) == bool(coo.ok) == o.get_v(s), s
        np.testing.assert_array_equal(level[i], np.asarray(coo.level))
        np.testing.assert_array_equal(sigma[i], np.asarray(coo.sigma))
        np.testing.assert_allclose(delta[i], np.asarray(coo.delta),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(level[i], _oracle_levels(o, s))
        exp = o.bc_dependencies(s) or {}
        np.testing.assert_allclose(
            delta[i], [exp.get(v, 0.0) for v in range(VCAP)],
            rtol=1e-5, atol=1e-5)
    if case == "reweighted_ties":
        assert sigma.max() > 1


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "kernel"])
@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("kind", ["bfs", "sssp", "bc"])
def test_dense_forms_match_coo_and_oracle(kind, case, kernel):
    """Per source, the dense form equals the COO query and the oracle:
    BFS distances, SSSP distances and negative-cycle flags, BC levels and
    sigma exactly and delta to 1e-5."""
    g, o, views, kw = _dense_args(case, kernel)
    srcs = jnp.asarray(SOURCES, jnp.int32)
    if kind == "bfs":
        _check_bfs(g, o, views, kw, srcs)
    elif kind == "sssp":
        _check_sssp(g, o, views, kw, srcs, case)
    else:
        _check_bc(g, o, views, kw, srcs, case)
