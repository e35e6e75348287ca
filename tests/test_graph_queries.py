"""BFS / SSSP / BC vs the sequential oracle, COO and dense paths."""
import functools

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    PUTE, PUTV, REME, REMV, apply_ops, bc, bc_dependencies, bfs,
    bfs_batched_dense, dense_views, dirty_vertices, make_graph, queries,
    sssp, sssp_batched_dense,
)
from repro.core.graph_state import NOKEY
from repro.core.queries import bc_level_cut, bfs_tree_parents, \
    sssp_tree_parents
from repro.data import load_rmat_graph, rmat_edges
from repro.engine.incremental import _delta_bc_at_cut, delta_bfs, delta_sssp
from oracle import GraphOracle
import queries_reference

INF = float("inf")


def build_pair(n, edges):
    g = make_graph(max(16, n), max(16, 4 * len(edges)))
    o = GraphOracle()
    ops = [(PUTV, v) for v in range(n)]
    ops += [(PUTE, u, v, w) for u, v, w in edges]
    g, _ = apply_ops(g, ops)
    for op in ops:
        if op[0] == PUTV:
            o.put_v(op[1])
        else:
            o.put_e(op[1], op[2], op[3])
    return g, o


def rand_graph(seed, n=24, m=80, weighted=True):
    rng = np.random.default_rng(seed)
    edges = []
    seen = set()
    while len(edges) < m:
        u, v = rng.integers(0, n, 2)
        if u == v or (u, v) in seen:
            continue
        seen.add((u, v))
        w = float(rng.integers(1, 9)) if weighted else 1.0
        edges.append((int(u), int(v), w))
    return build_pair(n, edges)


@pytest.mark.parametrize("seed", range(4))
def test_bfs_matches_oracle(seed):
    g, o = rand_graph(seed)
    for src in (0, 3, 17):
        r = bfs(g, src)
        exp = o.bfs(src)
        dist = np.asarray(r.dist)
        for v in range(24):
            e = exp.get(v, -1) if exp else -1
            assert dist[v] == e, (src, v)


@pytest.mark.parametrize("seed", range(4))
def test_sssp_matches_oracle(seed):
    g, o = rand_graph(seed)
    for src in (0, 5):
        r = sssp(g, src)
        exp, neg = o.sssp(src)
        assert not bool(r.negcycle) == (not neg)
        dist = np.asarray(r.dist)
        for v in range(24):
            assert dist[v] == pytest.approx(exp.get(v, INF)), (src, v)


def test_sssp_negative_cycle():
    g, o = build_pair(4, [(0, 1, 1.0), (1, 2, -5.0), (2, 1, 1.0),
                          (0, 3, 2.0)])
    r = sssp(g, 0)
    assert bool(r.negcycle)
    assert not bool(r.ok)
    # negative edges WITHOUT a cycle are fine
    g2, _ = build_pair(4, [(0, 1, 5.0), (0, 2, 2.0), (2, 1, -4.0)])
    r2 = sssp(g2, 0)
    assert not bool(r2.negcycle)
    assert np.asarray(r2.dist)[1] == pytest.approx(-2.0)


@pytest.mark.parametrize("seed", range(3))
def test_bc_dependencies_match_oracle(seed):
    g, o = rand_graph(seed, n=16, m=40, weighted=False)
    for src in (0, 7):
        r = bc_dependencies(g, src)
        exp = o.bc_dependencies(src)
        delta = np.asarray(r.delta)
        for v in range(16):
            assert delta[v] == pytest.approx(exp.get(v, 0.0), abs=1e-4), \
                (src, v)


def test_bc_full_sum():
    # known graph: path 0 -> 1 -> 2: BC(1) = 1 (only 0->2 passes through 1)
    g, _ = build_pair(3, [(0, 1, 1.0), (1, 2, 1.0)])
    val = bc(g, 1, sources=jnp.arange(3))
    assert float(val) == pytest.approx(1.0)


@pytest.mark.parametrize("seed", range(3))
def test_dense_batched_matches_coo(seed):
    g, _ = rand_graph(seed, n=20, m=60)
    am, wd, alive = dense_views(g)
    srcs = jnp.array([0, 3, 11])
    dd = np.asarray(bfs_batched_dense(am, srcs, alive))
    for i, s in enumerate([0, 3, 11]):
        ref = np.asarray(bfs(g, s).dist)
        assert np.array_equal(dd[i], ref)
    ds, neg = sssp_batched_dense(wd, srcs, alive)
    ds = np.asarray(ds)
    for i, s in enumerate([0, 3, 11]):
        ref = np.asarray(sssp(g, s).dist)
        assert np.allclose(ds[i], ref)


def test_queries_respect_dead_vertices():
    g, o = build_pair(4, [(0, 1, 1.0), (1, 2, 1.0), (2, 3, 1.0)])
    g, _ = apply_ops(g, [(REMV, 1)])
    o.rem_v(1)
    r = bfs(g, 0)
    exp = o.bfs(0)
    assert np.asarray(r.dist)[2] == -1
    assert np.asarray(r.reached).sum() == len(exp)


def test_query_on_dead_source():
    g, _ = build_pair(3, [(0, 1, 1.0)])
    g, _ = apply_ops(g, [(REMV, 0)])
    assert not bool(bfs(g, 0).ok)
    assert not bool(sssp(g, 0).ok)


def test_rmat_generator_properties():
    src, dst, w = rmat_edges(64, 400, seed=1)
    assert (src != dst).all()
    assert src.min() >= 0 and src.max() < 64
    assert w.min() >= 1 and w.max() <= 6  # log2(64)
    g = load_rmat_graph(64, 400, seed=1)
    r = bfs(g, int(src[0]))
    assert bool(r.ok)


# ------------- segmented reductions vs the scatter formulation -------------

#: the layout of a table this size (``queries._layout_cols``): 128 columns
#: of 24 rows, so the hub's 100 in-arcs span several columns.
REF_VCAP, REF_ECAP = 160, 3072
HUB, NO_IN, ISOLATED, DEAD = 7, 3, 150, 20
REF_SOURCES = (0, NO_IN, HUB, ISOLATED, DEAD)


@functools.lru_cache(maxsize=None)
def _reference_case(case):
    """``(before, after, dirty)``: a graph with tombstoned slots, a NOKEY
    tail, dead vertices, a hub whose in-arcs cross column boundaries, a
    vertex with out-arcs only and an isolated one; ``after`` is one
    commit of churn later."""
    rng = np.random.default_rng({"directed": 1, "undirected": 2,
                                 "negcycle": 3}[case])
    n = 140
    arcs = {}

    def arc(u, v, w):
        arcs[(u, v)] = w
        if case == "undirected":
            arcs[(v, u)] = w

    for u in rng.choice(np.arange(n), 100, replace=False):
        if u != HUB:
            arc(int(u), HUB, float(rng.integers(1, 9)))
    while len(arcs) < 900:
        u, v = (int(x) for x in rng.integers(0, n, 2))
        if u != v and v != NO_IN and u != NO_IN:
            arc(u, v, float(rng.integers(1, 9)))
    for v in rng.choice(np.arange(NO_IN + 1, n), 6, replace=False):
        arc(NO_IN, int(v), 2.0)
    if case == "negcycle":
        arc(0, 141, 1.0)
        arc(141, 142, -4.0)
        arc(142, 141, 1.0)
    g = make_graph(REF_VCAP, REF_ECAP)
    ops = [(PUTV, v) for v in range(143)] + [(PUTV, ISOLATED)]
    ops += [(PUTE, u, v, w) for (u, v), w in arcs.items()]
    g, _ = apply_ops(g, ops)
    dead = [DEAD, int(rng.integers(30, 60))]
    gone = list(arcs)[::17]
    g, _ = apply_ops(g, [(REMV, v) for v in dead]
                     + [(REME, u, v) for u, v in gone])
    assert int(np.sum(np.asarray(g.ew) == INF)
               - np.sum(np.asarray(g.esrc) == NOKEY)) > 0  # tombstones
    churn = [(REME, u, v) for u, v in list(arcs)[5::41]]
    churn += [(PUTE, int(u), int(v), float(rng.integers(1, 9)))
              for u, v in rng.integers(0, n, (12, 2)) if u != v]
    churn += [(REMV, int(rng.integers(60, 90))), (PUTV, dead[1])]
    g2, _ = apply_ops(g, churn)
    return g, g2, dirty_vertices(g, g2)


def _equal(got, want, kind):
    if kind in ("bc", "delta_bc"):
        assert np.array_equal(got.ok, want.ok)
        assert np.array_equal(got.level, want.level)
        assert np.array_equal(got.sigma, want.sigma)
        np.testing.assert_allclose(got.delta, want.delta, rtol=1e-5, atol=0)
    else:
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


def _run(kind, impl, g, g2, dirty, s):
    """One query of ``kind`` through ``impl`` (the program or the frozen
    scatter reference), on ``g2`` from priors computed on ``g``."""
    prog = impl == "program"
    full = {"bfs": (bfs, queries_reference.bfs_reference),
            "sssp": (sssp, queries_reference.sssp_reference),
            "bc": (bc_dependencies,
                   queries_reference.bc_dependencies_reference)}
    if kind in full:
        return full[kind][0 if prog else 1](g2, s)
    if kind.startswith("delta_"):
        base = kind[len("delta_"):]
        prior = full[base][0](g, s)
        if base == "bc":
            cut = bc_level_cut(prior.level, dirty, g2.alive)
            fn = (_delta_bc_at_cut if prog
                  else queries_reference.delta_bc_reference)
            return fn(g2, prior, cut, s)
        fn = {"bfs": (delta_bfs, queries_reference.delta_bfs_reference),
              "sssp": (delta_sssp, queries_reference.delta_sssp_reference),
              }[base][0 if prog else 1]
        return fn(g2, prior, dirty, s)
    srcs = jnp.asarray(REF_SOURCES, jnp.int32)
    if kind == "bfs_tree_parents":
        dist = jnp.stack([bfs(g2, int(x)).dist for x in REF_SOURCES])
        fn = (bfs_tree_parents if prog
              else queries_reference.bfs_tree_parents_reference)
    else:
        dist = jnp.stack([sssp(g2, int(x)).dist for x in REF_SOURCES])
        fn = (sssp_tree_parents if prog
              else queries_reference.sssp_tree_parents_reference)
    return fn(g2, dist, srcs)


@pytest.mark.parametrize("case", ["directed", "undirected", "negcycle"])
@pytest.mark.parametrize("kind", [
    "bfs", "sssp", "bc", "delta_bfs", "delta_sssp", "delta_bc",
    "bfs_tree_parents", "sssp_tree_parents"])
def test_segmented_queries_match_scatter_reference(kind, case):
    """Every COO query over the sorted edge orders equals the scatter
    formulation it replaced (``tests/queries_reference.py``): BFS and
    SSSP outputs and BC levels and sigma bit for bit, BC delta to 1e-5."""
    g, g2, dirty = _reference_case(case)
    sources = (0,) if kind.endswith("tree_parents") else REF_SOURCES
    for s in sources:
        _equal(_run(kind, "program", g, g2, dirty, s),
               _run(kind, "reference", g, g2, dirty, s), kind)


def test_reference_case_covers_its_shapes():
    """The differential cases hold what they claim: a hub segment that
    crosses columns, a negative cycle, a vertex with no in-arcs."""
    g, _, _ = _reference_case("negcycle")
    view = queries.edge_view(g)
    assert bool(view.by_dst.carry[HUB]) and bool(view.by_dst.has[HUB])
    assert not bool(view.by_dst.has[NO_IN])
    assert not bool(view.by_dst.has[ISOLATED])
    assert bool(sssp(g, 0).negcycle)


@pytest.mark.parametrize("case", ["directed", "undirected", "negcycle"])
def test_full_and_delta_bc_bit_identical(case):
    """The level-cut delta BC runs the same reductions as the full one, so
    the two agree bit for bit wherever the delta applies (cut >= 1)."""
    g, g2, dirty = _reference_case(case)
    ran = 0
    for s in REF_SOURCES:
        prior = bc_dependencies(g, s)
        cut = bc_level_cut(prior.level, dirty, g2.alive)
        if not bool(prior.ok) or int(cut) < 1:
            continue
        got = _delta_bc_at_cut(g2, prior, cut, s)
        for a, b in zip(got, bc_dependencies(g2, s)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        ran += 1
    assert ran
