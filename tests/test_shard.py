"""Sharded tile-grid engine: sharded-vs-local equivalence.

The multi-device tests run through ``conftest.run_multidevice`` (a
subprocess sets ``--xla_force_host_platform_device_count=4`` BEFORE
importing jax, so the placeholder devices never leak into other tests).
Equivalence bar (the PR's acceptance): distributed bfs/sssp dist
and bc level/sigma are BIT-identical to the single-device ``core.queries``
batched path on the same snapshot — including tombstones and dead vertices
— while bc delta/scores match to f32 summation order (the same caveat
``bc_batched_dense`` documents vs per-source Brandes).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import run_multidevice as _run_multidevice

from repro.core import (
    PUTE, REME, REMV, apply_ops, dense_views, queries,
)
from repro.core.partition import (
    SUPPORTED_KINDS, build_query_inputs, make_distributed_query,
)
from repro.core.updates import dirty_vertices
from repro.data import load_rmat_graph
from repro.shard import (
    as_graph_mesh,
    bc_batched,
    bfs,
    build_sharded_view,
    delta_bc_sharded,
    delta_bfs_sharded,
    delta_sssp_sharded,
    gather_view,
    refresh_sharded_view,
    refresh_stats,
    sharded_occupancy_stats,
    sssp,
    validate_incremental_sharded,
)


def _tombstoned_graph(n=64, edges=400, seed=3):
    g = load_rmat_graph(n, edges, seed=seed)
    return apply_ops(g, [(REME, int(g.esrc[5]), int(g.edst[5])),
                         (REME, int(g.esrc[40]), int(g.edst[40])),
                         (REMV, 7), (REMV, 33)])[0]


# ------------------------ in-process (1-device mesh) -----------------------

def test_make_distributed_query_rejects_unknown_kind():
    mesh = as_graph_mesh()
    with pytest.raises(ValueError) as ei:
        make_distributed_query(mesh, "pagerank")
    msg = str(ei.value)
    assert "pagerank" in msg
    for kind in SUPPORTED_KINDS:
        assert kind in msg


def test_sharded_matches_local_single_device():
    """The shard_map programs are mesh-size-agnostic: on a 1-device mesh
    they must already be bit-identical to the local batched path."""
    g = _tombstoned_graph()
    mesh = as_graph_mesh()
    view = build_sharded_view(g, mesh, tile=16)
    am, wd, alive = dense_views(g)
    srcs = jnp.asarray([0, 1, 7, 33, 63], jnp.int32)  # incl. dead sources

    r = bfs(view, g, srcs)
    assert np.array_equal(np.asarray(r.dist),
                          np.asarray(queries.bfs_batched_dense(am, srcs,
                                                               alive)))
    assert bool(r.agree)
    r2 = sssp(view, g, srcs)
    dref, negref = queries.sssp_batched_dense(wd, srcs, alive)
    assert np.array_equal(np.asarray(r2.dist), np.asarray(dref))
    assert np.array_equal(np.asarray(r2.negcycle), np.asarray(negref))

    r3 = bc_batched(view, g, srcs, src_chunk=2)
    d, s, lv, ok = queries.bc_batched_dense(am, srcs, alive, src_chunk=2)
    assert np.array_equal(np.asarray(r3.level), np.asarray(lv))
    assert np.array_equal(np.asarray(r3.sigma), np.asarray(s))
    assert np.array_equal(np.asarray(r3.ok), np.asarray(ok))
    assert np.allclose(np.asarray(r3.delta), np.asarray(d),
                       rtol=1e-5, atol=1e-5)


def test_bc_source_padding_and_default_sources():
    """Source counts that don't divide the mesh are padded with -1 and the
    padding sliced back off; ``srcs=None`` means every vertex slot."""
    g = _tombstoned_graph(n=32, edges=120)
    mesh = as_graph_mesh()
    view = build_sharded_view(g, mesh, tile=16)
    r = bc_batched(view, g, jnp.asarray([0, 5, 9], jnp.int32))
    assert r.delta.shape == (3, 32) and r.ok.shape == (3,)
    r_all = bc_batched(view, g, None)
    am, _, alive = dense_views(g)
    d, s, lv, ok = queries.bc_batched_dense(
        am, jnp.arange(32, dtype=jnp.int32), alive)
    scores = jnp.sum(jnp.where(ok[:, None], d, 0.0), axis=0)
    assert np.allclose(np.asarray(r_all.scores), np.asarray(scores),
                       rtol=1e-5, atol=1e-5)


def test_bc_ring_matches_gather_single_device():
    """Ring-mode BC on a 1-device mesh (a ring of one: no permutes) must
    already match the gathered oracle bit-for-bit on level/sigma."""
    g = _tombstoned_graph()
    mesh = as_graph_mesh()
    view = build_sharded_view(g, mesh, tile=16)
    srcs = jnp.asarray([0, 1, 7, 33, 63], jnp.int32)
    rg = bc_batched(view, g, srcs, src_chunk=2)
    rr = bc_batched(view, g, srcs, src_chunk=2, bc_mode="ring")
    assert np.array_equal(np.asarray(rr.level), np.asarray(rg.level))
    assert np.array_equal(np.asarray(rr.sigma), np.asarray(rg.sigma))
    assert np.array_equal(np.asarray(rr.ok), np.asarray(rg.ok))
    assert np.allclose(np.asarray(rr.delta), np.asarray(rg.delta),
                       rtol=1e-5, atol=1e-5)
    assert np.allclose(np.asarray(rr.scores), np.asarray(rg.scores),
                       rtol=1e-5, atol=1e-5)


def test_bc_mode_validation():
    """Unknown bc_mode raises with the supported modes listed, at both the
    query and the service layer."""
    g = _tombstoned_graph(n=32, edges=120)
    mesh = as_graph_mesh()
    view = build_sharded_view(g, mesh, tile=16)
    with pytest.raises(ValueError) as ei:
        bc_batched(view, g, jnp.asarray([0], jnp.int32), bc_mode="bogus")
    assert "gather" in str(ei.value) and "ring" in str(ei.value)
    from repro.shard import ShardedGraphService
    with pytest.raises(ValueError) as ei2:
        ShardedGraphService(g, mesh, tile=16, bc_mode="bogus")
    assert "ring" in str(ei2.value)


def test_sharded_sssp_negcycle_delta_fallback():
    """A negative cycle born since the cached answer: the delta re-relax
    surfaces it (exit-changed flag) and the service falls back to the full
    distributed collect for the canonical answer — under both bc_mode
    values (the knob must not disturb the sssp ladder)."""
    from repro.shard import ShardedGraphService

    g = _tombstoned_graph()
    mesh = as_graph_mesh()
    srcs = jnp.asarray([0], jnp.int32)

    # direct delta path: the new cycle flips the negcycle flag
    view = build_sharded_view(g, mesh, tile=16)
    prior = sssp(view, g, srcs)
    assert not bool(prior.negcycle.any())
    reached = np.flatnonzero(np.asarray(prior.dist[0]) < np.inf)
    a, b = (int(v) for v in reached[1:3])
    ops = [(PUTE, a, b, 1.0), (PUTE, b, a, -5.0)]
    g2, _ = apply_ops(g, ops)
    dirty = dirty_vertices(g, g2)
    view2 = refresh_sharded_view(g2, view, dirty)
    ds = delta_sssp_sharded(view2, g2, prior, dirty, srcs)
    assert bool(ds.negcycle[0]) and not bool(ds.ok[0])

    for bc_mode in ("gather", "ring"):
        svc = ShardedGraphService(g, mesh, tile=16, batch_size=4,
                                  bc_mode=bc_mode)
        rep0 = svc.query("sssp", [0])
        assert rep0.mode == "full" and not bool(rep0.result.negcycle[0])
        svc.submit_many(ops)
        svc.flush()
        # the ladder attempts delta (tiny touched dirty set, usable prior)
        # and its negcycle detection returns None = fall back to full
        ring_dirty = svc.ring.dirty_between(rep0.version, svc.version)
        state = svc.ring.latest.state
        assert svc._delta_collect("sssp", rep0.result, ring_dirty, [0],
                                  state) is None
        rep = svc.query("sssp", [0])
        assert rep.mode == "full" and bool(rep.result.negcycle[0])
        fresh = sssp(svc.view(), state, srcs)
        assert np.array_equal(np.asarray(rep.result.dist),
                              np.asarray(fresh.dist))
        # the canonical negcycle answer is cached; the NEXT query cannot
        # ride delta off it (negcycle prior is unusable) — localized churn
        # forces a fresh full collect, not a poisoned warm start
        svc.submit_many([(PUTE, a, int(reached[3]), 1.0)])
        svc.flush()
        rep2 = svc.query("sssp", [0])
        assert rep2.mode == "full" and bool(rep2.result.negcycle[0])


def test_sharded_parents_match_local_queries():
    """Full sharded bfs/sssp carry traversal-tree parents identical to the
    per-source COO queries (the arrays the delta poison step walks)."""
    g = _tombstoned_graph()
    mesh = as_graph_mesh()
    view = build_sharded_view(g, mesh, tile=16)
    srcs = jnp.asarray([0, 1, 7, 33, 63], jnp.int32)
    r, r2 = bfs(view, g, srcs), sssp(view, g, srcs)
    for i, s in enumerate([0, 1, 7, 33, 63]):
        assert np.array_equal(np.asarray(r.parent[i]),
                              np.asarray(queries.bfs(g, s).parent)), s
        assert np.array_equal(np.asarray(r2.parent[i]),
                              np.asarray(queries.sssp(g, s).parent)), s


def test_sharded_delta_queries_single_device():
    """Delta bfs/sssp/bc on a 1-device mesh: bit-identical to (a) a full
    sharded recompute and (b) the local engine's per-source delta path."""
    from repro.engine import delta_bfs, delta_sssp

    g = _tombstoned_graph()
    mesh = as_graph_mesh()
    view = build_sharded_view(g, mesh, tile=16)
    srcs = jnp.asarray([0, 1, 7, 33, 63], jnp.int32)
    pb, ps = bfs(view, g, srcs), sssp(view, g, srcs)
    pc = bc_batched(view, g, srcs, src_chunk=2)
    g2, _ = apply_ops(g, [(PUTE, 0, 40, 2.0), (REME, 1, int(g.edst[20])),
                          (PUTE, 20, 55, 1.0), (REMV, 12)])
    dirty = dirty_vertices(g, g2)
    view2 = refresh_sharded_view(g2, view, dirty)
    db = delta_bfs_sharded(view2, g2, pb, dirty, srcs)
    ds = delta_sssp_sharded(view2, g2, ps, dirty, srcs)
    dc = delta_bc_sharded(view2, g2, pc, dirty, srcs, src_chunk=2)
    assert validate_incremental_sharded(view2, g2, srcs, db, "bfs")
    assert validate_incremental_sharded(view2, g2, srcs, ds, "sssp")
    assert validate_incremental_sharded(view2, g2, srcs, dc, "bc",
                                        src_chunk=2)
    for i, s in enumerate([0, 1, 7, 33, 63]):
        lb = delta_bfs(g2, queries.bfs(g, s), dirty, s)
        assert np.array_equal(np.asarray(db.dist[i]), np.asarray(lb.dist)), s
        assert np.array_equal(np.asarray(db.parent[i]),
                              np.asarray(lb.parent)), s
        ls = delta_sssp(g2, queries.sssp(g, s), dirty, s)
        assert np.array_equal(np.asarray(ds.dist[i]), np.asarray(ls.dist)), s
        assert np.array_equal(np.asarray(ds.parent[i]),
                              np.asarray(ls.parent)), s


def test_sharded_delta_revived_source_restarts_cold():
    """A source that was dead when the prior was cached and resurrected
    since has an EMPTY prior row — invisible to the level cut and to the
    unchanged test — and must be recomputed from scratch, in BOTH bc_mode
    values (the ring warm start shares the gather path's cut/revive logic
    but runs a different program)."""
    from repro.core import PUTV
    from repro.engine import GraphService
    from repro.shard import ShardedGraphService

    g = _tombstoned_graph()  # vertices 7 and 33 are dead
    mesh = as_graph_mesh()
    view = build_sharded_view(g, mesh, tile=16)
    srcs = jnp.asarray([0, 7], jnp.int32)
    pb = bfs(view, g, srcs)
    pc = bc_batched(view, g, srcs, src_chunk=2)
    assert not bool(pb.ok[1])
    g2, _ = apply_ops(g, [(PUTV, 7), (PUTE, 7, 20, 1.0), (PUTE, 0, 40, 2.0)])
    dirty = dirty_vertices(g, g2)
    view2 = refresh_sharded_view(g2, view, dirty)
    db = delta_bfs_sharded(view2, g2, pb, dirty, srcs)
    assert validate_incremental_sharded(view2, g2, srcs, db, "bfs")
    assert bool(db.ok[1]) and int(db.dist[1, 7]) == 0
    for bc_mode in ("gather", "ring"):
        dc = delta_bc_sharded(view2, g2, pc, dirty, srcs, src_chunk=2,
                              bc_mode=bc_mode)
        assert validate_incremental_sharded(view2, g2, srcs, dc, "bc",
                                            src_chunk=2, bc_mode=bc_mode), \
            bc_mode
        assert bool(dc.ok[1]) and int(dc.level[1, 7]) == 0, bc_mode
    # the service ladder must not answer "unchanged" when the ONLY churn
    # is the resurrection (no prior-reached vertex is dirty)
    svc = ShardedGraphService(g, mesh, tile=16, batch_size=4)
    local = GraphService(g, batch_size=4)
    svc.query("bfs", [7])
    ops = [(PUTV, 7), (PUTE, 7, 20, 1.0)]
    svc.submit_many(ops); local.submit_many(ops)
    svc.flush(); local.flush()
    rep = svc.query("bfs", [7])
    assert rep.mode != "unchanged"
    lrep = local.query("bfs", 7)
    assert np.array_equal(np.asarray(rep.result.dist[0]),
                          np.asarray(lrep.result.dist))


def test_batched_refresh_dispatch_counts():
    """Same-width dirty rows fuse into one shard_map dispatch each batch:
    strictly fewer dispatches than rows, result identical to a rebuild."""
    rng = np.random.default_rng(5)
    g = load_rmat_graph(256, 2000, seed=2)
    mesh = as_graph_mesh()
    view = build_sharded_view(g, mesh, tile=16)
    ops = [(PUTE, int(rng.integers(0, 96)), int(rng.integers(0, 256)), 2.0)
           for _ in range(40)]
    g2, _ = apply_ops(g, ops)
    dirty = dirty_vertices(g, g2)
    r0, d0 = refresh_stats.rows, refresh_stats.dispatches
    view2 = refresh_sharded_view(g2, view, dirty)
    rows = refresh_stats.rows - r0
    dispatches = refresh_stats.dispatches - d0
    assert rows > 1 and dispatches < rows
    full, ref = gather_view(view2), gather_view(
        build_sharded_view(g2, mesh, tile=16))
    assert np.array_equal(np.asarray(full.w), np.asarray(ref.w))
    assert np.array_equal(np.asarray(full.occ), np.asarray(ref.occ))


def test_refresh_sharded_view_strategies():
    g = _tombstoned_graph()
    mesh = as_graph_mesh()
    view = build_sharded_view(g, mesh, tile=16)
    # empty dirty set: the very same view comes back
    same = refresh_sharded_view(g, view, jnp.zeros((64,), jnp.bool_))
    assert same is view
    # tile-size mismatch: falls back to a rebuild at the new grid
    g2, _ = apply_ops(g, [(PUTE, 3, 9, 2.0)])
    view2 = refresh_sharded_view(g2, view, dirty_vertices(g, g2), tile=32)
    assert view2.tile == 32
    full = gather_view(view2)
    ref = gather_view(build_sharded_view(g2, mesh, tile=32))
    assert np.array_equal(np.asarray(full.w), np.asarray(ref.w))
    assert np.array_equal(np.asarray(full.occ), np.asarray(ref.occ))
    # no prev and no mesh: explicit error
    with pytest.raises(ValueError):
        refresh_sharded_view(g2, None, None)


def test_build_query_inputs_roundtrip():
    g = _tombstoned_graph(n=32, edges=120)
    mesh = as_graph_mesh()
    fn, _, _ = make_distributed_query(mesh, "bfs", tile=16)
    args = build_query_inputs(g, mesh, [0, 2], tile=16)
    ok, dist, val_ecnt, agree = fn(*args)
    am, _, alive = dense_views(g)
    ref = queries.bfs_batched_dense(am, jnp.asarray([0, 2], jnp.int32), alive)
    assert np.array_equal(np.asarray(dist)[:, :32], np.asarray(ref))
    assert bool(agree)


# ------------------------- multi-device subprocess -------------------------

def test_sharded_view_refresh_multidevice():
    """Build + per-shard dirty-row refresh under an update stream, compact,
    and both grows: always bit-identical to a from-scratch sharded build."""
    out = _run_multidevice(r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import PUTE, PUTV, REME, REMV, apply_ops, compact, grow_edges, grow_vertices
from repro.core.graph_state import densify
from repro.core.updates import dirty_vertices
from repro.data import load_rmat_graph
from repro.shard import (as_graph_mesh, build_sharded_view, refresh_sharded_view,
                         gather_view, sharded_occupancy_stats)

mesh = as_graph_mesh()
assert mesh.devices.size == 4
g = load_rmat_graph(64, 400, seed=2)
view = build_sharded_view(g, mesh, tile=16)
assert view.vp % (4 * 16) == 0 and view.band == view.vp // 4
stats = sharded_occupancy_stats(view)
assert len(stats["per_shard_tile_skip_rate"]) == 4

def check(state, v):
    full = gather_view(v)
    vcap = state.vcap
    w = np.asarray(full.w)
    assert np.array_equal(w[:vcap, :vcap], np.asarray(densify(state)))
    assert np.isinf(w[vcap:, :]).all() and np.isinf(w[:, vcap:]).all()
    ref = gather_view(build_sharded_view(state, mesh, tile=16))
    assert np.array_equal(w, np.asarray(ref.w))
    assert np.array_equal(np.asarray(full.occ), np.asarray(ref.occ))

check(g, view)
rng = np.random.default_rng(0)
for i in range(6):
    ops = [(PUTE, int(rng.integers(0, 64)), int(rng.integers(0, 64)),
            float(rng.integers(1, 9))) for _ in range(5)]
    ops += [(REME, int(rng.integers(0, 64)), int(rng.integers(0, 64))),
            (REMV, int(rng.integers(0, 64))) if i == 3 else
            (PUTV, int(rng.integers(0, 64)))]
    g2, _ = apply_ops(g, ops)
    view = refresh_sharded_view(g2, view, dirty_vertices(g, g2))
    check(g2, view)
    g = g2
g2 = compact(g)
view = refresh_sharded_view(g2, view, jnp.zeros((64,), jnp.bool_))
check(g2, view)
g = g2
g2 = grow_edges(g)
g3, _ = apply_ops(g2, [(PUTE, 1, 2, 4.0)])
view = refresh_sharded_view(g3, view, dirty_vertices(g2, g3))
check(g3, view)
g4 = grow_vertices(g3)
g5, _ = apply_ops(g4, [(PUTV, 100), (PUTE, 1, 100, 2.0)])
view = refresh_sharded_view(g5, view, jnp.ones((g5.vcap,), jnp.bool_))
check(g5, view)
print("VIEW OK")
""")
    assert "VIEW OK" in out


def test_sharded_queries_equal_local_multidevice():
    """Distributed bfs/sssp/bc on a 4-way mesh vs the single-device path on
    an R-MAT graph with tombstones and dead vertices, plus the legacy
    edge-sharded oracle cross-check on BFS."""
    out = _run_multidevice(r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import REME, REMV, apply_ops, dense_views, queries
from repro.core.partition import build_query_inputs, make_distributed_query
from repro.data import load_rmat_graph
from repro.shard import as_graph_mesh, build_sharded_view, bc_batched, bfs, sssp

mesh = as_graph_mesh()
assert mesh.devices.size == 4
g = load_rmat_graph(64, 400, seed=3)
g, _ = apply_ops(g, [(REME, int(g.esrc[5]), int(g.edst[5])),
                     (REME, int(g.esrc[40]), int(g.edst[40])),
                     (REMV, 7), (REMV, 33)])
view = build_sharded_view(g, mesh, tile=16)
am, wd, alive = dense_views(g)
srcs = jnp.asarray([0, 1, 7, 33, 12, 63, 5, 2], jnp.int32)

r = bfs(view, g, srcs)
ref = queries.bfs_batched_dense(am, srcs, alive)
assert np.array_equal(np.asarray(r.dist), np.asarray(ref))
assert bool(r.agree)
# per-source COO oracle too
one = queries.bfs(g, 0)
assert np.array_equal(np.asarray(r.dist[0]), np.asarray(one.dist))

r2 = sssp(view, g, srcs)
dref, negref = queries.sssp_batched_dense(wd, srcs, alive)
assert np.array_equal(np.asarray(r2.dist), np.asarray(dref))
assert np.array_equal(np.asarray(r2.negcycle), np.asarray(negref))
ones = queries.sssp(g, 0)
assert np.array_equal(np.asarray(r2.dist[0]), np.asarray(ones.dist))

r3 = bc_batched(view, g, srcs, src_chunk=2)
d, s, lv, ok = queries.bc_batched_dense(am, srcs, alive, src_chunk=2)
assert np.array_equal(np.asarray(r3.level), np.asarray(lv))
assert np.array_equal(np.asarray(r3.sigma), np.asarray(s))
assert np.array_equal(np.asarray(r3.ok), np.asarray(ok))
assert np.allclose(np.asarray(r3.delta), np.asarray(d), rtol=1e-5, atol=1e-5)

# the partition front end over the same mesh
fn, _, _ = make_distributed_query(mesh, "bc", tile=16, src_chunk=2)
args = build_query_inputs(g, mesh, srcs, tile=16)
okp, dp, sp, lp, scores, val, agree = fn(*args)
assert np.array_equal(np.asarray(lp)[:, :64], np.asarray(lv))
assert bool(agree)

# legacy edge-sharded oracle agrees on the same snapshot (BFS dist)
from jax.sharding import Mesh
from repro.core.partition_legacy import make_distributed_query as legacy_q
from repro.core.partition_legacy import shard_edges
lmesh = Mesh(np.asarray(jax.devices()).reshape(2, 2), ("data", "model"))
gl = shard_edges(g, 4)
lfn, _, _ = legacy_q(lmesh, "bfs")
lreached, ldist, lparent, lec = jax.jit(lfn)(
    gl.alive, gl.ecnt, gl.esrc, gl.edst, gl.ew, jnp.int32(0))
assert np.array_equal(np.asarray(ldist), np.asarray(r.dist[0]))
print("QUERIES OK")
""")
    assert "QUERIES OK" in out


def test_sharded_delta_queries_multidevice():
    """Sharded delta bfs/sssp/bc on a 4-way mesh under churn that poisons
    vertices across shard boundaries, with tombstones and dead vertices:
    bit-identical to the local engine's delta path AND to a full sharded
    recompute on the same snapshot."""
    out = _run_multidevice(r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import PUTE, REME, REMV, apply_ops, queries
from repro.core.updates import dirty_vertices
from repro.data import load_rmat_graph
from repro.engine import delta_bfs, delta_sssp
from repro.shard import (as_graph_mesh, build_sharded_view, refresh_sharded_view,
                         bfs, sssp, bc_batched, delta_bfs_sharded,
                         delta_sssp_sharded, delta_bc_sharded,
                         validate_incremental_sharded)

mesh = as_graph_mesh()
assert mesh.devices.size == 4
g = load_rmat_graph(64, 400, seed=3)
g, _ = apply_ops(g, [(REME, int(g.esrc[5]), int(g.edst[5])),
                     (REMV, 7), (REMV, 33)])  # tombstones + dead vertices
view = build_sharded_view(g, mesh, tile=16)  # band = 16: shard i owns [16i, 16i+16)
srcs = jnp.asarray([0, 1, 7, 33, 12, 63, 5, 2], jnp.int32)
pb, ps = bfs(view, g, srcs), sssp(view, g, srcs)
pc = bc_batched(view, g, srcs, src_chunk=2)

# churn whose poison crosses shard boundaries: edges from shard 0/1 sources
# into shard 2/3 bands, plus a mid-band death
g2, _ = apply_ops(g, [(PUTE, 0, 40, 2.0), (REME, 1, int(g.edst[20])),
                      (PUTE, 20, 55, 1.0), (REMV, 12), (PUTE, 47, 18, 3.0)])
dirty = dirty_vertices(g, g2)
view2 = refresh_sharded_view(g2, view, dirty)

db = delta_bfs_sharded(view2, g2, pb, dirty, srcs)
ds = delta_sssp_sharded(view2, g2, ps, dirty, srcs)
dc = delta_bc_sharded(view2, g2, pc, dirty, srcs, src_chunk=2)
# (b) vs full sharded recompute: every field bit-equal
assert validate_incremental_sharded(view2, g2, srcs, db, 'bfs')
assert validate_incremental_sharded(view2, g2, srcs, ds, 'sssp')
assert validate_incremental_sharded(view2, g2, srcs, dc, 'bc', src_chunk=2)
# (a) vs the local engine's per-source delta path: dist AND parent bit-equal
for i, s in enumerate(np.asarray(srcs)):
    lb = delta_bfs(g2, queries.bfs(g, int(s)), dirty, int(s))
    assert np.array_equal(np.asarray(db.dist[i]), np.asarray(lb.dist)), s
    assert np.array_equal(np.asarray(db.parent[i]), np.asarray(lb.parent)), s
    ls = delta_sssp(g2, queries.sssp(g, int(s)), dirty, int(s))
    assert np.array_equal(np.asarray(ds.dist[i]), np.asarray(ls.dist)), s
    assert np.array_equal(np.asarray(ds.parent[i]), np.asarray(ls.parent)), s
# delta BC vs the local batched warm start on the gathered adjacency
from repro.core import dense_views
am2, _, alive2 = dense_views(g2)
dref, sref, lref, okref = queries.bc_batched_dense(am2, srcs, alive2, src_chunk=2)
assert np.array_equal(np.asarray(dc.level), np.asarray(lref))
assert np.array_equal(np.asarray(dc.sigma), np.asarray(sref))
print("DELTA OK")
""")
    assert "DELTA OK" in out


def test_sharded_service_delta_ladder_multidevice():
    """ShardedGraphService on a 4-way mesh climbs unchanged -> delta ->
    full with results bit-identical to the local GraphService at every
    step, and bc_scores rides the level-cut delta."""
    out = _run_multidevice(r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import PUTE, REME, apply_ops
from repro.data import load_rmat_graph
from repro.engine import GraphService
from repro.shard import ShardedGraphService, as_graph_mesh

mesh = as_graph_mesh()
g = load_rmat_graph(64, 600, seed=5)
svc = ShardedGraphService(g, mesh, tile=16, batch_size=4)
local = GraphService(g, batch_size=4)

assert svc.query("bfs", [0, 1]).mode == "full"
assert svc.query("sssp", [0]).mode == "full"
local.query("bfs", 0); local.query("sssp", 0)  # prime the local caches

# localized churn inside the reached region: the delta path answers
ops = [(PUTE, 0, v, 1.0) for v in (9, 11, 13, 15)] + [(REME, 0, 9)]
svc.submit_many(ops); local.submit_many(ops)
svc.flush(); local.flush()
rb = svc.query("bfs", [0, 1])
assert rb.mode == "delta" and bool(rb.result.agree)
lb = local.query("bfs", 0)
assert lb.mode == "delta"
assert np.array_equal(np.asarray(rb.result.dist[0]), np.asarray(lb.result.dist))
assert np.array_equal(np.asarray(rb.result.parent[0]), np.asarray(lb.result.parent))
rs = svc.query("sssp", [0])
assert rs.mode == "delta"
ls = local.query("sssp", 0)
assert np.array_equal(np.asarray(rs.result.dist[0]), np.asarray(ls.result.dist))

# churn outside every cached region: unchanged, however large
svc.submit_many([(PUTE, 200, 201 + i, 1.0) for i in range(4)])
svc.flush()
assert svc.query("bfs", [0, 1]).mode == "unchanged"

# bc_scores: full once, then the level-cut delta, bit-identical to local
s0, v0 = svc.bc_scores()
svc.submit_many([(PUTE, 3, 17, 1.0)]); svc.flush()
s1, v1 = svc.bc_scores()
assert v1 > v0 and svc.stats.delta >= 3
ref, _ = GraphService(svc.ring.latest.state).bc_scores()
a, b = np.asarray(s1), np.asarray(ref)
assert np.array_equal(np.isnan(a), np.isnan(b))
assert np.allclose(np.nan_to_num(a), np.nan_to_num(b), rtol=1e-4, atol=1e-4)

# cn double collect over the delta path still validates
svc.submit_many([(PUTE, 0, 21, 1.0)]); local.submit_many([(PUTE, 0, 21, 1.0)])
svc.flush(); local.flush()
rcn = svc.query("sssp", [0], mode="cn")
assert rcn.validated
lcn = local.query("sssp", 0, mode="cn")
assert np.array_equal(np.asarray(rcn.result.dist[0]), np.asarray(lcn.result.dist))
print("LADDER OK")
""")
    assert "LADDER OK" in out


def test_bc_ring_multidevice():
    """Ring-rotation BC on a 4-way mesh: bit-identical level/sigma to the
    gathered path AND the single-device batched path (full + level-cut
    delta), delta/scores to f32 summation order, and the collective-byte
    regression — ring-permute bytes per rotation match the O(Vp^2/n)
    formula off the compiled HLO, alongside the existing BFS int8-pmax /
    SSSP f32-min-merge byte formulas."""
    out = _run_multidevice(r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import PUTE, REME, REMV, apply_ops, dense_views, queries
from repro.core.updates import dirty_vertices
from repro.data import load_rmat_graph
from repro.shard import (as_graph_mesh, build_sharded_view, refresh_sharded_view,
                         bc_batched, bfs, sssp, delta_bc_sharded,
                         validate_incremental_sharded, query_fn)

mesh = as_graph_mesh()
assert mesh.devices.size == 4
g = load_rmat_graph(64, 400, seed=3)
g, _ = apply_ops(g, [(REME, int(g.esrc[5]), int(g.edst[5])),
                     (REMV, 7), (REMV, 33)])
view = build_sharded_view(g, mesh, tile=16)
am, wd, alive = dense_views(g)
srcs = jnp.asarray([0, 1, 7, 33, 12, 63, 5, 2], jnp.int32)

rg = bc_batched(view, g, srcs, src_chunk=2)
rr = bc_batched(view, g, srcs, src_chunk=2, bc_mode="ring")
d, s, lv, ok = queries.bc_batched_dense(am, srcs, alive, src_chunk=2)
for got, name in ((rg, "gather"), (rr, "ring")):
    assert np.array_equal(np.asarray(got.level), np.asarray(lv)), name
    assert np.array_equal(np.asarray(got.sigma), np.asarray(s)), name
    assert np.array_equal(np.asarray(got.ok), np.asarray(ok)), name
    assert np.allclose(np.asarray(got.delta), np.asarray(d),
                       rtol=1e-5, atol=1e-5), name
    assert bool(got.agree), name
assert np.allclose(np.asarray(rr.scores), np.asarray(rg.scores),
                   rtol=1e-5, atol=1e-5)
# unchunked sweep too
rr1 = bc_batched(view, g, srcs, bc_mode="ring")
assert np.array_equal(np.asarray(rr1.level), np.asarray(lv))

# level-cut delta under cross-shard churn, warm-started from a ring prior
g2, _ = apply_ops(g, [(PUTE, 0, 40, 2.0), (REME, 1, int(g.edst[20])),
                      (PUTE, 20, 55, 1.0), (REMV, 12), (PUTE, 47, 18, 3.0)])
dirty = dirty_vertices(g, g2)
view2 = refresh_sharded_view(g2, view, dirty)
dr = delta_bc_sharded(view2, g2, rr, dirty, srcs, src_chunk=2, bc_mode="ring")
assert validate_incremental_sharded(view2, g2, srcs, dr, "bc", src_chunk=2,
                                    bc_mode="ring")
dg = delta_bc_sharded(view2, g2, rg, dirty, srcs, src_chunk=2)
assert np.array_equal(np.asarray(dr.level), np.asarray(dg.level))
assert np.array_equal(np.asarray(dr.sigma), np.asarray(dg.sigma))
assert np.allclose(np.asarray(dr.scores), np.asarray(dg.scores),
                   rtol=1e-5, atol=1e-5)

# ---- collective-byte regression off the compiled HLO ----------------
from repro.obs.hlo import parse_collective_bytes
def coll(kind, extra=(), src_chunk=None):
    fn = query_fn(mesh, kind, 16, False, src_chunk)
    lowered = fn.lower(view.w, view.occ, g.alive, g.ecnt, srcs, g.version,
                       *extra)
    return parse_collective_bytes(lowered.compile().as_text())

S, vp = int(srcs.shape[0]), view.vp
band, rows, nt = view.band, view.rows_per_shard, view.n_tiles
slack = 64  # version-agreement scalars ride the same program

c = coll("bfs")
assert S * vp <= c["all-reduce"] <= S * vp + slack, c          # int8 pmax
c = coll("sssp")
assert 4 * S * vp <= c["all-reduce"] <= 4 * S * vp + slack, c  # f32 min-merge

# ring: one rotation = the shard's own band (int8 {0,1} adjacency + int32
# occ grid) = O(Vp^2/n) bytes; the compiled program carries exactly TWO
# rotation sites (forward loop, backward loop) per sweep
per_rot = band * vp + rows * nt * 4
assert per_rot == vp * vp // 4 + 4 * nt * nt // 4
for kind, chunks in (("bc_ring", 1),):
    c = coll(kind)
    assert c["collective-permute"] == 2 * chunks * per_rot, (kind, c)
# chunked: one rotation-site pair per source chunk (S/n sources per shard)
c = coll("bc_ring", src_chunk=1)
assert c["collective-permute"] == 2 * (S // 4) * per_rot, c
# gather mode moves the same band bytes once per query, n-fold amplified
c = coll("bc")
assert c["all-gather"] == vp * vp * 4 + nt * nt * 4, c
print("RING OK")
""")
    assert "RING OK" in out


def test_sharded_service_multidevice():
    """ShardedGraphService on a 4-way mesh: unchanged-shortcut, per-version
    caches, cn double collect, and bc_scores vs the local engine service."""
    out = _run_multidevice(r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import PUTE, REME, apply_ops
from repro.data import load_rmat_graph
from repro.engine import GraphService
from repro.shard import ShardedGraphService, as_graph_mesh

mesh = as_graph_mesh()
g = load_rmat_graph(64, 600, seed=5)
svc = ShardedGraphService(g, mesh, tile=16, batch_size=4)
local = GraphService(g, batch_size=4)

rep = svc.query("bfs", [0, 1])
assert rep.mode == "full" and bool(rep.result.agree)
lrep = local.query("bfs", 0)
assert np.array_equal(np.asarray(rep.result.dist[0]), np.asarray(lrep.result.dist))
assert svc.query("bfs", [0, 1]).mode == "unchanged"

# churn far from the reached region keeps the cached answer
svc.submit_many([(PUTE, 200, 201, 1.0)] * 4)
svc.flush()
rep2 = svc.query("sssp", [0])
assert rep2.mode == "full"
svc.submit_many([(PUTE, 200, 202, 1.0)] * 4)
svc.flush()
assert svc.query("sssp", [0]).mode == "unchanged"

# touching churn forces a fresh distributed collect, via cn double collect
ops = [(PUTE, 0, v, 1.0) for v in (9, 11, 13, 15)]
svc.submit_many(ops)
local.submit_many(ops)
svc.flush(); local.flush()
rep3 = svc.query("sssp", [0], mode="cn")
# the cn reply carries its FINAL collect's mode: the second collect sees
# the same ring version and reports unchanged (engine-service semantics)
assert rep3.validated and svc.stats.full >= 2
lrep3 = local.query("sssp", 0)
assert np.array_equal(np.asarray(rep3.result.dist[0]), np.asarray(lrep3.result.dist))

scores, ver = svc.bc_scores()
lscores, lver = local.bc_scores()
assert ver == svc.version and lver == local.version
a, b = np.asarray(scores), np.asarray(lscores)
assert np.array_equal(np.isnan(a), np.isnan(b))
assert np.allclose(np.nan_to_num(a), np.nan_to_num(b), rtol=1e-4, atol=1e-4)
print("SERVICE OK")
""")
    assert "SERVICE OK" in out
