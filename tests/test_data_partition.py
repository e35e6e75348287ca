"""Distributed graph queries + the HLO collective-byte parser.

The distributed-query tests run the tile-grid path (``core.partition``,
rebased onto ``repro.shard`` in PR 3) on a single-device graph mesh — the
shard_map programs are mesh-size-agnostic, and ``tests/test_shard.py``
covers the 4-way host-platform mesh in a subprocess.  The pre-PR-3
round-robin edge sharding stays exercised via ``core.partition_legacy``.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from repro.core import PUTE, PUTV, apply_ops, bfs, make_graph, queries, sssp
from repro.core.partition import (
    SUPPORTED_KINDS,
    build_query_inputs,
    distributed_query_specs,
    make_distributed_query,
)
from repro.core.partition_legacy import (
    make_distributed_query as legacy_distributed_query,
    shard_edges,
)
from repro.shard import as_graph_mesh


def _ring_graph():
    g = make_graph(16, 64)
    g, _ = apply_ops(g, [(PUTV, i) for i in range(8)]
                     + [(PUTE, i, (i + 1) % 8, float(i + 1))
                        for i in range(8)]
                     + [(PUTE, 0, 5, 1.0)])
    return g


def test_distributed_query_equals_local():
    """The tile-grid distributed path vs the local COO fixed points."""
    g = _ring_graph()
    mesh = as_graph_mesh()
    fn, _, _ = make_distributed_query(mesh, "bfs", tile=16)
    ok, dist, val_ecnt, agree = fn(*build_query_inputs(g, mesh, 0, tile=16))
    ref = bfs(g, 0)
    assert np.array_equal(np.asarray(dist)[0, :16], np.asarray(ref.dist))
    assert bool(agree)
    fn2, _, _ = make_distributed_query(mesh, "sssp", tile=16)
    ok2, neg, dist2, _, _ = fn2(*build_query_inputs(g, mesh, 0, tile=16))
    ref2 = sssp(g, 0)
    assert np.allclose(np.asarray(dist2)[0, :16], np.asarray(ref2.dist))
    assert bool(neg[0]) == bool(ref2.negcycle)


def test_distributed_bc_kind():
    """The PR-3 ``"bc"`` kind: level/sigma bit-equal to the local batched
    Brandes, delta to float summation order."""
    g = _ring_graph()
    mesh = as_graph_mesh()
    srcs = jnp.arange(8, dtype=jnp.int32)
    fn, _, _ = make_distributed_query(mesh, "bc", tile=16, src_chunk=4)
    ok, delta, sigma, level, scores, val_ecnt, agree = fn(
        *build_query_inputs(g, mesh, srcs, tile=16))
    am, _, alive = queries.dense_views(g)
    dref, sref, lref, okref = queries.bc_batched_dense(am, srcs, alive,
                                                       src_chunk=4)
    assert np.array_equal(np.asarray(level)[:, :16], np.asarray(lref))
    assert np.array_equal(np.asarray(sigma)[:, :16], np.asarray(sref))
    assert np.allclose(np.asarray(delta)[:, :16], np.asarray(dref),
                       rtol=1e-5, atol=1e-5)
    assert bool(agree)


def test_make_distributed_query_rejects_unknown_kind():
    mesh = as_graph_mesh()
    with pytest.raises(ValueError) as ei:
        make_distributed_query(mesh, "cc")
    msg = str(ei.value)
    assert "cc" in msg and all(k in msg for k in SUPPORTED_KINDS)


def test_distributed_query_specs_shapes():
    mesh = as_graph_mesh()
    specs = distributed_query_specs(100, mesh, tile=16, n_sources=4)
    w, occ, alive, ecnt, srcs, version = specs
    assert w.shape[0] % 16 == 0 and w.shape[0] >= 100
    assert occ.shape == (w.shape[0] // 16,) * 2
    assert alive.shape == (100,) and srcs.shape == (4,)


def test_legacy_edge_sharded_oracle_equals_local():
    """The pre-PR-3 edge-sharded decomposition is kept as a second,
    independent implementation; it must still match the local queries."""
    g = shard_edges(_ring_graph(), 1)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    fn, _, _ = legacy_distributed_query(mesh, "bfs")
    reached, dist, parent, ec = jax.jit(fn)(
        g.alive, g.ecnt, g.esrc, g.edst, g.ew, jnp.int32(0))
    ref = bfs(g, 0)
    assert np.array_equal(np.asarray(dist), np.asarray(ref.dist))
    assert np.array_equal(np.asarray(reached), np.asarray(ref.reached))
    fn2, _, _ = legacy_distributed_query(mesh, "sssp")
    _, dist2, neg, _ = jax.jit(fn2)(
        g.alive, g.ecnt, g.esrc, g.edst, g.ew, jnp.int32(0))
    ref2 = sssp(g, 0)
    assert np.allclose(np.asarray(dist2), np.asarray(ref2.dist))
    assert bool(neg) == bool(ref2.negcycle)


def test_collective_parser():
    from repro.obs.hlo import parse_collective_bytes
    hlo = """
      %ag = bf16[8,512,336]{2,1,0} all-gather(%x), replica_groups=...
      %ar = f32[1024]{0} all-reduce(%y), to_apply=%sum
      %rs = f32[2,64]{1,0} reduce-scatter(%z), dimensions={0}
      %a2a = bf16[16,40,128]{2,1,0} all-to-all(%w), dimensions={0}
      %cp = u32[7]{0} collective-permute(%q), source_target_pairs=...
      %ars = f32[12]{0} all-reduce-start(%y2), to_apply=%sum
      %not_a_collective = f32[9999]{0} add(%a, %b)
    """
    out = parse_collective_bytes(hlo)
    assert out["all-gather"] == 8 * 512 * 336 * 2
    assert out["all-reduce"] == 1024 * 4 + 12 * 4
    assert out["reduce-scatter"] == 128 * 4
    assert out["all-to-all"] == 16 * 40 * 128 * 2
    assert out["collective-permute"] == 7 * 4
    assert out["count"] == 6
    assert out["total"] == sum(v for k, v in out.items()
                               if k not in ("total", "count"))
