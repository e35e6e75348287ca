"""Compile rehearsal for a described TPU v5e: no chip needed.

The TPU compiler is installed with jaxlib, so programs can be compiled for
a described (not attached) v5e.  That catches what interpret mode hides:
block shapes Mosaic refuses, kernels that never lower to a
``tpu_custom_call``, and programs that do not fit a chip's 16 GB.  The
topology is described inside a module fixture, never at import time: only
one process may load the TPU library, and a test worker that loads it at
collection would make the other workers collect different tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from conftest import load_chip_smoke
from repro.core.graph_state import GraphState
from repro.kernels import backend, ops
from repro.serve import batch

#: a v5e chip's HBM (Google Cloud documentation, "TPU v5e").
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_tpu(monkeypatch):
    """Compile the kernels as the chip would run them: this process's
    backend is the CPU, so steer the interpret choice here, clear the
    trace caches (a CPU trace of the same shapes would be reused), and
    keep the persistent cache off (it cannot read TPU entries back)."""
    monkeypatch.setattr(backend, "interpret_mode", lambda: False)
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("masked", [False, True], ids=["dense", "masked"])
@pytest.mark.parametrize("name", ["bool_mm", "minplus_mm", "count_mm"])
def test_kernel_compiles_for_v5e(one_chip, for_tpu, name, masked):
    """Each semiring kernel, through its ``ops`` wrapper at real widths
    (128 sources x 8192 vertices), lowers to a Mosaic kernel."""
    s, v, tile = 128, 8192, 128
    x = _sds(one_chip, (s, v), jnp.float32)
    a = _sds(one_chip, (v, v), jnp.float32)
    fn = getattr(ops, name)
    if masked:
        occ = _sds(one_chip, (v // tile, v // tile), jnp.int32)
        lowered = jax.jit(lambda x, a, m: fn(x, a, amask=m)).lower(x, a, occ)
    else:
        lowered = jax.jit(fn).lower(x, a)
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_served_bfs_rung_fits_one_chip(one_chip, for_tpu):
    """The async front end's full BFS rung at Graph500 scale-20 shapes
    (the smoke's size and lane count) fits one chip's HBM."""
    vcap = 1 << 20
    ecap = int(16 * vcap * 1.5)
    state = GraphState(
        alive=_sds(one_chip, (vcap,), jnp.bool_),
        ecnt=_sds(one_chip, (vcap,), jnp.int32),
        esrc=_sds(one_chip, (ecap,), jnp.int32),
        edst=_sds(one_chip, (ecap,), jnp.int32),
        ew=_sds(one_chip, (ecap,), jnp.float32),
        version=_sds(one_chip, (), jnp.int32))
    srcs = _sds(one_chip, (load_chip_smoke().LANES,), jnp.int32)
    mem = batch._VFULL["bfs"].lower(state, srcs).compile().memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert need < V5E_HBM_BYTES, mem


def _state_sds(one_chip, vcap, ecap):
    return GraphState(
        alive=_sds(one_chip, (vcap,), jnp.bool_),
        ecnt=_sds(one_chip, (vcap,), jnp.int32),
        esrc=_sds(one_chip, (ecap,), jnp.int32),
        edst=_sds(one_chip, (ecap,), jnp.int32),
        ew=_sds(one_chip, (ecap,), jnp.float32),
        version=_sds(one_chip, (), jnp.int32))


def _nested_ops(op):
    for region in op.regions:
        for block in region.blocks:
            for inner in block.operations:
                yield inner
                yield from _nested_ops(inner)


def _index_sizes(lowered):
    """``(op, largest dimension)`` of the indices of every gather and of
    the indices and updates of every scatter in the lowered program."""
    from jax._src.lib.mlir import ir

    out = []
    for op in _nested_ops(lowered.compiler_ir("stablehlo").operation):
        name = op.operation.name
        if name == "stablehlo.gather":
            operands = [op.operands[1]]
        elif name == "stablehlo.scatter":
            operands = list(op.operands)[1:]   # one input: indices, updates
        else:
            continue
        out.append((name, max(max(ir.RankedTensorType(v.type).shape, default=1)
                              for v in operands)))
    return out


def _commit_at_paper_rmat_widths(one_chip):
    """``apply_batch`` lowered at paper-rmat's widths: vcap 2**20, ecap
    15,728,640, 32 ops."""
    from repro.core.updates import OpBatch, apply_batch

    ops = OpBatch(*(_sds(one_chip, (32,), dt) for dt in (
        jnp.int32, jnp.int32, jnp.int32, jnp.float32)))
    return apply_batch.lower(_state_sds(one_chip, 1 << 20, 15_728_640), ops)


def test_commit_scatters_and_gathers_no_edge_slots(one_chip, for_tpu):
    """The commit lowers with no gather whose indices and no scatter whose
    indices or updates span the edge table: its merge and invalidation are
    streaming passes, and what is scattered or gathered is bounded by the
    batch or the invalidation loop's chunk."""
    from repro.core.updates import _KILL_CHUNK

    sizes = _index_sizes(_commit_at_paper_rmat_widths(one_chip))
    assert {"stablehlo.gather", "stablehlo.scatter"} <= {n for n, _ in sizes}
    assert max(size for _, size in sizes) <= max(32, _KILL_CHUNK), sizes


def test_commit_compiles_for_v5e(one_chip, for_tpu):
    """The commit's shift merge lowers to a Mosaic kernel, and its
    temporaries stay under two edge-table arrays (the scatter merge it
    replaced took 129.5 MB here)."""
    compiled = _commit_at_paper_rmat_widths(one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 4 * 15_728_640


#: ``g500-served``'s widths (scale 15) and a dispatch of four lanes.
G500_VCAP, G500_ECAP, G500_LANES = 32_768, 1_572_864, 4


def _rung_lowered(one_chip, kind, rung):
    """``batch._VFULL[kind]`` or ``_VDELTA[kind]`` lowered at g500-served's
    widths for four stacked lanes."""
    from repro.core.queries import BCResult, BFSResult, SSSPResult

    lanes, vcap = G500_LANES, G500_VCAP
    state = _state_sds(one_chip, vcap, G500_ECAP)
    srcs = _sds(one_chip, (lanes,), jnp.int32)
    if rung == "full":
        return batch._VFULL[kind].lower(state, srcs)
    row = {dt: _sds(one_chip, (lanes, vcap), dt)
           for dt in (jnp.bool_, jnp.int32, jnp.float32)}
    one = _sds(one_chip, (lanes,), jnp.bool_)
    prior, extra = {
        "bfs": (BFSResult(one, row[jnp.bool_], row[jnp.int32],
                          row[jnp.int32]), row[jnp.bool_]),
        "sssp": (SSSPResult(one, one, row[jnp.float32], row[jnp.int32]),
                 row[jnp.bool_]),
        "bc": (BCResult(one, row[jnp.float32], row[jnp.float32],
                        row[jnp.int32]), _sds(one_chip, (lanes,), jnp.int32)),
    }[kind]
    return batch._VDELTA[kind].lower(state, prior, extra, srcs)


def _edge_sorts(lowered, span):
    """For each sort in the lowered program with an operand of ``span`` or
    more elements: whether it sits inside a loop (runs once per lane)."""
    from jax._src.lib.mlir import ir

    def walk(op, in_loop):
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    name = inner.operation.name
                    if name == "stablehlo.sort" and any(
                            max(ir.RankedTensorType(v.type).shape, default=1)
                            >= span for v in inner.operands):
                        yield in_loop
                    yield from walk(inner, in_loop or name == "stablehlo.while")

    return list(walk(lowered.compiler_ir("stablehlo").operation, False))


@pytest.mark.parametrize("rung", ["full", "delta"])
@pytest.mark.parametrize("kind", ["bfs", "sssp", "bc"])
def test_rung_scatters_no_edge_slots_and_sorts_once(one_chip, for_tpu, kind,
                                                    rung):
    """Every local COO rung program, at g500-served's widths: no scatter's
    indices or updates span the edge table (each level reduces over a
    sorted order with segmented scans), and the program holds exactly one
    edge-long sort, outside the lane loop, shared by its lanes."""
    lowered = _rung_lowered(one_chip, kind, rung)
    scatters = [size for name, size in _index_sizes(lowered)
                if name == "stablehlo.scatter"]
    assert max(scatters, default=0) < G500_ECAP, scatters
    assert _edge_sorts(lowered, G500_ECAP) == [False]
