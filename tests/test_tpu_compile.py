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
