"""Pallas TPU kernels for the perf-critical compute layers.

  * ``bool_mm``      -- boolean-semiring matmul (batched BFS, MXU)
  * ``minplus_mm``   -- tropical matmul (batched SSSP relax, VPU)
  * ``count_mm``     -- counting matmul (batched Brandes sigma, MXU)
  * ``shift_merge``  -- the commit's merge of a batch into the sorted edge
                        table, one streaming pass (``core/updates.py``)

Each semiring kernel also has a ``*_mm_masked`` tile-skipping variant driven
by SMEM occupancy grids (see ``repro.core.tiles``).  Each kernel:
``<name>.py`` (pl.pallas_call + BlockSpec), validated against the pure-jnp
oracle in ``ref.py``; ``ops.py`` holds the jit'd padding wrappers.
"""
from . import ops, ref  # noqa: F401
