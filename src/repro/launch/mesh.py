"""Production mesh construction, and the graph engine's 1-D view of it.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 chips per pod; the multi-pod mesh adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_graph_mesh(mesh: Optional[Mesh] = None) -> Mesh:
    """The 1-D logical ``graph`` axis the sharded tile-grid engine
    (``repro.shard``) partitions over: every device of ``mesh`` flattened
    (all local devices when ``None``).  The graph engine always sees one
    axis regardless of the production mesh's (pod, data, model) shape."""
    from repro.shard.tile_shard import as_graph_mesh
    return as_graph_mesh(mesh)

