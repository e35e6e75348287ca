"""Backend detection, shared guards and the shared Pallas call of the
semiring kernel modules.

Lives in its own module (rather than ``ops.py``) so the raw kernel modules
can default to the detected mode without importing ``ops`` back — ``ops``
imports the kernel modules, and a reverse import would be a cycle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def interpret_mode() -> bool:
    """Whether Pallas kernels run their body in interpret mode.

    Pallas kernels compile only on TPU; on every other backend they run
    the kernel body in interpret mode, which is what the oracle tests
    validate against.  Asked at trace time, never at import: importing
    the package must not start a JAX backend (on a TPU host that would
    claim the chip for a process that only imported the code).
    """
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """A raw kernel's ``interpret`` argument: ``None`` means the backend's
    default (:func:`interpret_mode`)."""
    return interpret_mode() if interpret is None else interpret


def check_blocks(name: str, s: int, kdim: int, n: int,
                 bm: int, bk: int, bn: int) -> None:
    """Refuse shapes the kernel grid would silently truncate.

    ``grid = (s // bm, n // bn, kdim // bk)`` drops trailing rows/columns
    when a dimension is not a block multiple; every raw kernel entry point
    calls this so a direct call can't return wrong-shaped results (the
    ``ops`` wrappers pad first and never trip it).
    """
    if s % bm or kdim % bk or n % bn:
        raise ValueError(
            f"{name}: shapes ({s}, {kdim}) x ({kdim}, {n}) are not "
            f"multiples of blocks (bm={bm}, bk={bk}, bn={bn}); grid "
            "truncation would drop trailing rows/columns — pad the operands "
            f"(repro.kernels.ops.{name} does) or pass dividing blocks")


def check_amask(name: str, amask_shape, kdim: int, n: int, tile: int) -> None:
    """The tile-occupancy grid must tile the right operand exactly.

    A mismatched grid (e.g. a ``TileView`` built at a different ``tile``)
    would be silently clipped by the block-mask coarsening and skip live
    slabs; shared by the ``ops`` wrappers and the jnp fallbacks in
    ``repro.core.semiring`` so both paths raise identically.
    """
    expect = (-(-kdim // tile), -(-n // tile))
    if tuple(amask_shape) != expect:
        raise ValueError(
            f"{name}: amask shape {tuple(amask_shape)} does not tile the "
            f"({kdim}, {n}) operand at tile={tile} (expected {expect}); "
            "was the tile view built with a different tile size?")


def semiring_call(name: str, body, x: jax.Array, y: jax.Array,
                  xmask: jax.Array | None, ymask: jax.Array | None, *,
                  bm: int, bn: int, bk: int,
                  interpret: bool | None) -> jax.Array:
    """The blocked ``(S, K) x (K, N) -> (S, N)`` f32 Pallas call shared by
    the three semiring kernels.

    Grid = (S/bm, N/bn, K/bk) with k innermost, so the output tile stays
    in VMEM across the k sweep.  ``body(x_ref, y_ref, o_ref, live, nk)``
    is the per-step kernel: ``live`` is ``True`` for the dense product,
    or, with ``xmask`` ([S/bm, K/bk]) and ``ymask`` ([K/bk, N/bn]), the
    traced predicate "both blocks hold a non-identity entry" read from
    the scalar-prefetched occupancy grids.  The grids ride in SMEM as
    flat int32 vectors: a ``(1, 1)`` SMEM block per step breaks Mosaic's
    (8, 128) block-shape rule, and a 2-D SMEM array pads its last dim.
    A zero mask MUST imply an all-identity block.
    """
    s, kdim = x.shape
    _, n = y.shape
    bm, bn, bk = min(bm, s), min(bn, n), min(bk, kdim)
    check_blocks(name, s, kdim, n, bm, bk, bn)
    grid = (s // bm, n // bn, kdim // bk)
    nk, nn = grid[2], grid[1]
    masked = xmask is not None
    if masked and (xmask.shape != (grid[0], nk) or ymask.shape != (nk, nn)):
        raise ValueError(
            f"{name}_masked: mask shapes {xmask.shape}/{ymask.shape} do not "
            f"match the block grid ({grid[0]}, {nk})/({nk}, {nn})")

    def kernel(*refs):
        if masked:
            xm_ref, ym_ref, x_ref, y_ref, o_ref = refs
            i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
            live = (xm_ref[i * nk + k] > 0) & (ym_ref[k * nn + j] > 0)
        else:
            x_ref, y_ref, o_ref = refs
            live = True
        body(x_ref, y_ref, o_ref, live, nk)

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 if masked else 0,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, k, *_: (i, k)),
                  pl.BlockSpec((bk, bn), lambda i, j, k, *_: (k, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, *_: (i, j)))
    masks = ((xmask.reshape(-1).astype(jnp.int32),
              ymask.reshape(-1).astype(jnp.int32)) if masked else ())
    return pl.pallas_call(
        kernel, grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((s, n), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(*masks, x, y)
