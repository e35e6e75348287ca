"""Pallas TPU kernel: boolean-semiring blocked matmul (BFS frontier expansion).

out[s, j] = OR_k f[s, k] AND a[k, j], computed as an f32 {0,1} mask matmul on
the MXU with a threshold epilogue.  Grid = (S/bm, V/bn, V/bk) with k innermost
so the output tile accumulates in VMEM across the k sweep (revisiting).

Block sizes are MXU-aligned (128x128 tiles, bk=512 to amortize the epilogue);
VMEM working set per step = bm*bk + bk*bn + bm*bn floats ~= (128*512*2 +
128*128)*4B ~= 0.6 MB, far under the ~16 MB/core budget, leaving room for
double buffering of the HBM->VMEM pipeline.

``bool_mm_masked`` adds scalar-prefetched occupancy grids (frontier slab
nonzero, weight tile holds a live edge) and skips the MXU dot where either
is empty — a skipped contribution is all-zero, the (or, and) semiring
identity, so the accumulator is untouched.  Init (k == 0) and the
threshold epilogue (k == nk-1) stay unconditional.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import semiring_call

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 512


def _body(f_ref, a_ref, o_ref, live, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _compute():
        o_ref[...] += jnp.dot(f_ref[...], a_ref[...],
                              preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        o_ref[...] = (o_ref[...] > 0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def bool_mm(f: jax.Array, a: jax.Array, *, bm: int = DEFAULT_BM,
            bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
            interpret: bool | None = None) -> jax.Array:
    """f: [S, V] {0,1} f32; a: [V, V'] {0,1} f32 -> [S, V'] {0,1} f32.

    Shapes must be multiples of the block sizes (``ops.bool_mm`` pads).
    """
    return semiring_call("bool_mm", _body, f, a, None, None, bm=bm, bn=bn,
                         bk=bk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def bool_mm_masked(f: jax.Array, a: jax.Array, fmask: jax.Array,
                   amask: jax.Array, *, bm: int = DEFAULT_BM,
                   bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                   interpret: bool | None = None) -> jax.Array:
    """Tile-skipping boolean-semiring product.

    ``fmask``: int32 [S/bm, K/bk] — nonzero iff the frontier slab has any
    set bit; ``amask``: int32 [K/bk, N/bn] — nonzero iff the adjacency tile
    has any live edge.  A zero mask MUST imply an all-zero block.
    """
    return semiring_call("bool_mm", _body, f, a, fmask, amask, bm=bm, bn=bn,
                         bk=bk, interpret=interpret)
