"""Pallas TPU kernel: counting-semiring blocked matmul (Brandes sigma).

out[s, j] = sum_k s[s, k] * a[k, j] — a plain f32 matmul on the MXU, but over
shortest-path *counts* flowing along adjacency masks, which is the third
semiring the batched Brandes sweep needs (bool for levels, count for sigma
and the backward dependency accumulation).  Counts are integers carried in
f32: exact as long as they stay below 2^24, which holds for the graph sizes
this reproduction targets.

Grid = (S/bm, V/bn, V/bk), k innermost with VMEM accumulation, identical to
``bool_mm`` minus the threshold epilogue.  ``count_mm_masked`` skips the MXU
dot for (slab, tile) pairs whose occupancy masks say the contribution is
all-zero (the (+, x) semiring identity), driven by the same
scalar-prefetched occupancy grids as the other masked kernels.
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


def _body(s_ref, a_ref, o_ref, live, nk: int):
    del nk
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _compute():
        # Full f32 passes: sigma counts above 256 are not exact in a
        # single bf16 pass, and the counting semiring must be exact.
        o_ref[...] += jnp.dot(s_ref[...], a_ref[...],
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def count_mm(s: jax.Array, a: jax.Array, *, bm: int = DEFAULT_BM,
             bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
             interpret: bool | None = None) -> jax.Array:
    """s: [S, V] f32 counts; a: [V, V'] f32 -> [S, V'] f32 (plain matmul)."""
    return semiring_call("count_mm", _body, s, a, None, None, bm=bm, bn=bn,
                         bk=bk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def count_mm_masked(s: jax.Array, a: jax.Array, smask: jax.Array,
                    amask: jax.Array, *, bm: int = DEFAULT_BM,
                    bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                    interpret: bool | None = None) -> jax.Array:
    """Tile-skipping counting product.

    ``smask``: int32 [S/bm, K/bk] — nonzero iff the count slab has any
    nonzero entry; ``amask``: int32 [K/bk, N/bn] — nonzero iff the
    adjacency tile has any live edge.  A zero mask MUST imply an all-zero
    block.
    """
    return semiring_call("count_mm", _body, s, a, smask, amask, bm=bm,
                         bn=bn, bk=bk, interpret=interpret)
