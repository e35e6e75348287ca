"""Pallas TPU kernel: tropical (min,+) blocked matmul (SSSP relaxation).

out[s, j] = min_k d[s, k] + w[k, j].  No MXU analogue exists for (min,+), so
the inner product runs on the VPU.  The k block is lane-aligned (bk=128:
Mosaic requires a block's last dim to be a multiple of 128) and the kernel
walks it in ``SUB``-row sub-slabs, each a broadcast-add + min-reduce whose
(bm, SUB, bn) working set is 128*8*128*4B = 0.5 MB of VMEM.  Grid = (S/bm,
V/bn, V/bk), k innermost with output-tile accumulation (running elementwise
min) across the k sweep.

+inf entries (absent edges / unreached sources) flow through min() untouched,
so the tombstone encoding of the graph state needs no special-casing.

``minplus_mm_masked`` is the tile-skipping variant: two scalar-prefetched
occupancy grids — ``dmask[S/bm, K/bk]`` (frontier slab holds any finite
distance) and ``wmask[K/bk, N/bn]`` (weight tile holds any live edge) —
and a ``pl.when`` guard skips the broadcast-min for (slab, tile) pairs
whose product is all-+inf, i.e. the semiring identity.  Output-tile init
still runs at k == 0, so a fully skipped output tile is +inf, exactly what
the dense kernel computes for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import semiring_call

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 128
SUB = 8  # k rows per broadcast-min step inside a block


def _body(d_ref, w_ref, o_ref, live, nk: int):
    del nk
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, jnp.inf)

    @pl.when(live)
    def _compute():
        bk = d_ref.shape[1]
        sub = min(SUB, bk)
        acc = o_ref[...]
        for c in range(0, bk, sub):
            d = d_ref[:, c:c + sub]          # (bm, sub)
            w = w_ref[c:c + sub, :]          # (sub, bn)
            acc = jnp.minimum(acc, jnp.min(d[:, :, None] + w[None, :, :],
                                           axis=1))
        o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def minplus_mm(d: jax.Array, w: jax.Array, *, bm: int = DEFAULT_BM,
               bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
               interpret: bool | None = None) -> jax.Array:
    """d: [S, V] f32; w: [V, V'] f32 -> [S, V'] f32 (min-plus product)."""
    return semiring_call("minplus_mm", _body, d, w, None, None, bm=bm,
                         bn=bn, bk=bk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def minplus_mm_masked(d: jax.Array, w: jax.Array, dmask: jax.Array,
                      wmask: jax.Array, *, bm: int = DEFAULT_BM,
                      bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                      interpret: bool | None = None) -> jax.Array:
    """Tile-skipping min-plus product.

    ``dmask``: int32 [S/bm, K/bk] — nonzero iff the d slab has a finite
    entry; ``wmask``: int32 [K/bk, N/bn] — nonzero iff the w tile has a
    finite entry.  A zero mask MUST imply the block is all-+inf (the
    semiring identity); callers derive both from the tile occupancy index
    (``repro.core.tiles``) or directly from the operands.
    """
    return semiring_call("minplus_mm", _body, d, w, dmask, wmask, bm=bm,
                         bn=bn, bk=bk, interpret=interpret)
