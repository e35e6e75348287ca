"""jit'd public wrappers around the Pallas kernels (padding + dispatch).

On a TPU these run compiled; on every other backend they execute the
kernel bodies in interpret mode (``backend.interpret_mode``), validated
against ``ref.py`` in ``tests/test_kernels.py``.

Each semiring wrapper optionally takes ``amask``, the tile-occupancy grid of
the right-hand (adjacency/weight) operand at ``tile`` granularity — see
``repro.core.tiles`` — and dispatches to the tile-skipping kernel variant:
the wrapper coarsens ``amask`` to the kernel's (bk, bn) block grid, derives
the left operand's slab-occupancy mask from the operand itself (frontier
slabs go all-identity as BFS/SSSP/BC levels saturate), and the kernel skips
every (slab, tile) pair whose contribution is the semiring identity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import bool_mm as _bool
from . import count_mm as _count
from . import minplus_mm as _minplus
from .backend import check_amask


def _pad2(x, bm, bn, value=0.0):
    m, n = x.shape
    mp, np_ = -(-m // bm) * bm, -(-n // bn) * bn
    return jnp.pad(x, ((0, mp - m), (0, np_ - n)), constant_values=value), (m, n)


def _block_ranges(nblocks: int, blk: int, tile: int, ntiles: int):
    """Static (first, last) tile index covered by each kernel block."""
    t0 = (np.arange(nblocks) * blk) // tile
    t1 = ((np.arange(nblocks) + 1) * blk - 1) // tile
    return (np.clip(t0, 0, ntiles - 1).astype(np.int32),
            np.clip(t1, 0, ntiles - 1).astype(np.int32))


def _coarsen_mask(occ: jax.Array, tile: int, blk_r: int, nbr: int,
                  blk_c: int, nbc: int) -> jax.Array:
    """Tile-granularity occupancy -> kernel-block granularity (any-reduce).

    Works for any (tile, block) size relation via prefix sums over the tile
    grid gathered at statically computed block->tile ranges.  Blocks that
    extend past the tile grid (operand padding) clip to the last tile — at
    worst an identity block is marked active, never the reverse.
    """
    occ_b = (occ > 0).astype(jnp.int32)
    nt_r, nt_c = occ_b.shape
    r0, r1 = _block_ranges(nbr, blk_r, tile, nt_r)
    cum_r = jnp.concatenate(
        [jnp.zeros((1, nt_c), jnp.int32), jnp.cumsum(occ_b, axis=0)], axis=0)
    rows = ((cum_r[r1 + 1] - cum_r[r0]) > 0).astype(jnp.int32)  # [nbr, nt_c]
    c0, c1 = _block_ranges(nbc, blk_c, tile, nt_c)
    cum_c = jnp.concatenate(
        [jnp.zeros((nbr, 1), jnp.int32), jnp.cumsum(rows, axis=1)], axis=1)
    return ((cum_c[:, c1 + 1] - cum_c[:, c0]) > 0).astype(jnp.int32)


def _slab_mask(xp: jax.Array, bm: int, bk: int, nonidentity) -> jax.Array:
    """Blockwise any(non-identity) over a padded left operand."""
    mp, kp = xp.shape
    return nonidentity(xp).reshape(
        mp // bm, bm, kp // bk, bk).any(axis=(1, 3)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "tile"))
def bool_mm(f: jax.Array, a: jax.Array, bm: int = 128, bn: int = 128,
            bk: int = 512, amask: jax.Array | None = None,
            tile: int = 128) -> jax.Array:
    """Padded boolean-semiring matmul; any (S, V) x (V, V') shapes.

    ``amask``: optional tile-occupancy grid of ``a`` (nonzero iff the
    ``tile`` x ``tile`` block holds any set bit) enabling tile skipping.
    """
    fp, (s, _) = _pad2(f.astype(jnp.float32), bm, bk)
    ap, (_, n) = _pad2(a.astype(jnp.float32), bk, bn)
    if amask is None:
        out = _bool.bool_mm(fp, ap, bm=bm, bn=bn, bk=bk)
    else:
        check_amask("bool_mm", amask.shape, a.shape[0], a.shape[1], tile)
        nbk, nbn = fp.shape[1] // bk, ap.shape[1] // bn
        fmask = _slab_mask(fp, bm, bk, lambda x: x != 0)
        am = _coarsen_mask(amask, tile, bk, nbk, bn, nbn)
        out = _bool.bool_mm_masked(fp, ap, fmask, am, bm=bm, bn=bn, bk=bk)
    return out[:s, :n]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "tile"))
def minplus_mm(d: jax.Array, w: jax.Array, bm: int = 128, bn: int = 128,
               bk: int = 128, amask: jax.Array | None = None,
               tile: int = 128) -> jax.Array:
    """Padded tropical matmul; +inf padding is the semiring identity.

    ``amask``: optional tile-occupancy grid of ``w`` (nonzero iff the
    ``tile`` x ``tile`` block holds any finite weight).
    """
    dp, (s, _) = _pad2(d, bm, bk, value=jnp.inf)
    wp, (_, n) = _pad2(w, bk, bn, value=jnp.inf)
    if amask is None:
        out = _minplus.minplus_mm(dp, wp, bm=bm, bn=bn, bk=bk)
    else:
        check_amask("minplus_mm", amask.shape, w.shape[0], w.shape[1], tile)
        nbk, nbn = dp.shape[1] // bk, wp.shape[1] // bn
        dmask = _slab_mask(dp, bm, bk, jnp.isfinite)
        am = _coarsen_mask(amask, tile, bk, nbk, bn, nbn)
        out = _minplus.minplus_mm_masked(dp, wp, dmask, am, bm=bm, bn=bn,
                                         bk=bk)
    return out[:s, :n]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "tile"))
def count_mm(s: jax.Array, a: jax.Array, bm: int = 128, bn: int = 128,
             bk: int = 512, amask: jax.Array | None = None,
             tile: int = 128) -> jax.Array:
    """Padded counting matmul (Brandes sigma); zero padding is the identity.

    ``amask``: optional tile-occupancy grid of ``a``.
    """
    sp, (m, _) = _pad2(s.astype(jnp.float32), bm, bk)
    ap, (_, n) = _pad2(a.astype(jnp.float32), bk, bn)
    if amask is None:
        out = _count.count_mm(sp, ap, bm=bm, bn=bn, bk=bk)
    else:
        check_amask("count_mm", amask.shape, a.shape[0], a.shape[1], tile)
        nbk, nbn = sp.shape[1] // bk, ap.shape[1] // bn
        smask = _slab_mask(sp, bm, bk, lambda x: x != 0)
        am = _coarsen_mask(amask, tile, bk, nbk, bn, nbn)
        out = _count.count_mm_masked(sp, ap, smask, am, bm=bm, bn=bn, bk=bk)
    return out[:m, :n]

