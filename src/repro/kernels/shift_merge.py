"""The commit's shift merge as one streaming Pallas pass.

``spread(cols, pos)`` moves slot ``j`` of each 32-bit array in ``cols`` to
``j + #{i: pos[i] <= j}``.  ``pos`` holds the sorted insertion points of a
batch's appends into the sorted edge table, so the shift is non-decreasing
and at most ``B = len(pos)``, and the arrays come out with a hole at each
append's sorted slot ``pos[i] + i`` for the caller to fill.

The table is viewed as rows of 128 lanes.  Each grid step reads one block
of rows plus the rows just before it (the halo, at least ``B`` slots),
counts each slot's shift against ``pos`` (scalar-prefetched), and moves the
elements with a barrel shift, one stage per bit of the shift, highest bit
first: an element moves ``2**m`` slots when bit ``m`` of its shift is set
and carries its shift along.  Taking the high bits first keeps any two
elements from meeting, so each stage is a select between the tile and a
rotated copy of it.  A slot an element leaves keeps a stale copy with its
shift cleared, so it never moves again.  Every element that lands in a
block starts in that block or its halo, so a block is written from what it
read: one pass over the table, with no gather or scatter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend

LANES = 128
#: rows of 128 slots a grid step writes (at least the halo's)
BLOCK_ROWS = 256


def _forward(x, d: int, lane):
    """``x`` (rows x 128, row-major slots) moved ``d`` slots towards the
    end; what enters at the front is wrapped from the end (never read)."""
    q, r = divmod(d, LANES)
    if r:
        x = pltpu.roll(x, r, 1)
    same = pltpu.roll(x, q, 0) if q else x
    if not r:
        return same
    return jnp.where(lane >= r, same, pltpu.roll(x, q + 1, 0))


def _kernel(pos_ref, *refs, rows: int, halo: int, n_pos: int):
    ins, outs = refs[:2 * len(refs) // 3], refs[2 * len(refs) // 3:]
    tiles = [jnp.concatenate([ins[k][...], ins[k + 1][...]], axis=0)
             for k in range(0, len(ins), 2)]
    shape = tiles[0].shape
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)
    flat = lax.broadcasted_iota(jnp.int32, shape, 0) * LANES + lane
    slot = pl.program_id(0) * (rows * LANES) - halo * LANES + flat
    shift = lax.fori_loop(
        0, n_pos, lambda i, s: s + (slot >= pos_ref[i]).astype(jnp.int32),
        jnp.zeros(shape, jnp.int32), unroll=n_pos <= 64)
    for m in reversed(range(n_pos.bit_length())):
        d = 1 << m
        go = (shift >> m) & 1
        come = (_forward(go, d, lane) == 1) & (flat >= d)
        tiles = [jnp.where(come, _forward(t, d, lane), t) for t in tiles]
        shift = jnp.where(come, _forward(shift, d, lane),
                          jnp.where(go == 1, 0, shift))
    for out, t in zip(outs, tiles):
        out[...] = t[halo:]


def spread(cols, pos):
    """``cols`` (equal-length 32-bit arrays) with slot ``j`` moved to
    ``j + #{i: pos[i] <= j}``; elements moved past the end are dropped and
    the holes hold stale values.  ``pos``: int32, sorted."""
    n, n_pos = cols[0].shape[0], pos.shape[0]
    # halo rows: a power of two (so it divides the block) covering B slots
    halo = max(8, 1 << max(0, -(-n_pos // LANES) - 1).bit_length())
    unit = halo * LANES
    padded = -(-n // unit) * unit
    rows = min(max(BLOCK_ROWS, halo), padded // LANES)
    tiles = [(jnp.pad(c, (0, padded - n)) if padded > n else c).reshape(
        -1, LANES) for c in cols]
    body = pl.BlockSpec((rows, LANES), lambda i, p: (i, 0))
    before = pl.BlockSpec(
        (halo, LANES), lambda i, p: (jnp.maximum(i * (rows // halo) - 1, 0), 0))
    out = pl.pallas_call(
        functools.partial(_kernel, rows=rows, halo=halo, n_pos=n_pos),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(pl.cdiv(padded // LANES, rows),),
            in_specs=[before, body] * len(cols), out_specs=[body] * len(cols)),
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tiles],
        interpret=backend.interpret_mode(),
        name="shift_merge",
    )(pos, *[t for t in tiles for _ in range(2)])
    return [o.reshape(-1)[:n] for o in out]
