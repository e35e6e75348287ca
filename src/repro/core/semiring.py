"""Semiring matmuls: the dense/MXU formulation of graph traversal.

  * bool semiring  (or, and)        -> BFS frontier expansion
  * tropical       (min, +)         -> SSSP relaxation
  * counting       (+, x) on masks  -> sigma path counting (Brandes)

``*_mm(..., use_kernel=True)`` dispatches to the Pallas TPU kernels in
``repro.kernels`` (validated in interpret mode on CPU); the default path is
pure jnp and serves as the oracle.

Each product optionally takes ``amask``, the right operand's tile-occupancy
grid (``repro.core.tiles``: nonzero iff the ``tile x tile`` block holds any
non-identity entry).  The kernel path skips per (slab, tile) block inside
the Pallas grid; the jnp fallback mirrors the skipping at k-slab granularity
— a ``lax.cond`` per k tile row elides slabs whose adjacency row is entirely
empty or whose frontier slab is all-identity.  Both produce results
identical to the unmasked dense sweep.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_BLOCK = 128  # MXU-aligned logical tile for the blocked jnp fallbacks


def _pad_axis(x, axis, mult, value):
    size = x.shape[axis]
    pad = -(-size // mult) * mult - size
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _check_amask(amask: jax.Array, kdim: int, n: int, tile: int, name: str):
    """Shared occupancy-grid validation (see ``kernels.backend``): both the
    fallbacks here and the ``ops`` wrappers raise identically on a grid
    that does not tile the operand."""
    from repro.kernels.backend import check_amask
    check_amask(name, amask.shape, kdim, n, tile)


def _krow_active(amask: jax.Array) -> jax.Array:
    """bool[nbk]: k tile row holds any active tile."""
    return (amask > 0).any(axis=1)


def bool_mm(f: jax.Array, a: jax.Array, use_kernel: bool = False,
            amask: jax.Array | None = None, tile: int = _BLOCK) -> jax.Array:
    """(S,V) x (V,V) boolean-semiring product, as f32 {0,1} masks."""
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.bool_mm(f, a, amask=amask, tile=tile)
    if amask is None:
        return (jnp.dot(f, a, precision=jax.lax.Precision.HIGHEST) > 0
                ).astype(jnp.float32)
    acc = _masked_count_accum(f.astype(jnp.float32), a, amask, tile,
                              "bool_mm")
    return (acc > 0).astype(jnp.float32)


def _masked_count_accum(fp_in: jax.Array, ap_in: jax.Array, amask: jax.Array,
                        tile: int, name: str) -> jax.Array:
    """Shared k-slab-skipping sum-of-dots: the masked fallback body of both
    ``bool_mm`` (which thresholds the result) and ``count_mm``.

    ``ap_in`` may be any dtype that holds its values exactly (the sharded
    ring passes int8 {0,1} bands); each k slab is widened to f32 only as
    it is used, so no f32 copy of the whole operand is ever made."""
    _check_amask(amask, ap_in.shape[0], ap_in.shape[1], tile, name)
    fp = _pad_axis(fp_in, 1, tile, 0.0)
    ap = _pad_axis(ap_in, 0, tile, 0.0)
    nbk = fp.shape[1] // tile
    krow = _krow_active(amask)

    def body(i, acc):
        fk = lax.dynamic_slice_in_dim(fp, i * tile, tile, axis=1)
        ak = lax.dynamic_slice_in_dim(ap, i * tile, tile, axis=0)
        return lax.cond(
            krow[i] & (fk != 0).any(),
            lambda acc: acc + jnp.dot(fk, ak.astype(jnp.float32),
                                      precision=jax.lax.Precision.HIGHEST),
            lambda acc: acc, acc)

    return lax.fori_loop(0, nbk, body,
                         jnp.zeros((fp_in.shape[0], ap_in.shape[1]),
                                   jnp.float32))


def minplus_mm(d: jax.Array, w: jax.Array, use_kernel: bool = False,
               amask: jax.Array | None = None, tile: int = _BLOCK) -> jax.Array:
    """(S,V) x (V,V) tropical product: out[s,j] = min_k d[s,k] + w[k,j]."""
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.minplus_mm(d, w, amask=amask, tile=tile)
    # Blocked over k to bound the (S, K, V) broadcast working set.
    if amask is not None:
        _check_amask(amask, w.shape[0], w.shape[1], tile, "minplus_mm")
    blk = min(tile, w.shape[0])
    dp = _pad_axis(d, 1, blk, jnp.inf)
    wp = _pad_axis(w, 0, blk, jnp.inf)
    nbk = dp.shape[1] // blk
    krow = None if amask is None else _krow_active(amask)

    def compute(i, acc):
        dk = lax.dynamic_slice_in_dim(dp, i * blk, blk, axis=1)
        wk = lax.dynamic_slice_in_dim(wp, i * blk, blk, axis=0)
        cand = jnp.min(dk[:, :, None] + wk[None, :, :], axis=1)
        return jnp.minimum(acc, cand)

    if krow is None:
        body = compute
    else:
        def body(i, acc):
            dk = lax.dynamic_slice_in_dim(dp, i * blk, blk, axis=1)
            return lax.cond(krow[i] & jnp.isfinite(dk).any(),
                            lambda acc: compute(i, acc),
                            lambda acc: acc, acc)

    init = jnp.full((d.shape[0], w.shape[1]), jnp.inf, d.dtype)
    return lax.fori_loop(0, nbk, body, init)


def count_mm(s: jax.Array, a: jax.Array, use_kernel: bool = False,
             amask: jax.Array | None = None, tile: int = _BLOCK) -> jax.Array:
    """(S,V) x (V,V) counting product (plain matmul on path counts)."""
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.count_mm(s, a, amask=amask, tile=tile)
    if amask is None:
        return jnp.dot(s, a, precision=jax.lax.Precision.HIGHEST)
    return _masked_count_accum(s.astype(jnp.float32), a, amask, tile,
                               "count_mm")
