"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def bool_mm_ref(f: jax.Array, a: jax.Array) -> jax.Array:
    """Boolean-semiring matmul on {0,1} f32 masks: out = (f @ a) > 0."""
    return (jnp.dot(f.astype(jnp.float32), a.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST) > 0).astype(jnp.float32)


def minplus_mm_ref(d: jax.Array, w: jax.Array) -> jax.Array:
    """Tropical matmul: out[s, j] = min_k d[s, k] + w[k, j]."""
    return jnp.min(d[:, :, None] + w[None, :, :], axis=1)


def count_mm_ref(s: jax.Array, a: jax.Array) -> jax.Array:
    """Counting matmul (Brandes sigma): plain f32 product of path counts."""
    return jnp.dot(s.astype(jnp.float32), a.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)

