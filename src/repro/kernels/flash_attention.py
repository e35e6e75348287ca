"""Pallas TPU kernel: causal GQA flash attention with online softmax.

Grid = (B*Hq, Sq/bq, Skv/bk), kv innermost.  Running max / denominator /
accumulator live in VMEM scratch and persist across the kv sweep (TPU grids
iterate sequentially, so scratch carries state between k steps of the same
(bh, q) tile).  Fully-masked kv blocks are skipped with ``pl.when`` -- for
causal training this halves the work; with a sliding window only
O(window/bk) blocks per query tile execute at all.

GQA is handled in the index map: query head h reads kv head h // group, so
no materialized ``repeat`` of K/V ever exists (the repeat in the oracle is
exactly the HBM traffic this kernel removes).

VMEM per step: q (bq,d) + k,v (bk,d each) + acc (bq,d) + p (bq,bk)
~= (3*128*128 + 2*128*128)*4B ~= 0.3 MB at the default 128 blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret


DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = float("-inf")


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            nk: int, bq: int, bk: int, scale: float, offs: int,
            q_len: int, kv_len: int, window: int | None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    q_start = qi * bq
    k_start = ki * bk

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Skip kv blocks entirely above the causal diagonal (or outside the
    # sliding window): no compute, no VMEM traffic beyond the prefetch.
    relevant = k_start <= q_start + bq - 1 + offs
    if window is not None:
        relevant &= k_start + bk - 1 >= q_start + offs - (window - 1)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (bq, bk)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = (kpos <= qpos + offs) & (kpos < kv_len) & (qpos < q_len)
        if window is not None:
            mask &= kpos > qpos + offs - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                  # (bq, 1)
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_cur))
        p = jnp.where(m_cur == NEG_INF, 0.0, jnp.exp(s - m_cur))
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_cur

    @pl.when(ki == nk - 1)
    def _final():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sm_scale", "window", "bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, sm_scale: float | None = None,
                    window: int | None = None,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool | None = None) -> jax.Array:
    """q: [B, Hq, Sq, D]; k, v: [B, Hkv, Skv, D] -> [B, Hq, Sq, D].

    Causal alignment matches the oracle: query i sees kv j iff
    j <= i + (Skv - Sq).  ``window`` enables sliding-window (local) masking.
    """
    b, hq, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = sm_scale if sm_scale is not None else float(d) ** -0.5
    offs = skv - sq

    bq_ = min(bq, _round_up(sq, 8))
    bk_ = min(bk, _round_up(skv, 8))
    sq_p, skv_p = _round_up(sq, bq_), _round_up(skv, bk_)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    qf = qp.reshape(b * hq, sq_p, d)
    kf = kp.reshape(b * hkv, skv_p, d)
    vf = vp.reshape(b * hkv, skv_p, d)

    nq, nk = sq_p // bq_, skv_p // bk_
    if not causal:
        offs_eff = skv_p  # everything visible
    else:
        offs_eff = offs

    def kv_index(bh, qi, ki):
        return ((bh // hq) * hkv + (bh % hq) // group, ki, 0)

    out = pl.pallas_call(
        functools.partial(
            _kernel, nk=nk, bq=bq_, bk=bk_, scale=scale, offs=offs_eff,
            q_len=sq, kv_len=skv, window=window),
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq_, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk_, d), kv_index),
            pl.BlockSpec((1, bk_, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq_, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)
    return out.reshape(b, hq, sq_p, d)[:, :, :sq, :]


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m
