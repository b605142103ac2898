"""Flash attention (online-softmax) Pallas TPU kernel.

Tiling: grid = (B*Hq, Sq/BQ, Skv/BK); the kv axis is innermost and
"arbitrary" (sequential) so the (BQ, D) f32 accumulator plus the (BQ,)
running max / sum live in VMEM scratch across kv steps. BQ = BK = 128
keeps both MXU matmuls (q·kᵀ and p·v) on 128-aligned shapes.

GQA is handled in the k/v index_map (query head h reads kv head
h // rep), so grouped K/V are never materialized per-query-head.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  causal: bool, sm_scale: float, n_kv_blocks: int,
                  bq: int, bk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * sm_scale  # (BQ, D)
    k = k_ref[0].astype(jnp.float32)  # (BK, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (BQ, BK)

    if causal:
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    m_ref[...] = m_cur
    v = v_ref[0].astype(jnp.float32)  # (BK, D)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(p, v)

    @pl.when(ki == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = False, bq: int = DEFAULT_BQ,
                    bk: int = DEFAULT_BK, interpret: bool = False):
    """q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D). Returns (B, Sq, Hq, D).

    Requires Sq % bq == 0 and Skv % bk == 0 (wrappers pad otherwise).
    """
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    rep = hq // hkv
    qr = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)
    n_q, n_kv = sq // bq, skv // bk
    grid = (b * hq, n_q, n_kv)

    kernel = functools.partial(
        _flash_kernel, causal=causal, sm_scale=1.0 / math.sqrt(d),
        n_kv_blocks=n_kv, bq=bq, bk=bk,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh // rep, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)
