"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

These are also the XLA fallback implementations used on CPU (and in the
multi-pod dry-run, which lowers on the CPU backend).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention(q, k, v, *, causal: bool = False, q_offset=0,
              kv_len: Optional[jnp.ndarray] = None):
    """Multi-head (GQA-aware) attention oracle.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D), Hq % Hkv == 0.
    kv_len: (B,) valid cache lengths (masks the tail), for decode.

    Mixed precision: K/V stay in their storage dtype (the matmuls
    accumulate in f32 via preferred_element_type) — materializing f32
    casts of a 32k-long KV cache costs terabytes of HBM traffic.
    """
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    rep = hq // hkv
    qf = (q.astype(jnp.float32) / math.sqrt(d)).astype(q.dtype)
    qf = qf.reshape(b, sq, hkv, rep, d)
    logits = jnp.einsum("bqhrd,bkhd->bhrqk", qf, k,
                        preferred_element_type=jnp.float32)
    if causal:
        qpos = jnp.arange(sq) + q_offset
        kpos = jnp.arange(skv)
        mask = kpos[None, :] <= qpos[:, None]
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    if kv_len is not None:
        valid = jnp.arange(skv)[None, :] < kv_len[:, None]
        logits = jnp.where(valid[:, None, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def tile_moments(tiles):
    """Color moments featurizer (paper §III-C): per-tile, per-channel
    mean / stddev / skewness. tiles: (N, H, W, C) -> (N, 3*C) float32."""
    x = tiles.astype(jnp.float32)
    mu = jnp.mean(x, axis=(1, 2))  # (N, C)
    var = jnp.mean(jnp.square(x - mu[:, None, None, :]), axis=(1, 2))
    sd = jnp.sqrt(var + 1e-12)
    m3 = jnp.mean((x - mu[:, None, None, :]) ** 3, axis=(1, 2))
    skew = jnp.cbrt(m3)
    return jnp.concatenate([mu, sd, skew], axis=-1)


def kmeans_assign(x, centroids):
    """x: (N, D), centroids: (K, D) -> (assign (N,) int32, sqdist (N,) f32).

    The cross term is a float32 matmul (``HIGHEST``), as in the Pallas
    kernel: at the TPU's default matmul precision its inputs would round
    to bfloat16, and nearest-centroid assignments could flip.
    """
    xf = x.astype(jnp.float32)
    cf = centroids.astype(jnp.float32)
    d2 = (
        jnp.sum(xf * xf, -1, keepdims=True)
        - 2.0 * jnp.matmul(xf, cf.T, precision=jax.lax.Precision.HIGHEST)
        + jnp.sum(cf * cf, -1)[None, :]
    )
    a = jnp.argmin(d2, axis=-1).astype(jnp.int32)
    return a, jnp.maximum(jnp.min(d2, axis=-1), 0.0)


def iou_matrix(boxes_a, boxes_b):
    """boxes: (N,4)/(M,4) as (x1,y1,x2,y2) -> IoU (N,M) float32."""
    a = boxes_a.astype(jnp.float32)
    b = boxes_b.astype(jnp.float32)
    ax1, ay1, ax2, ay2 = a[:, 0:1], a[:, 1:2], a[:, 2:3], a[:, 3:4]
    bx1, by1, bx2, by2 = b[None, :, 0], b[None, :, 1], b[None, :, 2], b[None, :, 3]
    ix = jnp.maximum(jnp.minimum(ax2, bx2) - jnp.maximum(ax1, bx1), 0.0)
    iy = jnp.maximum(jnp.minimum(ay2, by2) - jnp.maximum(ay1, by1), 0.0)
    inter = ix * iy
    area_a = jnp.maximum(ax2 - ax1, 0.0) * jnp.maximum(ay2 - ay1, 0.0)
    area_b = jnp.maximum(bx2 - bx1, 0.0) * jnp.maximum(by2 - by1, 0.0)
    union = area_a + area_b - inter
    return inter / jnp.maximum(union, 1e-9)


def int8_matmul(x_q, w_q, x_scale, w_scale):
    """Quantized matmul oracle.

    x_q: (M, K) int8, w_q: (K, N) int8; x_scale: (M,), w_scale: (N,)
    per-row / per-column scales -> (M, N) float32.
    """
    acc = jnp.dot(
        x_q.astype(jnp.int32), w_q.astype(jnp.int32),
        preferred_element_type=jnp.int32,
    )
    return acc.astype(jnp.float32) * x_scale[:, None] * w_scale[None, :]
