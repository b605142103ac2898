"""Public kernel entry points with platform dispatch.

Models call these; on the TPU they run the Pallas kernels, elsewhere the
pure-jnp oracles in ref.py — so the same model code runs in the CPU
tests and dry-run and on the chip. A kernel that fails to lower on the
TPU fails the run; nothing falls back to the reference there. (Tests
run the Pallas kernels on the CPU with ``interpret=True``.)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_matmul import int8_matmul as _int8_pallas
from repro.kernels.iou import iou_matrix as _iou_pallas
from repro.kernels.kmeans_assign import kmeans_assign as _kmeans_pallas
from repro.kernels.tile_moments import tile_moments as _moments_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention(q, k, v, *, causal: bool = False):
    """GQA attention: q (B,Sq,Hq,D), k/v (B,Skv,Hkv,D)."""
    sq, skv, d = q.shape[1], k.shape[1], q.shape[-1]
    aligned = sq % 128 == 0 and skv % 128 == 0 and d % 128 == 0
    if _on_tpu() and aligned:
        return flash_attention(q, k, v, causal=causal)
    return ref.attention(q, k, v, causal=causal)


def decode_attention(q, k, v, *, kv_len):
    """Single-token decode: q (B,1,Hq,D) against a full-length cache with
    per-batch valid lengths kv_len (B,)."""
    return ref.attention(q, k, v, causal=False, kv_len=kv_len)


def tile_moments(tiles, *, interpret: Optional[bool] = None):
    if _on_tpu():
        return _moments_pallas(tiles)
    if interpret:
        return _moments_pallas(tiles, interpret=True)
    return ref.tile_moments(tiles)


def kmeans_assign(x, centroids, *, interpret: Optional[bool] = None):
    if _on_tpu():
        return _kmeans_pallas(x, centroids)
    if interpret:
        return _kmeans_pallas(x, centroids, interpret=True)
    return ref.kmeans_assign(x, centroids)


def iou_matrix(a, b, *, interpret: Optional[bool] = None):
    if _on_tpu():
        return _iou_pallas(a, b)
    if interpret:
        return _iou_pallas(a, b, interpret=True)
    return ref.iou_matrix(a, b)


def int8_matmul(x_q, w_q, x_scale, w_scale, *, interpret: Optional[bool] = None):
    if _on_tpu():
        return _int8_pallas(x_q, w_q, x_scale, w_scale)
    if interpret:
        return _int8_pallas(x_q, w_q, x_scale, w_scale, interpret=True)
    return ref.int8_matmul(x_q, w_q, x_scale, w_scale)


def quantize_int8(x, axis=-1):
    """Symmetric per-row int8 quantization helper: returns (q, scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, jnp.squeeze(scale, axis)
