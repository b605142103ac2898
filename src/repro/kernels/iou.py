"""Pairwise-IoU Pallas kernel (NMS over the global detection matrix,
paper §IV-A2). Grid of (BN_a, BN_b) box blocks; each step computes a
(BN, BN) IoU tile entirely in VMEM/VREGs — the O(N²) matrix never
exists in HBM at f32 unless requested.

Coordinates enter coordinate-major, so no kernel slices a 4-wide lane
axis: ``a`` as (4, N, 1) columns that broadcast along lanes, ``b`` as
(4, M) rows that broadcast along sublanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BN = 128


def _kernel(a_ref, b_ref, o_ref):
    ax1, ay1, ax2, ay2 = (a_ref[k].astype(jnp.float32) for k in range(4))
    b = b_ref[...].astype(jnp.float32)  # (4, BM)
    bx1, by1, bx2, by2 = b[0:1], b[1:2], b[2:3], b[3:4]  # (1, BM) each
    ix = jnp.maximum(jnp.minimum(ax2, bx2) - jnp.maximum(ax1, bx1), 0.0)
    iy = jnp.maximum(jnp.minimum(ay2, by2) - jnp.maximum(ay1, by1), 0.0)
    inter = ix * iy
    area_a = jnp.maximum(ax2 - ax1, 0.0) * jnp.maximum(ay2 - ay1, 0.0)
    area_b = jnp.maximum(bx2 - bx1, 0.0) * jnp.maximum(by2 - by1, 0.0)
    union = area_a + area_b - inter
    o_ref[...] = inter / jnp.maximum(union, 1e-9)


def iou_matrix(boxes_a, boxes_b, *, bn: int = DEFAULT_BN, interpret: bool = False):
    """boxes_a: (N,4), boxes_b: (M,4) xyxy -> (N, M) f32 IoU."""
    n, m = boxes_a.shape[0], boxes_b.shape[0]
    pn, pm = -n % bn, -m % bn
    a_cols = jnp.pad(boxes_a, ((0, pn), (0, 0))).T[:, :, None]  # (4, N, 1)
    b_rows = jnp.pad(boxes_b, ((0, pm), (0, 0))).T              # (4, M)
    out = pl.pallas_call(
        _kernel,
        grid=((n + pn) // bn, (m + pm) // bn),
        in_specs=[
            pl.BlockSpec((4, bn, 1), lambda i, j: (0, i, 0)),
            pl.BlockSpec((4, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n + pn, m + pm), jnp.float32),
        interpret=interpret,
    )(a_cols, b_rows)
    return out[:n, :m]
