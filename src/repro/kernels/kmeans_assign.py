"""Fused k-means assignment Pallas kernel (dedup hot loop, paper §III-C).

One grid step loads a (D, BN) block of tile-features (features enter
transposed, so the BN points fill the lane axis) plus the full (K, D)
centroid table into VMEM, computes all pairwise squared distances with
one MXU matmul (-2 c·xᵀ) plus rank-1 norms, and fuses the argmin over
centroids — assignments never round-trip distances through HBM, and
each output block is one lane-dense (1, BN) row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BN = 256


def _kernel(xt_ref, c_ref, assign_ref, dist_ref):
    xt = xt_ref[...].astype(jnp.float32)  # (D, BN)
    c = c_ref[...].astype(jnp.float32)    # (K, D)
    x2 = jnp.sum(xt * xt, 0, keepdims=True)  # (1, BN)
    c2 = jnp.sum(c * c, -1, keepdims=True)   # (K, 1)
    d2 = x2 - 2.0 * jnp.dot(c, xt, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32) + c2
    assign_ref[...] = jnp.argmin(d2, axis=0).astype(jnp.int32)[None]
    dist_ref[...] = jnp.maximum(jnp.min(d2, axis=0), 0.0)[None]


def kmeans_assign(x, centroids, *, bn: int = DEFAULT_BN, interpret: bool = False):
    """x: (N, D), centroids: (K, D) -> ((N,) int32 assignment, (N,) f32 d²).

    N is padded to a multiple of bn internally.
    """
    n, d = x.shape
    k = centroids.shape[0]
    n_pad = -n % bn
    xt = jnp.pad(x, ((0, n_pad), (0, 0))).T  # (D, N + n_pad)
    grid = ((n + n_pad) // bn,)
    assign, dist = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((d, bn), lambda i: (0, i)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n + n_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n + n_pad), jnp.float32),
        ],
        interpret=interpret,
    )(xt, centroids)
    return assign[0, :n], dist[0, :n]
