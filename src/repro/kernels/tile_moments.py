"""Color-moments featurizer Pallas kernel (dedup front-end, paper §III-C).

Every captured tile passes through this before clustering, so it is the
highest-call-count op in the onboard pipeline. It is purely
bandwidth-bound: per-channel mean, stddev and skewness of each tile from
VMEM-resident data, in two passes over the block instead of three
separate reductions over HBM.

Layout: each (H, W, C) tile is flattened and laid out as (R, L) with
L = lcm(C, 128) lanes, so pixels (not the C=3 channels) fill the 128-wide
lane axis and lane ``l`` always holds channel ``l % C``. The kernel
returns per-lane power sums; the per-channel fold and the square/cube
roots run in XLA on the (N, 3, L) result.

Grid: one step per block of BN tiles, BN sized so a double-buffered
input block stays well under the 16 MiB scoped-VMEM default.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
BLOCK_BYTES = 4 << 20  # one input block; double-buffered by the pipeline


def _unroll(n_chunks: int, cap: int = 16) -> int:
    """Largest divisor of ``n_chunks`` that is <= cap (8-row slabs per
    loop step)."""
    return max(u for u in range(1, min(n_chunks, cap) + 1)
               if n_chunks % u == 0)


def _kernel(x_ref, chan_ref, out_ref, *, hw: int, c: int, valid: int,
            unroll: int):
    bn, rows, lanes = x_ref.shape
    chan = chan_ref[...]  # (1, L) channel id of each lane
    step = SUBLANES * unroll
    n_steps = rows // step
    masked = valid < rows * lanes  # static: flat padding present

    def slab(t, base, j):
        x = x_ref[t, pl.ds(base + j * SUBLANES, SUBLANES), :]
        return x.astype(jnp.float32)  # (8, L)

    def in_range(base, j):
        r = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, lanes), 0)
        ln = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, lanes), 1)
        return (base + j * SUBLANES + r) * lanes + ln < valid

    def one_tile(t, carry):
        def p1(i, acc):
            base = i * step
            for j in range(unroll):
                acc = acc + slab(t, base, j)  # pad entries are zeros
            return acc

        s = jax.lax.fori_loop(0, n_steps, p1,
                              jnp.zeros((SUBLANES, lanes), jnp.float32))
        s = jnp.sum(s, axis=0, keepdims=True)  # (1, L)
        mu = jnp.zeros((1, lanes), jnp.float32)
        for k in range(c):
            is_k = chan == k
            mu_k = jnp.sum(jnp.where(is_k, s, 0.0), axis=1,
                           keepdims=True) / hw
            mu = jnp.where(is_k, mu_k, mu)

        def p2(i, acc):
            a2, a3 = acc
            base = i * step
            for j in range(unroll):
                d = slab(t, base, j) - mu
                if masked:
                    d = jnp.where(in_range(base, j), d, 0.0)
                d2 = d * d
                a2, a3 = a2 + d2, a3 + d2 * d
            return a2, a3

        z = jnp.zeros((SUBLANES, lanes), jnp.float32)
        a2, a3 = jax.lax.fori_loop(0, n_steps, p2, (z, z))
        out_ref[pl.ds(t, 1), pl.ds(0, 1), :] = s[None]
        out_ref[pl.ds(t, 1), pl.ds(1, 1), :] = jnp.sum(
            a2, axis=0, keepdims=True)[None]
        out_ref[pl.ds(t, 1), pl.ds(2, 1), :] = jnp.sum(
            a3, axis=0, keepdims=True)[None]
        return carry

    jax.lax.fori_loop(0, bn, one_tile, 0)


def tile_moments(tiles, *, interpret: bool = False):
    """tiles: (N, H, W, C) -> (N, 3C) float32 color moments
    [mean | stddev | cbrt(third central moment)], per channel."""
    n, h, w, c = tiles.shape
    lanes = c * LANES // math.gcd(c, LANES)
    valid = h * w * c
    rows = -(-valid // lanes)
    rows += -rows % SUBLANES
    # as many tiles per grid step as fit one BLOCK_BYTES input block
    tile_bytes = rows * lanes * jnp.dtype(tiles.dtype).itemsize
    bn = max(1, min(BLOCK_BYTES // tile_bytes, n))
    n_pad = -n % bn
    flat = tiles.reshape(n, valid)
    flat = jnp.pad(flat, ((0, n_pad), (0, rows * lanes - valid)))
    x = flat.reshape(n + n_pad, rows, lanes)
    chan = (jnp.arange(lanes, dtype=jnp.int32) % c).reshape(1, lanes)
    kernel = functools.partial(
        _kernel, hw=h * w, c=c, valid=valid,
        unroll=_unroll(rows // SUBLANES))
    sums = pl.pallas_call(
        kernel,
        grid=((n + n_pad) // bn,),
        in_specs=[pl.BlockSpec((bn, rows, lanes), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, lanes), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bn, 3, lanes), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n + n_pad, 3, lanes), jnp.float32),
        interpret=interpret,
    )(x, chan)[:n]
    # fold lanes onto channels: lane l holds channel l % C
    per_ch = sums.reshape(n, 3, lanes // c, c).sum(axis=2) / (h * w)
    mean, m2, m3 = per_ch[:, 0], per_ch[:, 1], per_ch[:, 2]
    return jnp.concatenate([mean, jnp.sqrt(m2 + 1e-12), jnp.cbrt(m3)],
                           axis=-1)
