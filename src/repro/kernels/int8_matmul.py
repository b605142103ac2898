"""Int8 quantized matmul Pallas kernel — the low-power onboard inference
path (beyond-paper: the space-tier counter runs weight+activation
quantized, modelling the RPi-class power envelope on the MXU).

Grid (M/BM, N/BN, K/BK), K innermost; int32 accumulator in VMEM scratch;
per-row activation scales and per-column weight scales are applied once
on the final K step. 128-cubed blocks keep the MXU int8 path saturated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_B = 128


def _kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_ref, *, n_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.int32)
    w = w_ref[...].astype(jnp.int32)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )

    @pl.when(ki == n_k - 1)
    def _finish():
        xs = xs_ref[...].astype(jnp.float32)  # (BM,)
        ws = ws_ref[...].astype(jnp.float32)  # (BN,)
        o_ref[...] = acc_ref[...].astype(jnp.float32) * xs[:, None] * ws[None, :]


def int8_matmul(x_q, w_q, x_scale, w_scale, *, bm: int = DEFAULT_B,
                bn: int = DEFAULT_B, bk: int = DEFAULT_B,
                interpret: bool = False):
    """x_q (M,K) int8 @ w_q (K,N) int8 -> (M,N) f32, scaled per row/col."""
    m, k = x_q.shape
    n = w_q.shape[1]
    pm, pn, pk = -m % bm, -n % bn, -k % bk
    xp = jnp.pad(x_q, ((0, pm), (0, pk)))
    wp = jnp.pad(w_q, ((0, pk), (0, pn)))
    xsp = jnp.pad(x_scale, (0, pm))
    wsp = jnp.pad(w_scale, (0, pn))
    grid = ((m + pm) // bm, (n + pn) // bn, (k + pk) // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm,), lambda i, j, kk: (i,)),
            pl.BlockSpec((bn,), lambda i, j, kk: (j,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + pm, n + pn), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xp, wp, xsp, wsp)
    return out[:m, :n]
