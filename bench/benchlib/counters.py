"""Seeded counters, their FLOPs, and their plain reference.

A counter is one of the program's detectors, described here only by its
published sizes (a ``counters`` entry of a configuration file). Its
architecture lives in a file of its own, ``bench/archs/<arch>.py``, which
the entry names with its ``"arch"`` key (``plain-grid`` where it has
none) and :func:`loader.arch` finds. An architecture file imports
nothing of the program and gives:

* ``forward_gflops(spec)``: the operations of one forward pass on one
  tile (GFLOP), as the program prices them;
* ``init_params(key, spec)``: the weights on the device from a key, in
  the parameter layout the program's detector reads;
* ``calibrate_head(params, spec, tiles)``: the head(s) rescaled and
  biased so that NMS both keeps and suppresses boxes (after
  ``chip_smoke.py``, which rescales by a fixed gain);
* ``reference_forward(params, spec, tiles, mode)``: the plain forward
  pass in ``jax.lax``, its convs through :func:`conv` -> the raw output,
  any pytree of arrays;
* ``decode(raw, spec)``: that output on the host -> boxes ``(B, N, 4)``
  xyxy px and scores ``(B, N)``, float64, over all of its grids.

The functions of those names here dispatch on the entry's architecture.
This module keeps what every architecture shares: the bilinear resize,
:func:`conv` with the reference's precision modes, and greedy NMS and
counting in float64 numpy. It never imports the program.
"""
from __future__ import annotations

import math

import numpy as np

from . import loader

MAX_DET = 128  # candidates NMS looks at per tile (top scores)
DEFAULT_ARCH = "plain-grid"  # a counter entry with no "arch" key


def arch(spec):
    """The architecture module of a counter entry."""
    return loader.arch(spec.get("arch", DEFAULT_ARCH))


def forward_gflops(spec) -> float:
    """Operations of one forward pass on one tile (GFLOP)."""
    return arch(spec).forward_gflops(spec)


def init_params(key, spec):
    """Weights from ``key``, in the program's layout for the architecture."""
    return arch(spec).init_params(key, spec)


def calibrate_head(params, spec, tiles):
    """``params`` with the head(s) set to the configuration's
    ``head_logits`` statistics over ``tiles`` (at the counter's input
    size). Jit with :func:`init_params`: one call from the seed."""
    return arch(spec).calibrate_head(params, spec, tiles)


def reference_forward(params, spec, tiles, mode: str = "default"):
    """tiles (B, S, S, 3) -> the architecture's raw output (a pytree),
    every conv at ``mode`` (see :func:`conv`)."""
    return arch(spec).reference_forward(params, spec, tiles, mode)


def decode(raw, spec):
    """The raw output on the host -> boxes (B, N, 4) xyxy px, scores
    (B, N), float64, over all of the architecture's grids."""
    return arch(spec).decode(raw, spec)


def resize(tiles, size: int, dtype=None):
    """(N, S, S, C) -> (N, size, size, C) bilinear at half-pixel centres
    (:func:`resize_matrix`), one einsum at ``HIGHEST``."""
    import jax.numpy as jnp
    from jax import lax
    dt = dtype or jnp.float32
    r = jnp.asarray(resize_matrix(tiles.shape[1], size), dt)
    return jnp.einsum("oh,nhwc,pw->nopc", r, tiles.astype(dt), r,
                      precision=lax.Precision.HIGHEST,
                      preferred_element_type=dt)


def resize_matrix(n_in: int, n_out: int) -> np.ndarray:
    """(n_out, n_in) bilinear weights at half-pixel centres; out-of-range
    taps are dropped and each row renormalised (edge pixels repeat).
    Shrinking widens the triangle by the scale (antialiasing)."""
    scale = n_out / n_in
    width = max(1.0 / scale, 1.0)
    x = (np.arange(n_out) + 0.5) / scale - 0.5
    w = np.maximum(0.0, 1.0 - np.abs(x[:, None] - np.arange(n_in)[None, :])
                   / width)
    return w / w.sum(1, keepdims=True)


MODES = ("default", "highest", "int8")


def conv(x, w, stride: int, mode: str = "default"):
    """The reference's convolution: NHWC input, HWIO weights, ``SAME``
    padding, float32 out.

    ``mode``:
      ``default``  what the configuration states: float32 weights and
                   activations, the operands rounded to bfloat16 (the
                   TPU's default matmul precision) and products summed in
                   float32;
      ``highest``  float32 throughout;
      ``int8``     the control, one step below bfloat16 operands: the
                   weights quantised to int8 per output channel and the
                   input per tile (symmetric, max-abs scales).
    It runs at ``Precision.HIGHEST``, so the TPU adds no rounding of its
    own.
    """
    import jax.numpy as jnp
    from jax import lax

    if mode not in MODES:
        raise ValueError(f"conv mode {mode!r} is not one of {MODES}")
    dt = jnp.float32

    def quant(a, axes):
        s = jnp.max(jnp.abs(a), axis=axes, keepdims=True) / 127.0
        s = jnp.where(s > 0, s, 1.0)
        return jnp.clip(jnp.round(a / s), -127, 127) * s

    def rnd(a, weight):
        if mode == "default":
            return a.astype(jnp.bfloat16).astype(jnp.float32)
        if mode == "int8":
            return quant(a, (0, 1, 2) if weight else (1, 2, 3))
        return a

    return lax.conv_general_dilated(
        rnd(x, False), rnd(w.astype(dt), True), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST, preferred_element_type=dt)


def _iou(a, b):
    ix = np.maximum(np.minimum(a[:, None, 2], b[None, :, 2])
                    - np.maximum(a[:, None, 0], b[None, :, 0]), 0.0)
    iy = np.maximum(np.minimum(a[:, None, 3], b[None, :, 3])
                    - np.maximum(a[:, None, 1], b[None, :, 1]), 0.0)
    inter = ix * iy
    area = lambda z: (np.maximum(z[:, 2] - z[:, 0], 0.0)
                      * np.maximum(z[:, 3] - z[:, 1], 0.0))
    union = area(a)[:, None] + area(b)[None, :] - inter
    return inter / np.maximum(union, 1e-9)


def kept_scores(boxes, scores, iou_thresh: float):
    """Greedy NMS of one tile over its ``MAX_DET`` best candidates, with
    no score threshold: the kept scores, descending. Greedy suppression
    only looks back, so the count at any threshold ``t`` is the number
    of kept scores above ``t`` and the confidence is their mean."""
    order = np.argsort(-scores, kind="stable")[:MAX_DET]
    bx, sc = boxes[order], scores[order]
    iou = _iou(bx, bx)
    keep = np.ones(len(order), bool)
    for i in range(len(order)):
        if keep[i]:
            later = np.arange(len(order)) > i
            keep &= ~((iou[i] > iou_thresh) & later)
    return sc[keep]


def count_and_conf(kept, thresh: float):
    above = kept[kept > thresh]
    return len(above), (float(above.mean()) if len(above) else 0.0)


def count_gap(count: int, kept, thresh: float) -> float:
    """How far the score threshold must move for the reference's NMS to
    give ``count`` boxes: 0 where it already does. ``inf`` where no
    threshold does (more boxes than the reference keeps at all)."""
    m = len(kept)
    if count > m or count < 0:
        return math.inf
    upper = kept[count - 1] if count >= 1 else math.inf  # need t < upper
    lower = kept[count] if count < m else -math.inf      # need t >= lower
    if lower <= thresh < upper:
        return 0.0
    return float(thresh - upper) if thresh >= upper else float(lower - thresh)
