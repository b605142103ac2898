"""Seeded counters, their FLOPs, and their plain reference.

A counter is the grid detector of the program's ``models/detector.py``,
described here only by its published sizes (a ``counters`` entry of a
configuration file). This module never imports the program:

* :func:`init_params` makes the weights on the device from a key, in the
  parameter layout the program's detector reads, and
  :func:`calibrate_head` rescales and biases the head so that NMS both
  keeps and suppresses boxes (after ``chip_smoke.py``, which rescales it
  by a fixed gain).
* :func:`forward_gflops` is the conv arithmetic of one forward pass
  (copied from ``repro.core.energy.detector_gflops``).
* :func:`reference_forward`, :func:`decode` and :func:`kept_scores` are
  the reference: a plain conv stack in ``jax.lax`` at ``HIGHEST``
  precision, decode and greedy NMS in float64 numpy.
"""
from __future__ import annotations

import math

import numpy as np

MAX_DET = 128  # candidates NMS looks at per tile (top scores)


def conv_layers(spec):
    """[(stride, c_in, c_out)] of the trunk; the head is a 1x1 conv."""
    widths = spec["widths"]
    layers = [(1, 3, widths[0])]
    prev = widths[0]
    for w in widths[1:]:
        layers.append((2, prev, w))
        layers += [(1, w, w)] * (spec["n_blocks_per_stage"] - 1)
        prev = w
    return layers


def head_width(spec) -> int:
    return spec["n_anchors"] * (5 + spec["n_classes"])


def forward_gflops(spec) -> float:
    """Operations of one forward pass on one tile (GFLOP): every conv at
    its output size, 2 per multiply-add; the head included."""
    h = spec["input_size"]
    total = 0.0
    for stride, c_in, c_out in conv_layers(spec):
        h = -(-h // stride)
        total += h * h * 9 * c_in * c_out * 2
    total += h * h * conv_layers(spec)[-1][2] * head_width(spec) * 2
    return total / 1e9


def init_params(key, spec):
    """Weights from ``key`` in the program's detector layout:
    ``{"stem", "stages": [[{"w", "b"}, ...]], "head_w", "head_b"}``.
    Convs are truncated normals (+-2 sigma) with sigma 1/sqrt(fan-in),
    conv biases 0, the 1x1 head sigma 0.01 and bias 0 (see
    :func:`calibrate_head`)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(spec["param_dtype"])
    layers = conv_layers(spec)
    keys = jax.random.split(key, len(layers) + 1)

    def normal(k, shape, std):
        return (jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
                * std).astype(dt)

    ws = [normal(k, (3, 3, ci, co), 1.0 / math.sqrt(9 * ci))
          for k, (_, ci, co) in zip(keys, layers)]
    stages, i = [], 1
    for _ in spec["widths"][1:]:
        blocks = []
        for _ in range(spec["n_blocks_per_stage"]):
            blocks.append({"w": ws[i], "b": jnp.zeros((ws[i].shape[-1],), dt)})
            i += 1
        stages.append(blocks)
    prev, hw = layers[-1][2], head_width(spec)
    return {"stem": ws[0], "stages": stages,
            "head_w": normal(keys[-1], (1, 1, prev, hw), 0.01),
            "head_b": jnp.zeros((hw,), dt)}


def calibrate_head(params, spec, tiles):
    """Rescale and bias the 1x1 head so that its logits over ``tiles``
    (at the counter's input size) have the configuration's
    ``head_logits`` statistics: box offsets mean 0 and ``box_std``,
    objectness ``objectness_mean`` and ``objectness_std``, classes mean 0
    and ``class_std``, channel by channel. At init a random trunk's
    features reach the head at ~1e-3 with a seed-dependent offset, so
    nothing would clear a score threshold, or everything would; this
    makes the detections of every seed equally sparse. Jit with
    :func:`init_params`: one call from the seed."""
    import jax.numpy as jnp
    t = spec["head_logits"]
    raw = reference_forward(params, spec, tiles, "highest")
    flat = raw.reshape(-1, raw.shape[-2] * raw.shape[-1])
    mu, sd = flat.mean(0), flat.std(0) + 1e-12
    kind = np.tile(np.arange(5 + spec["n_classes"]), spec["n_anchors"])
    mean = np.where(kind == 4, t["objectness_mean"], 0.0).astype(np.float32)
    std = np.select([kind < 4, kind == 4],
                    [t["box_std"], t["objectness_std"]],
                    t["class_std"]).astype(np.float32)
    scale = std / sd
    dt = params["head_w"].dtype
    return dict(params, head_w=(params["head_w"] * scale).astype(dt),
                head_b=jnp.asarray(mean - mu * scale).astype(dt))


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


def reference_forward(params, spec, tiles, mode: str = "default"):
    """tiles (B, S, S, 3) -> raw head (B, G, G, A, 5 + classes) float32.

    ``mode``:
      ``default``  what the configuration states: float32 weights and
                   activations, each conv's operands rounded to bfloat16
                   (the TPU's default matmul precision) and products
                   summed in float32;
      ``highest``  float32 throughout;
      ``int8``     the control, one step below bfloat16 operands: each
                   conv's weights quantised to int8 per output channel
                   and its input per tile (symmetric, max-abs scales).
    Every conv runs at ``Precision.HIGHEST``, so the TPU adds no rounding
    of its own.
    """
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST
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

    def conv(x, w, stride):
        return lax.conv_general_dilated(
            rnd(x, False), rnd(w.astype(dt), True), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=hi,
            preferred_element_type=dt)

    def leaky(x):
        return jnp.where(x >= 0, x, x * jnp.asarray(0.1, x.dtype))

    x = leaky(conv(tiles.astype(dt), params["stem"], 1))
    for stage in params["stages"]:
        for j, blk in enumerate(stage):
            x = leaky(conv(x, blk["w"], 2 if j == 0 else 1)
                      + blk["b"].astype(dt))
    x = conv(x, params["head_w"], 1) + params["head_b"].astype(dt)
    b, g = x.shape[0], x.shape[1]
    return x.reshape(b, g, g, spec["n_anchors"],
                     5 + spec["n_classes"]).astype(jnp.float32)


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def decode(raw, spec):
    """raw (B, G, G, A, 5 + C) -> boxes (B, N, 4) xyxy px, scores (B, N),
    float64: box centre offsets within the cell, sizes up to 4 cells,
    score = objectness x best class probability."""
    raw = np.asarray(raw, np.float64)
    b, g = raw.shape[0], raw.shape[1]
    cell = spec["input_size"] / g
    cy = (np.arange(g) + 0.5)[None, :, None, None]
    cx = (np.arange(g) + 0.5)[None, None, :, None]
    box = _sigmoid(raw[..., :4])
    bx = (cx + box[..., 0] - 0.5) * cell
    by = (cy + box[..., 1] - 0.5) * cell
    bw = box[..., 2] * 4 * cell
    bh = box[..., 3] * 4 * cell
    boxes = np.stack([bx - bw / 2, by - bh / 2, bx + bw / 2, by + bh / 2], -1)
    logits = raw[..., 5:]
    e = np.exp(logits - logits.max(-1, keepdims=True))
    cls = (e / e.sum(-1, keepdims=True)).max(-1)
    scores = _sigmoid(raw[..., 4]) * cls
    return boxes.reshape(b, -1, 4), scores.reshape(b, -1)


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
