"""The plain grid counter: the program's ``models/detector.py`` stack.

A 3x3 stem at stride 1, then one stage per further width, each opened by
a stride-2 3x3 conv and followed by ``n_blocks_per_stage - 1`` stride-1
3x3 convs, every conv with a bias and leaky ReLU (0.1); a 1x1 head of
``n_anchors * (5 + n_classes)`` channels on the last stage's single grid.
Decode puts sigmoid box centres within their cell and sizes up to 4
cells; a score is objectness times the best class probability.
"""
from __future__ import annotations

import math

import numpy as np

from benchlib.counters import conv


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
    its output size, 2 per multiply-add; the head included (copied from
    ``repro.core.energy.detector_gflops``)."""
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
    makes the detections of every seed equally sparse."""
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


def reference_forward(params, spec, tiles, mode: str):
    """tiles (B, S, S, 3) -> raw head (B, G, G, A, 5 + classes) float32."""
    import jax.numpy as jnp

    dt = jnp.float32

    def leaky(x):
        return jnp.where(x >= 0, x, x * jnp.asarray(0.1, x.dtype))

    x = leaky(conv(tiles.astype(dt), params["stem"], 1, mode))
    for stage in params["stages"]:
        for j, blk in enumerate(stage):
            x = leaky(conv(x, blk["w"], 2 if j == 0 else 1, mode)
                      + blk["b"].astype(dt))
    x = conv(x, params["head_w"], 1, mode) + params["head_b"].astype(dt)
    b, g = x.shape[0], x.shape[1]
    return x.reshape(b, g, g, spec["n_anchors"],
                     5 + spec["n_classes"]).astype(jnp.float32)


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def decode(raw, spec):
    """raw (B, G, G, A, 5 + C) -> boxes (B, N, 4) xyxy px, scores (B, N),
    float64."""
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
