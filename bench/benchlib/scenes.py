"""Synthetic Earth-observation scenes, made on the host from a seed.

A copy of ``repro.data.synthetic`` (``SceneSpec``, ``make_scene``,
``revisit_frames``; its noise upsampling made separable), kept here so
that a change to the program's data module does not move the
benchmark's traffic. Scenes are a textured
background with planted objects and, with probability
``cloud_fraction``, a cloud; revisits are shifted, re-lit and, half of
the time, rotated copies of one scene.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import numpy as np


@dataclass(frozen=True)
class SceneSpec:
    name: str
    scene_px: int
    objects_per_scene: Tuple[int, int]   # (lo, hi)
    object_px: Tuple[int, int]           # (lo, hi)
    n_classes: int = 8
    cloud_fraction: float = 0.3
    texture_scale: int = 64


_CLASS_COLORS = np.array([
    [0.9, 0.2, 0.2], [0.2, 0.9, 0.2], [0.2, 0.3, 0.9], [0.9, 0.9, 0.2],
    [0.9, 0.2, 0.9], [0.2, 0.9, 0.9], [0.95, 0.6, 0.1], [0.7, 0.7, 0.7],
])


def spec_from(d: dict) -> SceneSpec:
    return SceneSpec(d["name"], int(d["scene_px"]),
                     tuple(d["objects_per_scene"]), tuple(d["object_px"]),
                     int(d.get("n_classes", 8)),
                     float(d.get("cloud_fraction", 0.3)),
                     int(d.get("texture_scale", 64)))


def _smooth_noise(rng, size, scale):
    """Bilinear upsampling of a coarse random grid to (size, size, 3).
    Bilinear interpolation is separable, so it is two small matmuls (the
    program's copy gathers four corners per pixel: the same values to
    float64 rounding, thirty times slower)."""
    small = rng.random((size // scale + 2, size // scale + 2, 3))
    idx = np.linspace(0, small.shape[0] - 1.001, size)
    i0 = idx.astype(int)
    f = idx - i0
    w = np.zeros((size, small.shape[0]))
    w[np.arange(size), i0] = 1 - f
    w[np.arange(size), i0 + 1] += f
    return np.einsum("xi,ijc,yj->xyc", w, small, w, optimize=True)


def make_scene(rng: np.random.Generator, spec: SceneSpec):
    """-> (image (S,S,3) f32 in [0,1], boxes (M,4) xyxy px, classes (M,))."""
    s = spec.scene_px
    img = 0.25 + 0.35 * _smooth_noise(rng, s, spec.texture_scale)
    img += 0.03 * rng.standard_normal((s, s, 3))
    n_obj = int(rng.integers(*spec.objects_per_scene))
    boxes, classes = [], []
    for _ in range(n_obj):
        w = int(rng.integers(*spec.object_px))
        h = int(rng.integers(*spec.object_px))
        x = int(rng.integers(0, s - w))
        y = int(rng.integers(0, s - h))
        c = int(rng.integers(0, spec.n_classes))
        col = _CLASS_COLORS[c] * (0.8 + 0.4 * rng.random())
        yy, xx = np.mgrid[y:y + h, x:x + w]
        cy, cx = y + h / 2, x + w / 2
        inside = (((yy - cy) / (h / 2)) ** 2 + ((xx - cx) / (w / 2)) ** 2) <= 1.0
        region = img[y:y + h, x:x + w]
        region[inside] = col * 0.85 + 0.15 * region[inside]
        boxes.append([x, y, x + w, y + h])
        classes.append(c)
    if rng.random() < spec.cloud_fraction:
        cs = int(rng.integers(s // 4, s // 2))
        cx0 = int(rng.integers(0, s - cs))
        cy0 = int(rng.integers(0, s - cs))
        cloud = 0.85 + 0.1 * _smooth_noise(rng, cs, max(cs // 4, 2))
        img[cy0:cy0 + cs, cx0:cx0 + cs] = (
            0.7 * cloud + 0.3 * img[cy0:cy0 + cs, cx0:cx0 + cs])
        keep = []
        for i, (x1, y1, x2, y2) in enumerate(boxes):
            cxm, cym = (x1 + x2) / 2, (y1 + y2) / 2
            if not (cx0 < cxm < cx0 + cs and cy0 < cym < cy0 + cs):
                keep.append(i)
        boxes = [boxes[i] for i in keep]
        classes = [classes[i] for i in keep]
    img = np.clip(img, 0.0, 1.0).astype(np.float32)
    b = np.asarray(boxes, np.float32).reshape(-1, 4)
    c = np.asarray(classes, np.int32).reshape(-1)
    return img, b, c


def revisit_frames(rng, img, boxes, classes, n_frames: int,
                   max_shift: int = 24):
    """Repeated passes over one ground area: shifted, re-lit, and half of
    the time rotated copies of the scene."""
    s = img.shape[0]
    frames = []
    for _ in range(n_frames):
        dx = int(rng.integers(-max_shift, max_shift + 1))
        dy = int(rng.integers(-max_shift, max_shift + 1))
        f = np.roll(img, (dy, dx), axis=(0, 1))
        b = boxes.copy()
        if len(b):
            b[:, [0, 2]] = (b[:, [0, 2]] + dx) % s
            b[:, [1, 3]] = (b[:, [1, 3]] + dy) % s
            ok = (b[:, 2] > b[:, 0]) & (b[:, 3] > b[:, 1])
            b, cl = b[ok], classes[ok]
        else:
            cl = classes
        f = np.clip(f * (0.92 + 0.16 * rng.random()), 0, 1)
        if rng.random() < 0.5:
            rot = int(rng.integers(1, 4))
            f = np.rot90(f, rot).copy()
            b2 = b.copy()
            for _ in range(rot):
                if len(b2):
                    x1, y1 = b2[:, 0].copy(), b2[:, 1].copy()
                    x2, y2 = b2[:, 2].copy(), b2[:, 3].copy()
                    b2 = np.stack([y1, s - x2, y2, s - x1], axis=1)
            b = b2
        frames.append((f.astype(np.float32), b, cl))
    return frames


def pass_pool(rng, spec: SceneSpec, n_passes: int, scenes_per_pass: int,
              revisits: int):
    """``n_passes`` passes, each ``scenes_per_pass`` scenes seen
    ``revisits`` times: a list of frame lists."""
    pool = []
    for _ in range(n_passes):
        frames = []
        for _ in range(scenes_per_pass):
            img, b, c = make_scene(rng, spec)
            frames += revisit_frames(rng, img, b, c, revisits)
        pool.append(frames)
    return pool
