"""Satellite-ground cascade: the two-tier counter pair.

The space tier (cheap counter, optionally int8-quantized) produces
(count, confidence) per tile; the ground tier (expensive counter)
recounts the downlinked tiles. Both tiers are jit-compiled batch
programs; counter training (`fit_counter`) lives here too so examples /
benchmarks / tests share one code path.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DetectorConfig
from repro.core import obs, tiling, xfer
from repro.core.dedup import bucket_size
from repro.models import detector
from repro.optim.adamw import adamw
from repro.optim.schedule import cosine_with_warmup


def _count_tiles_body(params, cfg: DetectorConfig, tiles,
                      score_thresh: float = 0.3, nms_iou: float = 0.25):
    raw = detector.forward(params, cfg, tiles)
    return detector.count_and_confidence(raw, cfg, score_thresh=score_thresh,
                                         iou_thresh=nms_iou)


@partial(jax.jit, static_argnames=("cfg", "score_thresh", "nms_iou"))
def count_tiles(params, cfg: DetectorConfig, tiles, score_thresh: float = 0.3,
                nms_iou: float = 0.25):
    """tiles (N, S, S, 3) already at cfg.input_size -> (counts, conf)."""
    return _count_tiles_body(params, cfg, tiles, score_thresh, nms_iou)


@partial(jax.jit, static_argnames=("cfg", "score_thresh", "nms_iou",
                                   "mesh"))
def _count_tiles_chunks(params, cfg: DetectorConfig, chunks,
                        score_thresh: float, nms_iou: float, mesh=None):
    """:func:`count_tiles` vmapped over a stacked (n_chunks, batch, ...)
    axis; with the chunk axis placed along a ``sats`` device ``mesh``,
    each device counts its share of the fleet's batches in parallel.
    The detector is per-sample, so per-chunk outputs are bit-equal to
    looping the single-chunk program."""
    from repro.core.fleet_sharding import map_lanes
    count = jax.vmap(lambda p, t: _count_tiles_body(p, cfg, t, score_thresh,
                                                    nms_iou),
                     in_axes=(None, 0))
    return map_lanes(count, mesh, replicated=1)(params, chunks)


def _tier_batch(n: int, batch: int, floor: int = 8) -> int:
    """Size-tiered effective batch: the smallest power-of-two tier in
    [floor, batch] covering ``n``. Small workloads (a handful of
    representatives, a short downlink) stop paying the full-batch
    padding — n=10 runs a 16-slot forward, not a 64-slot one — while the
    compiled-program count stays bounded at log2(batch/floor)+1 per cfg
    instead of growing with workload size like the seed path."""
    return min(bucket_size(n, floor), batch)


def _count_forward(params, cfg, t, batch: int, score_thresh, nms_iou,
                   sharding=None, defer: bool = False):
    """Shared forward tail: zero-pad rows to whole ``batch`` chunks, run
    the one fixed-shape compiled program per chunk, and transfer
    (counts, conf) to host in a single copy -> (2, n_rows_padded).

    With an on-mesh :class:`~repro.core.fleet_sharding.FleetSharding`
    and more than one chunk, the chunks are stacked, lane-padded to a
    device multiple, and counted in ONE sharded
    :func:`_count_tiles_chunks` call across the mesh.

    ``defer=True`` dispatches the forward and returns the stacked
    (2, n_rows_padded) *device* array WITHOUT the blocking host copy —
    the caller resolves it at its own round boundary (the fleet's
    ingest-overlap pipeline), so device compute keeps running behind
    whatever the foreground does next.
    """
    from repro.core.fleet_sharding import ctx
    sh = ctx(sharding)
    pad = -t.shape[0] % batch
    with obs.span("count.pad"):
        if pad:
            t = jnp.concatenate([t, jnp.zeros((pad, *t.shape[1:]), t.dtype)])
        t = t.reshape(-1, batch, *t.shape[1:])
    n_chunks = t.shape[0]
    if sh.on_mesh and n_chunks > 1:
        # pad the chunk axis to a power-of-two bucket x device multiple
        # (zero chunks are inert): the stacked forward compiles per
        # chunk count, and workloads present many distinct counts
        n_stack = sh.pad(bucket_size(n_chunks, 1))
        obs.count("count.rows_computed", n_stack * batch)
        with obs.span("count.pad"):
            if n_stack != n_chunks:
                t = jnp.concatenate(
                    [t, jnp.zeros((n_stack - n_chunks, *t.shape[1:]),
                                  t.dtype)])
        with obs.span("count.program"):
            c, f = _count_tiles_chunks(params, cfg, sh.device_put(t),
                                       score_thresh, nms_iou, mesh=sh.mesh)
            out = jnp.stack([c[:n_chunks].reshape(-1),
                             f[:n_chunks].reshape(-1)])
        if defer:
            return out
        with obs.span("count.fetch"):
            # analysis: waive(host-sync): the designated single host copy
            # of a counting batch; callers passing defer=True skip it
            return np.asarray(out)
    from repro.core.fleet_sharding import on_one_device
    obs.count("count.rows_computed", n_chunks * batch)
    with obs.span("count.program"):
        t = on_one_device(t)
        outs_c, outs_f = [], []
        for i in range(n_chunks):
            c, f = count_tiles(params, cfg, t[i], score_thresh, nms_iou)
            outs_c.append(c)
            outs_f.append(f)
        out = jnp.stack([jnp.concatenate(outs_c), jnp.concatenate(outs_f)])
    if defer:
        return out
    with obs.span("count.fetch"):
        # analysis: waive(host-sync): same designated copy, small-batch path
        return np.asarray(out)


def count_tiles_batched(params, cfg, tiles, batch: int = 64, score_thresh=0.3,
                        nms_iou: float = 0.25, idx=None):
    """Fixed-shape batching: EVERY batch — including the trailing one and
    small inputs — is padded up to a power-of-two size tier of `batch`
    (see :func:`_tier_batch`), so XLA compiles a handful of programs per
    cfg and reuses them for any n. Per-batch results stay on device; the
    host transfer happens once at the end.

    ``idx``: optional tile indices to count (a device-side gather). The
    index vector is padded to a whole number of batches, so selecting
    any subset of a bucketed tile array reuses a handful of compiled
    gathers instead of compiling per subset size — and the forward only
    ever runs at the tiered (batch, ...) shapes.

    (The detector is per-sample — convs + per-tile NMS — so padding
    never perturbs real tiles.)
    """
    n = int(len(idx)) if idx is not None else tiles.shape[0]
    if n == 0:
        return np.zeros((0,), np.float32), np.zeros((0,), np.float32)
    batch = _tier_batch(n, batch)
    obs.count("count.rows_real", n)
    if idx is not None:
        with obs.span("count.gather"):
            n_pad = -(-n // batch) * batch
            idx_pad = np.zeros(n_pad, np.int64)
            idx_pad[:n] = np.asarray(idx)
            # content-keyed upload cache: repeated-shape rounds gather
            # with the same index vectors, so steady state makes no
            # transfers
            t = jnp.asarray(tiles)[xfer.device_constant(idx_pad)]
    else:
        t = jnp.asarray(tiles)
    # padding trimmed host-side, so every device op ran at a bucketed shape
    out = _count_forward(params, cfg, t, batch, score_thresh, nms_iou)
    return out[0, :n], out[1, :n]


def count_tiles_multi(params, cfg, parts, batch: int = 64, score_thresh=0.3,
                      nms_iou: float = 0.25, sharding=None,
                      defer: bool = False):
    """Count several independent gathers in SHARED fixed-shape batches.

    ``parts``: list of ``(tiles, idx)`` — e.g. one per satellite of a
    fleet, each gathering its own tile subset from its own (bucketed)
    tile array. Each part's index vector is padded to a small bucket
    multiple (so gather/concat programs are reused across subset sizes),
    the gathers are concatenated, padded to a whole number of
    ``batch``-sized forward calls, and results are split back per part.
    Per-tile outputs are identical to calling
    :func:`count_tiles_batched` per part (the detector is per-sample, so
    batch composition never perturbs a tile), but the trailing-batch
    padding is paid once for the whole fleet instead of once per
    satellite — 8 satellites with ~10 representatives each run one
    64-slot forward instead of eight. ``sharding``: optional
    :class:`~repro.core.fleet_sharding.FleetSharding`; on-mesh, the
    shared batches are placed along the ``sats`` mesh axis and counted
    in one sharded forward call.

    Returns ``[(counts, conf), ...]`` aligned with ``parts``. With
    ``defer=True`` the forward is dispatched but the device->host result
    copy is NOT taken: a zero-argument resolver is returned instead,
    producing that same list when called — the fleet's ingest-overlap
    pipeline resolves it at the round's Aggregate/recount boundary while
    the detector forwards run behind later dispatch.
    """
    # pad each part's gather to a power-of-two bucket (floor 2): shapes
    # stay log-bounded per part size AND tiny parts pack tightly — a
    # 1-tile ground-recount window contributes 2 slots to the shared
    # batch instead of the 8-slot floor a per-part forward would pay,
    # which is where the batched contact tier beats the FIFO loop
    sizes = [int(len(idx)) for _, idx in parts]
    total = sum(sizes)
    empty = (np.zeros((0,), np.float32), np.zeros((0,), np.float32))
    if total == 0:
        out = [empty for _ in parts]
        return (lambda: out) if defer else out
    obs.count("count.rows_real", total)
    gathered, spans, off = [], [], 0
    for (tiles, idx), k in zip(parts, sizes):
        if not k:
            spans.append((0, 0))
            continue
        k_pad = bucket_size(k, 2)
        idx_pad = np.zeros(k_pad, np.int64)  # pad slots gather tile 0,
        idx_pad[:k] = np.asarray(idx)        # trimmed after the forward
        gathered.append(jnp.asarray(tiles)[xfer.device_constant(idx_pad)])
        spans.append((off, k))
        off += k_pad
    t = gathered[0] if len(gathered) == 1 else jnp.concatenate(gathered)
    fwd = _count_forward(params, cfg, t, _tier_batch(off, batch),
                         score_thresh, nms_iou, sharding=sharding,
                         defer=defer)
    if defer:
        def resolve():
            # analysis: waive(host-sync): the single deferred host copy —
            # callers resolve() at a pipeline boundary, not per round
            out = np.asarray(fwd)
            return [(out[0, o:o + k], out[1, o:o + k]) if k else empty
                    for o, k in spans]
        return resolve
    return [(fwd[0, o:o + k], fwd[1, o:o + k]) if k else empty
            for o, k in spans]


def count_tiles_batched_ref(params, cfg, tiles, batch: int = 64, score_thresh=0.3,
                            nms_iou: float = 0.25):
    """Seed host-side batching wrapper, kept as the parity/bench reference.

    Pads only when n > batch, so every distinct small-n call compiles a
    fresh XLA program — the behavior the fixed-shape version eliminates.
    """
    outs_c, outs_f = [], []
    tiles = np.asarray(tiles)
    n = tiles.shape[0]
    for i in range(0, n, batch):
        sl = tiles[i:i + batch]
        pad = 0
        if sl.shape[0] < batch and n > batch:
            pad = batch - sl.shape[0]
            sl = np.concatenate([sl, np.zeros((pad, *sl.shape[1:]), sl.dtype)])
        c, f = count_tiles(params, cfg, jnp.asarray(sl), score_thresh, nms_iou)
        c, f = np.asarray(c), np.asarray(f)
        if pad:
            c, f = c[:-pad], f[:-pad]
        outs_c.append(c)
        outs_f.append(f)
    return np.concatenate(outs_c), np.concatenate(outs_f)


# ---------------------------------------------------------------------------
# counter training (shared by examples / benchmarks / tests)
# ---------------------------------------------------------------------------


def _scene_targets(boxes, classes, n_tiles: int, g: int, grid: int,
                   n_anchors: int, n_classes: int, input_size: int,
                   tile_size: int):
    """Vectorized per-scene target tensor (n_tiles, G, G, A, 5+C).

    Matches the loop semantics of `clip_boxes_to_tile` +
    `boxes_to_targets`: boxes are center-assigned to tiles, localized,
    scaled to model-input px, and fill anchor slots in box order (boxes
    past `n_anchors` in a cell are dropped).
    """
    t = np.zeros((n_tiles, grid, grid, n_anchors, 5 + n_classes), np.float32)
    if len(boxes) == 0:
        return t
    b = np.asarray(boxes, np.float32)
    scale = np.float32(input_size / tile_size)
    cell = np.float32(input_size / grid)
    cx_s = (b[:, 0] + b[:, 2]) / 2
    cy_s = (b[:, 1] + b[:, 3]) / 2
    tx = np.minimum((cx_s // tile_size).astype(np.int64), g - 1)
    ty = np.minimum((cy_s // tile_size).astype(np.int64), g - 1)
    tile_idx = ty * g + tx
    # tile-local, model-input-px corner coordinates (float32 throughout,
    # scaled corner-first — matching the scalar arithmetic of the former
    # clip_boxes_to_tile + boxes_to_targets per-box loop bit-for-bit)
    x1 = (b[:, 0] - (tx * tile_size).astype(np.float32)) * scale
    x2 = (b[:, 2] - (tx * tile_size).astype(np.float32)) * scale
    y1 = (b[:, 1] - (ty * tile_size).astype(np.float32)) * scale
    y2 = (b[:, 3] - (ty * tile_size).astype(np.float32)) * scale
    cx, cy, w, h = (x1 + x2) / 2, (y1 + y2) / 2, x2 - x1, y2 - y1
    gx = np.minimum((cx / cell).astype(np.int64), grid - 1)
    gy = np.minimum((cy / cell).astype(np.int64), grid - 1)
    # anchor slot = occurrence index of the box within its (tile, cell)
    key = (tile_idx * grid + gy) * grid + gx
    order = np.argsort(key, kind="stable")
    sk = key[order]
    new_grp = np.r_[True, sk[1:] != sk[:-1]]
    starts = np.flatnonzero(new_grp)
    occ = np.empty(len(key), np.int64)
    occ[order] = np.arange(len(key)) - starts[np.cumsum(new_grp) - 1]
    m = occ < n_anchors
    ti, gyi, gxi, ai = tile_idx[m], gy[m], gx[m], occ[m]
    t[ti, gyi, gxi, ai, 0] = np.clip(cx[m] / cell - gxi.astype(np.float32), 0, 1)
    t[ti, gyi, gxi, ai, 1] = np.clip(cy[m] / cell - gyi.astype(np.float32), 0, 1)
    t[ti, gyi, gxi, ai, 2] = np.clip(w[m] / (4 * cell), 0, 1)
    t[ti, gyi, gxi, ai, 3] = np.clip(h[m] / (4 * cell), 0, 1)
    t[ti, gyi, gxi, ai, 4] = 1.0
    t[ti, gyi, gxi, ai, 5 + np.asarray(classes)[m].astype(np.int64)] = 1.0
    return t


def build_target_pool(cfg: DetectorConfig, scenes, tile_size: int):
    """(xs, ys) tile/target training pool for `fit_counter`.

    One vectorized pass per scene instead of the former O(tiles) nested
    Python loops over (ty, tx) cells.
    """
    grid = detector.grid_size(cfg)
    xs, ys = [], []
    for img, boxes, classes in scenes:
        s = img.shape[0]
        g = (s + tile_size - 1) // tile_size
        t = tiling.tile_image(jnp.asarray(img), tile_size)
        xs.append(np.asarray(tiling.resize_tiles(t, cfg.input_size)))
        ys.append(_scene_targets(boxes, classes, g * g, g, grid,
                                 cfg.n_anchors, cfg.n_classes,
                                 cfg.input_size, tile_size))
    return (np.concatenate(xs).astype(np.float32),
            np.concatenate(ys).astype(np.float32))


def fit_counter(cfg: DetectorConfig, scenes, tile_size: int, steps: int,
                key, batch: int = 16, lr: float = 3e-3, log_every: int = 0):
    """Train a counter on (image, boxes, classes) scenes.

    Tiles each scene, builds YOLO-style targets, runs AdamW. Returns
    (params, final_loss).
    """
    params = detector.init(key, cfg)
    xs, ys = build_target_pool(cfg, scenes, tile_size)

    opt_init, opt_update = adamw(cosine_with_warmup(lr, steps // 10 + 1, steps))
    opt_state = opt_init(params)

    @jax.jit
    def train_step(params, opt_state, xb, yb):
        (loss, _), grads = jax.value_and_grad(detector.loss_fn, has_aux=True)(
            params, cfg, xb, yb)
        params, opt_state, _ = opt_update(grads, opt_state, params)
        return params, opt_state, loss

    rng = np.random.default_rng(0)
    loss = None
    for step in range(steps):
        idx = rng.integers(0, len(xs), batch)
        params, opt_state, loss = train_step(params, opt_state,
                                             jnp.asarray(xs[idx]), jnp.asarray(ys[idx]))
        if log_every and step % log_every == 0:
            print(f"  step {step:4d} loss {float(loss):.4f}")
    return params, float(loss)
