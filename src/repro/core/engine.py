"""Device-resident batched pipeline engine (run_pipeline stages 0-2).

The seed pipeline orchestrated its hot path from the host: a Python
loop tiled and resized each frame separately, the ROI filter launched an
ad-hoc ``jnp.std`` round-trip over all tiles, dedup re-read every tile
to recompute the color moments, and every distinct counting batch shape
triggered a fresh XLA compile. This module replaces all of that with a
small number of shape-stable jit programs:

* ``_frame_program`` — one fused compiled call that tiles a fixed-size
  bucket of frames, resizes to BOTH counter input sizes, and computes
  ``tile_moments`` once. The moments feed the ROI variance filter (the
  stddev moment IS the ROI statistic) and are reused by dedup
  (:func:`repro.core.dedup.dedup_from_moments`) — the tiles are read
  exactly once.
* frame batches are padded to ``frame_bucket`` so the program compiles
  per distinct frame *resolution*, never per frame *count*.
* tile arrays stay on device (`jnp`): downstream gathers
  (``tiles[process]``) and the fixed-shape ``count_tiles_batched``
  consume them without host round-trips; results transfer once.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import obs, tiling
from repro.core.dedup import bucket_size
from repro.kernels import ops as kops

FRAME_BUCKET = 4  # frames per fused-program invocation (padded up)


@dataclass
class PreparedFrames:
    """Stage-0/1 output: device-resident tiles + per-tile statistics.

    Device arrays are zero-padded to a power-of-two tile bucket
    (rows past ``n`` are zero tiles), so every downstream gather and
    counting program compiles once per bucket instead of once per
    workload size. Host arrays (`roi_std`, `true`) hold the ``n`` real
    tiles only. ``moments``/``roi_std`` are ``None`` when prepared with
    ``with_stats=False`` (the policy uses neither ROI nor dedup, so the
    fused program skips the statistics entirely).
    """
    tiles_sp: jnp.ndarray   # (N_pad, s_sp, s_sp, C) space-tier input, device
    tiles_gd: jnp.ndarray   # (N_pad, s_gd, s_gd, C) ground-tier input, device
    moments: object         # (N_pad, 3C) raw color moments, device (or None)
    roi_std: object         # (n,) mean per-channel stddev, host (or None)
    true: np.ndarray        # (n,) ground-truth per-tile counts
    n: int                  # real tile count (rows [n:] are padding)


def _tile_batch(imgs, tile_size: int, sp_size: int, gd_size: int,
                with_stats: bool = True):
    """(B, H, W, C) frames -> (tiles_sp, tiles_gd[, moments, roi_std]).

    Fused tile -> resize(space) -> resize(ground) -> tile_moments in one
    compiled program; ``tiling.tile_image`` (vmapped over the frame
    batch) stays the single definition of tile order — row-major within
    each frame, frames in batch order. ``with_stats=False`` (policies
    that use neither the ROI filter nor dedup) compiles a variant
    without the statistics tail — the tile values are identical, the
    moments pass simply never runs.
    """
    b, _, _, c = imgs.shape
    t = jax.vmap(lambda im: tiling.tile_image(im, tile_size))(imgs)
    t = t.reshape(b * t.shape[1], tile_size, tile_size, c)
    tiles_sp = tiling.resize_tiles(t, sp_size)
    tiles_gd = tiling.resize_tiles(t, gd_size)
    if not with_stats:
        return tiles_sp, tiles_gd
    moments = kops.tile_moments(tiles_sp)
    roi_std = jnp.mean(moments[:, c:2 * c], axis=-1)
    return tiles_sp, tiles_gd, moments, roi_std


def _frame_program_body(frames, tile_size: int, sp_size: int, gd_size: int,
                        with_stats: bool = True):
    """A bucket of separate (H, W, C) device frames -> :func:`_tile_batch`
    of their stack. The stack runs inside the program, where it fuses
    with the tiling; the bucket's length is fixed, so the program still
    compiles once per frame shape. (Device traces find the capture
    program by this function's name.)"""
    return _tile_batch(jnp.stack(frames), tile_size, sp_size, gd_size,
                       with_stats)


_frame_program = partial(jax.jit, static_argnames=(
    "tile_size", "sp_size", "gd_size", "with_stats"))(_frame_program_body)


@partial(jax.jit, static_argnames=("tile_size", "sp_size", "gd_size",
                                   "with_stats", "mesh"))
def _frame_program_multi(chunks, tile_size: int, sp_size: int, gd_size: int,
                         with_stats: bool = True, mesh=None):
    """The fused frame program vmapped over a stacked chunk axis.

    ``chunks`` is (n_chunks, frame_bucket, H, W, C); with the chunk axis
    placed along a ``sats`` device ``mesh``, each device captures its
    share of the fleet's frame buckets in parallel. The body is
    per-sample, so per-chunk outputs are bit-equal to looping
    :func:`_frame_program`.
    """
    from repro.core.fleet_sharding import map_lanes
    return map_lanes(jax.vmap(lambda imgs: _tile_batch(
        imgs, tile_size, sp_size, gd_size, with_stats)), mesh)(chunks)


def _bucketed_chunks(imgs, shape, tile_size: int, sp_size: int, gd_size: int,
                     frame_bucket: int, sharding=None,
                     with_stats: bool = True):
    """Run the fused program over a same-resolution image list in whole
    ``frame_bucket``s, the last one zero-padded (the single definition of
    bucket rounding, shared by every capture entry point).

    Off-mesh, each real frame is placed on the device on its own and pad
    frames are device zeros: nothing is staged on the host, and a frame
    that is already a device array never leaves the device. Frames are
    read in place, and the copy to the device may still be in flight
    when this returns, so a caller must not mutate a frame array until
    the pass it was ingested in has its results.

    With an on-mesh :class:`~repro.core.fleet_sharding.FleetSharding`,
    the chunks are stacked in one host array, lane-padded to a device
    multiple, and run as ONE sharded :func:`_frame_program_multi` call —
    capture parallelizes across the mesh instead of queueing per-chunk on
    one device.
    """
    from repro.core.fleet_sharding import ctx
    sh = ctx(sharding)
    nb = -(-len(imgs) // frame_bucket) * frame_bucket
    obs.count("capture.frames_real", len(imgs))
    n_chunks = nb // frame_bucket
    if sh.on_mesh and n_chunks > 1:
        # pad the chunk axis to a power-of-two bucket x device multiple:
        # chunk counts vary per round, and the stacked program compiles
        # per chunk count — bucketing bounds the program count
        n_stack = sh.pad(bucket_size(n_chunks, 1))
        with obs.span("capture.fill"):
            chunks_arr = np.zeros((n_stack, frame_bucket, *shape), np.float32)
            flat = chunks_arr.reshape(-1, *shape)
            for j, img in enumerate(imgs):
                flat[j] = img
        obs.count("capture.frames_staged", len(imgs))
        obs.count("capture.frames_computed", n_stack * frame_bucket)
        obs.count("capture.h2d_bytes", chunks_arr.nbytes)
        with obs.span("capture.to_device"):
            stacked = sh.device_put(jnp.asarray(chunks_arr))
        with obs.span("capture.program"):
            outs = _frame_program_multi(stacked, tile_size, sp_size, gd_size,
                                        with_stats, mesh=sh.mesh)
        return [tuple(o[i] for o in outs) for i in range(n_chunks)]
    obs.count("capture.frames_computed", nb)
    n_host = sum(not isinstance(img, jax.Array) for img in imgs)
    obs.count("capture.h2d_bytes", n_host * 4 * int(np.prod(shape)))
    out = []
    for c0 in range(0, nb, frame_bucket):
        with obs.span("capture.fill"):
            bucket = list(imgs[c0:c0 + frame_bucket])
        with obs.span("capture.to_device"):
            frames = [jnp.asarray(img, jnp.float32) for img in bucket]
            n_pad = frame_bucket - len(frames)  # only the last bucket pads
            if n_pad:
                frames += [jnp.zeros(shape, jnp.float32)] * n_pad
        with obs.span("capture.program"):
            out.append(_frame_program(tuple(frames), tile_size, sp_size,
                                      gd_size, with_stats))
    return out


def _per_frame_pieces(frames, tile_size: int, sp_size: int, gd_size: int,
                      frame_bucket: int, sharding=None,
                      with_stats: bool = True):
    """Run the fused frame program grouped by resolution; return the
    (tiles_sp, tiles_gd[, moments, roi_std]) piece of EVERY frame, in
    input order. Each frame's piece is a pure function of that frame
    alone (the program is per-sample), so any regrouping of frames into
    buckets yields bit-identical pieces."""
    groups: dict = {}
    for i, (img, _, _) in enumerate(frames):
        # np.shape reads the .shape attribute — np.asarray(img).shape
        # would materialize a full host copy of a device-resident frame
        # just to group it
        groups.setdefault(np.shape(img), []).append(i)
    per_frame = [None] * len(frames)
    for shape, idxs in groups.items():
        chunks = _bucketed_chunks([frames[i][0] for i in idxs], shape,
                                  tile_size, sp_size, gd_size, frame_bucket,
                                  sharding=sharding, with_stats=with_stats)
        ntile = chunks[0][0].shape[0] // frame_bucket
        for j, i in enumerate(idxs):
            ck, off = chunks[j // frame_bucket], (j % frame_bucket) * ntile
            per_frame[i] = tuple(a[off:off + ntile] for a in ck)
    return per_frame


def _assemble(parts, frames, tile_size: int, roi_std=None,
              n: int = None, defer_stats: bool = False) -> PreparedFrames:
    """Per-frame pieces (input order) -> one bucket-padded PreparedFrames.

    ``roi_std``: optional precomputed (n,) ROI stddev rows (the
    multi-workload path transfers the fleet's roi_std in one
    device->host copy and hands out slices — or device slices under
    ``defer_stats``). ``n``: explicit real tile count when the pieces
    carry trailing pad-frame rows (the single-resolution fast paths pass
    whole program chunks). ``defer_stats=True`` leaves ``roi_std`` a
    device array (a lazy slice of the fused program's output) instead of
    forcing the device->host sync here — the caller fetches it at its
    own round boundary, or never (policies that don't use ROI)."""
    from repro.data.synthetic import tile_counts

    with obs.span("capture.assemble"):
        if n is None:
            n = sum(p[0].shape[0] for p in parts)

        def cat(j):
            return parts[0][j] if len(parts) == 1 else jnp.concatenate(
                [p[j] for p in parts])

        n_pad = bucket_size(n)

        def pad(a):
            if a.shape[0] == n_pad:
                return a
            if a.shape[0] > n_pad:
                return a[:n_pad]
            return jnp.concatenate(
                [a, jnp.zeros((n_pad - a.shape[0], *a.shape[1:]), a.dtype)])

        with_stats = len(parts[0]) == 4
        tiles_sp = pad(cat(0))
        tiles_gd = pad(cat(1))
        moments = pad(cat(2)) if with_stats else None
        if roi_std is None and with_stats:
            rs = pad(cat(3))[:n]
            # analysis: waive(host-sync): the per-workload roi_std copy is the
            # designed transfer point; defer_stats keeps it lazy on device
            roi_std = rs if defer_stats else np.asarray(rs)
        true = np.concatenate([
            tile_counts(boxes, np.shape(img)[0], tile_size)
            for img, boxes, _ in frames
        ]).astype(np.float64)
        return PreparedFrames(tiles_sp, tiles_gd, moments, roi_std, true, n)


def _empty_prepared(sp_size: int, gd_size: int,
                    with_stats: bool = True) -> PreparedFrames:
    n_pad = bucket_size(0)
    return PreparedFrames(
        tiles_sp=jnp.zeros((n_pad, sp_size, sp_size, 3), jnp.float32),
        tiles_gd=jnp.zeros((n_pad, gd_size, gd_size, 3), jnp.float32),
        moments=jnp.zeros((n_pad, 9), jnp.float32) if with_stats else None,
        roi_std=np.zeros(0) if with_stats else None,
        true=np.zeros(0, np.float64), n=0)


def prepare_frames_multi(workloads, tile_size: int, sp_size: int,
                         gd_size: int,
                         frame_bucket: int = FRAME_BUCKET, sharding=None,
                         with_stats: bool = True,
                         defer_stats: bool = False):
    """Constellation-batched capture: N independent frame workloads (one
    per satellite) flow through SHARED frame buckets of the fused
    program, then split back into one :class:`PreparedFrames` per
    workload.

    Per-workload outputs are bit-identical (real rows) to calling
    :func:`prepare_frames` on each workload alone — the fused program is
    per-sample, so bucket composition never perturbs a frame's tiles —
    but the padded-bucket cost is paid once across the fleet instead of
    once per satellite: 8 satellites with 2 frames each run 4 full
    buckets instead of 8 half-empty ones. ``sharding``: optional
    :class:`~repro.core.fleet_sharding.FleetSharding`; on-mesh, the
    shared frame buckets are placed along the ``sats`` mesh axis and
    captured in one sharded program call per resolution.

    ``defer_stats=True`` (the fleet's ``ingest_overlap`` path) skips the
    fleet-wide ``roi_std`` device->host copy: each workload's
    ``PreparedFrames.roi_std`` is then a *device* slice of the fused
    program's output (values bit-identical), and the caller materializes
    it lazily — only for satellites whose policy reads it, and only when
    it reaches its round's resolution boundary.
    """
    flat = [f for w in workloads for f in w]
    if not flat:
        return [_empty_prepared(sp_size, gd_size, with_stats)
                for _ in workloads]

    shapes = {np.shape(img) for img, _, _ in flat}
    if len(shapes) == 1:
        # common case (one frame resolution fleet-wide): run the shared
        # buckets once and hand each workload a contiguous slice of the
        # chunk outputs — no per-frame device slicing
        (shape,) = shapes
        chunks = _bucketed_chunks([img for img, _, _ in flat], shape,
                                  tile_size, sp_size, gd_size, frame_bucket,
                                  sharding=sharding, with_stats=with_stats)
        ntile = chunks[0][0].shape[0] // frame_bucket
        if len(chunks) == 1:
            cat = list(chunks[0])
        else:
            cat = [jnp.concatenate([ck[j] for ck in chunks])
                   for j in range(len(chunks[0]))]
        # ONE device->host copy of the fleet's ROI stats — or, under
        # defer_stats, no copy at all: workloads get lazy device slices
        roi_all = cat[3] if with_stats else None
        if with_stats and not defer_stats:
            # analysis: waive(host-sync): ONE fleet-wide ROI-stat copy per
            # ingest (see comment above); defer_stats elides it entirely
            roi_all = np.asarray(roi_all)
        out, pos = [], 0
        for w in workloads:
            if not w:
                out.append(_empty_prepared(sp_size, gd_size, with_stats))
                continue
            parts = [tuple(a[pos * ntile:(pos + len(w)) * ntile] for a in cat)]
            roi = (roi_all[pos * ntile:(pos + len(w)) * ntile]
                   if with_stats else None)
            pos += len(w)
            out.append(_assemble(parts, w, tile_size, roi_std=roi))
        return out

    per_frame = _per_frame_pieces(flat, tile_size, sp_size, gd_size,
                                  frame_bucket, sharding=sharding,
                                  with_stats=with_stats)
    out, pos = [], 0
    for w in workloads:
        if not w:
            out.append(_empty_prepared(sp_size, gd_size, with_stats))
            continue
        parts = per_frame[pos:pos + len(w)]
        pos += len(w)
        out.append(_assemble(parts, w, tile_size, defer_stats=defer_stats))
    return out


def prepare_frames(frames, tile_size: int, sp_size: int, gd_size: int,
                   frame_bucket: int = FRAME_BUCKET,
                   with_stats: bool = True) -> PreparedFrames:
    """Run the fused frame program over a workload of (img, boxes, classes).

    Frames are grouped by resolution and processed in fixed-size buckets
    (zero-padded), so the number of compiled programs is bounded by the
    number of distinct frame shapes — not by workload size. Ground-truth
    counts are collected host-side alongside. ``with_stats=False`` skips
    the moments/ROI statistics (policies that use neither); tiles are
    bit-identical either way.
    """
    if not frames:
        return _empty_prepared(sp_size, gd_size, with_stats)

    groups: dict = {}
    for i, (img, _, _) in enumerate(frames):
        groups.setdefault(np.shape(img), []).append(i)

    if len(groups) == 1:
        # common case (one frame resolution): chunk outputs are already in
        # frame order — pad frames land at the tail and fold into
        # _assemble's tile padding, so no per-frame reassembly is needed
        (shape, idxs), = groups.items()
        parts = _bucketed_chunks([frames[i][0] for i in idxs], shape,
                                 tile_size, sp_size, gd_size, frame_bucket,
                                 with_stats=with_stats)
        ntile = parts[0][0].shape[0] // frame_bucket
        return _assemble(parts, frames, tile_size, n=ntile * len(idxs))

    parts = _per_frame_pieces(frames, tile_size, sp_size, gd_size,
                              frame_bucket, with_stats=with_stats)
    return _assemble(parts, frames, tile_size)
