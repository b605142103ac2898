"""Clustering-based data deduplication (paper §III-C).

Tiles are embedded with the color-moments featurizer (rotation/
translation-invariant global channel statistics — matching the paper's
requirement that contexts survive 'geographic label transformations'),
k-means-clustered into geographic contexts, and only the tile nearest
each centroid is processed/downlinked. Cluster sizes are retained so the
representative's count stands for the whole context.

The pipeline engine computes tile moments once per frame batch and
enters through :func:`dedup_from_moments`; :func:`dedup` keeps the
featurize-from-raw-tiles entry point.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import xfer
from repro.kernels import ops as kops

# shape-stable dedup: inputs are zero-padded to power-of-two bucket sizes
# so the compiled program count grows with log(workload), not workload
_N_BUCKET = 64
_K_BUCKET = 16
_FAR = 1e15  # sentinel for unused centroid slots (d2 stays finite in f32)


def bucket_size(v: int, floor: int = _N_BUCKET) -> int:
    """Next power-of-two bucket >= max(v, floor) for shape-stable padding."""
    b = floor
    while b < v:
        b *= 2
    return b


def dedup_pad_size(n: int) -> int:
    """Input bucket `dedup_from_moments` expects for a pre-padded gather."""
    return bucket_size(n, 2 * _N_BUCKET)


class DedupResult(NamedTuple):
    assign: jnp.ndarray        # (N,) int32 cluster id
    centroids: jnp.ndarray     # (K, D)
    rep_mask: jnp.ndarray      # (N,) bool — True for cluster representatives
    cluster_sizes: jnp.ndarray  # (K,) int32
    rep_idx: jnp.ndarray       # (K,) int32 index of each cluster's representative


def normalize_moments(f: jnp.ndarray) -> jnp.ndarray:
    """(N, D) raw color moments -> centered features.

    Centered per feature but scaled by one GLOBAL factor: per-feature
    z-scoring would blow up low-information dimensions (e.g. nearly
    constant tile stds) into pure noise axes and break the clustering.
    """
    mu = jnp.mean(f, 0, keepdims=True)
    scale = jnp.std(f) + 1e-6
    return (f - mu) / scale


def features(tiles: jnp.ndarray) -> jnp.ndarray:
    """(N, H, W, C) -> (N, 3C) normalized color-moment features."""
    return normalize_moments(kops.tile_moments(tiles))


def _kmeanspp_init(x, k, key):
    """k-means++ (greedy D² farthest-point) initialization, incremental.

    Maintains a running min-d² vector updated against only the newest
    centroid: O(N·D) per pick instead of re-scoring all K centroids
    (O(N·K·D)) on every scan step like `_kmeanspp_init_scan`.
    """
    n = x.shape[0]
    first = jax.random.randint(key, (), 0, n)
    cent0 = x[first]
    _, d2 = kops.kmeans_assign(x, cent0[None])

    def pick(carry, i):
        cents, d2 = carry
        nxt = jnp.argmax(d2)  # greedy farthest point (deterministic)
        c = x[nxt]
        cents = jax.lax.dynamic_update_slice(cents, c[None], (i, 0))
        _, d2_new = kops.kmeans_assign(x, c[None])
        return (cents, jnp.minimum(d2, d2_new)), None

    cents = jnp.tile(cent0[None], (k, 1))
    (cents, _), _ = jax.lax.scan(pick, (cents, d2), jnp.arange(1, k))
    return cents


def _kmeanspp_init_scan(x, k, key):
    """Pre-engine init: full kmeans_assign against all K slots per pick.

    Kept as the equivalence reference for `_kmeanspp_init` (the unfilled
    slots duplicate centroid 0, so the min-distance — and therefore the
    pick sequence — is identical).
    """
    n = x.shape[0]
    first = jax.random.randint(key, (), 0, n)
    cent0 = x[first]

    def pick(carry, key_i):
        cents, i = carry
        _, d2 = kops.kmeans_assign(x, cents)
        nxt = jnp.argmax(d2)
        cents = jax.lax.dynamic_update_slice(cents, x[nxt][None], (i, 0))
        return (cents, i + 1), None

    cents = jnp.tile(cent0[None], (k, 1))
    (cents, _), _ = jax.lax.scan(pick, (cents, 1), jnp.arange(k - 1))
    return cents


def kmeans(x: jnp.ndarray, k: int, key, iters: int = 10):
    """k-means with k-means++ init. Returns (assign, centroids, d2)."""
    cent = _kmeanspp_init(x, k, key)

    def step(cent, _):
        assign, _ = kops.kmeans_assign(x, cent)
        one = jax.nn.one_hot(assign, k, dtype=x.dtype)  # (N, K)
        tot = jnp.einsum("nk,nd->kd", one, x)
        cnt = jnp.sum(one, 0)[:, None]
        new = jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1), cent)
        return new, None

    cent, _ = jax.lax.scan(step, cent, None, length=iters)
    assign, d2 = kops.kmeans_assign(x, cent)
    return assign, cent, d2


def dedup(tiles: jnp.ndarray, k: int, key, iters: int = 10) -> DedupResult:
    """Full dedup pass: featurize -> cluster -> pick representatives."""
    return dedup_from_moments(kops.tile_moments(tiles), k, key, iters)


def _dedup_core_body(m_pad, n, k, key, *, k_pad: int, iters: int):
    """Shape-stable featurize + k-means over padded raw moments.

    ``m_pad`` is (n_pad, D) with real rows [:n]; rows past ``n`` may
    hold ANY finite values (zero padding or junk from a padded gather) —
    the first masked `where` zeroes them, after which every path is a
    pure function of the real rows. ``n`` and ``k`` are dynamic scalars,
    so ONE compilation per (n_pad, k_pad) bucket serves every workload
    size — successive orbital passes of different sizes stop triggering
    fresh XLA compiles of the clustering scans. Pad rows carry weight 0
    in every centroid update and never win the farthest-point argmax;
    unused centroid slots sit at a far sentinel no point can select, so
    real clusters evolve exactly as if the pads were absent.
    """
    n_pad, d = m_pad.shape
    mask = jnp.arange(n_pad) < n
    maskc = mask[:, None]
    nf = n.astype(jnp.float32)

    # masked normalize_moments (same two-pass mean / global-std formula)
    m0 = jnp.where(maskc, m_pad, 0.0)
    mu = jnp.sum(m0, 0, keepdims=True) / nf
    gmu = jnp.sum(m0) / (nf * d)
    var = jnp.sum(jnp.where(maskc, jnp.square(m_pad - gmu), 0.0)) / (nf * d)
    scale = jnp.sqrt(var) + 1e-6
    x = jnp.where(maskc, (m_pad - mu) / scale, 0.0)

    # --- incremental k-means++ init (O(N·D) per pick), masked ---
    first = jax.random.randint(key, (), 0, n)
    cent0 = x[first]
    _, d2 = kops.kmeans_assign(x, cent0[None])
    far = jnp.full((d,), jnp.float32(_FAR), x.dtype)

    def pick(carry, i):
        cents, d2 = carry
        nxt = jnp.argmax(jnp.where(mask, d2, -jnp.inf))
        c = jnp.where(i < k, x[nxt], far)
        cents = jax.lax.dynamic_update_slice(cents, c[None], (i, 0))
        _, d2n = kops.kmeans_assign(x, c[None])
        d2 = jnp.where(i < k, jnp.minimum(d2, d2n), d2)
        return (cents, d2), None

    cents = jnp.tile(cent0[None], (k_pad, 1))
    (cents, _), _ = jax.lax.scan(pick, (cents, d2), jnp.arange(1, k_pad))

    # --- Lloyd iterations, masked ---
    def step(cent, _):
        assign, _ = kops.kmeans_assign(x, cent)
        one = jax.nn.one_hot(assign, k_pad, dtype=x.dtype) * maskc
        tot = jnp.einsum("nk,nd->kd", one, x)
        cnt = jnp.sum(one, 0)[:, None]
        new = jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1), cent)
        return new, None

    cent, _ = jax.lax.scan(step, cents, None, length=iters)
    return x, cent


_dedup_padded_core = partial(jax.jit, static_argnames=("k_pad", "iters"))(
    _dedup_core_body)


def _dedup_finalize_body(x_pad, cent, nj):
    """Final assignment + representative pick over the padded features.

    ``nj`` stays an operand so one compiled program per (n_pad, k_pad)
    bucket serves every workload size.
    """
    n_pad = x_pad.shape[0]
    k_pad = cent.shape[0]
    assign, d2 = kops.kmeans_assign(x_pad, cent)
    mask = jnp.arange(n_pad) < nj
    big = jnp.float32(1e30)
    d2m = jnp.where(mask, d2, big)
    per_cluster = jnp.full((k_pad,), big).at[assign].min(d2m)
    is_min = d2m <= per_cluster[assign] + 0.0
    idxs = jnp.arange(n_pad)
    rep_idx = jnp.full((k_pad,), n_pad, jnp.int32).at[assign].min(
        jnp.where(is_min & mask, idxs, n_pad).astype(jnp.int32))
    rep_found = rep_idx < nj
    rep_clip = jnp.clip(rep_idx, 0, nj - 1)
    # scatter-max: duplicate empty-cluster writes can't clobber a real rep
    rep_mask = jnp.zeros((n_pad,), bool).at[rep_clip].max(rep_found)
    sizes = jnp.zeros((k_pad,), jnp.int32).at[assign].add(mask.astype(jnp.int32))
    return assign, rep_mask, sizes, rep_clip


_dedup_finalize = jax.jit(_dedup_finalize_body)


# --- vmapped multi-satellite core (one call per bucket, no per-sat loop) ---

@partial(jax.jit, static_argnames=("k_pad", "iters", "mesh"))
def _dedup_multi_core(m_pad, n, k, key, *, k_pad: int, iters: int,
                      mesh=None):
    """:func:`_dedup_core_body` batched over a leading sat axis.

    Inputs stack one satellite per leading row: ``m_pad`` (S, n_pad, D),
    ``n``/``k`` (S,) int32, ``key`` (S, 2). The body is per-sample, so
    lane *i* computes exactly the sequential core's arithmetic for
    satellite *i* — batching (and sharding the sat axis across a device
    mesh) changes which device runs a lane, not what it computes.
    """
    from repro.core.fleet_sharding import map_lanes
    return map_lanes(jax.vmap(
        lambda m, nn, kk, ke: _dedup_core_body(m, nn, kk, ke,
                                               k_pad=k_pad, iters=iters)
    ), mesh)(m_pad, n, k, key)


@partial(jax.jit, static_argnames=("mesh",))
def _dedup_finalize_multi(x_pad, cent, nj, mesh=None):
    """:func:`_dedup_finalize_body` batched over a leading sat axis."""
    from repro.core.fleet_sharding import map_lanes
    return map_lanes(jax.vmap(_dedup_finalize_body), mesh)(x_pad, cent, nj)


def _buckets_for(n: int, k: int):
    """(n_pad, k_pad) shape bucket of one dedup workload.

    n_pad is floored at 2x the base bucket so small passes share the
    compiled core with mid-size ones; k's bucket is tied to n's so one
    compiled core serves each size bucket (k <= n/2 in every pipeline
    call; bucket up for odd explicit k).
    """
    n_pad = dedup_pad_size(n)
    k_pad = (n_pad // 2 if int(k) <= n_pad // 2
             else bucket_size(int(k), _K_BUCKET))
    return n_pad, k_pad


def _pad_rows(moments, n: int, n_pad: int):
    d = int(moments.shape[1])
    if int(moments.shape[0]) == n_pad:
        return jnp.asarray(moments)
    return jnp.zeros((n_pad, d), jnp.float32).at[:n].set(moments[:n])


def dedup_from_moments(moments: jnp.ndarray, k: int, key, iters: int = 10,
                       n: int = None) -> DedupResult:
    """Dedup pass over raw color moments: featurize -> cluster -> reps.

    The canonical clustering path — the engine AND the reference host
    path both enter here, so identical real rows yield bit-identical
    results. ``moments`` is (N, 3C); pass ``n`` when the trailing rows
    are padding from an already-bucketed gather (their values are
    ignored). Everything runs on power-of-two padded shapes: one
    compiled program per size bucket serves every workload.
    """
    from repro.core.fleet_sharding import on_one_device
    n = int(moments.shape[0]) if n is None else int(n)
    n_pad, k_pad = _buckets_for(n, k)
    nj = jnp.int32(n)
    m_pad = on_one_device(_pad_rows(moments, n, n_pad))
    x_pad, cent = _dedup_padded_core(m_pad, nj, jnp.int32(k), key,
                                     k_pad=k_pad, iters=iters)
    assign, rep_mask, sizes, rep_clip = _dedup_finalize(x_pad, cent, nj)
    return DedupResult(assign[:n], cent[:k], rep_mask[:n], sizes[:k],
                       rep_clip[:k])


def dedup_multi(parts, iters: int = 10, sharding=None):
    """Batched multi-satellite dedup: the whole constellation's
    clustering in one vmapped core call per shape bucket.

    ``parts``: list of ``(moments, k, key, n)`` — one entry per
    satellite, where ``moments`` is that satellite's (possibly already
    bucket-padded) raw color moments and ``n`` its real row count
    (``None`` = all rows real). Satellites are grouped by their
    (n_pad, k_pad) shape bucket; each group runs
    :func:`_dedup_multi_core` + the vmapped finalize ONCE, eliminating
    ingest's last per-satellite Python loop (~the k-means dispatch cost
    per sat per round). With a :class:`~repro.core.fleet_sharding.
    FleetSharding` mesh context, each group's sat axis is placed along
    the ``sats`` mesh axis (lane-padded to a device multiple with inert
    duplicate rows; pad lanes are dropped before results are read).

    Per-satellite results are bit-equal on CPU to calling
    :func:`dedup_from_moments` per satellite (enforced by
    tests/test_fleet.py); backends whose batched reductions reassociate
    should use the sequential path via ``Fleet(strict_parity=True)``.

    Returns a list of :class:`DedupResult` aligned with ``parts``.
    """
    from repro.core.fleet_sharding import ctx
    sh = ctx(sharding)
    groups = {}
    for slot, (moments, k, key, n) in enumerate(parts):
        n = int(moments.shape[0]) if n is None else int(n)
        bucket = _buckets_for(n, k)
        groups.setdefault(bucket, []).append((slot, moments, k, key, n))
    out = [None] * len(parts)
    for (n_pad, k_pad), items in groups.items():
        m = jnp.stack([_pad_rows(mo, n, n_pad) for _, mo, _, _, n in items])
        ns = np.asarray([n for *_, n in items], np.int32)
        ks = np.asarray([k for _, _, k, _, _ in items], np.int32)
        # keys are stacked host-side (keys come straight from host
        # seeds, so this forces no real compute) and uploaded through
        # the content-keyed transfer cache below — the fleet's dedup
        # seeds repeat every round, so steady-state rounds re-upload
        # neither the key stack nor the lane/cluster count vectors
        keys = np.stack([np.asarray(key) for _, _, _, key, _ in items])
        g = len(items)
        # lane-pad the sat axis to a power-of-two bucket (then to a
        # device multiple on-mesh): group sizes vary round to round and
        # fleet to fleet, and the stacked cores compile per lane count —
        # bucketing bounds that at log2(fleet) programs per shape bucket
        g_pad = sh.pad(bucket_size(g, 1))
        if g_pad != g:
            # inert pad lanes: repeat lane 0 (all-real shapes, so the
            # padded program never sees degenerate n=0 inputs)
            reps = np.zeros(g_pad - g, np.int64)
            m = jnp.concatenate([m, m[xfer.device_constant(reps)]])
            ns = np.concatenate([ns, ns[reps]])
            ks = np.concatenate([ks, ks[reps]])
            keys = np.concatenate([keys, keys[reps]])
        m = sh.device_put(m)
        ns_j = xfer.device_constant(ns, sharding=sh)
        ks_j = xfer.device_constant(ks, sharding=sh)
        keys = xfer.device_constant(keys, sharding=sh)
        x, cent = _dedup_multi_core(m, ns_j, ks_j, keys, k_pad=k_pad,
                                    iters=iters, mesh=sh.mesh)
        assign, rep_mask, sizes, rep_clip = _dedup_finalize_multi(
            x, cent, ns_j, mesh=sh.mesh)
        for i, (slot, _, k, _, n) in enumerate(items):
            out[slot] = DedupResult(assign[i, :n], cent[i, :k],
                                    rep_mask[i, :n], sizes[i, :k],
                                    rep_clip[i, :k])
    return out


def expanded_counts(rep_counts: jnp.ndarray, res: DedupResult) -> jnp.ndarray:
    """Counts measured on representatives only -> per-tile estimated counts
    (each tile inherits its cluster representative's count)."""
    return rep_counts[res.rep_idx][res.assign]
