"""Content-keyed host->device transfer cache for control-plane arrays.

The fleet's batched round re-uploads the same *little* host arrays every
``ingest()``: pow2-padded gather indices (dedup moment gathers, counting
subset gathers), per-group lane counts / cluster counts, and stacked PRNG
keys (the dedup seed is per-config, so the key stack is literally
identical round over round). Each upload is cheap alone, but the count
scales with fleet size x rounds, and on a mesh every one also builds
placement metadata. Since these arrays are pure *values* (no aliasing,
never donated, never mutated), they can be cached by content —
``(mesh, dtype, shape, bytes)`` — and a repeated-shape scenario then
issues ZERO transfers for them after its first round.

The counters are the honest ledger the bench gates on (count-based, not
timing-based), kept as :mod:`repro.core.obs` counters
(``xfer.device_puts``, ``xfer.cache_reuses``):
``transfer_stats()['device_puts']`` counts real
host->device placements issued through this module and through
:meth:`repro.core.fleet_sharding.FleetSharding.device_put`;
``cache_reuses`` counts uploads avoided. ``tests/test_fleet.py`` and
``benchmarks/fleet_bench.py`` assert that a repeated-shape round issues
strictly fewer transfers than the cold round that preceded it.

Thread safety: recount workers (:mod:`repro.core.contact`) call
:func:`repro.core.cascade.count_tiles_multi` off the foreground thread,
so cache and counters are lock-protected.
"""
from __future__ import annotations

import threading

import numpy as np

from repro.core import obs

# cache only small control-plane arrays: index vectors, lane counts, key
# stacks. Data arrays (tiles, frames, moments) are content-unique per
# round and would only churn the dict.
_MAX_ITEM_BYTES = 1 << 16
_MAX_ENTRIES = 4096

_lock = threading.Lock()
_cache: dict = {}
_COUNTERS = {"device_puts": "xfer.device_puts",
             "cache_reuses": "xfer.cache_reuses"}


def record_transfer(n: int = 1) -> None:
    """Count ``n`` real host->device placements (called by every path
    that issues one: this module's misses and
    :meth:`FleetSharding.device_put`)."""
    obs.count(_COUNTERS["device_puts"], n)


def transfer_stats() -> dict:
    """Snapshot of the transfer counters (copies; safe to diff)."""
    c = obs.counters()
    return {k: c.get(name, 0) for k, name in _COUNTERS.items()}


def reset_transfer_stats() -> None:
    obs.reset(*_COUNTERS.values())


def clear_cache() -> None:
    """Drop every cached resident (test isolation; counters unchanged)."""
    with _lock:
        _cache.clear()


def cache_size() -> int:
    with _lock:
        return len(_cache)


def _put(arr, sharding, on_mesh):
    import jax.numpy as jnp

    dev = jnp.asarray(arr)
    if on_mesh:
        return sharding.device_put(dev)  # device_put records the transfer
    record_transfer()
    return dev


def device_constant(arr, sharding=None):
    """Return ``arr`` as a device-resident constant, cached by content.

    ``arr`` is a small host ndarray whose value tends to repeat across
    rounds. With an on-mesh
    :class:`~repro.core.fleet_sharding.FleetSharding`, the cached
    resident is placed along the ``sats`` axis (the cache key includes
    the mesh, so meshes never share residents); off-mesh it is a plain
    device array. Arrays above the size cap bypass the cache but are
    still counted as transfers. The returned array must be treated as
    immutable — every caller only gathers/consumes it.
    """
    arr = np.asarray(arr)
    on_mesh = sharding is not None and getattr(sharding, "on_mesh", False)
    if arr.nbytes > _MAX_ITEM_BYTES:
        return _put(arr, sharding, on_mesh)
    key = (id(sharding.mesh) if on_mesh else None,
           arr.dtype.str, arr.shape, arr.tobytes())
    with _lock:
        hit = _cache.get(key)
    if hit is not None:
        obs.count(_COUNTERS["cache_reuses"])
        return hit
    dev = _put(arr, sharding, on_mesh)
    with _lock:
        if len(_cache) >= _MAX_ENTRIES:
            _cache.clear()
        _cache[key] = dev
    return dev
