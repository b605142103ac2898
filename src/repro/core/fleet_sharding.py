"""Device-mesh fleet runtime: shard constellation work along a ``sats`` axis.

The fleet engine batches every satellite's work into stacked device
arrays (shared fused-capture frame buckets, fleet-wide counting batches,
the vmapped multi-satellite dedup core, (n_lanes,) budget-ledger lanes).
All of those arrays are *independent per lane/chunk*, so placing their
leading axis along a one-axis device mesh turns the fleet round into an
SPMD program: each device runs the identical per-sample arithmetic on
its shard of the constellation, and XLA inserts no cross-device
collectives because nothing couples lanes.

The batched ground segment rides the same axis: a ContactPlan drain
step's lane-stacked throttle call
(:func:`repro.core.throttle.throttle_padded_batch`) and the shared
ground-recount batches place their leading *window-lane* axis along the
mesh too — contact lanes, like satellite lanes, never couple, so the
placement is pure SPMD and per-lane masks are unchanged.

:class:`FleetSharding` is the placement context threaded through
``fleet.py`` / ``engine.py`` / ``cascade.py`` / ``energy.py``. It
follows the off-mesh no-op pattern of :mod:`repro.sharding.ctx`: built
without a mesh, every helper degrades to identity, so the single-device
fleet path (and every existing test) runs through the exact same code
unchanged.

Parity story: on the CPU backend the sharded fleet is *bit-equal* to
the single-device fleet (enforced by ``tests/test_fleet.py`` under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` and by the
``benchmarks/fleet_bench.py`` multi-device sweep) — every batched
program is per-sample and sharding only changes which device computes a
lane. Backends whose batched clustering reductions may reassociate can
force the sequential per-satellite dedup core with
``Fleet(strict_parity=True)``.

Uneven fleets (``n_sats % n_devices != 0``) are handled by *lane
padding*: leading axes are zero-padded up to a device multiple before
placement (:meth:`FleetSharding.pad` / :meth:`FleetSharding.shard`),
and pad lanes are sliced off before any result is read — they never
perturb real lanes.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

SATS_AXIS = "sats"


def sats_mesh(n_devices: Optional[int] = None) -> Optional[Mesh]:
    """One-axis ``sats`` mesh over the first ``n_devices`` devices.

    ``None`` uses every visible device. Returns ``None`` (= off-mesh,
    single-device fleet path) when only one device would participate —
    callers never special-case device counts. (The CPU tests get several
    devices from ``XLA_FLAGS=--xla_force_host_platform_device_count=N``,
    set before the first jax import.)
    """
    devs = jax.devices()
    n = len(devs) if n_devices is None else int(n_devices)
    if n > len(devs):
        raise ValueError(
            f"sats_mesh: {n} devices requested but only {len(devs)} "
            f"{devs[0].platform} device(s) visible")
    if n <= 1:
        return None
    return Mesh(np.asarray(devs[:n]), (SATS_AXIS,))


def map_lanes(fn, mesh: Optional[Mesh], replicated: int = 0):
    """``fn`` (per-lane work over the leading axis of its arguments)
    run so each device computes its own lanes: under ``shard_map`` on a
    ``sats`` ``mesh``, as-is off-mesh (``mesh=None``). The first
    ``replicated`` arguments (e.g. counter weights) go whole to every
    device.

    The stacked fleet programs call Pallas kernels, and XLA cannot
    partition a Pallas kernel by itself; ``shard_map`` hands each device
    its local lanes explicitly. Lanes never couple, so no collective is
    needed."""
    if mesh is None:
        return fn

    def run(*args):
        specs = tuple(P() if i < replicated else P(SATS_AXIS)
                      for i in range(len(args)))
        # check_vma off: Pallas outputs carry no varying-axes type
        return jax.shard_map(fn, mesh=mesh, in_specs=specs,
                             out_specs=P(SATS_AXIS), check_vma=False)(*args)
    return run


def on_one_device(x):
    """``x`` committed to one device (the lowest-id device it spans).

    A program that calls a Pallas kernel outside :func:`map_lanes`
    cannot be partitioned, and an array cut from a mesh-sharded fleet
    output may span the whole mesh; single-device arrays pass as-is."""
    devs = x.sharding.device_set
    if len(devs) == 1:
        return x
    return jax.device_put(x, min(devs, key=lambda d: d.id))


class FleetSharding:
    """Placement context for the ``sats`` axis (no-op when ``mesh`` is None).

    The two primitives every sharded call site composes:

    * :meth:`pad` — round a lane/chunk count up to a device multiple.
    * :meth:`shard` — zero-pad the leading axis to that multiple and
      ``device_put`` with ``NamedSharding(P("sats", None, ...))``.

    Off-mesh both are identity (``pad(n) == n``; ``shard`` returns its
    input as-is), which is what keeps the single-device fleet byte-for-
    byte on its pre-sharding code path.
    """

    __slots__ = ("mesh", "_placements")

    def __init__(self, mesh: Optional[Mesh] = None):
        self.mesh = mesh
        # NamedSharding cache, one entry per ndim: the placement spec is
        # a pure function of (mesh, ndim), but building it per call made
        # every round's device_put re-derive sharding metadata — real
        # churn at fleet scale (hundreds of placements per round)
        self._placements: dict = {}

    @property
    def on_mesh(self) -> bool:
        return self.mesh is not None

    @property
    def n_devices(self) -> int:
        return 1 if self.mesh is None else int(self.mesh.size)

    def pad(self, n: int) -> int:
        """Smallest device multiple >= n (lane padding; identity off-mesh)."""
        nd = self.n_devices
        return -(-int(n) // nd) * nd

    def spec(self, ndim: int) -> P:
        return P(*((SATS_AXIS,) + (None,) * (ndim - 1)))

    def placement(self, ndim: int) -> NamedSharding:
        """The cached ``NamedSharding`` for an ndim-dimensional array
        (built once per ndim per mesh, reused every round)."""
        s = self._placements.get(ndim)
        if s is None:
            s = NamedSharding(self.mesh, self.spec(ndim))
            self._placements[ndim] = s
        return s

    def device_put(self, arr):
        """Place ``arr`` with its (device-multiple) leading axis split
        along ``sats``; identity off-mesh. Every real placement is
        counted in :mod:`repro.core.xfer`'s transfer ledger (the
        count-based churn gate in the fleet bench)."""
        if self.mesh is None:
            return arr
        from repro.core import xfer
        xfer.record_transfer()
        return jax.device_put(arr, self.placement(arr.ndim))

    def shard(self, arr):
        """Zero-pad the leading axis to a device multiple and place it.

        Pad rows hold zeros — every sharded fleet program is per-sample,
        so they produce garbage *in their own rows only*; callers slice
        results back to the real count. Off-mesh: identity.
        """
        if self.mesh is None:
            return arr
        n = arr.shape[0]
        n_pad = self.pad(n)
        if n_pad != n:
            arr = jnp.concatenate(
                [jnp.asarray(arr),
                 jnp.zeros((n_pad - n, *arr.shape[1:]),
                           jnp.asarray(arr).dtype)])
        return self.device_put(jnp.asarray(arr))


# the shared off-mesh singleton: call sites take `sharding=None` and
# normalize through this so `None` and "no mesh" behave identically
OFF_MESH = FleetSharding(None)


def ctx(sharding: Optional[FleetSharding]) -> FleetSharding:
    """Normalize an optional sharding argument (None -> off-mesh no-op)."""
    return OFF_MESH if sharding is None else sharding
