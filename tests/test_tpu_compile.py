"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers a kernel at the shapes the pipeline
feeds it and compiles it with the TPU compiler for one chip of a
described (not attached) ``v5e:2x2`` topology. That catches what
interpret mode cannot — unsupported primitives, layouts the chip
refuses, blocks that overflow scoped VMEM — and asserts the compiled
program calls the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest-xdist workers all import
this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import dedup
from repro.core.dedup import _buckets_for
from repro.core.fleet_sharding import SATS_AXIS
from repro.kernels.iou import iou_matrix
from repro.kernels.kmeans_assign import kmeans_assign
from repro.kernels import ops
from repro.kernels.tile_moments import tile_moments
from repro.launch import compile_cache

TILE = 416          # published counter input size
FRAME_TILES = 256   # one 4-frame bucket of 1024-px scenes, 128-px tiles


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # keep the TPU compiler's logs out of the file system
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def described(topo):
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without the chip
    with compile_cache.disabled():
        yield topo


@pytest.fixture(scope="module")
def one_chip(described):
    return SingleDeviceSharding(described.devices[0])


@pytest.fixture(scope="module")
def four_chips(described):
    return Mesh(np.asarray(described.devices[:4]), (SATS_AXIS,))


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("chunks", [None, 2], ids=["plain", "vmapped"])
def test_tile_moments_compiles_at_416(one_chip, chunks):
    """Plain, as the fused frame program calls it, and vmapped over a
    chunk axis, as ``engine._frame_program_multi`` does."""
    shape = (FRAME_TILES, TILE, TILE, 3)
    fn = tile_moments
    if chunks:
        shape, fn = (chunks, *shape), jax.vmap(tile_moments)
    _assert_kernel(_compile(fn, shape, sharding=one_chip))


@pytest.mark.parametrize("n", [100, 256, 300])
@pytest.mark.parametrize("k_stage", ["init", "lloyd"])
def test_kmeans_assign_compiles_at_dedup_buckets(one_chip, n, k_stage):
    """The padded (n_pad, 9) dedup features against one centroid
    (k-means++ picks) and against the k_pad table (Lloyd, finalize)."""
    n_pad, k_pad = _buckets_for(n, n // 2)
    k = 1 if k_stage == "init" else k_pad
    _assert_kernel(_compile(kmeans_assign, (n_pad, 9), (k, 9),
                            sharding=one_chip))


@pytest.mark.parametrize("batch", [None, 64], ids=["alone", "vmapped"])
def test_iou_matrix_compiles_for_nms(one_chip, batch):
    """NMS's 128 top boxes per tile, alone and vmapped over a 64-tile
    counting batch."""
    fn = lambda b: iou_matrix(b, b)  # noqa: E731
    shape = (128, 4)
    if batch:
        shape, fn = (batch, *shape), jax.vmap(fn)
    _assert_kernel(_compile(fn, shape, sharding=one_chip))


def test_sharded_dedup_core_compiles_on_four_chips(four_chips, monkeypatch):
    """The fleet's stacked programs call Pallas kernels, which XLA cannot
    partition: on a ``sats`` mesh they must run under ``shard_map``.
    Compiled here for the 2x2 mesh with the kernels dispatched as on
    the chip."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    n_pad, k_pad = _buckets_for(256, 128)

    def lanes(shape, dtype):
        spec = P(SATS_AXIS, *([None] * (len(shape) - 1)))
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(four_chips, spec))

    core = jax.jit(lambda m, n, k, key: dedup._dedup_multi_core(
        m, n, k, key, k_pad=k_pad, iters=10, mesh=four_chips))
    compiled = core.lower(lanes((4, n_pad, 9), jnp.float32),
                          lanes((4,), jnp.int32), lanes((4,), jnp.int32),
                          lanes((4, 2), jnp.uint32)).compile()
    _assert_kernel(compiled)
