"""Ahead-of-time compiles of the TPU path for a described v5e chip.

Every kernel and XLA program that ``repro.join`` and the dedup service
run on a TPU is lowered and compiled here against a ``v5e:2x2``
topology description, at the widths of the repo's Table-1 profiles
(``data/synth.DATASETS``, scale 1.0). Nothing runs: these tests catch
what the TPU compiler refuses (misaligned blocks, VMEM overruns,
unlowerable ops) without a chip. The topology is described inside a
fixture, never at import, and the tests stay in this one file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import distributed, tile_join
from repro.kernels import bitmap_join, lfvt_walk, onehot_join, ops

HBM_BYTES = 16 * 10 ** 9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits_chip(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used


# (m, n, universe) of the bitmap-family profiles: one R block of the
# single-device driver (global_config.r_block rows) against all of S
ENRON = (1024, 3000, 7900)
DBLP = (1024, 5000, 27500)


BITMAP = (bitmap_join.bitmap_join_live_tiled, bitmap_join.DEFAULT_TILES)
ONEHOT = (onehot_join.onehot_join_live_tiled, onehot_join.DEFAULT_TILES)


@pytest.mark.parametrize("kernel,shape", [
    (BITMAP, ENRON), (BITMAP, DBLP), (ONEHOT, ENRON)],
    ids=["bitmap-enron", "bitmap-dblp", "onehot-enron"])
def test_live_kernel_compiles(one_chip, kernel, shape):
    """A live-tiled Pallas join kernel at ``ops.pick_tiles``' tiles."""
    live_fn, defaults = kernel
    m, n, universe = shape
    w = (universe + 31) // 32
    tm, tn, tw = ops.pick_tiles(m, n, w, defaults)
    mp, np_, wp = -(-m // tm) * tm, -(-n // tn) * tn, -(-w // tw) * tw
    n_live = (mp // tm) * (np_ // tn)
    i32 = jnp.int32
    args = [_spec((n_live,), i32, one_chip), _spec((n_live,), i32, one_chip),
            _spec((mp, wp), jnp.uint32, one_chip), _spec((mp, 1), i32, one_chip),
            _spec((np_, wp), jnp.uint32, one_chip),
            _spec((1, np_), i32, one_chip),
            _spec((mp, 1), i32, one_chip), _spec((mp, 1), i32, one_chip)]
    compiled = jax.jit(
        lambda *a: live_fn(*a, t=0.8, tiles=(tm, tn, tw))).lower(
            *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_chip(compiled)


@pytest.mark.parametrize("shape", [ENRON, DBLP], ids=["enron", "dblp"])
def test_popcount_qualify_compiles(one_chip, shape):
    """The XLA popcount path (method='popcount') for one R block."""
    m, n, universe = shape
    w = (universe + 31) // 32
    i32 = jnp.int32
    args = [_spec((m, w), jnp.uint32, one_chip), _spec((m,), i32, one_chip),
            _spec((n, w), jnp.uint32, one_chip), _spec((n,), i32, one_chip),
            _spec((m,), i32, one_chip), _spec((m,), i32, one_chip)]
    compiled = tile_join._popcount_qualify.lower(
        *args, t=0.8, measure="jaccard").compile()
    _fits_chip(compiled)


def test_lfvt_walk_dispatched_on_tpu_compiles(one_chip):
    """The walk ``ops.lfvt_walk_join_pairs_dispatch`` launches on a TPU
    (the compiled jnp twin) for one dblp R block: 70,817 S tuples, 5000
    S columns, R sets of up to 45 elements."""
    tm = 16
    mp, lr, tp, np_ = 1024, 45, 70912, 5120
    i32 = jnp.int32
    args = [_spec((mp // tm,), i32, one_chip),
            _spec((mp, lr), i32, one_chip), _spec((mp, lr), i32, one_chip),
            _spec((1, tp), i32, one_chip), _spec((1, tp), i32, one_chip),
            _spec((1, np_), i32, one_chip), _spec((mp, 1), i32, one_chip),
            _spec((mp, 1), i32, one_chip), _spec((mp, 1), i32, one_chip)]
    compiled = lfvt_walk.lfvt_walk_live_tiled_ref.lower(
        *args, t=0.8, measure="jaccard", max_steps=1024, tm=tm).compile()
    _fits_chip(compiled)


def test_mesh_walk_body_compiles(topo):
    """One bucket of the mesh LFVT path (``method='lfvt'`` with a mesh)
    on the four described chips: a dblp-sized shard per device."""
    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("data",))
    sh = NamedSharding(mesh, P("data"))
    k, ep, tp, np_, mp, lr = 4, 8192, 24576, 1280, 1280, 45
    i32 = jnp.int32
    shapes = [(k, ep), (k, ep), (k, ep), (k, tp), (k, tp), (k, np_),
              (k, mp, lr), (k, mp), (k, mp), (k, mp)]
    fn = distributed._lfvt_walk_fn(mesh, "data", 0.8, "jaccard", 512, 16,
                                   "planned")
    compiled = fn.lower(*[_spec(s, i32, sh) for s in shapes]).compile()
    _fits_chip(compiled)
