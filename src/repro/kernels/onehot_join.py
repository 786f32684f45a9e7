"""Pallas TPU kernel: candidate-free one-hot matmul join (MXU path).

The FVT traversal as systolic compute (DESIGN.md §2/§5): each universe
block of TW uint32 words is unpacked in VMEM to a (tile, TW*32) bf16
membership matrix, and intersection counts accumulate as
``F += B_R @ B_S^T`` on the MXU with an f32 VMEM accumulator. Counts are
exact: each product term is 0/1 and per-block sums are < 2^24.

Same candidate-free contract as bitmap_join: Jaccard threshold + window
applied in kernel, tile-level early stop via the host skip mask (dense
fallback, grid (m/TM, n/TN, W/TW), k innermost) or via the live-tile
schedule (``onehot_join_live_tiled``, DESIGN.md §6): a 1-D grid over the
host-compacted live (i, j) tile list with scalar-prefetched index maps,
emitting per-tile qualifying sub-masks + exact pair counts for the
jnp-level pair compaction in ``ops``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import measures

__all__ = ["onehot_join_tiled", "onehot_join_live_tiled", "DEFAULT_TILES"]

# (TM, TN, TW): TW = 128 words (a lane-aligned word block, see
# bitmap_join.DEFAULT_TILES), so the matmul K = TW*32 = 4096. The v5e
# compiler fits (128, 128, 128) in VMEM and refuses TN = 256 for VMEM.
DEFAULT_TILES = (128, 128, 128)


def _unpack_bits(words: jax.Array) -> jax.Array:
    """(rows, TW) uint32 -> (rows, TW*32) bf16 membership matrix."""
    rows, tw = words.shape
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, 32), 2)
    bits = jnp.bitwise_and(jnp.right_shift(words[:, :, None], shifts), jnp.uint32(1))
    # Mosaic has no uint32 -> bfloat16 cast; int32 -> bfloat16 lowers
    return bits.reshape(rows, tw * 32).astype(jnp.int32).astype(jnp.bfloat16)


def _matmul_accumulate(r_bm_ref, s_bm_ref, acc_ref):
    br = _unpack_bits(r_bm_ref[...])              # (TM, K) bf16
    bs = _unpack_bits(s_bm_ref[...])              # (TN, K) bf16
    acc_ref[...] += jax.lax.dot_general(
        br, bs, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _qualify_tile(f, r_sz_ref, s_sz_ref, lo_ref, hi_ref, j, *, t, measure,
                  tn):
    # the f32 accumulator holds exact integer counts (< 2^24): the
    # measure predicate casts to int32 and compares integer-exactly
    cols = j * tn + jax.lax.broadcasted_iota(jnp.int32, (1, tn), 1)
    in_window = (cols >= lo_ref[...]) & (cols < hi_ref[...])
    q = measures.device_qualify(f, r_sz_ref[...], s_sz_ref[...], t, measure)
    return q & in_window


def _kernel(skip_ref, r_bm_ref, s_bm_ref, r_sz_ref, s_sz_ref, lo_ref, hi_ref,
            out_ref, acc_ref, *, t: float, measure: str, n_kblocks: int,
            tn: int):
    # program_id read outside pl.when bodies (interpret-mode requirement)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(skip_ref[0, 0, 0] == 0)
    def _accumulate():
        _matmul_accumulate(r_bm_ref, s_bm_ref, acc_ref)

    @pl.when(k == n_kblocks - 1)
    def _qualify():
        out_ref[...] = _qualify_tile(acc_ref[...], r_sz_ref, s_sz_ref,
                                     lo_ref, hi_ref, j, t=t, measure=measure,
                                     tn=tn)


@functools.partial(jax.jit,
                   static_argnames=("t", "measure", "tiles", "interpret"))
def onehot_join_tiled(r_bitmaps, r_sizes, s_bitmaps, s_sizes, lo, hi, skip,
                      *, t: float, measure: str = "jaccard",
                      tiles=DEFAULT_TILES, interpret: bool = False):
    """Same contract as bitmap_join_tiled; MXU execution."""
    TM, TN, TW = tiles
    M, W = r_bitmaps.shape
    N = s_bitmaps.shape[0]
    assert M % TM == 0 and N % TN == 0 and W % TW == 0, (M, N, W, tiles)
    grid = (M // TM, N // TN, W // TW)
    n_tiles = grid[1]

    kernel = functools.partial(_kernel, t=t, measure=measure,
                               n_kblocks=grid[2], tn=TN)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1), lambda i, j, k: (i * n_tiles + j, 0, 0)),
            pl.BlockSpec((TM, TW), lambda i, j, k: (i, k)),
            pl.BlockSpec((TN, TW), lambda i, j, k: (j, k)),
            pl.BlockSpec((TM, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, TN), lambda i, j, k: (0, j)),
            pl.BlockSpec((TM, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((TM, 1), lambda i, j, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((TM, TN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.bool_),
        scratch_shapes=[pltpu.VMEM((TM, TN), jnp.float32)],
        interpret=interpret,
    )(skip.reshape(-1, 1, 1), r_bitmaps, s_bitmaps, r_sizes, s_sizes, lo, hi)


# ---------------------------------------------------------------------- #
# live-tile schedule: sparse pair emission (DESIGN.md §6)
# ---------------------------------------------------------------------- #
def _live_kernel(ti_ref, tj_ref, r_bm_ref, s_bm_ref, r_sz_ref, s_sz_ref,
                 lo_ref, hi_ref, mask_ref, cnt_ref, acc_ref, *,
                 t: float, measure: str, n_kblocks: int, tn: int):
    l = pl.program_id(0)
    k = pl.program_id(1)
    j = tj_ref[l]  # column-tile coordinate of this live tile

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # no skip gate: only live tiles exist in the grid at all
    _matmul_accumulate(r_bm_ref, s_bm_ref, acc_ref)

    @pl.when(k == n_kblocks - 1)
    def _emit():
        q = _qualify_tile(acc_ref[...], r_sz_ref, s_sz_ref, lo_ref, hi_ref,
                          j, t=t, measure=measure, tn=tn)
        mask_ref[...] = q[None]
        cnt_ref[...] = jnp.sum(q, dtype=jnp.int32).reshape(1, 1, 1)


@functools.partial(jax.jit,
                   static_argnames=("t", "measure", "tiles", "interpret"))
def onehot_join_live_tiled(tile_i, tile_j, r_bitmaps, r_sizes, s_bitmaps,
                           s_sizes, lo, hi, *, t: float,
                           measure: str = "jaccard", tiles=DEFAULT_TILES,
                           interpret: bool = False):
    """MXU join over the live tiles only; contract of bitmap_join_live_tiled."""
    TM, TN, TW = tiles
    M, W = r_bitmaps.shape
    N = s_bitmaps.shape[0]
    L = tile_i.shape[0]
    assert M % TM == 0 and N % TN == 0 and W % TW == 0, (M, N, W, tiles)
    grid = (L, W // TW)

    kernel = functools.partial(_live_kernel, t=t, measure=measure,
                               n_kblocks=grid[1], tn=TN)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TM, TW), lambda l, k, ti, tj: (ti[l], k)),
            pl.BlockSpec((TN, TW), lambda l, k, ti, tj: (tj[l], k)),
            pl.BlockSpec((TM, 1), lambda l, k, ti, tj: (ti[l], 0)),
            pl.BlockSpec((1, TN), lambda l, k, ti, tj: (0, tj[l])),
            pl.BlockSpec((TM, 1), lambda l, k, ti, tj: (ti[l], 0)),
            pl.BlockSpec((TM, 1), lambda l, k, ti, tj: (ti[l], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, TM, TN), lambda l, k, ti, tj: (l, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda l, k, ti, tj: (l, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((TM, TN), jnp.float32)],
    )
    mask, counts = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((L, TM, TN), jnp.bool_),
            jax.ShapeDtypeStruct((L, 1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(tile_i, tile_j, r_bitmaps, s_bitmaps, r_sizes, s_sizes, lo, hi)
    return mask, counts.reshape(L, 1)
