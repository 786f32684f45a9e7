"""Pallas TPU kernel: candidate-free bitmap-popcount join (VPU path).

The LFVT adaptation (DESIGN.md §2/§5): S membership is packed 32 universe
elements per uint32 lane. The kernel walks the universe in TW-word blocks
(the "tree traversal" = the k grid dimension), accumulating intersection
counts in a VMEM scratch tile, and on the last block applies the Jaccard
threshold and the Lemma-3.1 column window *in kernel* — only a boolean
qualifying tile ever leaves VMEM (candidate-free: no pair list, no counts
are spilled to HBM).

Tile-level early stop (Theorem 3.3) comes in two flavours:

  * dense fallback (``bitmap_join_tiled``): a host-computed
    (m_tiles, n_tiles) skip mask gates the accumulation body with
    ``pl.when`` — out-of-window tiles do zero VPU work but still cost a
    (predicated) grid step. Grid (m/TM, n/TN, W/TW), k innermost.
  * live-tile schedule (``bitmap_join_live_tiled``, DESIGN.md §6): the
    host compacts the skip mask into a list of live (i, j) tile
    coordinates; the kernel runs a 1-D grid over live tiles only, with
    scalar-prefetched index maps steering the block DMAs. Skipped tiles
    contribute zero grid steps. Each live tile emits its qualifying
    sub-mask plus an exact per-tile pair count, the input to the
    jnp-level pair compaction in ``ops`` — only packed (r, s) index
    pairs ever cross the host boundary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import measures

__all__ = ["bitmap_join_tiled", "bitmap_join_live_tiled", "DEFAULT_TILES"]

# (TM, TN, TW). On the TPU a word block's last dim must be a multiple of
# 128 lanes or the whole padded W (``ops.pick_tiles`` shrinks TW to one
# block spanning a narrow W), so TW = 128. The (TM, TN, TW) uint32 AND
# intermediate is then 8 MiB at (128, 128, 128), which fits VMEM; the
# v5e compiler refuses (256, 256, 256) for VMEM. tests/test_tpu_compile.py
# compiles these tiles for a v5e chip. Their speed is not measured yet.
DEFAULT_TILES = (128, 128, 128)


def _popcount_accumulate(r_bm_ref, s_bm_ref, acc_ref):
    # (TM, 1, TW) & (1, TN, TW) -> popcount -> (TM, TN)
    inter = jnp.bitwise_and(r_bm_ref[...][:, None, :], s_bm_ref[...][None, :, :])
    acc_ref[...] += jnp.sum(
        jax.lax.population_count(inter).astype(jnp.int32), axis=-1
    )


def _qualify_tile(acc, r_sz_ref, s_sz_ref, lo_ref, hi_ref, j, *, t, measure,
                  tn):
    """Threshold + size window for one (TM, TN) tile at column-tile j.

    The predicate is the measure's integer-exact cross-multiplied
    comparison (int32 VPU ops — DESIGN.md §8), not float32: the float form
    misclassifies exact-boundary pairs.
    """
    cols = j * tn + jax.lax.broadcasted_iota(jnp.int32, (1, tn), 1)
    in_window = (cols >= lo_ref[...]) & (cols < hi_ref[...])
    q = measures.device_qualify(acc, r_sz_ref[...], s_sz_ref[...], t, measure)
    return q & in_window


def _kernel(skip_ref, r_bm_ref, s_bm_ref, r_sz_ref, s_sz_ref, lo_ref, hi_ref,
            out_ref, acc_ref, *, t: float, measure: str, n_kblocks: int,
            tn: int):
    # program_id must be read outside pl.when bodies: the interpreter only
    # substitutes it at kernel-trace time, not inside cond branch jaxprs.
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(skip_ref[0, 0, 0] == 0)
    def _accumulate():
        _popcount_accumulate(r_bm_ref, s_bm_ref, acc_ref)

    @pl.when(k == n_kblocks - 1)
    def _qualify():
        out_ref[...] = _qualify_tile(acc_ref[...], r_sz_ref, s_sz_ref,
                                     lo_ref, hi_ref, j, t=t, measure=measure,
                                     tn=tn)


@functools.partial(
    jax.jit, static_argnames=("t", "measure", "tiles", "interpret")
)
def bitmap_join_tiled(r_bitmaps, r_sizes, s_bitmaps, s_sizes, lo, hi, skip,
                      *, t: float, measure: str = "jaccard",
                      tiles=DEFAULT_TILES, interpret: bool = False):
    """All inputs pre-padded to tile multiples; see ops.bitmap_join.

    r_bitmaps (M, W) uint32 | s_bitmaps (N, W) uint32
    r_sizes/lo/hi (M, 1) int32 | s_sizes (1, N) int32
    skip (m_tiles, n_tiles) int32   -> out (M, N) bool
    """
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
            # skip flag of tile (i, j): a (1, 1, 1) block over the
            # (m_tiles * n_tiles, 1, 1) flag column, so its last two dims
            # are the full array's
            pl.BlockSpec((1, 1, 1), lambda i, j, k: (i * n_tiles + j, 0, 0)),
            pl.BlockSpec((TM, TW), lambda i, j, k: (i, k)),        # r bitmaps
            pl.BlockSpec((TN, TW), lambda i, j, k: (j, k)),        # s bitmaps
            pl.BlockSpec((TM, 1), lambda i, j, k: (i, 0)),         # r sizes
            pl.BlockSpec((1, TN), lambda i, j, k: (0, j)),         # s sizes
            pl.BlockSpec((TM, 1), lambda i, j, k: (i, 0)),         # lo
            pl.BlockSpec((TM, 1), lambda i, j, k: (i, 0)),         # hi
        ],
        out_specs=pl.BlockSpec((TM, TN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.bool_),
        scratch_shapes=[pltpu.VMEM((TM, TN), jnp.int32)],
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
    _popcount_accumulate(r_bm_ref, s_bm_ref, acc_ref)

    @pl.when(k == n_kblocks - 1)
    def _emit():
        q = _qualify_tile(acc_ref[...], r_sz_ref, s_sz_ref, lo_ref, hi_ref,
                          j, t=t, measure=measure, tn=tn)
        mask_ref[...] = q[None]
        cnt_ref[...] = jnp.sum(q, dtype=jnp.int32).reshape(1, 1, 1)


@functools.partial(jax.jit,
                   static_argnames=("t", "measure", "tiles", "interpret"))
def bitmap_join_live_tiled(tile_i, tile_j, r_bitmaps, r_sizes, s_bitmaps,
                           s_sizes, lo, hi, *, t: float,
                           measure: str = "jaccard", tiles=DEFAULT_TILES,
                           interpret: bool = False):
    """Popcount join over the live tiles only; see ops.bitmap_join_pairs.

    tile_i/tile_j (L,) int32 live-tile coordinates (scalar-prefetched);
    remaining operands pre-padded as in ``bitmap_join_tiled``. Returns
    (mask (L, TM, TN) bool, counts (L, 1) int32): the qualifying sub-mask
    and exact pair count per live tile. Both stay device-resident — the
    jnp compaction in ``ops`` turns them into the packed pair array.
    """
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
        scratch_shapes=[pltpu.VMEM((TM, TN), jnp.int32)],
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
