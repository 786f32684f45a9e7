"""Pallas scalar-prefetch kernel for the flat-LFVT array walk (DESIGN.md §10).

``core/lfvt_flat.py`` turned the paper's winning CF-RS-Join/LFVT into a
device-resident array walk, but PR 4 executed it as plain jnp: a
``fori_loop`` over ``max|seq|`` steps that re-materializes a full
``(mb, n)`` scatter-add array per step and always runs the global
worst-case step count, even after every lane has died. This module is
the Mosaic execution layer for that walk:

  * **1-D live row-tile grid** (PR 1's live-tile schedule, collapsed to
    rows): the R block is sorted by set size (rows with near-identical
    Lemma-3.1 windows share a tile) and cut into ``ROW_TILE``-row tiles;
    tiles whose windows exclude every S column never enter the grid.
  * **Scalar prefetch for the schedule only** (``PrefetchScalarGridSpec``):
    the live-tile id list rides in SMEM ahead of the body and steers the
    per-tile block DMAs like the bitmap live-tile kernel's ``(ti, tj)``
    lists. The bulk lane state — the per-R-element entry rows resolved
    to lane ``(position, remaining)`` pairs, and the fused ``seq_next``
    hop column — is **VMEM-fed**: BlockSpec'd tiles DMA'd per live row
    tile (lanes) or once per launch (the seq/nxt rows), so the working
    set scales with VMEM, not the old ``SMEM_PREFETCH_BUDGET`` that
    forced a fallback to the jnp twin past Mp·Lr + Σ|seq| ≈ 2^20
    (``walk_vmem_tile_bytes`` is the replacement accounting, surfaced
    in driver stats).
  * **VMEM-resident count tile**: each grid step owns one
    ``(ROW_TILE, S_cols)`` int32 overlap-count tile that stays on-chip
    across all walk steps — nothing ``(mb, n)``-shaped is re-built per
    step, and only the qualifying boolean sub-mask + exact pair count
    leave the core.
  * **Per-step early stop** (Theorem 3.3): walk rows strictly decrease,
    so a lane whose emitted row drops below its window's ``lo`` is dead
    for every later step; the walk is a ``while_loop`` that exits as
    soon as the tile has no live lanes. Dead walk rows cost no VMEM
    traffic — their steps never execute. ``walk_steps``/``early_stops``
    are emitted per tile so drivers can report the savings.

The Mosaic body does not lower for the TPU yet: the compiler refuses
its 1-D ``seq[safe]`` / ``nxt[safe]`` gathers ("Only 2D gather is
supported"), and its ``(1, 1)`` counter blocks are not tile-aligned
either. So ``ops.lfvt_walk_join_pairs_dispatch`` runs
``lfvt_walk_live_tiled_ref`` — the XLA-compiled jnp twin of the exact
same tiled schedule (bit-identical masks/counts/stats) — on every
backend, and the Pallas kernel runs only under the interpreter, as the
parity harness of the tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import measures
from repro.core.config import global_config

__all__ = ["DEFAULT_ROW_TILE", "COL_PAD", "TileShapeError",
           "plan_row_tiles", "plan_row_tiles_device", "entry_state",
           "walk_vmem_tile_bytes", "fits_vmem",
           "lfvt_walk_live_tiled", "lfvt_walk_live_tiled_ref",
           "lfvt_walk_planned", "lfvt_walk_planned_ref"]

# Historical aliases — ``core.config.global_config`` is the source of
# truth (row_tile / col_pad); call sites resolve at call time.
DEFAULT_ROW_TILE = global_config.row_tile
COL_PAD = global_config.col_pad


def walk_vmem_tile_bytes(tm: int, lr: int, npad: int, tp: int) -> int:
    """Per-grid-step VMEM residency of the walk kernel's working set.

    Two (tm, lr) int32 lane tiles (entry position / remaining steps,
    DMA'd per live row tile), the (1, tp) int32 seq_row + seq_next rows,
    the (1, npad) int32 S-size row, three (tm, 1) int32 window columns,
    the (tm, npad) int32 count scratch and the (tm, npad) bool mask
    output tile. Replaces the removed SMEM prefetch budget: only the
    live-tile id list is scalar-prefetched now, so the lane state scales
    with VMEM and there is no fallback-to-twin threshold — drivers
    surface this accounting in stats instead.
    """
    return (4 * (2 * tm * lr + 2 * tp + npad + 3 * tm + tm * npad)
            + tm * npad)


def fits_vmem(tm: int, lr: int, npad: int, tp: int,
              budget: int | None = None) -> bool:
    """Advisory check of the per-step working set against the VMEM budget
    (``global_config.vmem_budget`` by default)."""
    budget = global_config.vmem_budget if budget is None else budget
    return walk_vmem_tile_bytes(tm, lr, npad, tp) <= budget


class TileShapeError(ValueError):
    """A row-count / row-tile mismatch that would silently mis-plan.

    The tile planners reshape ``(rows,)`` window arrays into
    ``(rows // tm, tm)`` tiles; a ragged tail used to surface as an
    opaque reshape ``ValueError`` (or, worse, a silent ``//`` truncation
    when the lengths happened to factor). Callers pad rows to the tile
    multiple *before* planning (``ops`` pads with empty ``[0, 0)``
    windows, which can never qualify)."""


def _check_tile_rows(n_rows: int, tm: int, who: str) -> int:
    if tm <= 0:
        raise TileShapeError(f"{who}: row tile must be positive, got {tm}")
    if n_rows % tm:
        raise TileShapeError(
            f"{who}: {n_rows} rows is not a multiple of row_tile={tm}; "
            f"pad the trailing {n_rows % tm} rows to the tile boundary "
            "with empty [0, 0) windows before planning")
    return n_rows // tm


def plan_row_tiles(lo: np.ndarray, hi: np.ndarray, tm: int) -> np.ndarray:
    """Live row-tile ids: tiles where at least one row has a non-empty
    [lo, hi) window. Everything else is skipped before launch (the 1-D
    analogue of ``ops._live_tiles``); host numpy because the result
    parameterizes the grid. Raises ``TileShapeError`` on a ragged tail."""
    m_tiles = _check_tile_rows(len(lo), tm, "plan_row_tiles")
    live = (np.asarray(lo).reshape(m_tiles, tm)
            < np.asarray(hi).reshape(m_tiles, tm)).any(axis=1)
    return np.nonzero(live)[0].astype(np.int32)


def plan_row_tiles_device(lo, hi, tm: int):
    """Traceable twin of ``plan_row_tiles`` — the device-side live-tile
    plan the mesh path runs *inside* its shard_map body (host planning
    cannot execute under tracing; this is the ROADMAP item's
    sort-and-slice form).

    Computes the exact host criterion (``any(lo < hi)`` per row tile)
    on device, then stable-partitions tile ids so the live ones occupy
    the front in ascending order: the composite key ``dead * m_tiles +
    tile_id`` makes a plain ``argsort`` order-preserving without relying
    on a stable sort. Returns ``(ti_sorted
    (m_tiles,) int32, n_live () int32)`` with ``ti_sorted[:n_live] ==
    plan_row_tiles(lo, hi, tm)``.
    """
    lo1, hi1 = jnp.reshape(lo, (-1,)), jnp.reshape(hi, (-1,))
    m_tiles = _check_tile_rows(lo1.shape[0], tm, "plan_row_tiles_device")
    live = jnp.any(lo1.reshape(m_tiles, tm) < hi1.reshape(m_tiles, tm),
                   axis=1)
    ids = jnp.arange(m_tiles, dtype=jnp.int32)
    key = jnp.where(live, 0, m_tiles).astype(jnp.int32) + ids
    return (ids[jnp.argsort(key)].astype(jnp.int32),
            jnp.sum(live, dtype=jnp.int32))


@jax.jit
def entry_state(dev, r_padded):
    """Resolve the per-R-element entry rows: (mb, Lr) element lists ->
    lane (walk position, remaining steps) pairs, parked at (0, 0) for -1
    pads and absent elements (binary search over the sparse entry table,
    exactly like the jnp walk).

    Each row's lanes come back sorted by remaining walk length
    (descending). Counts, masks and the step/stop counters are invariant
    to lane order within a row, but the sort lets the compiled twin run
    its live-lane staircase: once every lane right of a pow2 boundary is
    dead, the walk continues on the narrowed slice, so scatter traffic
    tracks the live lanes instead of Lr x max|seq| (the walk-length-skew
    analogue of the live-tile schedule)."""
    E = dev.entry_elem.shape[0]
    idx = jnp.minimum(jnp.searchsorted(dev.entry_elem, r_padded), E - 1)
    present = (r_padded >= 0) & (dev.entry_elem[idx] == r_padded)
    pos = jnp.where(
        present, dev.node_seq_off[dev.entry_node[idx]] + dev.entry_off[idx],
        0).astype(jnp.int32)
    rem = jnp.where(present, dev.entry_len[idx], 0).astype(jnp.int32)
    order = jnp.argsort(-rem, axis=1)
    return (jnp.take_along_axis(pos, order, axis=1),
            jnp.take_along_axis(rem, order, axis=1))


def _walk_tile(pos, rem, lo_col, nxt, seq, counts, accumulate,
               max_steps: int):
    """One tile's lockstep walk: early-exiting while_loop over at most
    ``max_steps`` steps. Identical per-step emission order to the PR-4
    jnp walk (``lfvt_flat._walk_counts``) — counts may differ from it
    only at columns outside the window, which qualify masks off — plus
    the step/stop counters. ``accumulate`` abstracts the count-tile
    update (scatter-add for the compiled twin, iota-compare for the
    Mosaic body); ``nxt`` is the fused node_seq_off/seq_len/parent hop
    column, so a step costs two gathers and the update."""

    def cond(state):
        step, _, rem, _, _ = state
        return (step < max_steps) & jnp.any(rem > 0)

    def body(state):
        step, pos, rem, counts, stops = state
        active = rem > 0
        safe = jnp.where(active, pos, 0)
        row = seq[safe]
        counts = accumulate(counts, row, active)
        # window early stop (Theorem 3.3): walk rows strictly decrease,
        # so row < lo means every remaining step is out-of-window too
        stop = active & (row < lo_col)
        stops = stops + jnp.sum(stop & (rem > 1), dtype=jnp.int32)
        rem = jnp.where(active & ~stop, rem - 1, 0)
        pos = jnp.where(rem > 0, jnp.maximum(nxt[safe], 0), 0)
        return step + 1, pos, rem, counts, stops

    init = (jnp.int32(0), pos, rem, counts, jnp.int32(0))
    step, _, _, counts, stops = jax.lax.while_loop(cond, body, init)
    return counts, step, stops


def _qualify(counts, r_sz, s_sz, lo, hi, t, measure):
    """Measure predicate + [lo, hi) column window on one count tile."""
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, counts.shape[1]), 1)
    in_window = (cols >= lo) & (cols < hi)
    return measures.device_qualify(counts, r_sz, s_sz, t, measure) & in_window


# ---------------------------------------------------------------------- #
# compiled jnp twin — the execution path on every backend
# ---------------------------------------------------------------------- #
def _staircase(ti, lane_pos, lane_rem, nxt2d, seq2d, ssz2d,
               rsz, lo, hi, *, t: float, measure: str,
               max_steps: int, tm: int):
    """The twin's live-lane staircase over the row tiles named by ``ti``.

    Shared core of ``lfvt_walk_live_tiled_ref`` (static all/live tiles)
    and ``lfvt_walk_planned_ref`` (device-planned chunks — ``ti`` may be
    a traced gather index). Per-tile outputs depend only on that tile's
    rows, so batching tiles differently never changes masks or counts —
    the batch-invariance the parity tests pin.
    """
    Lr = lane_pos.shape[1]
    NP = ssz2d.shape[1]
    L = ti.shape[0]
    M = L * tm
    seq = seq2d[0]
    nxt = nxt2d[0]

    def lanes(x):
        return x.reshape(-1, tm, Lr)[ti].reshape(M, Lr)

    def rows(x):
        return x.reshape(-1, tm)[ti].reshape(M, 1)

    r_sz, lo_c, hi_c = rows(rsz), rows(lo), rows(hi)
    row_ix = jnp.broadcast_to(
        jnp.arange(M, dtype=jnp.int32)[:, None], (M, Lr))

    def stage_cond(w_next, w):
        def cond(state):
            step, _, rem, _, _, _ = state
            outer = rem if w_next == 0 else rem[:, w_next:]
            return (step < max_steps) & jnp.any(outer > 0)
        return cond

    def stage_body(w):
        def body(state):
            step, pos, rem, counts, stops, steps_t = state
            active = rem > 0
            steps_t = steps_t + jnp.any(
                active.reshape(L, tm * w), axis=1).astype(jnp.int32)
            safe = jnp.where(active, pos, 0)
            row = seq[safe]
            counts = counts.at[row_ix[:, :w],
                               jnp.where(active, row, 0)].add(
                active.astype(jnp.int32))
            stop = active & (row < lo_c)
            stops = stops + jnp.sum(
                (stop & (rem > 1)).reshape(L, tm * w), axis=1,
                dtype=jnp.int32)
            rem = jnp.where(active & ~stop, rem - 1, 0)
            pos = jnp.where(rem > 0, jnp.maximum(nxt[safe], 0), 0)
            return step + 1, pos, rem, counts, stops, steps_t
        return body

    state = (jnp.int32(0), lanes(lane_pos), lanes(lane_rem),
             jnp.zeros((M, NP), jnp.int32), jnp.zeros(L, jnp.int32),
             jnp.zeros(L, jnp.int32))
    w = Lr
    while w:  # static pow2 staircase, ~log2(Lr) chained while_loops
        w_next = (w + 1) // 2 if w > 1 else 0
        state = jax.lax.while_loop(stage_cond(w_next, w), stage_body(w),
                                   state)
        step, pos, rem, counts, stops, steps_t = state
        state = (step, pos[:, :w_next], rem[:, :w_next], counts, stops,
                 steps_t)
        w = w_next
    _, _, _, counts, stops, steps_t = state
    q = _qualify(counts, r_sz, ssz2d, lo_c, hi_c, t, measure)
    masks = q.reshape(L, tm, NP)
    cnts = jnp.sum(masks, axis=(1, 2), dtype=jnp.int32)
    return (masks, cnts.reshape(L, 1), steps_t.reshape(L, 1),
            stops.reshape(L, 1))


@functools.partial(jax.jit,
                   static_argnames=("t", "measure", "max_steps", "tm"))
def lfvt_walk_live_tiled_ref(ti, lane_pos, lane_rem, nxt2d, seq2d, ssz2d,
                             rsz, lo, hi, *, t: float, measure: str,
                             max_steps: int, tm: int):
    """jnp twin of ``lfvt_walk_live_tiled`` — the XLA-compiled walk that
    runs on every backend.

    Same live row-tile schedule and per-step algebra as the Mosaic body,
    with two CPU-shaped scheduling changes that leave every output and
    counter bit-identical:

      * the live tiles are batched into one (L·tm, Lr) lane block so the
        whole block shares each loop step (XLA CPU pays per-op dispatch;
        L sequential tile loops would multiply it);
      * the walk runs as a **live-lane staircase**: lanes arrive sorted
        by remaining length (``entry_state``), so once every lane right
        of a pow2 column boundary is dead the loop continues on the
        narrowed slice. Scatter traffic then tracks the live lanes —
        one hot element no longer drags all Lr lane columns through
        max|seq| steps (ROADMAP's walk-length-skew item).

    Per-tile ``walk_steps``/``early_stops`` are maintained in-loop (a
    tile's step counter advances only while it still has live lanes), so
    masks, counts and stats match running each tile's while_loop
    separately, which the parity tests pin against the Pallas kernel.

    Returns (masks (L, tm, NP) bool, counts/steps/stops (L, 1) int32).
    """
    return _staircase(ti, lane_pos, lane_rem, nxt2d, seq2d, ssz2d, rsz,
                      lo, hi, t=t, measure=measure, max_steps=max_steps,
                      tm=tm)


@functools.partial(
    jax.jit,
    static_argnames=("t", "measure", "max_steps", "tm", "chunk"))
def lfvt_walk_planned_ref(ti_sorted, n_live, lane_pos, lane_rem, nxt2d,
                          seq2d, ssz2d, rsz, lo, hi, *, t: float,
                          measure: str, max_steps: int, tm: int,
                          chunk: int | None = None):
    """Device-planned twin: walk only the live tile prefix of a
    ``plan_row_tiles_device`` schedule, fully inside the trace.

    The schedule — ``ti_sorted`` with the live tiles stable-partitioned
    to the front and the traced live count ``n_live`` — is consumed by a
    bounded ``while_loop`` over ``chunk``-tile slices
    (``lax.dynamic_slice`` keeps shapes static; the last slice clamps
    into the live prefix's tail and harmlessly re-walks a few already
    -done tiles — per-tile outputs are batch-invariant and the scatter
    is ``.set``, so reprocessing is idempotent). Dead tiles never enter
    the staircase: they cost zero ``walk_steps``, and their lanes are
    zeroed up front so even clamp-overlap slices cannot walk them.

    Returns the same quadruple as ``lfvt_walk_live_tiled_ref`` over the
    **full** tile range in original tile order — masks/counts are
    bit-identical to the static all-tiles schedule (dead tiles qualify
    all-False either way); only the ``walk_steps``/``early_stops``
    counters drop, which is the point.
    """
    m_tiles = ti_sorted.shape[0]
    NP = ssz2d.shape[1]
    _check_tile_rows(rsz.shape[0], tm, "lfvt_walk_planned_ref")
    G = max(min(chunk or global_config.plan_chunk_tiles, m_tiles), 1)
    lo1 = lo.reshape(-1)
    tile_live = jnp.any((lo1 < hi.reshape(-1)).reshape(m_tiles, tm), axis=1)
    keep = jnp.repeat(tile_live, tm)[:, None]
    lane_rem = jnp.where(keep, lane_rem, 0)

    def cond(state):
        g = state[0]
        return g * G < n_live

    def body(state):
        g, masks, cnts, steps, stops = state
        start = jnp.minimum(g * G, m_tiles - G).astype(jnp.int32)
        ti_c = jax.lax.dynamic_slice(ti_sorted, (start,), (G,))
        mk, ck, sk, pk = _staircase(
            ti_c, lane_pos, lane_rem, nxt2d, seq2d, ssz2d, rsz, lo, hi,
            t=t, measure=measure, max_steps=max_steps, tm=tm)
        return (g + 1, masks.at[ti_c].set(mk), cnts.at[ti_c].set(ck),
                steps.at[ti_c].set(sk), stops.at[ti_c].set(pk))

    init = (jnp.int32(0),
            jnp.zeros((m_tiles, tm, NP), jnp.bool_),
            jnp.zeros((m_tiles, 1), jnp.int32),
            jnp.zeros((m_tiles, 1), jnp.int32),
            jnp.zeros((m_tiles, 1), jnp.int32))
    _, masks, cnts, steps, stops = jax.lax.while_loop(cond, body, init)
    return masks, cnts, steps, stops


# ---------------------------------------------------------------------- #
# Pallas kernel — VMEM-fed Mosaic body (only the tile ids are prefetched)
# ---------------------------------------------------------------------- #
def _walk_kernel(ti_ref, lpos_ref, lrem_ref, nxt_ref, seq_ref, ssz_ref,
                 rsz_ref, lo_ref, hi_ref, mask_ref, cnt_ref, steps_ref,
                 stops_ref, acc_ref, *, t: float, measure: str,
                 max_steps: int, tm: int):
    del ti_ref  # consumed by the BlockSpec index maps, not the body
    # VMEM-fed lane state: this tile's (tm, Lr) entry rows arrive as a
    # BlockSpec'd DMA steered by the prefetched live-tile ids, and the
    # fused node_seq_off/seq_len/parent hop column rides in VMEM beside
    # the seq rows — nothing lane-shaped lives in SMEM anymore
    pos = lpos_ref[...]
    rem = lrem_ref[...]
    nxt = nxt_ref[...][0]
    seq = seq_ref[...][0]
    npad = acc_ref.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, npad), 2)

    def onehot(counts, row, active):
        # branchless count-tile update: a lane contributes 1 to exactly
        # its emitted row's column (VPU compare + reduce, no scatter)
        sel = jnp.where(active, row, -1)  # -1 matches no column
        return counts + jnp.sum(sel[:, :, None] == iota, axis=1,
                                dtype=jnp.int32)

    counts0 = jnp.zeros_like(acc_ref)
    counts, steps, stops = _walk_tile(pos, rem, lo_ref[...], nxt, seq,
                                      counts0, onehot, max_steps)
    acc_ref[...] = counts  # the tile's VMEM home; qualify reads it back
    q = _qualify(acc_ref[...], rsz_ref[...], ssz_ref[...], lo_ref[...],
                 hi_ref[...], t, measure)
    mask_ref[...] = q[None]
    cnt_ref[...] = jnp.sum(q, dtype=jnp.int32).reshape(1, 1)
    steps_ref[...] = steps.reshape(1, 1)
    stops_ref[...] = stops.reshape(1, 1)


@functools.partial(
    jax.jit,
    static_argnames=("t", "measure", "max_steps", "tm", "interpret"))
def lfvt_walk_live_tiled(ti, lane_pos, lane_rem, nxt, seq2d, ssz2d, rsz,
                         lo, hi, *, t: float, measure: str, max_steps: int,
                         tm: int, interpret=False):
    """Flat-LFVT walk over live row tiles only; see ops.lfvt_walk_join_pairs.

    ti (L,) live row-tile ids — the only scalar-prefetch operand (it
    steers the index maps). lane_pos/lane_rem (Mp, Lr) resolved entry
    rows and nxt (1, Tp) fused hop column are BlockSpec'd VMEM operands:
    each grid step DMAs its own (tm, Lr) lane tile, so the lane working
    set is bounded by ``walk_vmem_tile_bytes`` rather than the removed
    SMEM prefetch budget. seq2d (1, Tp) tuple rows, ssz2d (1, NP) padded
    S sizes, rsz/lo/hi (Mp, 1). Returns (mask (L, tm, NP) bool, counts,
    walk_steps, early_stops — each (L, 1) int32), all device-resident
    for the ``PendingPairs`` compaction protocol.
    """
    L = ti.shape[0]
    Lr = lane_pos.shape[1]
    NP = ssz2d.shape[1]
    assert rsz.shape[0] % tm == 0, (rsz.shape, tm)
    kernel = functools.partial(_walk_kernel, t=t, measure=measure,
                               max_steps=max_steps, tm=tm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(L,),
        in_specs=[
            pl.BlockSpec((tm, Lr), lambda l, ti: (ti[l], 0)),  # lane pos
            pl.BlockSpec((tm, Lr), lambda l, ti: (ti[l], 0)),  # lane rem
            pl.BlockSpec(nxt.shape, lambda l, ti: (0, 0)),     # hop column
            pl.BlockSpec(seq2d.shape, lambda l, ti: (0, 0)),   # seq rows
            pl.BlockSpec((1, NP), lambda l, ti: (0, 0)),       # s sizes
            pl.BlockSpec((tm, 1), lambda l, ti: (ti[l], 0)),   # r sizes
            pl.BlockSpec((tm, 1), lambda l, ti: (ti[l], 0)),   # lo
            pl.BlockSpec((tm, 1), lambda l, ti: (ti[l], 0)),   # hi
        ],
        out_specs=[
            pl.BlockSpec((1, tm, NP), lambda l, *pf: (l, 0, 0)),
            pl.BlockSpec((1, 1), lambda l, *pf: (l, 0)),
            pl.BlockSpec((1, 1), lambda l, *pf: (l, 0)),
            pl.BlockSpec((1, 1), lambda l, *pf: (l, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((tm, NP), jnp.int32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((L, tm, NP), jnp.bool_),
            jax.ShapeDtypeStruct((L, 1), jnp.int32),
            jax.ShapeDtypeStruct((L, 1), jnp.int32),
            jax.ShapeDtypeStruct((L, 1), jnp.int32),
        ],
        interpret=interpret,
    )(ti, lane_pos, lane_rem, nxt, seq2d, ssz2d, rsz, lo, hi)


def _walk_kernel_planned(ti_ref, nl_ref, lpos_ref, lrem_ref, nxt_ref,
                         seq_ref, ssz_ref, rsz_ref, lo_ref, hi_ref,
                         mask_ref, cnt_ref, steps_ref, stops_ref, acc_ref,
                         *, t: float, measure: str, max_steps: int,
                         tm: int):
    """Planned-schedule Mosaic body: grid step ``l`` walks tile
    ``ti_sorted[l]`` only while ``l < n_live`` (both scalar-prefetched);
    dead steps write zero outputs and skip the walk entirely."""
    l = pl.program_id(0)  # hoisted out of the pl.when bodies (PR 1)
    live = l < nl_ref[0]

    @pl.when(live)
    def _walk():
        _walk_kernel(ti_ref, lpos_ref, lrem_ref, nxt_ref, seq_ref,
                     ssz_ref, rsz_ref, lo_ref, hi_ref, mask_ref, cnt_ref,
                     steps_ref, stops_ref, acc_ref, t=t, measure=measure,
                     max_steps=max_steps, tm=tm)

    @pl.when(jnp.logical_not(live))
    def _dead():
        mask_ref[...] = jnp.zeros(mask_ref.shape, mask_ref.dtype)
        cnt_ref[...] = jnp.zeros(cnt_ref.shape, cnt_ref.dtype)
        steps_ref[...] = jnp.zeros(steps_ref.shape, steps_ref.dtype)
        stops_ref[...] = jnp.zeros(stops_ref.shape, stops_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("t", "measure", "max_steps", "tm", "interpret"))
def lfvt_walk_planned(ti_sorted, n_live, lane_pos, lane_rem, nxt, seq2d,
                      ssz2d, rsz, lo, hi, *, t: float, measure: str,
                      max_steps: int, tm: int, interpret=False):
    """Device-planned Mosaic walk: the ``plan_row_tiles_device`` schedule
    (live-partitioned tile ids + traced live count) rides in SMEM as two
    scalar-prefetch operands, and the ``pl.when`` guard turns every grid
    step past the live prefix into a zero-write — no walk loop, no count
    tile. The grid stays ``(m_tiles,)`` (a traced count cannot size a
    grid), so dead tiles still cost their block DMAs but zero walk
    steps; outputs come back scattered to original tile order so the
    result is drop-in for the static kernel's over the full tile range.

    Returns (mask (m_tiles, tm, NP) bool, counts, walk_steps,
    early_stops — each (m_tiles, 1) int32), bit-identical masks/counts
    to ``lfvt_walk_live_tiled`` over an all-tiles schedule.
    """
    m_tiles = ti_sorted.shape[0]
    Lr = lane_pos.shape[1]
    NP = ssz2d.shape[1]
    _check_tile_rows(rsz.shape[0], tm, "lfvt_walk_planned")
    nl2d = jnp.reshape(n_live, (1,)).astype(jnp.int32)
    kernel = functools.partial(_walk_kernel_planned, t=t, measure=measure,
                               max_steps=max_steps, tm=tm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m_tiles,),
        in_specs=[
            pl.BlockSpec((tm, Lr), lambda l, ti, nl: (ti[l], 0)),
            pl.BlockSpec((tm, Lr), lambda l, ti, nl: (ti[l], 0)),
            pl.BlockSpec(nxt.shape, lambda l, ti, nl: (0, 0)),
            pl.BlockSpec(seq2d.shape, lambda l, ti, nl: (0, 0)),
            pl.BlockSpec((1, NP), lambda l, ti, nl: (0, 0)),
            pl.BlockSpec((tm, 1), lambda l, ti, nl: (ti[l], 0)),
            pl.BlockSpec((tm, 1), lambda l, ti, nl: (ti[l], 0)),
            pl.BlockSpec((tm, 1), lambda l, ti, nl: (ti[l], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tm, NP), lambda l, *pf: (l, 0, 0)),
            pl.BlockSpec((1, 1), lambda l, *pf: (l, 0)),
            pl.BlockSpec((1, 1), lambda l, *pf: (l, 0)),
            pl.BlockSpec((1, 1), lambda l, *pf: (l, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((tm, NP), jnp.int32)],
    )
    masks, cnts, steps, stops = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m_tiles, tm, NP), jnp.bool_),
            jax.ShapeDtypeStruct((m_tiles, 1), jnp.int32),
            jax.ShapeDtypeStruct((m_tiles, 1), jnp.int32),
            jax.ShapeDtypeStruct((m_tiles, 1), jnp.int32),
        ],
        interpret=interpret,
    )(ti_sorted, nl2d, lane_pos, lane_rem, nxt, seq2d, ssz2d, rsz, lo, hi)
    # grid order is the sorted schedule; hand results back per tile id
    inv = jnp.argsort(ti_sorted)
    return masks[inv], cnts[inv], steps[inv], stops[inv]
