"""Public jit'd wrappers for the join kernels: padding, scheduling, emission.

``bitmap_join`` / ``onehot_join`` accept unpadded device arrays (the layout
produced by ``SetCollection``), pad to tile multiples, derive the
tile-level early-stop mask from the per-row windows (Theorem 3.3 at tile
granularity), invoke the Pallas kernel and slice the result back. They
return the dense (m, n) boolean mask — the fallback output format.

``bitmap_join_pairs`` / ``onehot_join_pairs`` are the sparse emission path
(DESIGN.md §6): the host compacts the skip mask into live (i, j) tile
coordinates, a 1-D live-tile grid computes per-tile qualifying sub-masks +
exact pair counts (skipped tiles cost zero grid steps), and an on-device
segment compaction packs qualifying (r, s) index pairs into a flat int32
array. Only the per-tile counts (4·L bytes) and the packed pair array
(8·P bytes) ever cross the host↔device boundary — output traffic scales
with the result size, not O(m·n).

On TPU the join kernels compile to Mosaic; on other backends they run
under the Mosaic TPU interpreter (Python semantics, bit-exact). The LFVT
walk runs its XLA-compiled jnp twin on every backend: the Mosaic walk
body does not lower yet (``lfvt_walk_join_pairs_dispatch``).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core.config import global_config
from repro.core.resilience import fault_point
from repro.core.tile_join import PAIR_CAP_GRAIN, round_capacity

from . import bitmap_join as _bj
from . import onehot_join as _oj

__all__ = ["bitmap_join", "onehot_join", "bitmap_join_pairs",
           "onehot_join_pairs", "join_pairs", "pick_tiles", "round_capacity",
           "PAIR_CAP_GRAIN", "PendingPairs", "bitmap_join_pairs_dispatch",
           "onehot_join_pairs_dispatch", "lfvt_join_pairs",
           "lfvt_join_pairs_dispatch", "lfvt_walk_join_pairs",
           "lfvt_walk_join_pairs_dispatch", "join_pairs_finalize",
           "join_mask_finalize", "lfvt_walk_join_mask"]


def _interpret_default():
    """Compile to Mosaic on TPU; elsewhere run the Mosaic TPU interpreter
    (exact Python semantics)."""
    if jax.default_backend() == "tpu":
        return False
    return pltpu.InterpretParams()


def pick_tiles(m: int, n: int, w: int, defaults) -> tuple[int, int, int]:
    """Shrink default tiles for small problems (pads at most 2x)."""
    TM, TN, TW = defaults
    def shrink(size, tile, floor):
        while tile > floor and tile // 2 >= size:
            tile //= 2
        return tile
    return shrink(m, TM, 8), shrink(n, TN, 128), shrink(w, TW, 1)


def _pad_to(x: jax.Array, axis: int, mult: int, value=0) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _tile_skip_mask(lo, hi, m_tiles, n_tiles, tm, tn):
    """(m_tiles, n_tiles) int32: 1 if the tile is fully outside all windows.

    Tile (i, j) covers columns [j*tn, (j+1)*tn). It can be skipped iff for
    every row in the tile, the window [lo, hi) misses that column range —
    conservatively: min(lo) >= tile_end or max(hi) <= tile_start.
    """
    lo2 = lo.reshape(m_tiles, tm)
    hi2 = hi.reshape(m_tiles, tm)
    tile_lo = jnp.min(lo2, axis=1)   # (m_tiles,)
    tile_hi = jnp.max(hi2, axis=1)
    starts = jnp.arange(n_tiles, dtype=jnp.int32) * tn
    ends = starts + tn
    skip = (tile_lo[:, None] >= ends[None, :]) | (tile_hi[:, None] <= starts[None, :])
    return skip.astype(jnp.int32)


def _live_tiles(lo_p, hi_p, m_tiles, n_tiles, tm, tn):
    """Host-side skip-mask compaction -> live (i, j) tile coordinate lists.

    Same conservative criterion as ``_tile_skip_mask``, evaluated in numpy
    so the live list exists before kernel launch (it parameterizes the
    grid). Returns two (L,) int32 arrays, row-major tile order. Raises
    ``lfvt_walk.TileShapeError`` when the padded row/column counts are
    not tile multiples (a ragged tail would silently mis-plan).
    """
    from .lfvt_walk import TileShapeError, _check_tile_rows
    if _check_tile_rows(np.shape(lo_p)[0], tm, "_live_tiles") != m_tiles:
        raise TileShapeError(
            f"_live_tiles: {np.shape(lo_p)[0]} window rows do not fill "
            f"{m_tiles} row tiles of {tm}")
    lo2 = np.asarray(lo_p).reshape(m_tiles, tm)
    hi2 = np.asarray(hi_p).reshape(m_tiles, tm)
    tile_lo = lo2.min(axis=1)
    tile_hi = hi2.max(axis=1)
    starts = np.arange(n_tiles, dtype=np.int64) * tn
    live = (tile_lo[:, None] < starts[None, :] + tn) & (
        tile_hi[:, None] > starts[None, :])
    ti, tj = np.nonzero(live)
    return ti.astype(np.int32), tj.astype(np.int32)


def _pad_operands(r_bitmaps, r_sizes, s_bitmaps, s_sizes, lo, hi, tiles,
                  defaults):
    m, w = r_bitmaps.shape
    n = s_bitmaps.shape[0]
    TM, TN, TW = tiles if tiles is not None else pick_tiles(m, n, w, defaults)
    rb = _pad_to(_pad_to(r_bitmaps, 0, TM), 1, TW)
    sb = _pad_to(_pad_to(s_bitmaps, 0, TN), 1, TW)
    r_sz = _pad_to(r_sizes.astype(jnp.int32), 0, TM).reshape(-1, 1)
    s_sz = _pad_to(s_sizes.astype(jnp.int32), 0, TN).reshape(1, -1)
    # padded rows get an empty window [0, 0) -> they can never qualify
    lo_p = _pad_to(lo.astype(jnp.int32), 0, TM).reshape(-1, 1)
    hi_p = _pad_to(hi.astype(jnp.int32), 0, TM).reshape(-1, 1)
    return rb, r_sz, sb, s_sz, lo_p, hi_p, (TM, TN, TW), m, n


def _prepare(r_bitmaps, r_sizes, s_bitmaps, s_sizes, lo, hi, tiles, defaults):
    rb, r_sz, sb, s_sz, lo_p, hi_p, tls, m, n = _pad_operands(
        r_bitmaps, r_sizes, s_bitmaps, s_sizes, lo, hi, tiles, defaults)
    TM, TN, _ = tls
    m_tiles, n_tiles = rb.shape[0] // TM, sb.shape[0] // TN
    skip = _tile_skip_mask(lo_p[:, 0], hi_p[:, 0], m_tiles, n_tiles, TM, TN)
    return rb, r_sz, sb, s_sz, lo_p, hi_p, skip, tls, m, n


# ---------------------------------------------------------------------- #
# dense-mask fallback
# ---------------------------------------------------------------------- #
def bitmap_join(r_bitmaps, r_sizes, s_bitmaps, s_sizes, lo, hi, t: float,
                tiles=None, interpret: bool | None = None,
                measure: str = "jaccard") -> jax.Array:
    """(m, n) bool qualifying-pair matrix via the popcount kernel."""
    interpret = _interpret_default() if interpret is None else interpret
    rb, r_sz, sb, s_sz, lo_p, hi_p, skip, tls, m, n = _prepare(
        r_bitmaps, r_sizes, s_bitmaps, s_sizes, lo, hi, tiles, _bj.DEFAULT_TILES)
    out = _bj.bitmap_join_tiled(rb, r_sz, sb, s_sz, lo_p, hi_p, skip,
                                t=t, measure=measure, tiles=tls,
                                interpret=interpret)
    return out[:m, :n]


def onehot_join(r_bitmaps_or_padded, r_sizes, s_bitmaps, s_sizes, lo, hi,
                t: float, universe: int | None = None, tiles=None,
                interpret: bool | None = None,
                measure: str = "jaccard") -> jax.Array:
    """(m, n) bool qualifying-pair matrix via the MXU one-hot kernel.

    Accepts bitmaps directly; ``universe`` kept for API symmetry. If handed
    padded element lists (int32 with -1 pads), converts to bitmaps first.
    """
    interpret = _interpret_default() if interpret is None else interpret
    r_in, s_in = _coerce_bitmaps(r_bitmaps_or_padded, s_bitmaps, universe)
    rb, r_sz, sb, s_sz, lo_p, hi_p, skip, tls, m, n = _prepare(
        r_in, r_sizes, s_in, s_sizes, lo, hi, tiles, _oj.DEFAULT_TILES)
    out = _oj.onehot_join_tiled(rb, r_sz, sb, s_sz, lo_p, hi_p, skip,
                                t=t, measure=measure, tiles=tls,
                                interpret=interpret)
    return out[:m, :n]


def _coerce_bitmaps(r_in, s_in, universe):
    if r_in.dtype != jnp.uint32:
        assert universe is not None, "universe required to pack element lists"
        r_in = _pack_bitmaps(r_in, universe)
    if s_in.dtype != jnp.uint32:
        assert universe is not None
        s_in = _pack_bitmaps(s_in, universe)
    W = max(r_in.shape[1], s_in.shape[1])
    return _pad_to(r_in, 1, W), _pad_to(s_in, 1, W)


# ---------------------------------------------------------------------- #
# sparse pair emission (live-tile schedule + on-device compaction)
# ---------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("tm", "tn", "size"))
def _compact_live(mask_tiles, tile_i, tile_j, *, tm, tn, size):
    """(L, TM, TN) live-tile masks -> packed (size, 2) int32 global pairs.

    Rows past the true pair count are (-1, -1). Padded rows/columns of the
    operand arrays can never qualify (empty windows / col >= hi), so no
    post-filter is needed.
    """
    l, r, c = jnp.nonzero(mask_tiles, size=size, fill_value=-1)
    valid = l >= 0
    rows = jnp.where(valid, tile_i[l] * tm + r, -1)
    cols = jnp.where(valid, tile_j[l] * tn + c, -1)
    return jnp.stack([rows, cols], axis=1)


@dataclasses.dataclass
class PendingPairs:
    """In-flight sparse join: device handles dispatched, counts not synced.

    Produced by ``*_join_pairs_dispatch`` and resolved by
    ``join_pairs_finalize``. Holding the staged masks + per-tile counts as
    device arrays lets a driver launch the *next* block's kernel before
    paying the host sync for this one (double-buffered R-block streaming,
    DESIGN.md §6).
    """

    masks: jax.Array | None   # (L, TM, TN) staged qualifying sub-masks
    counts: jax.Array | None  # (L, 1) exact per-tile pair counts (device)
    tile_i: jax.Array | None  # (L,) live tile rows
    tile_j: jax.Array | None  # (L,) live tile cols
    tm: int
    tn: int
    live_tiles: int
    total_tiles: int
    dense_mask_bytes: int
    # kernel-specific device counters (e.g. the LFVT walk's walk_steps /
    # early_stops); summed into the caller's stats dict at finalize
    extras: dict | None = None
    # optional packed-row remap: the LFVT walk sorts R rows by size so
    # row tiles hold near-identical windows; row_map[packed_row] is the
    # original block row (-1 for capacity padding)
    row_map: jax.Array | None = None
    # the LFVT walk implementation this dispatch launched ('jnp' twin or
    # 'pallas' kernel); reported as stats['walk_impl'] at finalize
    walk_impl: str | None = None


def _join_pairs_dispatch(live_fn, defaults, r_bitmaps, r_sizes, s_bitmaps,
                         s_sizes, lo, hi, t, tiles, interpret,
                         measure="jaccard") -> PendingPairs:
    """Launch the live-tile kernel; return device handles without syncing."""
    fault_point("walk_dispatch")
    interpret = _interpret_default() if interpret is None else interpret
    rb, r_sz, sb, s_sz, lo_p, hi_p, tls, m, n = _pad_operands(
        r_bitmaps, r_sizes, s_bitmaps, s_sizes, lo, hi, tiles, defaults)
    TM, TN, _ = tls
    m_tiles, n_tiles = rb.shape[0] // TM, sb.shape[0] // TN
    ti, tj = _live_tiles(lo_p[:, 0], hi_p[:, 0], m_tiles, n_tiles, TM, TN)
    L = len(ti)
    if L == 0:
        return PendingPairs(None, None, None, None, TM, TN, 0,
                            m_tiles * n_tiles, m * n)
    masks, counts = live_fn(jnp.asarray(ti), jnp.asarray(tj), rb, r_sz,
                            sb, s_sz, lo_p, hi_p, t=t, measure=measure,
                            tiles=tls, interpret=interpret)
    return PendingPairs(masks, counts, jnp.asarray(ti), jnp.asarray(tj),
                        TM, TN, L, m_tiles * n_tiles, m * n)


@jax.jit
def _remap_rows(pairs, row_map):
    """Translate packed pair rows through ``row_map`` (-1 pads kept)."""
    r = pairs[:, 0]
    valid = r >= 0
    rows = jnp.where(valid, row_map[jnp.where(valid, r, 0)], -1)
    return jnp.stack([rows, pairs[:, 1]], axis=1)


def join_pairs_finalize(pending: PendingPairs, capacity: int | None = None,
                        stats: dict | None = None):
    """Sync a dispatched join's counts and compact -> (pairs, n_pairs)."""
    fault_point("compact")
    L = pending.live_tiles
    with obs.span("repro.sync"):
        # the first blocking reads: the walk counters and per-tile counts
        extras = ({key: int(np.asarray(dev).sum())
                   for key, dev in pending.extras.items()}
                  if stats is not None and pending.extras else {})
        counts_np = np.asarray(pending.counts)[:, 0] if L else None
    if stats is not None:
        stats["live_tiles"] = L
        stats["total_tiles"] = pending.total_tiles
        stats["dense_mask_bytes"] = pending.dense_mask_bytes
        stats.update(extras)
        if pending.walk_impl is not None:
            stats["walk_impl"] = pending.walk_impl
    if L == 0:
        if stats is not None:
            stats.update(pair_count=0, pair_bytes=0, counts_bytes=0,
                         output_bytes=0, regrows=0)
        return jnp.zeros((0, 2), jnp.int32), 0
    # per-tile counts are exact even when a capacity hint is too small:
    # they tell us the regrown capacity without a second kernel pass.
    total = int(counts_np.sum())
    cap = round_capacity(total if capacity is None else capacity)
    regrows = 0
    if cap < total:  # overflow: regrow to the exact requirement, recompact
        fault_point("regrow")
        cap = round_capacity(total)
        regrows += 1
    pairs = (_compact_live(pending.masks, pending.tile_i, pending.tile_j,
                           tm=pending.tm, tn=pending.tn, size=cap)
             if cap else jnp.zeros((0, 2), jnp.int32))
    if pending.row_map is not None and cap:
        pairs = _remap_rows(pairs, pending.row_map)
    if stats is not None:
        stats["pair_count"] = total
        stats["pair_bytes"] = cap * 8          # what the packed array ships
        stats["counts_bytes"] = L * 4          # per-tile count transfer
        stats["output_bytes"] = cap * 8 + L * 4
        stats["regrows"] = regrows
    return pairs, total


def join_mask_finalize(pending: PendingPairs, m: int, n: int,
                       stats: dict | None = None) -> np.ndarray:
    """Resolve a dispatched sparse join into the dense (m, n) bool mask.

    The emit='mask' counterpart of ``join_pairs_finalize``: the staged
    live-tile sub-masks are scattered back onto the full row-tile grid
    (skipped tiles stay all-False — their windows are empty, so that is
    exact), the dispatch's size-sort is undone through ``row_map``, and
    the padding is sliced off. Shares the same ``PendingPairs`` handle,
    so mask emission now rides the same kernel dispatch (and reports the
    same ``walk_steps``/``early_stops`` counters) as pair emission.
    """
    fault_point("compact")
    L = pending.live_tiles
    if stats is not None:
        stats["live_tiles"] = L
        stats["total_tiles"] = pending.total_tiles
        stats["dense_mask_bytes"] = pending.dense_mask_bytes
        if pending.extras:
            for key, dev in pending.extras.items():
                stats[key] = int(np.asarray(dev).sum())
        if pending.walk_impl is not None:
            stats["walk_impl"] = pending.walk_impl
    if L == 0:
        return np.zeros((m, n), bool)
    masks = np.asarray(pending.masks)  # (L, tm, NP)
    tm = pending.tm
    ti = np.asarray(pending.tile_i)
    full = np.zeros((pending.total_tiles * tm, masks.shape[2]), bool)
    full.reshape(pending.total_tiles, tm, -1)[ti] = masks
    if pending.row_map is None:
        return full[:m, :n]
    out = np.zeros((m, n), bool)
    rm = np.asarray(pending.row_map)
    valid = rm >= 0
    out[rm[valid]] = full[valid][:, :n]
    return out


def lfvt_walk_join_mask(flat, r_padded, r_sizes, lo, hi, t: float,
                        measure: str = "jaccard", impl: str | None = None,
                        row_tile: int | None = None,
                        interpret: bool | None = None,
                        stats: dict | None = None,
                        schedule: str = "host") -> np.ndarray:
    """Dense-mask flat-LFVT join through the live row-tiled walk kernel.

    Same dispatch as ``lfvt_walk_join_pairs`` (so emit='mask' gets the
    kernel and its walk counters too), resolved by
    ``join_mask_finalize`` instead of pair compaction.
    """
    pending = lfvt_walk_join_pairs_dispatch(
        flat, r_padded, r_sizes, lo, hi, t, measure=measure, impl=impl,
        row_tile=row_tile, interpret=interpret, schedule=schedule)
    return join_mask_finalize(pending, int(np.shape(r_padded)[0]),
                              flat.n_sets, stats)


def _join_pairs(live_fn, defaults, r_bitmaps, r_sizes, s_bitmaps, s_sizes,
                lo, hi, t, tiles, interpret, capacity, stats,
                measure="jaccard"):
    pending = _join_pairs_dispatch(live_fn, defaults, r_bitmaps, r_sizes,
                                   s_bitmaps, s_sizes, lo, hi, t, tiles,
                                   interpret, measure)
    return join_pairs_finalize(pending, capacity, stats)


def bitmap_join_pairs(r_bitmaps, r_sizes, s_bitmaps, s_sizes, lo, hi,
                      t: float, tiles=None, interpret: bool | None = None,
                      capacity: int | None = None, stats: dict | None = None,
                      measure: str = "jaccard"):
    """Sparse popcount join -> (pairs (P, 2) int32 device array, n_pairs).

    ``pairs[:n_pairs]`` are the qualifying (row, col) indices into the
    unpadded operands; later rows are (-1, -1) capacity padding. P is
    ``capacity`` rounded up (regrown automatically on overflow — the
    per-tile counts make the retry exact, never a second kernel pass).
    """
    return _join_pairs(_bj.bitmap_join_live_tiled, _bj.DEFAULT_TILES,
                       r_bitmaps, r_sizes, s_bitmaps, s_sizes, lo, hi,
                       t, tiles, interpret, capacity, stats, measure)


def onehot_join_pairs(r_bitmaps_or_padded, r_sizes, s_bitmaps, s_sizes, lo,
                      hi, t: float, universe: int | None = None, tiles=None,
                      interpret: bool | None = None,
                      capacity: int | None = None, stats: dict | None = None,
                      measure: str = "jaccard"):
    """Sparse MXU join; same contract as ``bitmap_join_pairs``."""
    r_in, s_in = _coerce_bitmaps(r_bitmaps_or_padded, s_bitmaps, universe)
    return _join_pairs(_oj.onehot_join_live_tiled, _oj.DEFAULT_TILES,
                       r_in, r_sizes, s_in, s_sizes, lo, hi,
                       t, tiles, interpret, capacity, stats, measure)


def bitmap_join_pairs_dispatch(r_bitmaps, r_sizes, s_bitmaps, s_sizes, lo,
                               hi, t: float, tiles=None,
                               interpret: bool | None = None,
                               measure: str = "jaccard",
                               hints=None) -> PendingPairs:
    """Async half of ``bitmap_join_pairs``: launch, don't sync.

    ``hints`` (optional mapping, e.g. from a ``JoinPlan``) may carry
    ``tiles`` — explicit kwargs win over hints.
    """
    if hints and tiles is None:
        tiles = hints.get("tiles")
    return _join_pairs_dispatch(_bj.bitmap_join_live_tiled, _bj.DEFAULT_TILES,
                                r_bitmaps, r_sizes, s_bitmaps, s_sizes,
                                lo, hi, t, tiles, interpret, measure)


def onehot_join_pairs_dispatch(r_bitmaps_or_padded, r_sizes, s_bitmaps,
                               s_sizes, lo, hi, t: float,
                               universe: int | None = None, tiles=None,
                               interpret: bool | None = None,
                               measure: str = "jaccard",
                               hints=None) -> PendingPairs:
    """Async half of ``onehot_join_pairs``: launch, don't sync.

    ``hints`` as in :func:`bitmap_join_pairs_dispatch`.
    """
    if hints and tiles is None:
        tiles = hints.get("tiles")
    r_in, s_in = _coerce_bitmaps(r_bitmaps_or_padded, s_bitmaps, universe)
    return _join_pairs_dispatch(_oj.onehot_join_live_tiled, _oj.DEFAULT_TILES,
                                r_in, r_sizes, s_in, s_sizes, lo, hi,
                                t, tiles, interpret, measure)


def lfvt_join_pairs_dispatch(flat, r_padded, r_sizes, lo, hi, t: float,
                             measure: str = "jaccard") -> PendingPairs:
    """Flat-LFVT array-walk join as an in-flight sparse emission.

    ``flat`` is a ``core.lfvt_flat.FlatLFVT`` (device arrays cached on
    the instance); ``r_padded`` the (mb, Lr) -1-padded R element lists.
    The whole (mb, n) qualifying mask is one "live tile", so the PR-1
    ``PendingPairs`` protocol — deferred count sync, ``_compact_live``
    packing, power-of-two regrow — applies unchanged.
    """
    from repro.core.lfvt_flat import flat_join_mask  # deferred: no cycle
    fault_point("walk_dispatch")
    mb, n = r_padded.shape[0], flat.n_sets
    if mb == 0 or n == 0:
        return PendingPairs(None, None, None, None, max(mb, 1), max(n, 1),
                            0, 1, mb * n)
    mask = flat_join_mask(flat, r_padded, r_sizes, lo, hi, t, measure)
    counts = jnp.sum(mask, dtype=jnp.int32).reshape(1, 1)
    zero = jnp.zeros(1, jnp.int32)
    return PendingPairs(mask[None], counts, zero, zero, mb, n, 1, 1, mb * n)


def lfvt_join_pairs(flat, r_padded, r_sizes, lo, hi, t: float,
                    capacity: int | None = None, stats: dict | None = None,
                    measure: str = "jaccard"):
    """Sparse flat-LFVT join; same contract as ``bitmap_join_pairs``."""
    pending = lfvt_join_pairs_dispatch(flat, r_padded, r_sizes, lo, hi, t,
                                       measure)
    return join_pairs_finalize(pending, capacity, stats)


def lfvt_walk_join_pairs_dispatch(flat, r_padded, r_sizes, lo, hi, t: float,
                                  measure: str = "jaccard",
                                  impl: str | None = None,
                                  row_tile: int | None = None,
                                  interpret: bool | None = None,
                                  schedule: str = "host",
                                  hints=None
                                  ) -> PendingPairs:
    """Flat-LFVT walk as a live row-tiled kernel dispatch (DESIGN.md §10).

    The R block is sorted by set size (rows with near-identical Lemma-3.1
    windows share a tile), cut into ``row_tile``-row tiles, and row tiles
    with empty windows are dropped before launch — PR 1's live-tile
    schedule collapsed to one dimension, each surviving tile owning a
    VMEM-resident ``(row_tile, n)`` count tile for the whole walk.

    impl: None/'auto'/'jnp' — the XLA-compiled jnp twin, on every
          backend; 'pallas' — the Pallas kernel under the interpreter,
          the parity harness the tests pin. The Mosaic walk body does
          not lower for the TPU (its 1-D ``seq``/``nxt`` gathers are
          refused, ROADMAP Speed 3), so 'pallas' on a TPU raises
          ``NotImplementedError`` instead of compiling. The launched
          implementation is reported as ``stats['walk_impl']``, and the
          per-step VMEM working set the kernel would need as
          ``walk_vmem_tile_bytes`` (via ``PendingPairs.extras``).
    schedule: 'host' (default) — ``plan_row_tiles`` builds the live-tile
          list on host and only live tiles are staged (zero dead grid
          steps); 'device' — ``plan_row_tiles_device`` computes the same
          plan inside the trace and the planned walk
          (``lfvt_walk_planned`` / ``lfvt_walk_planned_ref``) consumes
          the live prefix, staging the full tile range with dead tiles
          zeroed. The device schedule never syncs the plan to host — the
          variant the mesh shard bodies run — and reports its live count
          through ``extras['live_tiles']``. Masks/pairs are bit-identical
          across schedules.
    Emits ``walk_steps``/``early_stops`` device counters via
    ``PendingPairs.extras`` and the row sort via ``row_map``; the shared
    finalize folds both back out.
    """
    from . import lfvt_walk as _lw

    fault_point("walk_dispatch")
    # planner hints (JoinPlan tuning knobs): explicit kwargs win
    if hints:
        if row_tile is None:
            row_tile = hints.get("row_tile")
        if impl in (None, "auto") and hints.get("impl"):
            impl = hints["impl"]
    if impl in (None, "auto"):
        impl = "jnp"
    if impl not in ("pallas", "jnp"):
        raise ValueError(f"unknown lfvt walk impl {impl!r}")
    if impl == "pallas":
        interpret = _interpret_default() if interpret is None else interpret
        if interpret is False:
            raise NotImplementedError(
                "the Mosaic LFVT walk does not lower for the TPU (1-D "
                "gathers in its body); use impl='jnp'")
    if schedule not in ("host", "device"):
        raise ValueError(f"unknown walk schedule {schedule!r}")
    tm = row_tile or global_config.row_tile
    r_padded = jnp.asarray(r_padded)
    m, Lr = r_padded.shape
    n = flat.n_sets
    m_tiles = max(-(-m // tm), 1)
    if (m == 0 or n == 0 or Lr == 0 or len(flat.entry_elem) == 0
            or flat.max_seq_len == 0):
        return PendingPairs(None, None, None, None, tm, max(n, 1), 0,
                            m_tiles, m * n)
    dev = flat.to_device()
    # host-side plan: size-sorted row order, tile padding, live row tiles
    order = np.argsort(-np.asarray(r_sizes), kind="stable").astype(np.int32)
    pad_rows = (-m) % tm
    lo_p = np.concatenate(
        [np.asarray(lo)[order], np.zeros(pad_rows, np.int64)])
    hi_p = np.concatenate(
        [np.asarray(hi)[order], np.zeros(pad_rows, np.int64)])
    sz_p = np.concatenate(
        [np.asarray(r_sizes)[order], np.zeros(pad_rows, np.int64)])
    m_tiles = (m + pad_rows) // tm
    ti = None
    if schedule == "host":
        ti = _lw.plan_row_tiles(lo_p, hi_p, tm)
        if len(ti) == 0:
            return PendingPairs(None, None, None, None, tm, n, 0, m_tiles,
                                m * n)
    r_perm = jnp.pad(jnp.take(r_padded, jnp.asarray(order), axis=0),
                     ((0, pad_rows), (0, 0)), constant_values=-1)
    lane_pos, lane_rem = _lw.entry_state(dev, r_perm)
    seq2d = _pad_to(dev.seq_row.reshape(1, -1), 1, _lw.COL_PAD)
    nxt2d = _pad_to(dev.seq_next.reshape(1, -1), 1, _lw.COL_PAD)
    ssz2d = _pad_to(dev.s_sizes.reshape(1, -1), 1, _lw.COL_PAD)
    operands = (lane_pos, lane_rem, nxt2d, seq2d, ssz2d,
                jnp.asarray(sz_p, dtype=jnp.int32).reshape(-1, 1),
                jnp.asarray(lo_p, dtype=jnp.int32).reshape(-1, 1),
                jnp.asarray(hi_p, dtype=jnp.int32).reshape(-1, 1))
    kw = dict(t=t, measure=measure, max_steps=int(flat.max_seq_len), tm=tm)
    extras = {
        # host int: the per-grid-step VMEM working set this launch was
        # accounted at (replaces the SMEM budget)
        "walk_vmem_tile_bytes": _lw.walk_vmem_tile_bytes(
            tm, Lr, ssz2d.shape[1], seq2d.shape[1])}
    if schedule == "device":
        # traced plan: the live count stays a device scalar (no sync at
        # dispatch); finalize folds it over the staged-tile default via
        # extras — the full tile range is staged, dead tiles zeroed
        ti_sorted, n_live = _lw.plan_row_tiles_device(
            operands[-2], operands[-1], tm)
        planned = (_lw.lfvt_walk_planned if impl == "pallas"
                   else _lw.lfvt_walk_planned_ref)
        if impl == "pallas":
            kw["interpret"] = interpret
        else:
            # explicit static arg: a config change retraces, a frozen
            # trace-time default would not
            kw["chunk"] = global_config.plan_chunk_tiles
        masks, counts, steps, stops = planned(ti_sorted, n_live,
                                              *operands, **kw)
        tile_i, L = jnp.arange(m_tiles, dtype=jnp.int32), m_tiles
        extras["live_tiles"] = n_live
    elif impl == "pallas":
        masks, counts, steps, stops = _lw.lfvt_walk_live_tiled(
            jnp.asarray(ti), *operands, interpret=interpret, **kw)
        tile_i, L = jnp.asarray(ti), len(ti)
    else:
        masks, counts, steps, stops = _lw.lfvt_walk_live_tiled_ref(
            jnp.asarray(ti), *operands, **kw)
        tile_i, L = jnp.asarray(ti), len(ti)
    extras.update(walk_steps=steps, early_stops=stops)
    row_map = jnp.asarray(np.concatenate(
        [order, np.full(pad_rows, -1, np.int32)]))
    return PendingPairs(
        masks, counts, tile_i, jnp.zeros(L, jnp.int32),
        tm, ssz2d.shape[1], L, m_tiles, m * n,
        extras=extras, row_map=row_map, walk_impl=impl)


def lfvt_walk_join_pairs(flat, r_padded, r_sizes, lo, hi, t: float,
                         capacity: int | None = None,
                         stats: dict | None = None,
                         measure: str = "jaccard", impl: str | None = None,
                         row_tile: int | None = None,
                         interpret: bool | None = None,
                         schedule: str = "host"):
    """Sparse kernel-walk flat-LFVT join; contract of ``bitmap_join_pairs``."""
    pending = lfvt_walk_join_pairs_dispatch(
        flat, r_padded, r_sizes, lo, hi, t, measure=measure, impl=impl,
        row_tile=row_tile, interpret=interpret, schedule=schedule)
    return join_pairs_finalize(pending, capacity, stats)


def join_pairs(method: str, *args, **kw):
    """Dispatch sparse emission by family ('bitmap' | 'onehot' | 'lfvt'
    — the kernel walk — | 'lfvt_ref' — the PR-4 whole-block jnp walk)."""
    if method == "bitmap":
        return bitmap_join_pairs(*args, **kw)
    if method == "onehot":
        return onehot_join_pairs(*args, **kw)
    if method == "lfvt":
        return lfvt_walk_join_pairs(*args, **kw)
    if method == "lfvt_ref":
        return lfvt_join_pairs(*args, **kw)
    raise ValueError(f"unknown pair-emission method {method!r}")


def _pack_bitmaps(padded: jax.Array, universe: int) -> jax.Array:
    """(rows, L) int32 element lists (-1 pad) -> (rows, W) uint32 bitmaps.

    Elements within a set are unique, so each (word, bit) target is hit at
    most once and scatter-add of single-bit values equals scatter-or.
    """
    W = max((universe + 31) // 32, 1)
    rows, L = padded.shape
    valid = padded >= 0
    word = jnp.where(valid, padded // 32, 0)
    bit = jnp.where(valid, padded % 32, 0).astype(jnp.uint32)
    onehot = jnp.where(valid, jnp.left_shift(jnp.uint32(1), bit), jnp.uint32(0))
    out = jnp.zeros((rows, W), jnp.uint32)
    rows_idx = jnp.broadcast_to(jnp.arange(rows)[:, None], (rows, L))
    return out.at[rows_idx, word].add(onehot)
