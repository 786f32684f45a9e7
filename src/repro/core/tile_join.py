"""TPU-native candidate-free tile join (DESIGN.md §2).

The FVT traversal becomes a tiled intersection accumulation over a
size-sorted S:

  * S is sorted by set size descending (the FVT "bigger nearer the root"
    invariant). The Lemma-3.1 window of any ``R_i`` is then a contiguous
    column range ``[lo_i, hi_i)`` found by binary search — tile skipping is
    the Theorem-3.3 early stop at tile granularity.
  * ``f_{i,j} = sum_a [a in R_i][a in S_j]`` is computed blockwise either
    on the MXU (one-hot matmul) or the VPU (bitmap popcount) — see
    ``repro.kernels``. This module provides the pure-jnp forms used as
    oracles and as the CPU execution path, plus the host driver that
    streams R blocks and emits qualifying pairs (no candidate pairs are
    ever materialized in HBM: thresholding happens on-device).
  * Output is sparse by default (DESIGN.md §6): qualifying pairs are
    compacted on device and only the packed (r, s) index array crosses
    the host boundary, so output traffic scales with the result size.
    The sorted-S device representation is cached per collection across R
    blocks and across calls.
"""
from __future__ import annotations

import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from . import measures
from .config import global_config
from .planner import build_plan
from .resilience import (PairCapacityError, build_resilience, checked_flat,
                         collection_digest, fault_point, resilience_stats,
                         sorted_pairs)
from .sets import EmptyCollectionError, SetCollection

__all__ = [
    "popcount_counts",
    "popcount_row_block",
    "onehot_counts",
    "qualify",
    "window_bounds",
    "cf_rs_join_device",
    "clear_s_rep_cache",
    "clear_r_block_cache",
    "round_capacity",
    "PAIR_CAP_GRAIN",
]


# ---------------------------------------------------------------------- #
# device-side primitives (pure jnp; kernels mirror these)
# ---------------------------------------------------------------------- #
def popcount_row_block(m: int, n: int) -> int:
    """R-row block size bounding ``popcount_counts``' (mb, n, W) staged
    intermediate. Shared with the benchmarks' feasibility gate so the
    modeled intermediate always matches what the kernel stages."""
    return max(1, min(m, 4096 // max(1, n // 1024 + 1)))


def popcount_counts(r_bitmaps: jax.Array, s_bitmaps: jax.Array) -> jax.Array:
    """(m, W) x (n, W) uint32 -> (m, n) int32 intersection sizes.

    Blocked over R rows to bound the (mb, n, W) intermediate.
    """
    def row_block(rb):  # (mb, W)
        inter = jnp.bitwise_and(rb[:, None, :], s_bitmaps[None, :, :])
        return jnp.sum(jax.lax.population_count(inter), axis=-1, dtype=jnp.int32)

    m = r_bitmaps.shape[0]
    mb = popcount_row_block(m, s_bitmaps.shape[0])
    if m <= mb:
        return row_block(r_bitmaps)
    pad = (-m) % mb
    rp = jnp.pad(r_bitmaps, ((0, pad), (0, 0)))
    out = jax.lax.map(row_block, rp.reshape(-1, mb, rp.shape[1]))
    return out.reshape(-1, s_bitmaps.shape[0])[:m]


def onehot_counts(r_padded: jax.Array, r_sizes: jax.Array,
                  s_padded: jax.Array, s_sizes: jax.Array,
                  universe: int, block: int = 512) -> jax.Array:
    """Intersection sizes via blocked one-hot matmuls (MXU formulation).

    Streams the universe in ``block``-wide chunks: membership matrices
    ``B_R (m, block)``, ``B_S (n, block)`` and ``F += B_R @ B_S^T``.
    """
    m, n = r_padded.shape[0], s_padded.shape[0]
    nblocks = -(-universe // block)

    def body(carry, b):
        start = b * block
        br = _membership_block(r_padded, start, block)  # (m, block) f32
        bs = _membership_block(s_padded, start, block)
        return carry + br @ bs.T, None

    init = jnp.zeros((m, n), jnp.float32)
    out, _ = jax.lax.scan(body, init, jnp.arange(nblocks))
    return out.astype(jnp.int32)


def _membership_block(padded: jax.Array, start, block: int) -> jax.Array:
    """One-hot membership of elements in [start, start+block) -> (rows, block)."""
    rel = padded - start
    valid = (rel >= 0) & (rel < block) & (padded >= 0)
    rel = jnp.where(valid, rel, 0)
    onehot = jax.nn.one_hot(rel, block, dtype=jnp.float32) * valid[..., None]
    return onehot.sum(axis=1)


def qualify(counts: jax.Array, r_sizes: jax.Array, s_sizes: jax.Array,
            t: float, measure: str = "jaccard") -> jax.Array:
    """``sim >= t`` as a boolean tile via the integer-exact cross-multiplied
    predicate (DESIGN.md §8); f > 0 required.

    Replaces the float32 form ``f*(1+t) >= t*(|R|+|S|)``, which
    misclassifies exact-boundary pairs (e.g. |R|=|S|=5, f=4 at t=2/3 —
    see tests/test_measures.py::test_float32_boundary_regression).
    """
    return measures.device_qualify(counts, r_sizes[:, None],
                                   s_sizes[None, :], t, measure)


def window_bounds(r_sizes: np.ndarray, s_sizes_desc: np.ndarray, t: float,
                  measure: str = "jaccard"):
    """Column window [lo, hi) per R row over size-descending S (Lemma 3.1,
    generalized per measure — DESIGN.md §8).

    ``s_sizes_desc`` must be non-increasing. Rows outside the window can be
    skipped entirely (Theorem 3.3 / tile early stop).
    """
    asc = s_sizes_desc[::-1]
    n = len(asc)
    lo_size, hi_size = measures.get_measure(measure).size_window_arrays(
        np.asarray(r_sizes, dtype=np.int64), t)  # inclusive, integer-exact
    # first index (in desc order) with size <= hi_size:
    lo = n - np.searchsorted(asc, hi_size, side="right")
    # one past last index with size >= lo_size:
    hi = n - np.searchsorted(asc, lo_size, side="left")
    return lo.astype(np.int64), hi.astype(np.int64)


# ---------------------------------------------------------------------- #
# host driver — streams R blocks, emits qualifying pairs
# ---------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("t", "measure"))
def _popcount_qualify(r_bm, r_sz, s_bm, s_sz, col_lo, col_hi, *, t,
                      measure="jaccard"):
    counts = popcount_counts(r_bm, s_bm)
    cols = jnp.arange(s_bm.shape[0])[None, :]
    in_window = (cols >= col_lo[:, None]) & (cols < col_hi[:, None])
    return qualify(counts, r_sz, s_sz, t, measure) & in_window


@functools.partial(jax.jit, static_argnames=("t", "universe", "measure"))
def _onehot_qualify(r_pad, r_sz, s_pad, s_sz, col_lo, col_hi, *, t, universe,
                    measure="jaccard"):
    counts = onehot_counts(r_pad, r_sz, s_pad, s_sz, universe)
    cols = jnp.arange(s_pad.shape[0])[None, :]
    in_window = (cols >= col_lo[:, None]) & (cols < col_hi[:, None])
    return qualify(counts, r_sz, s_sz, t, measure) & in_window


# Capacity rounding for the jitted compactions (static output size):
# next power-of-two multiple of the grain, so recompiles are O(log) in
# result size. The grain lives in ``core.config`` now; this name is the
# import-time alias the kernels layer re-exports.
PAIR_CAP_GRAIN = global_config.pair_cap_grain


def round_capacity(n: int) -> int:
    """Regrow protocol: next power-of-two multiple of the capacity grain
    (``global_config.pair_cap_grain``) >= n, capped at
    ``global_config.pair_cap_ceiling``.

    Every pair-buffer allocation in the repo routes through here, so the
    ceiling is the single guard against the doubling protocol allocating
    toward the int32 pair-count limit: requests past it raise
    :class:`~repro.core.resilience.PairCapacityError` (a named error the
    degradation ladder treats as "split or fall back", never a silent
    wrap). When the ceiling is not itself a power-of-two multiple of the
    grain, in-range requests clamp to the ceiling instead of rounding
    past it.
    """
    if n <= 0:
        return 0
    ceiling = int(global_config.pair_cap_ceiling)
    if n > ceiling:
        raise PairCapacityError(
            f"pair buffer request {n} exceeds pair_cap_ceiling {ceiling} "
            f"(raise global_config.pair_cap_ceiling / REPRO_PAIR_CAP_CEILING"
            f" or reduce the block size)")
    cap = global_config.pair_cap_grain
    while cap < n:
        cap *= 2
    return min(cap, ceiling)




@jax.jit
def _mask_total(mask):
    return jnp.sum(mask, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("size",))
def _compact_mask(mask, *, size):
    """Device-side segment compaction of a dense bool mask.

    Works for any rank: an (m, n) mask packs to (size, 2) (row, col)
    int32, an (n_shards, m, n) stack to (size, 3) (shard, row, col).
    Entries past the true count are -1 capacity padding.
    """
    idx = jnp.nonzero(mask, size=size, fill_value=-1)
    return jnp.stack(idx, axis=1)


# ------------------------------------------------------------------ #
# device-resident S representation cache
#
# The sorted-S side of the join is reused across every R block of a call
# AND across calls (the LLM-dedup pipeline joins each incoming batch
# against the same curated corpus): keep the size-sorted collection plus
# its device arrays alive per source collection. WeakKeyDictionary ->
# entries die with the collection, no manual invalidation needed
# (collections are immutable by convention).
# ------------------------------------------------------------------ #
_S_REP_CACHE: "weakref.WeakKeyDictionary[SetCollection, dict]" = (
    weakref.WeakKeyDictionary())


def clear_s_rep_cache() -> None:
    _S_REP_CACHE.clear()


def _s_device_rep(S: SetCollection, family: str, W: int,
                  stats: dict | None = None):
    """-> (sorted collection, device rep, device sizes, np sizes).

    family 'bitmap' -> (n, W) uint32 device bitmaps; 'padded' -> (n, L)
    int32 element lists; 'lfvt' -> the ``FlatLFVT`` itself (its device
    arrays are uploaded once via ``to_device`` and live on the instance,
    which this cache keeps alive beside the other reps).
    """
    fault_point("device_upload")
    entry = _S_REP_CACHE.get(S)
    if entry is None:
        entry = {}
        _S_REP_CACHE[S] = entry
    key = (("bitmap", W) if family == "bitmap" else
           ("lfvt",) if family == "lfvt" else ("padded",))
    hit = "sorted" in entry and key in entry
    if "sorted" not in entry:
        # None = "the key itself is already sorted": the cache value must
        # not hold a strong reference to its own WeakKeyDictionary key,
        # or the entry (and the device arrays) can never be evicted
        Ss = None if S.sorted_by_size else S.sort_by_size()
        entry["sorted"] = Ss
        entry["sizes_np"] = (S if Ss is None else Ss).sizes()
        entry["sizes_dev"] = jnp.asarray(entry["sizes_np"])
    Ss = entry["sorted"] if entry["sorted"] is not None else S
    if key not in entry:
        if family == "bitmap":
            entry[key] = jnp.asarray(Ss.bitmaps(W))
        elif family == "lfvt":
            flat = Ss.flat_lfvt()  # memoized on the collection
            flat.to_device()       # one upload, cached on the FlatLFVT
            entry[key] = flat
        else:
            entry[key] = jnp.asarray(Ss.padded()[0])
    if stats is not None:
        stats["s_rep_cache_hit"] = hit
    return Ss, entry[key], entry["sizes_dev"], entry["sizes_np"]


# ------------------------------------------------------------------ #
# device-resident R-block representation cache
#
# Mirror of _S_REP_CACHE for the streamed side: the dedup pipeline joins
# the same R batch against several thresholds/corpora, and the MR driver
# re-blocks the same R on every call. Keyed per source collection
# (weakly) by (family, word width, block range) -> uploaded device array.
# ------------------------------------------------------------------ #
_R_BLOCK_CACHE: "weakref.WeakKeyDictionary[SetCollection, dict]" = (
    weakref.WeakKeyDictionary())
# bound on cached block uploads per collection: joining the same R against
# corpora of different universes (word widths) or with different r_block
# grids would otherwise retain a device copy per combination until R dies
_R_BLOCK_CACHE_MAX_ENTRIES = 64


def clear_r_block_cache() -> None:
    _R_BLOCK_CACHE.clear()


def _r_block_rep(R: SetCollection, family: str, W: int, start: int,
                 stop: int):
    """-> (device rep of R[start:stop], cache_hit). Host rep is memoized on
    the collection (``SetCollection.bitmaps``/``padded``); this adds the
    per-block device upload."""
    fault_point("device_upload")
    entry = _R_BLOCK_CACHE.get(R)
    if entry is None:
        entry = {}
        _R_BLOCK_CACHE[R] = entry
    # the padded-list rep does not depend on W: one key (and one upload)
    # serves corpora of every universe width AND both consumers of the
    # layout (the one-hot matmul and the flat-LFVT array walk)
    key = (family, W, start, stop) if family == "bitmap" else (
        "padded", start, stop)
    hit = key in entry
    if hit:
        entry[key] = entry.pop(key)  # LRU: move to the fresh end
    else:
        if len(entry) >= _R_BLOCK_CACHE_MAX_ENTRIES:
            entry.pop(next(iter(entry)))  # evict least-recently used
        host = (R.bitmaps(W) if family == "bitmap" else R.padded()[0])
        entry[key] = jnp.asarray(host[start:stop])
    return entry[key], hit


def cf_rs_join_device(R: SetCollection, S: SetCollection, t: float,
                      method: str = "popcount", r_block: int | None = None,
                      stats: dict | None = None, emit: str = "pairs",
                      pair_capacity: int | None = None,
                      double_buffer: bool | None = None,
                      measure: str = "jaccard",
                      fault_plan=None,
                      checkpoint_dir: str | None = None,
                      plan=None) -> set:
    """Candidate-free device join. Returns {(r_id, s_id)}.

    method: 'auto' — the cost-model planner (core/planner.py,
            DESIGN.md §14) probes the inputs and picks the cheapest rep
            family; the decision lands in ``stats["plan"]`` (the
            ``repro.join`` front door defaults to this). Or force one:
            'popcount' (bitmaps, VPU, the legacy default) | 'onehot'
            (membership matmul, MXU)
            | 'kernel_bitmap' | 'kernel_onehot' (Pallas: Mosaic on TPU,
            the interpreter elsewhere)
            | 'lfvt' (flat-array LFVT walk, DESIGN.md §9-§10 — S-side
            device memory ~ Σ|seq| tuples plus E ≤ Σ|seq| sparse entry
            rows, never O(U), instead of the |S|·⌈U/32⌉ bitmap sheet;
            the path for large element universes; both emit modes run
            the live row-tiled walk — its compiled jnp twin on every
            backend, ``stats['walk_impl']`` — with walk_steps/
            early_stops/live_tiles stats) | 'lfvt_ref' (the PR-4 whole-block
            jnp walk, kept as the reference fallback and the
            `--impl ref` bench axis).
    measure: 'jaccard' | 'cosine' | 'dice' | 'overlap' (DESIGN.md §8) —
            the qualify predicate and Lemma-3.1 window both specialize.
    emit:   'pairs' (default) — qualifying pairs are compacted on device
            and only the packed (row, col) int32 array crosses the host
            boundary (output bytes ~ result size; kernel methods also run
            the live-tile schedule, so skipped tiles cost zero grid
            steps). 'mask' — dense fallback: the (m, n) boolean mask is
            transferred and scanned on host (output bytes O(m·n)).
    pair_capacity: optional initial pair-array capacity per R block for
            emit='pairs'; regrown automatically on overflow.
    double_buffer: stream R blocks double-buffered — block k+1's device
            work is dispatched *before* block k's pair count is synced to
            host, so device compute overlaps host-side result building.
            Results are identical with it off (debug knob).

    fault_plan / checkpoint_dir activate the resilience layer
    (core/resilience.py, DESIGN.md §12): per-R-block tasks run under the
    retry + degradation ladder (method -> host oracle), with optional
    per-block checkpoints for resume. None/None (the default) keeps the
    original streaming path byte-for-byte.

    ``r_block`` and ``double_buffer`` default to ``global_config``
    (core/config.py) when None.
    """
    # kwarg lattice + method='auto' resolution live in the plan builder
    # (ISSUE 10): the legacy kwargs of this signature are overrides onto
    # the JoinPlan; a precomputed ``plan`` skips re-planning entirely
    if plan is None:
        plan = build_plan(R, S, t, driver="device", method=method,
                          measure=measure, emit=emit, r_block=r_block,
                          pair_capacity=pair_capacity,
                          double_buffer=double_buffer)
    method = plan.method
    r_block = plan.r_block or global_config.r_block
    double_buffer = plan.double_buffer
    R.validate()
    S.validate()
    if global_config.strict_validation and (not len(R) or not len(S)):
        side = "R" if not len(R) else "S"
        raise EmptyCollectionError(
            f"empty {side} collection (strict_validation is on)")
    res = build_resilience(checkpoint_dir, fault_plan)
    if not len(R) or not len(S):
        if stats is not None:  # consumers index these unconditionally
            stats.update(method=method, emit=emit, r_blocks=0, pair_count=0,
                         output_bytes=0, dense_mask_bytes=0,
                         double_buffered=double_buffer, regrows=0,
                         r_rep_cache_hits=0, plan=plan.to_dict())
            resilience_stats(stats, res)
        return set()
    family = ("lfvt" if method in ("lfvt", "lfvt_ref") else
              "onehot" if method == "onehot" else "bitmap")
    universe = max(R.universe, S.universe)
    W = max((universe + 31) // 32, 1)
    Ss, s_rep, s_sz, s_sizes = _s_device_rep(S, family, W, stats)
    r_sizes_all = R.sizes()
    # int32 exactness guard for the device predicate (DESIGN.md §8)
    measures.get_measure(measure).validate(
        t, max(int(r_sizes_all.max(initial=0)), int(s_sizes.max(initial=0))))
    lo_all, hi_all = window_bounds(r_sizes_all, s_sizes, t, measure)

    kernel_methods = ("kernel_bitmap", "kernel_onehot", "lfvt", "lfvt_ref")
    kernel_pairs = method in kernel_methods and emit == "pairs"
    if method in kernel_methods:
        from repro.kernels import ops as kops  # deferred: optional dep

    pairs: set = set()
    m = len(R)
    # speculative per-block compaction capacity: fixed (never carried
    # between blocks) so the byte accounting stays deterministic
    spec_cap = round_capacity(pair_capacity) if pair_capacity else (
        PAIR_CAP_GRAIN)

    def zero_acc() -> dict:
        return {"out_sparse": 0, "out_dense": 0, "n_pairs": 0, "live": 0,
                "total_tiles": 0, "regrows": 0, "r_rep_hits": 0,
                "walk_steps": 0, "early_stops": 0, "walk_vmem": 0,
                "walk_impl": None}

    acc = zero_acc()

    def fold_kernel_stats(acc: dict, kstats: dict) -> None:
        acc["live"] += kstats.get("live_tiles", 0)
        acc["total_tiles"] += kstats.get("total_tiles", 0)
        acc["walk_steps"] += kstats.get("walk_steps", 0)
        acc["early_stops"] += kstats.get("early_stops", 0)
        acc["walk_vmem"] = max(acc["walk_vmem"],
                               kstats.get("walk_vmem_tile_bytes", 0))
        acc["walk_impl"] = kstats.get("walk_impl", acc["walk_impl"])

    def dispatch(start: int, stop: int, acc: dict) -> dict:
        """Launch all of one R block's device work; no host syncs."""
        sl = slice(start, stop)
        r_rep, hit = _r_block_rep(R, family, W, start, stop)
        acc["r_rep_hits"] += hit
        r_sz = jnp.asarray(r_sizes_all[sl])
        lo = jnp.asarray(lo_all[sl])
        hi = jnp.asarray(hi_all[sl])
        acc["out_dense"] += (stop - start) * len(Ss)
        blk: dict = {"start": start}
        if kernel_pairs:
            # live-tile schedule + in-kernel counts; count sync deferred
            if method == "kernel_bitmap":
                blk["pending"] = kops.bitmap_join_pairs_dispatch(
                    r_rep, r_sz, s_rep, s_sz, lo, hi, t, measure=measure)
            elif method == "kernel_onehot":
                blk["pending"] = kops.onehot_join_pairs_dispatch(
                    r_rep, r_sz, s_rep, s_sz, lo, hi, t, universe=universe,
                    measure=measure)
            elif method == "lfvt":
                # live row-tiled walk kernel; host np row metadata so the
                # dispatch plans tiles without syncing device arrays
                blk["pending"] = kops.lfvt_walk_join_pairs_dispatch(
                    s_rep, r_rep, r_sizes_all[sl], lo_all[sl], hi_all[sl],
                    t, measure=measure, hints={"row_tile": plan.row_tile})
            else:  # lfvt_ref: whole-block jnp walk as one live tile
                blk["pending"] = kops.lfvt_join_pairs_dispatch(
                    s_rep, r_rep, r_sz, lo, hi, t, measure=measure)
            return blk
        if method in ("lfvt", "lfvt_ref"):
            # emit='mask' rides the same dispatch as emit='pairs' (the
            # walk kernel for 'lfvt', the whole-block jnp walk for
            # 'lfvt_ref'); only the finalize differs — the staged tile
            # masks are scattered back dense instead of pair-compacted
            if method == "lfvt":
                blk["mask_pending"] = kops.lfvt_walk_join_pairs_dispatch(
                    s_rep, r_rep, r_sizes_all[sl], lo_all[sl], hi_all[sl],
                    t, measure=measure, hints={"row_tile": plan.row_tile})
            else:
                blk["mask_pending"] = kops.lfvt_join_pairs_dispatch(
                    s_rep, r_rep, r_sz, lo, hi, t, measure=measure)
            blk["mb"] = stop - start
            return blk
        if method == "popcount":
            mask = _popcount_qualify(r_rep, r_sz, s_rep, s_sz, lo, hi, t=t,
                                     measure=measure)
        elif method == "onehot":
            mask = _onehot_qualify(r_rep, r_sz, s_rep, s_sz, lo, hi, t=t,
                                   universe=universe, measure=measure)
        elif method == "kernel_bitmap":
            mask = kops.bitmap_join(r_rep, r_sz, s_rep, s_sz, lo, hi, t,
                                    measure=measure)
        elif method == "kernel_onehot":
            mask = kops.onehot_join(r_rep, r_sz, s_rep, s_sz, lo, hi, t,
                                    universe, measure=measure)
        else:
            raise ValueError(f"unknown method {method!r}")
        blk["mask"] = mask
        if emit == "pairs":
            # speculative on-device compaction at the fixed capacity; the
            # exact count rides along and is synced only at finalize
            blk["total"] = _mask_total(mask)
            blk["packed"] = _compact_mask(mask, size=spec_cap)
        return blk

    def finalize(blk: dict, acc: dict, out_pairs: set) -> None:
        """Sync one block's count, regrow if the speculation overflowed,
        and fold its pairs into the result set."""
        start = blk["start"]
        fault_point("compact")
        if kernel_pairs:
            kstats: dict = {}
            pp, n_pairs = kops.join_pairs_finalize(
                blk["pending"], capacity=pair_capacity, stats=kstats)
            local = np.asarray(pp[:n_pairs] if n_pairs else pp[:0])
            acc["out_sparse"] += 8 * n_pairs + 4 + kstats.get(
                "counts_bytes", 0)
            acc["regrows"] += kstats.get("regrows", 0)
            fold_kernel_stats(acc, kstats)
        elif emit == "pairs":
            n_pairs = int(blk["total"])  # the only host sync per block
            cap = spec_cap
            if cap < n_pairs:  # overflow: regrow exactly once (count known)
                fault_point("regrow")
                cap = round_capacity(n_pairs)
                blk["packed"] = _compact_mask(blk["mask"], size=cap)
                acc["regrows"] += 1
            # device-side slice: only the n_pairs rows + the count cross
            # the host boundary; the cap buffer stays device-resident
            local = (np.asarray(blk["packed"][:n_pairs])
                     if cap else np.zeros((0, 2), np.int64))
            acc["out_sparse"] += 8 * n_pairs + 4
        else:
            if "mask_pending" in blk:
                kstats = {}
                mask_np = kops.join_mask_finalize(
                    blk["mask_pending"], blk["mb"], len(Ss), kstats)
                fold_kernel_stats(acc, kstats)
            else:
                mask_np = np.asarray(blk["mask"])
            acc["out_sparse"] += mask_np.size
            rr, ss = np.nonzero(mask_np)
            local = np.stack([rr, ss], axis=1) if len(rr) else (
                np.zeros((0, 2), np.int64))
            n_pairs = len(local)
        if len(local):
            rid = R.ids[start + local[:, 0]]
            sid = Ss.ids[local[:, 1]]
            out_pairs.update(zip(map(int, rid), map(int, sid)))
        acc["n_pairs"] += n_pairs

    if res is None:
        in_flight: dict | None = None
        for start in range(0, m, r_block):
            # block k+1 launches before block k syncs
            blk = dispatch(start, min(start + r_block, m), acc)
            if in_flight is not None:
                finalize(in_flight, acc, pairs)
            if double_buffer:
                in_flight = blk
            else:
                finalize(blk, acc, pairs)
        if in_flight is not None:
            finalize(in_flight, acc, pairs)
    else:
        # resilience path (DESIGN.md §12): per-R-block tasks, run
        # synchronously under the retry + degradation ladder so a retry
        # can never double-count a block's stats or pairs
        from .join import brute_force_join  # deferred: the oracle rung
        if res.ledger.dir:
            res.ledger.open_run({
                "version": 1, "driver": "cf_rs_join_device", "t": float(t),
                "method": method, "emit": emit, "measure": measure,
                "r_block": int(r_block),
                "R": collection_digest(R), "S": collection_digest(S)})

        def fold(delta: dict) -> None:
            if delta.get("walk_impl"):
                acc["walk_impl"] = delta["walk_impl"]
            for k, v in delta.items():
                if k in acc and isinstance(v, (int, float)) \
                        and not isinstance(v, bool):
                    acc[k] = max(acc[k], v) if k == "walk_vmem" \
                        else acc[k] + v

        def primary(a: int, b: int):
            sub_acc, sub_pairs = zero_acc(), set()
            if family == "lfvt":
                checked_flat(s_rep)  # injected-corruption detection site
            finalize(dispatch(a, b, sub_acc), sub_acc, sub_pairs)
            return sorted_pairs(sub_pairs), sub_acc

        def oracle(a: int, b: int):
            subR = SetCollection([R.sets[i] for i in range(a, b)],
                                 R.universe, R.ids[a:b].astype(np.int32))
            got = brute_force_join(subR, S, t, measure=measure)
            sub_acc = zero_acc()
            sub_acc["n_pairs"] = len(got)
            return sorted_pairs(got), sub_acc

        budget = int(global_config.vmem_budget)
        for start in range(0, m, r_block):
            stop = min(start + r_block, m)
            spans = [(start, stop)]
            if global_config.memory_guardrail:
                # pre-dispatch guardrail: the dense (mb, n) count tile is
                # the block's dominant device working set
                est = (stop - start) * len(Ss) * 4
                if est > budget:
                    k = min(stop - start, -(-est // budget))
                    cuts = np.linspace(start, stop, k + 1).astype(int)
                    spans = [(int(cuts[i]), int(cuts[i + 1]))
                             for i in range(k) if cuts[i + 1] > cuts[i]]
                    res.guardrail_splits += len(spans) - 1
            for a, b in spans:
                tid = f"device_join/{method}/{emit}/{measure}/rows={a}-{b}"
                got, delta = res.run(
                    tid, [(method, functools.partial(primary, a, b)),
                          ("oracle", functools.partial(oracle, a, b))])
                pairs.update((int(r), int(s)) for r, s in got)
                fold(delta)

    if stats is not None:
        stats["method"] = method
        stats["measure"] = measure
        stats["emit"] = emit
        stats["plan"] = plan.to_dict()
        stats["r_blocks"] = -(-m // r_block)
        stats["pair_count"] = acc["n_pairs"]
        stats["output_bytes"] = acc["out_sparse"]
        stats["dense_mask_bytes"] = acc["out_dense"]
        stats["double_buffered"] = double_buffer
        stats["regrows"] = acc["regrows"]
        stats["r_rep_cache_hits"] = acc["r_rep_hits"]
        if kernel_pairs or method in ("lfvt", "lfvt_ref"):
            stats["live_tiles"] = acc["live"]
            stats["total_tiles"] = acc["total_tiles"]
        if method == "lfvt":
            # both emit modes run the kernel dispatch now, so the walk
            # counters (and the VMEM tile accounting that replaced the
            # SMEM prefetch budget) are always available
            stats["walk_steps"] = acc["walk_steps"]
            stats["early_stops"] = acc["early_stops"]
            stats["walk_vmem_tile_bytes"] = acc["walk_vmem"]
            stats["walk_impl"] = acc["walk_impl"]
        if method in ("lfvt", "lfvt_ref"):
            # the §9 memory axis: what the flat S rep holds on device vs
            # what the bitmap sheet would have cost at this universe
            stats["s_flat_bytes"] = s_rep.nbytes()
            stats["s_flat_seq_bytes"] = int(s_rep.seq_row.nbytes)
            stats["s_bitmap_bytes_equiv"] = len(Ss) * W * 4
        resilience_stats(stats, res)
    return pairs


