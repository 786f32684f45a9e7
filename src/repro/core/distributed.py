"""MR-CF-RS-Join: the paper's single MapReduce job as a JAX SPMD program.

Mapping (DESIGN.md §2, §7):
  map     -> host routing via ``core.partition`` (length-range, Eq. 2-3)
  shuffle -> the sharded device layout itself; bytes counted exactly
  reduce  -> per-shard candidate-free tile join; with ``emit='pairs'``
             compaction happens *inside* the shard-local body (under
             ``shard_map`` on the mesh path), so each shard ships only a
             fixed-capacity ``(cap, 2)`` pair buffer plus an exact count —
             the dense ``(n_shards, m_max, n_max)`` mask stack never
             exists (DESIGN.md §7).

Two execution paths share the same shard-local compute:
  * ``shard_map``: one shard per device along the mesh ``data`` axis
    (optionally x ``pod`` for a second R split) — the production path.
  * ``loop``: sequential shard loop on one device — used by CPU benchmarks,
    which report the exact per-shard load model the paper plots (Fig. 8).
    The loop path additionally supports *bucketed* shard packing: shards
    are grouped by power-of-two (m, n) footprint and each bucket is padded
    only to its own maxima, so one skewed shard no longer inflates every
    shard's memory and compute.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from .config import global_config
from .measures import get_measure
from .partition import Partitioning, hash_partition, load_aware_partition, route
from .planner import build_plan, validate_join_args
from .resilience import (build_resilience, checked_flat, collection_digest,
                         fault_point, resilience_stats, sorted_pairs)
from .sets import EmptyCollectionError, SetCollection
from .tile_join import (PAIR_CAP_GRAIN, popcount_counts, qualify,
                        round_capacity, window_bounds)

__all__ = ["mr_cf_rs_join", "shard_blocks", "local_join_mask", "ShardBlock"]


# ---------------------------------------------------------------------- #
# shard-local compute (identical under loop and shard_map)
# ---------------------------------------------------------------------- #
def local_join_mask(r_bm, r_sz, s_bm, s_sz, lo, hi, t: float,
                    method: str = "popcount", measure: str = "jaccard"):
    """Shard-local candidate-free join -> (m, n) bool qualifying mask."""
    if method in ("kernel_bitmap", "kernel_onehot"):
        from repro.kernels import ops as kops
        fn = kops.bitmap_join if method == "kernel_bitmap" else kops.onehot_join
        return fn(r_bm, r_sz, s_bm, s_sz, lo, hi, t, measure=measure)
    counts = popcount_counts(r_bm, s_bm)
    cols = jnp.arange(s_bm.shape[0], dtype=jnp.int32)[None, :]
    in_window = (cols >= lo[:, None]) & (cols < hi[:, None])
    return qualify(counts, r_sz, s_sz, t, measure) & in_window


# ---------------------------------------------------------------------- #
# host map phase: routing + vectorized, bucket-padded shard blocks
# ---------------------------------------------------------------------- #
@dataclasses.dataclass
class ShardBlock:
    """One bucket of shards padded to a common (m_pad, n_pad).

    ``arrays`` stacks (r_bm, r_sz, s_bm, s_sz, lo, hi) along a leading
    shard axis of length ``len(shard_ids)``; ``r_ids``/``s_ids`` map packed
    rows/columns back to original set ids (-1 = padding).
    """

    shard_ids: np.ndarray  # (K,) global shard indices in this bucket
    arrays: tuple          # (r_bm, r_sz, s_bm, s_sz, lo, hi), leading dim K
    r_ids: np.ndarray      # (K, m_pad) int64
    s_ids: np.ndarray      # (K, n_pad) int64

    @property
    def n_local(self) -> int:
        return len(self.shard_ids)

    @property
    def m_pad(self) -> int:
        return self.r_ids.shape[1]

    @property
    def n_pad(self) -> int:
        return self.s_ids.shape[1]

    def block_bytes(self) -> int:
        return int(self.arrays[0].nbytes + self.arrays[2].nbytes)


def _ceil_pow2(x: int) -> int:
    return 1 << (int(max(x, 1)) - 1).bit_length()


def _flatten_routes(rows_per_shard):
    """Per-shard row lists -> (rows, shard_of, pos_in_shard) flat arrays."""
    counts = np.asarray([len(g) for g in rows_per_shard], dtype=np.int64)
    rows = (np.concatenate([np.asarray(g, dtype=np.int64)
                            for g in rows_per_shard])
            if counts.sum() else np.zeros(0, np.int64))
    shard_of = np.repeat(np.arange(len(rows_per_shard), dtype=np.int64),
                         counts)
    starts = np.concatenate([[0], np.cumsum(counts)])[:-1]
    pos = np.arange(len(rows), dtype=np.int64) - starts[shard_of]
    return rows, shard_of, pos, counts


def _pack_side(rows, shard_of, pos, local_of_shard, K, pad, all_bm, sizes,
               ids):
    """Gather/scatter one side's flat routed rows into stacked block arrays."""
    W = all_bm.shape[1]
    bm = np.zeros((K, pad, W), np.uint32)
    sz = np.zeros((K, pad), np.int32)
    out_ids = np.full((K, pad), -1, np.int64)
    sel = local_of_shard[shard_of] >= 0
    if sel.any():
        k = local_of_shard[shard_of[sel]]
        p = pos[sel]
        r = rows[sel]
        bm[k, p] = all_bm[r]
        sz[k, p] = sizes[r]
        out_ids[k, p] = ids[r]
    return bm, sz, out_ids


def shard_blocks(R: SetCollection, S: SetCollection, part: Partitioning,
                 t: float, pad: str = "global"):
    """Build the post-shuffle layout: stacked, padded per-shard arrays.

    Routing and the per-shard size windows follow ``part.measure``
    (Lemma 3.1 generalized — DESIGN.md §8).

    pad: 'global' — every shard padded to the global (m_max, n_max); one
         ``ShardBlock`` covering all shards (required by ``shard_map``).
         'bucket' — shards grouped by power-of-two (m, n) footprint; each
         bucket padded to its own bucket maxima, so a skewed shard only
         inflates its bucket (paper Eq. 2-3 skew pathology).

    Returns ``(blocks, stats)`` where blocks is a list of ``ShardBlock``.
    Packing is vectorized: per-shard S rows are ordered by one global
    lexsort (shard, size desc, id asc) and all bitmaps/sizes/ids land via
    single fancy-index scatters — no per-shard Python packing loop.
    """
    if pad not in ("global", "bucket"):
        raise ValueError(f"unknown pad mode {pad!r}")
    s_rows, r_rows, stats = route(R, S, part)
    n_shards = part.n_shards
    universe = max(R.universe, S.universe)
    W = max((universe + 31) // 32, 1)
    all_r_bm, all_s_bm = R.bitmaps(W), S.bitmaps(W)
    r_sizes, s_sizes = R.sizes(), S.sizes()

    sf, s_shard, s_pos, n_k = _flatten_routes(s_rows)
    rf, r_shard, r_pos, m_k = _flatten_routes(r_rows)
    # FVT root-ward invariant per shard: S rows by (size desc, id asc),
    # grouped by shard — one stable lexsort instead of per-shard sorts
    order = np.lexsort((S.ids[sf], -s_sizes[sf].astype(np.int64), s_shard))
    sf = sf[order]

    if pad == "bucket":
        keys = [(_ceil_pow2(int(m_k[k])), _ceil_pow2(int(n_k[k])))
                for k in range(n_shards)]
        buckets: dict[tuple[int, int], list[int]] = {}
        for k, key in enumerate(keys):
            buckets.setdefault(key, []).append(k)
        # the pow-2 key only groups; each bucket pads to its own maxima,
        # so bucketed padding never exceeds the global-max packing
        groups = [(ids := np.asarray(v, np.int64),
                   max(1, int(m_k[ids].max())), max(1, int(n_k[ids].max())))
                  for v in (buckets[key] for key in sorted(buckets))]
    else:
        groups = [(np.arange(n_shards, dtype=np.int64),
                   max(1, int(m_k.max(initial=1))),
                   max(1, int(n_k.max(initial=1))))]

    blocks: list[ShardBlock] = []
    alloc_rows = np.ones(n_shards, np.float64)
    for shard_ids, m_pad, n_pad in groups:
        alloc_rows[shard_ids] = m_pad + n_pad
        K = len(shard_ids)
        local = np.full(n_shards, -1, np.int64)
        local[shard_ids] = np.arange(K)
        s_bm, s_sz, s_ids = _pack_side(sf, s_shard, s_pos, local, K, n_pad,
                                       all_s_bm, s_sizes, S.ids)
        r_bm, r_sz, r_ids = _pack_side(rf, r_shard, r_pos, local, K, m_pad,
                                       all_r_bm, r_sizes, R.ids)
        lo = np.zeros((K, m_pad), np.int32)
        hi = np.zeros((K, m_pad), np.int32)
        for lk, k in enumerate(shard_ids):
            mk, nk = int(m_k[k]), int(n_k[k])
            if mk and nk:
                l, h = window_bounds(r_sz[lk, :mk], s_sz[lk, :nk], t,
                                     part.measure)
                lo[lk, :mk] = l
                hi[lk, :mk] = h
        blocks.append(ShardBlock(shard_ids, (r_bm, r_sz, s_bm, s_sz, lo, hi),
                                 r_ids, s_ids))

    # packing stats: exact bytes + per-shard padding waste (fraction of
    # allocated bitmap rows that are padding)
    used_rows = (m_k + n_k).astype(np.float64)
    waste = 1.0 - used_rows / np.maximum(alloc_rows, 1.0)
    stats["shard_block_bytes"] = sum(b.block_bytes() for b in blocks)
    stats["shard_block_bytes_per_shard"] = (
        stats["shard_block_bytes"] / max(n_shards, 1))
    stats["pad_waste_max"] = float(waste.max(initial=0.0))
    stats["pad_waste_mean"] = float(waste.mean()) if n_shards else 0.0
    stats["pad"] = pad
    stats["n_buckets"] = len(blocks)
    return blocks, stats


# ---------------------------------------------------------------------- #
# reduce phase — dense-mask fallback (emit='mask')
# ---------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("t", "method", "measure"))
def _loop_reduce(blocks, *, t: float, method: str, measure: str):
    def per_shard(args):
        r_bm, r_sz, s_bm, s_sz, lo, hi = args
        return local_join_mask(r_bm, r_sz, s_bm, s_sz, lo, hi, t, method,
                               measure)
    return jax.lax.map(per_shard, blocks)


@functools.lru_cache(maxsize=64)
def _shard_map_mask_fn(mesh: Mesh, axis: str, t: float, method: str,
                       measure: str):
    """Jitted shard_map dense reduce, cached so repeated calls on the same
    mesh hit the jit cache instead of retracing (meshes are few and
    long-lived; the bounded cache holds them strongly)."""
    spec = P(axis)
    def body(r_bm, r_sz, s_bm, s_sz, lo, hi):
        mask = local_join_mask(r_bm[0], r_sz[0], s_bm[0], s_sz[0],
                               lo[0], hi[0], t, method, measure)
        return mask[None]
    return jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,) * 6,
                             out_specs=spec))


def _shard_map_reduce(blocks, mesh: Mesh, axis: str, *, t: float, method: str,
                      measure: str):
    fault_point("device_upload")
    spec = P(axis)
    placed = tuple(
        jax.device_put(jnp.asarray(b), NamedSharding(mesh, spec)) for b in blocks
    )
    fault_point("shard_map")
    return _shard_map_mask_fn(mesh, axis, t, method, measure)(*placed)


# ---------------------------------------------------------------------- #
# reduce phase — shard-sparse (emit='pairs'): compaction inside the
# shard-local body; only (cap, 2) buffers + counts leave a shard
# ---------------------------------------------------------------------- #
def _shard_pairs_body(mask, cap: int):
    """In-shard compaction: (m, n) bool mask -> ((cap, 2) int32 pairs,
    exact int32 count). The count is exact even when ``nonzero`` truncates
    at ``cap`` — the regrow protocol depends on that."""
    count = jnp.sum(mask, dtype=jnp.int32)
    rr, cc = jnp.nonzero(mask, size=cap, fill_value=-1)
    return jnp.stack([rr, cc], axis=1).astype(jnp.int32), count


@functools.partial(jax.jit, static_argnames=("t", "method", "cap", "measure"))
def _loop_reduce_pairs(arrays, *, t: float, method: str, cap: int,
                       measure: str):
    """lax.map over shards -> ((K, cap, 2) int32 pairs, (K,) int32 counts).

    The per-shard dense mask exists only inside the map body (one shard at
    a time); the stacked output is already compacted.
    """
    def per_shard(args):
        r_bm, r_sz, s_bm, s_sz, lo, hi = args
        mask = local_join_mask(r_bm, r_sz, s_bm, s_sz, lo, hi, t, method,
                               measure)
        return _shard_pairs_body(mask, cap)
    return jax.lax.map(per_shard, arrays)


@functools.lru_cache(maxsize=64)
def _shard_map_pairs_fn(mesh: Mesh, axis: str, t: float, method: str,
                        cap: int, measure: str):
    """Jitted shard_map shard-sparse reduce, cached per (mesh, axis, t,
    method, cap, measure) — repeated joins (the dedup pipeline) and regrow
    retries reuse the compiled executable instead of retracing."""
    spec = P(axis)
    def body(r_bm, r_sz, s_bm, s_sz, lo, hi):
        mask = local_join_mask(r_bm[0], r_sz[0], s_bm[0], s_sz[0],
                               lo[0], hi[0], t, method, measure)
        pairs, count = _shard_pairs_body(mask, cap)
        return pairs[None], count[None]
    return jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,) * 6,
                             out_specs=(spec, spec)))


def _shard_map_reduce_pairs(placed, mesh: Mesh, axis: str, *, t: float,
                            method: str, cap: int, measure: str):
    """shard_map reduce with in-shard compaction: each device computes its
    own shard's mask, counts it, and packs qualifying (row, col) pairs into
    a fixed-capacity buffer — the all-gathered output is (n_shards, cap, 2)
    + (n_shards,) counts, never the dense mask stack.

    ``placed`` must already be device_put with the shard sharding (the
    regrow retry then re-runs only the compute, not the upload)."""
    fault_point("shard_map")
    return _shard_map_pairs_fn(mesh, axis, t, method, cap, measure)(*placed)


def _block_pairs_reduce(block: ShardBlock, *, t: float, method: str,
                        cap_hint: int, mesh: Mesh | None, axis: str,
                        measure: str):
    """Run the shard-sparse reduce for one bucket with the power-of-two
    regrow protocol: per-shard counts are exact, so an overflow regrows the
    capacity in one step and reruns at most once.

    Returns (pairs (K, cap, 2) device array, counts (K,) np, cap, regrows);
    the caller transfers only each shard's ``[:count]`` slice.
    """
    cap = round_capacity(max(cap_hint, 1))
    regrows = 0
    fault_point("device_upload")
    if mesh is not None:  # upload once; regrow retries reuse the placement
        spec = P(axis)
        placed = tuple(
            jax.device_put(jnp.asarray(a), NamedSharding(mesh, spec))
            for a in block.arrays)
    else:
        placed = tuple(jnp.asarray(a) for a in block.arrays)
    while True:
        fault_point("compact")
        if mesh is not None:
            pairs_dev, counts_dev = _shard_map_reduce_pairs(
                placed, mesh, axis, t=t, method=method, cap=cap,
                measure=measure)
        else:
            pairs_dev, counts_dev = _loop_reduce_pairs(
                placed, t=t, method=method, cap=cap, measure=measure)
        counts = np.asarray(counts_dev).reshape(-1)
        mx = int(counts.max(initial=0))
        if mx <= cap:
            return pairs_dev, counts, cap, regrows
        fault_point("regrow")
        cap = round_capacity(mx)
        regrows += 1


def _kernel_block_pairs(block: ShardBlock, *, t: float, method: str,
                        cap_hint: int, measure: str):
    """Per-shard live-tiled kernel reduce (loop path, kernel methods).

    Reuses the §6 live-tile schedule shard by shard: each shard's
    qualifying mask is computed tile-by-tile with skipped tiles costing
    zero grid steps, and compacted on device into its own pair buffer.
    Shards stream double-buffered (shard k+1 dispatched before shard k's
    count syncs) so at most two shards' staged tile masks are resident.
    Returns (list of (n_k, 2) np pair arrays, counts, output_bytes,
    regrows, live_tiles, total_tiles, staged_mask_peak_bytes).
    """
    from repro.kernels import ops as kops
    dispatch = (kops.bitmap_join_pairs_dispatch if method == "kernel_bitmap"
                else kops.onehot_join_pairs_dispatch)
    r_bm, r_sz, s_bm, s_sz, lo, hi = block.arrays
    per_shard, counts = [], []
    acc = {"out_bytes": 0, "regrows": 0, "live": 0, "total": 0}

    def settle(pending):
        kstats: dict = {}
        pp, n = kops.join_pairs_finalize(pending, capacity=cap_hint,
                                         stats=kstats)
        per_shard.append(np.asarray(pp[:n]))  # device slice: ship n rows
        counts.append(n)
        acc["out_bytes"] += 8 * n + 4 + kstats.get("counts_bytes", 0)
        acc["regrows"] += kstats.get("regrows", 0)
        acc["live"] += kstats.get("live_tiles", 0)
        acc["total"] += kstats.get("total_tiles", 0)

    in_flight = None
    staged_sizes = []  # per-shard (L, TM, TN) staged live-tile mask bytes
    for lk in range(block.n_local):
        cur = dispatch(jnp.asarray(r_bm[lk]), jnp.asarray(r_sz[lk]),
                       jnp.asarray(s_bm[lk]), jnp.asarray(s_sz[lk]),
                       jnp.asarray(lo[lk]), jnp.asarray(hi[lk]), t,
                       measure=measure)
        staged_sizes.append(cur.live_tiles * cur.tm * cur.tn)
        if in_flight is not None:
            settle(in_flight)
        in_flight = cur
    if in_flight is not None:
        settle(in_flight)
    # double-buffering keeps at most two consecutive shards' staged masks
    # resident at once
    staged_peak = max(
        (staged_sizes[i] + (staged_sizes[i + 1] if i + 1 < len(staged_sizes)
                            else 0) for i in range(len(staged_sizes))),
        default=0)
    return (per_shard, np.asarray(counts), acc["out_bytes"], acc["regrows"],
            acc["live"], acc["total"], staged_peak)


# ---------------------------------------------------------------------- #
# reduce phase — flat-LFVT loop path (method='lfvt', DESIGN.md §9)
# ---------------------------------------------------------------------- #
_PEAK_KEYS = ("peak_mask", "peak_inter", "walk_vmem", "waste_max")


def _devices(arr) -> set:
    """Ids of the devices holding shards of a reduce output — where the
    reduce actually ran (``stats['reduce_devices']`` counts their union
    over a join)."""
    return {shard.device.id for shard in arr.addressable_shards}


def _fold_delta(acc: dict, delta: dict) -> None:
    """Fold a resilience task's stat deltas into a driver accumulator:
    peaks combine by max, counters by sum, the walk implementation is
    carried over, other non-numeric keys (the rung name) are dropped."""
    if delta.get("walk_impl"):
        acc["walk_impl"] = delta["walk_impl"]
    for k, v in delta.items():
        if k in acc and isinstance(v, (int, float)) \
                and not isinstance(v, bool):
            acc[k] = max(acc[k], v) if k in _PEAK_KEYS else acc[k] + v


def _sub_collection(C: SetCollection, rows) -> SetCollection:
    """Row-subset collection keeping global ids (oracle-rung input)."""
    return SetCollection([C.sets[int(i)] for i in rows], C.universe,
                         C.ids[rows].astype(np.int32))


def _guardrail_spans(rows, n_cols: int, res) -> list:
    """Pre-dispatch memory guardrail: split a shard's R rows so the
    estimated dense (|rows|, n_cols) int32 working set fits the VMEM
    budget. Active only on the resilience path."""
    if res is None or not global_config.memory_guardrail or not len(rows):
        return [rows]
    est = len(rows) * n_cols * 4
    budget = int(global_config.vmem_budget)
    if est <= budget:
        return [rows]
    chunks = min(len(rows), -(-est // budget))
    spans = [c for c in np.array_split(np.asarray(rows), chunks) if len(c)]
    res.guardrail_splits += len(spans) - 1
    return spans


def _lfvt_loop_join(R: SetCollection, S: SetCollection, t: float, part,
                    *, emit: str, pair_capacity: int | None, measure: str,
                    stats: dict | None, impl: str = "kernel",
                    res=None, shard_methods=None) -> set:
    """Per-shard flat-LFVT reduce on the sequential loop path.

    The map side routes rows exactly like the bitmap paths, but each
    shard's S partition is compiled to a ``FlatLFVT`` on the host and
    shipped as plain int32 ndarrays — reducers never rebuild pointer
    trees, and nothing |S|·W-shaped is ever materialized. Shards
    stream double-buffered: shard k+1's walk is dispatched before shard
    k's pair count syncs. ``impl='kernel'`` (method='lfvt') runs each
    shard's reduce (both emit modes) through the live row-tiled walk
    kernel dispatch (DESIGN.md §10) and mirrors its walk_steps/
    early_stops/live_tiles stats; ``impl='ref'`` (method='lfvt_ref')
    keeps the PR-4 whole-block jnp walk.

    Raggedness means the jitted walk specializes per shard shape
    (mb, n, E, T, max|seq| all differ), so every shard pays a trace —
    acceptable on this CPU-bench path. ``_lfvt_mesh_join`` is the
    ``shard_map`` counterpart: it sentinel-pads the flat tables into
    pow-2 buckets so shards share compiled shapes (DESIGN.md §11).

    ``shard_methods`` (ISSUE 10, method='auto' heterogeneous dispatch)
    overrides the rep family per shard: 'popcount' shards take the
    dense bitmap reduce (cheap for light, small-universe shards),
    'lfvt'/'lfvt_ref' shards the flat-LFVT walk above — mixed freely,
    since the loop path never stacks shard shapes.
    """
    from repro.kernels import ops as kops

    s_rows, r_rows, route_stats = route(R, S, part)
    r_sizes = R.sizes()
    s_sizes_all = S.sizes()
    r_pad_all, _ = R.padded()
    W = max((max(R.universe, S.universe) + 31) // 32, 1)
    pairs: set = set()

    def _impl_of(kind: str | None) -> str:
        if kind is None:
            return impl
        return {"popcount": "popcount", "lfvt": "kernel",
                "lfvt_ref": "ref"}[kind]

    kinds = (list(shard_methods) if shard_methods is not None
             else [None] * part.n_shards)

    def zero_acc() -> dict:
        return {"reduce": 0, "result": 0, "regrows": 0, "dense": 0,
                "peak_mask": 0, "peak_inter": 0, "ship": 0, "shards": 0,
                "walk_steps": 0, "early_stops": 0, "live": 0, "total": 0,
                "walk_vmem": 0, "walk_impl": None}

    acc = zero_acc()

    def dispatch(rs, ss, acc: dict, use_impl: str) -> dict | None:
        if not len(rs) or not len(ss):
            return None
        if use_impl == "popcount":
            # planner-picked light shard: dense bitmap popcount reduce.
            # Columns are sorted size-descending (ties by id) so
            # ``window_bounds`` applies exactly like the tile driver.
            order = np.lexsort((S.ids[ss], -s_sizes_all[ss]))
            ss = np.asarray(ss)[order]
            s_bm, s_sz = S.bitmaps(W)[ss], s_sizes_all[ss]
            r_bm, sz = R.bitmaps(W)[rs], r_sizes[rs]
            lo, hi = window_bounds(sz, s_sz, t, measure)
            acc["ship"] += (s_bm.nbytes + s_sz.nbytes + r_bm.nbytes
                            + sz.nbytes)
            acc["dense"] += len(rs) * len(ss)
            acc["shards"] += 1
            mask = local_join_mask(
                jnp.asarray(r_bm), jnp.asarray(sz), jnp.asarray(s_bm),
                jnp.asarray(s_sz), jnp.asarray(lo), jnp.asarray(hi), t,
                method="popcount", measure=measure)
            return {"rs": rs, "mask": mask, "sids": S.ids[ss]}
        sub = SetCollection([S.sets[int(j)] for j in ss], S.universe,
                            S.ids[ss].astype(np.int32))
        flat = checked_flat(sub.flat_lfvt())
        r_pad, sz = r_pad_all[rs], r_sizes[rs]
        lo, hi = window_bounds(sz, flat.s_sizes, t, measure)
        # map-output bytes: the serialized flat arrays + the shard's R rows
        acc["ship"] += flat.nbytes() + r_pad.nbytes + sz.nbytes
        acc["dense"] += len(rs) * len(ss)
        acc["shards"] += 1
        # both emit modes share the same dispatch (the walk kernel for
        # 'lfvt', the whole-block jnp walk for 'lfvt_ref'); emit='mask'
        # is resolved by ``join_mask_finalize`` instead of compaction
        ctx = {"rs": rs, "flat": flat}
        if use_impl == "ref":
            ctx["pending"] = kops.lfvt_join_pairs_dispatch(
                flat, jnp.asarray(r_pad), jnp.asarray(sz), jnp.asarray(lo),
                jnp.asarray(hi), t, measure=measure)
        else:
            ctx["pending"] = kops.lfvt_walk_join_pairs_dispatch(
                flat, r_pad, sz, lo, hi, t, measure=measure)
        return ctx

    def finalize(ctx: dict, acc: dict, out_pairs: set) -> None:
        if "mask" in ctx:  # popcount shard (planner heterogeneous path)
            rs = ctx["rs"]
            mask = np.asarray(ctx["mask"])
            rr, cc = np.nonzero(mask)
            if emit == "pairs":
                acc["reduce"] += 8 * len(rr) + 4
                acc["result"] += len(rr)
            else:
                acc["reduce"] += mask.size
            acc["peak_mask"] = max(acc["peak_mask"], mask.size)
            acc["peak_inter"] = max(acc["peak_inter"],
                                    mask.size + 8 * len(rr))
            if len(rr):
                rid = R.ids[rs[rr]]
                sid = ctx["sids"][cc]
                out_pairs.update(zip(map(int, rid), map(int, sid)))
            return
        rs, flat = ctx["rs"], ctx["flat"]
        if emit == "pairs":
            kstats: dict = {}
            pp, nk = kops.join_pairs_finalize(
                ctx["pending"], capacity=pair_capacity, stats=kstats)
            local = np.asarray(pp[:nk] if nk else pp[:0])
            acc["reduce"] += 8 * nk + 4 + kstats.get("counts_bytes", 0)
            acc["regrows"] += kstats.get("regrows", 0)
            acc["result"] += nk
            acc["walk_steps"] += kstats.get("walk_steps", 0)
            acc["early_stops"] += kstats.get("early_stops", 0)
            acc["live"] += kstats.get("live_tiles", 0)
            acc["total"] += kstats.get("total_tiles", 0)
            acc["walk_vmem"] = max(acc["walk_vmem"],
                                   kstats.get("walk_vmem_tile_bytes", 0))
            acc["walk_impl"] = kstats.get("walk_impl", acc["walk_impl"])
            mask_cells = len(rs) * flat.n_sets
            acc["peak_mask"] = max(acc["peak_mask"], mask_cells)
            acc["peak_inter"] = max(
                acc["peak_inter"], mask_cells + kstats.get("pair_bytes", 0))
        else:
            kstats = {}
            mask = kops.join_mask_finalize(
                ctx["pending"], len(rs), flat.n_sets, kstats)
            acc["walk_steps"] += kstats.get("walk_steps", 0)
            acc["early_stops"] += kstats.get("early_stops", 0)
            acc["live"] += kstats.get("live_tiles", 0)
            acc["total"] += kstats.get("total_tiles", 0)
            acc["walk_vmem"] = max(acc["walk_vmem"],
                                   kstats.get("walk_vmem_tile_bytes", 0))
            acc["walk_impl"] = kstats.get("walk_impl", acc["walk_impl"])
            rr, cc = np.nonzero(mask)
            local = (np.stack([rr, cc], axis=1) if len(rr)
                     else np.zeros((0, 2), np.int64))
            acc["reduce"] += mask.size
            acc["peak_mask"] = max(acc["peak_mask"], mask.size)
            acc["peak_inter"] = max(acc["peak_inter"], mask.size)
        if len(local):
            rid = R.ids[rs[local[:, 0]]]
            sid = flat.s_ids[local[:, 1]]
            out_pairs.update(zip(map(int, rid), map(int, sid)))

    if res is None:
        in_flight: dict | None = None
        for k in range(part.n_shards):
            ctx = dispatch(r_rows[k], s_rows[k], acc, _impl_of(kinds[k]))
            if in_flight is not None:
                finalize(in_flight, acc, pairs)
                in_flight = None
            if ctx is not None:
                in_flight = ctx
        if in_flight is not None:
            finalize(in_flight, acc, pairs)
    else:
        # resilience ladder per shard (DESIGN.md §12): the kernel walk
        # degrades to the whole-block jnp walk, then to the host oracle;
        # oversized shards are guardrail-split before dispatch
        from .join import brute_force_join  # deferred: the oracle rung

        def run_impl(use_impl: str, rs, ss):
            sub_acc, sub_pairs = zero_acc(), set()
            ctx = dispatch(rs, ss, sub_acc, use_impl)
            if ctx is not None:
                finalize(ctx, sub_acc, sub_pairs)
            return sorted_pairs(sub_pairs), sub_acc

        def oracle(rs, ss):
            got = brute_force_join(_sub_collection(R, rs),
                                   _sub_collection(S, ss), t,
                                   measure=measure)
            sub_acc = zero_acc()
            sub_acc["shards"] += 1
            if emit == "pairs":
                sub_acc["result"] = len(got)
            return sorted_pairs(got), sub_acc

        for k in range(part.n_shards):
            rs, ss = r_rows[k], s_rows[k]
            if not len(rs) or not len(ss):
                continue
            use = _impl_of(kinds[k])
            spans = _guardrail_spans(rs, len(ss), res)
            for si, sub_rs in enumerate(spans):
                if kinds[k] is not None:
                    tid = f"auto_loop/{kinds[k]}/{emit}/{measure}/shard={k}"
                else:
                    tid = f"lfvt_loop/{impl}/{emit}/{measure}/shard={k}"
                if len(spans) > 1:
                    tid += f"/span={si}"
                if use == "popcount":
                    rungs = [("popcount",
                              functools.partial(run_impl, "popcount",
                                                sub_rs, ss))]
                else:
                    rungs = [("lfvt" if use == "kernel" else "lfvt_ref",
                              functools.partial(run_impl, use, sub_rs, ss))]
                    if use == "kernel":
                        rungs.append(("lfvt_ref",
                                      functools.partial(run_impl, "ref",
                                                        sub_rs, ss)))
                rungs.append(("oracle",
                              functools.partial(oracle, sub_rs, ss)))
                got, delta = res.run(tid, rungs)
                pairs.update((int(a), int(b)) for a, b in got)
                _fold_delta(acc, delta)

    n_result = acc["result"] if emit == "pairs" else len(pairs)
    if stats is not None:
        stats.update(route_stats)
        stats.update(
            intervals=part.intervals, psi=part.psi, n_shards=part.n_shards,
            emit=emit, measure=measure, result_pairs=n_result,
            pair_bytes=n_result * 8, reduce_bytes=acc["reduce"],
            dense_mask_bytes=acc["dense"],
            reduce_intermediate_peak_bytes=acc["peak_inter"],
            reduce_mask_peak_bytes=acc["peak_mask"],
            walk_steps=acc["walk_steps"], early_stops=acc["early_stops"],
            live_tiles=acc["live"], total_tiles=acc["total"],
            walk_vmem_tile_bytes=acc["walk_vmem"],
            walk_impl=acc["walk_impl"],
            regrows=acc["regrows"], pad="ragged", n_buckets=acc["shards"],
            shard_block_bytes=acc["ship"],
            shard_block_bytes_per_shard=acc["ship"] / max(part.n_shards, 1),
            pad_waste_max=0.0, pad_waste_mean=0.0)
        resilience_stats(stats, res)
    return pairs


# ---------------------------------------------------------------------- #
# reduce phase — mesh flat-LFVT path (method='lfvt' under shard_map,
# DESIGN.md §11): bucketed pow-2 sentinel padding makes the per-shard
# flat tables rectangular, so shard_map can stack them
# ---------------------------------------------------------------------- #
def _lfvt_local_mask(entry_elem, entry_pos, entry_len, seq, nxt, s_sizes,
                     r_padded, r_sizes, lo, hi, *, t: float, measure: str,
                     max_steps: int, tm: int, schedule: str = "planned"):
    """One shard's flat-LFVT walk + qualify, traceable under shard_map.

    The shard-local compute of the mesh path: lane prep mirrors the
    kernel driver's ``entry_state`` (sparse binary-search entry lookup,
    lanes sorted by remaining walk length), then the shard walks its row
    tiles with the compiled jnp twin. Under ``schedule='planned'`` (the
    default) the live-tile plan is **device-computed inside the trace**
    (``plan_row_tiles_device``: the host planner's exact ``any(lo < hi)``
    criterion, stable-partitioned live-ids-first) and
    ``lfvt_walk_planned_ref`` walks only the live prefix through a
    bounded while_loop/dynamic-slice chunk schedule — fully-dead row
    tiles (bucket pad rows, window-dead R rows) cost zero walk steps,
    recovering the loop path's tile skipping that the old *static
    all-tiles* schedule gave up (host planning cannot run inside a
    traced body; the device plan can). ``schedule='static'`` keeps the
    all-tiles schedule as the measured baseline. Masks are bit-identical
    across schedules; both keep the twin's live-lane staircase. Entry
    rows arrive pre-resolved to absolute walk positions
    (``lfvt_flat.entry_positions``), so the node table never ships.
    Sentinel rows (padded entries/seq/sets) are unreachable: pad
    entries have ``entry_len`` 0, no real hop chain points past the
    original T, and padded S columns have size 0 — outside every window
    and failing the f > 0 predicate.

    Returns (mask (mp, n) bool, walk_steps, early_stops, live_tiles —
    scalars; the counters are per-tile sums, same semantics as the
    kernel stats; under 'static' live_tiles is the full tile count,
    every tile having entered the staircase).
    """
    from repro.kernels import lfvt_walk as _lw  # lazy: mirrors kops

    mp, _ = r_padded.shape
    E = entry_elem.shape[0]
    idx = jnp.minimum(jnp.searchsorted(entry_elem, r_padded), E - 1)
    present = (r_padded >= 0) & (entry_elem[idx] == r_padded)
    pos = jnp.where(present, entry_pos[idx], 0).astype(jnp.int32)
    rem = jnp.where(present, entry_len[idx], 0).astype(jnp.int32)
    order = jnp.argsort(-rem, axis=1)
    lane_pos = jnp.take_along_axis(pos, order, axis=1)
    lane_rem = jnp.take_along_axis(rem, order, axis=1)
    lo32 = lo.astype(jnp.int32).reshape(-1, 1)
    hi32 = hi.astype(jnp.int32).reshape(-1, 1)
    operands = (lane_pos, lane_rem, nxt.reshape(1, -1), seq.reshape(1, -1),
                s_sizes.astype(jnp.int32).reshape(1, -1),
                r_sizes.astype(jnp.int32).reshape(-1, 1), lo32, hi32)
    kw = dict(t=t, measure=measure, max_steps=max_steps, tm=tm)
    if schedule == "planned":
        ti_sorted, n_live = _lw.plan_row_tiles_device(lo32, hi32, tm)
        masks, _, steps, stops = _lw.lfvt_walk_planned_ref(
            ti_sorted, n_live, *operands, **kw)
        live = n_live
    else:
        ti = jnp.arange(mp // tm, dtype=jnp.int32)
        masks, _, steps, stops = _lw.lfvt_walk_live_tiled_ref(
            ti, *operands, **kw)
        live = jnp.int32(mp // tm)
    return (masks.reshape(mp, -1),
            jnp.sum(steps, dtype=jnp.int32),
            jnp.sum(stops, dtype=jnp.int32),
            live)


@functools.lru_cache(maxsize=16)
def _lfvt_submesh(mesh: Mesh, axis: str, first: int, k: int) -> Mesh:
    """Devices ``[first, first + k)`` of the mesh for a bucket of k shards.
    Buckets take consecutive device ranges, so every shard of a join has
    a device of its own. Cached so Mesh identity — and with it the jit
    cache — is stable across calls."""
    if (tuple(mesh.axis_names) == (axis,) and first == 0
            and mesh.shape[axis] == k):
        return mesh
    return Mesh(mesh.devices.reshape(-1)[first:first + k], (axis,))


@functools.lru_cache(maxsize=64)
def _lfvt_walk_fn(mesh: Mesh, axis: str, t: float, measure: str,
                  max_steps: int, tm: int, schedule: str):
    """Jitted shard_map flat-LFVT walk for one bucket shape family.

    Returns per-shard (mask, steps, stops, live_tiles), all
    P(axis)-sharded — the mask stays device-resident so the compact
    stage (and its regrow retries) never replays the walk. Cached per
    (mesh, axis, t, measure, max_steps, tm, schedule) so repeated joins
    reuse the compiled executable; the inner jit specializes per
    stacked-array shape (one trace per bucket footprint, shared by
    every shard in the bucket — the point of the pow-2 padding)."""
    spec = P(axis)

    def body(ee, ep, el, seq, nxt, ssz, rpad, rsz, lo, hi):
        mask, steps, stops, live = _lfvt_local_mask(
            ee[0], ep[0], el[0], seq[0], nxt[0], ssz[0], rpad[0], rsz[0],
            lo[0], hi[0], t=t, measure=measure, max_steps=max_steps,
            tm=tm, schedule=schedule)
        return (mask[None], steps.reshape(1), stops.reshape(1),
                live.reshape(1))

    # check_vma=False: every output is per-shard (nothing replicated), so
    # there is no varying-axes invariant to check
    return jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,) * 10,
                             out_specs=(spec,) * 4, check_vma=False))


@functools.lru_cache(maxsize=64)
def _lfvt_compact_fn(mesh: Mesh, axis: str, cap: int):
    """Jitted shard_map in-shard pair compaction over a device-resident
    mask stack (PR-2 fixed-cap protocol; on overflow the caller calls
    again with a bigger cap — compute-only, the walk is not re-run)."""
    spec = P(axis)

    def body(mask):
        pairs, count = _shard_pairs_body(mask[0], cap)
        return pairs[None], count.reshape(1)

    return jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                             out_specs=(spec,) * 2, check_vma=False))


def _lfvt_bucket_arrays(bucket, caps, Lr, r_pad_all, r_sizes_all, R_ids,
                        t: float, measure: str):
    """Stack one bucket's shards into rectangular sentinel-padded arrays.

    ``bucket`` is [(shard_id, FlatLFVT, r_row_indices, max|r|)]; ``caps``
    the bucket maxima (mp, np_, Ep, Tp, max_steps) and ``Lr`` the bucket
    lane width (max|r| over the bucket — R rows are sliced to it, which
    only drops -1 pad columns). Returns (device operand tuple, r_ids
    (K, mp), s_ids (K, np_), used/alloc int32 cell counts per shard for
    the pad-waste stats).
    """
    from .lfvt_flat import entry_positions, pad_flat_tables

    mp, np_, Ep, Tp, _ = caps
    K = len(bucket)
    ee = np.full((K, Ep), global_config.flat_pad_sentinel, np.int32)
    epos = np.zeros((K, Ep), np.int32)
    elen = np.zeros((K, Ep), np.int32)
    seq = np.zeros((K, Tp), np.int32)
    nxt = np.full((K, Tp), -1, np.int32)
    ssz = np.zeros((K, np_), np.int32)
    s_ids = np.full((K, np_), -1, np.int64)
    rpad = np.full((K, mp, Lr), -1, np.int32)
    rsz = np.zeros((K, mp), np.int32)
    lo = np.zeros((K, mp), np.int32)
    hi = np.zeros((K, mp), np.int32)
    r_ids = np.full((K, mp), -1, np.int64)
    used = np.zeros(K, np.float64)
    for lk, (_, flat, rs, lr_k) in enumerate(bucket):
        mk, nk = len(rs), flat.n_sets
        Ek, Tk = len(flat.entry_elem), len(flat.seq_row)
        padded = pad_flat_tables(flat, n_entries=Ep, n_seq=Tp, n_sets=np_)
        ee[lk] = padded.entry_elem
        epos[lk] = entry_positions(padded)
        elen[lk] = padded.entry_len
        seq[lk] = padded.seq_row
        nxt[lk] = padded.seq_next
        ssz[lk] = padded.s_sizes
        s_ids[lk] = padded.s_ids
        rpad[lk, :mk] = r_pad_all[rs][:, :Lr]
        rsz[lk, :mk] = r_sizes_all[rs]
        l, h = window_bounds(r_sizes_all[rs], flat.s_sizes, t, measure)
        lo[lk, :mk] = l
        hi[lk, :mk] = h
        r_ids[lk, :mk] = R_ids[rs]
        # shipped walk-table cells: R side mk·(max|r|+3) [elements +
        # size/lo/hi at the shard's own lane width], S side 3·E + 2·T
        # + n [entry triplet + seq/hop + set sizes]
        used[lk] = mk * (lr_k + 3) + 3 * Ek + 2 * Tk + nk
    alloc = float(mp * (Lr + 3) + 3 * Ep + 2 * Tp + np_)
    arrays = (ee, epos, elen, seq, nxt, ssz, rpad, rsz, lo, hi)
    return arrays, r_ids, s_ids, used, alloc


def _lfvt_mesh_join(R: SetCollection, S: SetCollection, t: float, part,
                    mesh: Mesh, axis: str, *, emit: str, pad: str,
                    pair_capacity: int | None, measure: str,
                    stats: dict | None, res=None,
                    schedule: str | None = None) -> set:
    """MR-CF-RS-Join/LFVT under shard_map: the paper's headline method as
    a real multi-device mesh path (DESIGN.md §11).

    Map phase (host): route rows, compile each shard's S partition to a
    ``FlatLFVT``, resolve entries to absolute walk positions, then group
    shards into pow-2 footprint buckets (PR 2's ``ShardBlock`` bucketing
    extended to the flat node/seq/entry tables) and sentinel-pad each
    bucket to its own maxima — rectangular arrays that ``shard_map`` can
    stack, with pad waste reported like PR 2's packing stats.

    Reduce phase (device): one shard_map per bucket over the first
    ``K_b`` mesh devices; each shard runs the lockstep flat-array walk
    (``_lfvt_local_mask``) and — for emit='pairs' — the PR-2 in-shard
    fixed-cap compaction with the power-of-two regrow protocol (upload
    once, rerun compute-only on overflow). Only (cap, 2) buffers +
    counts + the walk counters leave a shard.

    ``schedule``: 'planned' (default via ``global_config.walk_schedule``)
    runs the device-computed live-tile plan inside each shard body —
    dead row tiles cost zero walk steps; 'static' keeps the all-tiles
    schedule as the measured baseline. Results are bit-identical.
    """
    schedule = schedule or global_config.walk_schedule
    s_rows, r_rows, route_stats = route(R, S, part)
    r_sizes_all = R.sizes()
    r_pad_all, _ = R.padded()
    Lr = r_pad_all.shape[1] if r_pad_all.ndim == 2 else 0
    n_devices = mesh.shape[axis]

    shards = []
    for k in range(part.n_shards):
        rs, ss = r_rows[k], s_rows[k]
        if not len(rs) or not len(ss):
            continue
        # size-sort each shard's R rows (stable, descending — the loop
        # dispatch's order): rows with near-identical Lemma-3.1 windows
        # share a row tile, so window-dead rows cluster into fully-dead
        # tiles the planned schedule can skip; pairs are id-mapped, so
        # the row order never changes results
        rs = rs[np.argsort(-r_sizes_all[rs], kind="stable")]
        sub = SetCollection([S.sets[int(j)] for j in ss], S.universe,
                            S.ids[ss].astype(np.int32))
        shards.append((k, sub.flat_lfvt(), rs))

    # pow-2 bucketing over the flat-table footprint axes (m, n, E, T)
    # plus the *shard-local* R lane width max|r| — like PR 2 the key
    # only groups, each bucket pads to its own per-axis maxima, so
    # bucketed padding never exceeds the global-max packing. Including
    # the lane width is the big win: load-aware size windows
    # anti-correlate shard structure (many tiny R sets vs few huge
    # ones), so per-bucket lane slicing ships (and walks!) max|r|-wide
    # rows instead of the global Lr — less pad waste *and* fewer dead
    # scatter lanes per step. pad='global' keeps one all-shards launch
    # (maximum device parallelism) at the cost of global-cap padding.
    buckets: dict[tuple, list] = {}
    for k, flat, rs in shards:
        lr_k = max(int(r_sizes_all[rs].max(initial=0)), 1)
        key = (1,) if pad == "global" else (
            _ceil_pow2(len(rs)), _ceil_pow2(flat.n_sets),
            _ceil_pow2(max(len(flat.entry_elem), 1)),
            _ceil_pow2(max(len(flat.seq_row), 1)), _ceil_pow2(lr_k))
        buckets.setdefault(key, []).append((k, flat, rs, lr_k))

    pairs: set = set()

    def zero_acc() -> dict:
        return {"reduce": 0, "result": 0, "regrows": 0, "dense": 0,
                "peak_mask": 0, "peak_inter": 0, "ship": 0,
                "walk_steps": 0, "early_stops": 0, "walk_vmem": 0,
                "live": 0, "total": 0,
                "waste_sum": 0.0, "waste_max": 0.0, "waste_n": 0}

    acc = zero_acc()
    reduce_devices: set = set()
    cap_hint = pair_capacity if pair_capacity else PAIR_CAP_GRAIN
    tm = global_config.row_tile

    def launch_bucket(bucket, caps, lr_b, first: int, acc: dict) -> dict:
        """One bucket's pack, upload and walk dispatch on devices
        ``[first, first + K)``; returns device handles, no host sync."""
        K = len(bucket)
        for _, flat, _, _ in bucket:
            checked_flat(flat)  # injected-corruption detection site
        arrays, r_ids, s_ids, used, alloc = _lfvt_bucket_arrays(
            bucket, caps, lr_b, r_pad_all, r_sizes_all, R.ids, t, measure)
        w = 1.0 - used / alloc
        acc["waste_sum"] += float(w.sum())
        acc["waste_max"] = max(acc["waste_max"], float(w.max(initial=0.0)))
        acc["waste_n"] += len(w)
        acc["ship"] += 4 * K * int(alloc)
        mp, np_ = caps[0], caps[1]
        acc["dense"] += K * mp * np_
        submesh = _lfvt_submesh(mesh, axis, first, K)
        spec = P(axis)
        fault_point("device_upload")
        placed = tuple(
            jax.device_put(a, NamedSharding(submesh, spec)) for a in arrays)
        fault_point("shard_map")
        masks_dev, steps_dev, stops_dev, live_dev = _lfvt_walk_fn(
            submesh, axis, t, measure, caps[4], tm, schedule)(*placed)
        return {"K": K, "caps": caps, "lr_b": lr_b, "submesh": submesh,
                "r_ids": r_ids, "s_ids": s_ids, "masks": masks_dev,
                "steps": steps_dev, "stops": stops_dev, "live": live_dev}

    def finish_bucket(ctx: dict, acc: dict, out_pairs: set) -> None:
        """Compact, collect and count one launched bucket."""
        K, caps, lr_b = ctx["K"], ctx["caps"], ctx["lr_b"]
        mp, np_ = caps[0], caps[1]
        r_ids, s_ids, masks_dev = ctx["r_ids"], ctx["s_ids"], ctx["masks"]
        reduce_devices.update(_devices(masks_dev))
        if emit == "pairs":
            cap = round_capacity(max(cap_hint, 1))
            while True:  # PR-2 regrow: exact counts, compact-only rerun
                fault_point("compact")
                pairs_dev, counts_dev = _lfvt_compact_fn(
                    ctx["submesh"], axis, cap)(masks_dev)
                counts = np.asarray(counts_dev).reshape(-1)
                mx = int(counts.max(initial=0))
                if mx <= cap:
                    break
                fault_point("regrow")
                cap = round_capacity(mx)
                acc["regrows"] += 1
            for lk, local in _shard_prefixes(pairs_dev, counts).items():
                rid = r_ids[lk, local[:, 0]]
                sid = s_ids[lk, local[:, 1]]
                keep = (rid >= 0) & (sid >= 0)
                out_pairs.update(zip(map(int, rid[keep]),
                                     map(int, sid[keep])))
            acc["reduce"] += int(counts.sum()) * 8 + K * 4
            acc["result"] += int(counts.sum())
            acc["peak_mask"] = max(acc["peak_mask"], mp * np_)
            acc["peak_inter"] = max(acc["peak_inter"],
                                    mp * np_ + K * (cap * 8 + 4))
        else:
            masks = np.asarray(masks_dev)
            # per-shard mask cells (mk x n_k real rows/cols, the loop
            # path's truth) — the stacked (K, mp, np_) transfer includes
            # sentinel padding, which is an artifact of the bucket
            # rectangle, not reduce output
            cells = ((r_ids >= 0).sum(axis=1).astype(np.int64)
                     * (s_ids >= 0).sum(axis=1))
            for lk in range(K):
                rr, cc = np.nonzero(masks[lk])
                out_pairs.update(
                    (int(r_ids[lk, i]), int(s_ids[lk, j]))
                    for i, j in zip(rr, cc)
                    if r_ids[lk, i] >= 0 and s_ids[lk, j] >= 0)
            acc["reduce"] += int(cells.sum())
            acc["peak_mask"] = max(acc["peak_mask"],
                                   int(cells.max(initial=0)))
            acc["peak_inter"] = max(acc["peak_inter"],
                                    int(cells.max(initial=0)))
        acc["walk_steps"] += int(np.asarray(ctx["steps"]).sum())
        acc["early_stops"] += int(np.asarray(ctx["stops"]).sum())
        acc["live"] += int(np.asarray(ctx["live"]).sum())
        acc["total"] += K * (mp // tm)
        # advisory §10 per-grid-step residency for this bucket's layout
        # (the shard body runs the twin, but the accounting is shared)
        from repro.kernels import lfvt_walk as _lw
        acc["walk_vmem"] = max(
            acc["walk_vmem"],
            _lw.walk_vmem_tile_bytes(tm, lr_b, np_, caps[3]))

    # buckets take consecutive device ranges, and every walk is launched
    # before any bucket syncs, so the buckets' shards run side by side
    first_device = 0
    launched = []
    for key in sorted(buckets):
        bucket = buckets[key]
        K = len(bucket)
        first, first_device = first_device, first_device + K
        # mp rounds up to the row-tile multiple: the shard-local walk is
        # row-tiled, and the extra rows are -1-padded with lo = hi = 0
        # (dead lanes — under the planned schedule whole pad tiles are
        # skipped by the device live-tile plan); lane width slices to
        # the bucket max|r| (columns past a row's own size are -1 pads,
        # so slicing drops only dead lanes)
        caps = (-(-max(len(rs) for _, _, rs, _ in bucket) // tm) * tm,
                max(f.n_sets for _, f, _, _ in bucket),
                max(max(len(f.entry_elem), 1) for _, f, _, _ in bucket),
                max(max(len(f.seq_row), 1) for _, f, _, _ in bucket),
                max(f.max_seq_len for _, f, _, _ in bucket))
        lr_b = min(max(lr for _, _, _, lr in bucket), Lr) if Lr else 1
        if res is None:
            launched.append(launch_bucket(bucket, caps, lr_b, first, acc))
            continue
        # resilience ladder per bucket (DESIGN.md §12): mesh -> per-shard
        # loop walk -> host oracle; an over-budget bucket skips straight
        # to the loop rung (memory guardrail)
        from repro.kernels import ops as kops
        from .join import brute_force_join
        tid = (f"lfvt_mesh/{emit}/{measure}/shards="
               + "-".join(str(k) for k, _, _, _ in bucket))

        def mesh_rung(bucket=bucket, caps=caps, lr_b=lr_b, first=first):
            sub_acc, sub_pairs = zero_acc(), set()
            finish_bucket(launch_bucket(bucket, caps, lr_b, first, sub_acc),
                          sub_acc, sub_pairs)
            return sorted_pairs(sub_pairs), sub_acc

        def loop_rung(bucket=bucket):
            sub_acc, sub_pairs = zero_acc(), set()
            for _, flat, rs, _ in bucket:
                checked_flat(flat)
                sz = r_sizes_all[rs]
                lo, hi = window_bounds(sz, flat.s_sizes, t, measure)
                pp, nk = kops.lfvt_join_pairs(
                    flat, jnp.asarray(r_pad_all[rs]), jnp.asarray(sz),
                    jnp.asarray(lo), jnp.asarray(hi), t,
                    capacity=pair_capacity, measure=measure)
                local = np.asarray(pp[:nk] if nk else pp[:0])
                if len(local):
                    rid = R.ids[rs[local[:, 0]]]
                    sid = flat.s_ids[local[:, 1]]
                    sub_pairs.update(zip(map(int, rid), map(int, sid)))
                if emit == "pairs":
                    sub_acc["result"] += nk
                sub_acc["reduce"] += 8 * nk + 4
            return sorted_pairs(sub_pairs), sub_acc

        def oracle_rung(bucket=bucket):
            sub_acc, sub_pairs = zero_acc(), set()
            for _, flat, rs, _ in bucket:
                ss = np.nonzero(np.isin(
                    np.asarray(S.ids), np.asarray(flat.s_ids)))[0]
                got = brute_force_join(_sub_collection(R, rs),
                                       _sub_collection(S, ss), t,
                                       measure=measure)
                sub_pairs.update(got)
                if emit == "pairs":
                    sub_acc["result"] += len(got)
            return sorted_pairs(sub_pairs), sub_acc

        rungs = [("mesh", mesh_rung)]
        mp, np_ = caps[0], caps[1]
        if (global_config.memory_guardrail
                and K * mp * np_ * 4 > int(global_config.vmem_budget)):
            res.degradations.append(f"{tid}:mesh->loop(guardrail)")
            rungs = []
        rungs += [("loop", loop_rung), ("oracle", oracle_rung)]
        got, delta = res.run(tid, rungs)
        pairs.update((int(a), int(b)) for a, b in got)
        _fold_delta(acc, delta)
    for ctx in launched:
        finish_bucket(ctx, acc, pairs)

    n_result = acc["result"] if emit == "pairs" else len(pairs)
    if stats is not None:
        stats.update(route_stats)
        stats.update(
            intervals=part.intervals, psi=part.psi, n_shards=part.n_shards,
            emit=emit, measure=measure, result_pairs=n_result,
            pair_bytes=n_result * 8, reduce_bytes=acc["reduce"],
            dense_mask_bytes=acc["dense"],
            reduce_intermediate_peak_bytes=acc["peak_inter"],
            reduce_mask_peak_bytes=acc["peak_mask"],
            walk_steps=acc["walk_steps"], early_stops=acc["early_stops"],
            # per-shard device-plan live counts summed across the mesh
            # ('static' reports every tile as entered)
            live_tiles=acc["live"], total_tiles=acc["total"],
            walk_schedule=schedule,
            # the shard body always runs the compiled jnp twin
            walk_impl="jnp",
            walk_vmem_tile_bytes=acc["walk_vmem"],
            regrows=acc["regrows"], pad=pad, n_buckets=len(buckets),
            mesh_devices=n_devices, reduce_devices=len(reduce_devices),
            shard_block_bytes=acc["ship"],
            shard_block_bytes_per_shard=acc["ship"] / max(part.n_shards, 1),
            pad_waste_max=acc["waste_max"],
            pad_waste_mean=(acc["waste_sum"] / acc["waste_n"]
                            if acc["waste_n"] else 0.0),
            flat_pad_waste=(acc["waste_sum"] / acc["waste_n"]
                            if acc["waste_n"] else 0.0))
        resilience_stats(stats, res)
    return pairs


def _emit_shard_pairs(block: ShardBlock, lk: int, local: np.ndarray,
                      out: set) -> None:
    """Map one shard's packed (row, col) indices back to original ids."""
    if not len(local):
        return
    rid = block.r_ids[lk, local[:, 0]]
    sid = block.s_ids[lk, local[:, 1]]
    keep = (rid >= 0) & (sid >= 0)  # belt: padding can't qualify
    out.update(zip(map(int, rid[keep]), map(int, sid[keep])))


def _shard_prefixes(pairs_dev, counts: np.ndarray) -> dict:
    """{k: pairs_dev[k, :counts[k]] on the host} for every shard k with
    pairs, from a stacked (K, cap, 2) buffer.

    Each prefix is sliced on the device that holds that shard
    (``addressable_shards``), so a mesh-sharded stack is never indexed
    across devices and only the counted rows cross the host boundary —
    the cap-sized buffer stays device-resident (reduce output bytes are
    ``8·n_k + 4`` per shard, the Fig. 8 model)."""
    out = {}
    for shard in pairs_dev.addressable_shards:
        first = shard.index[0].start or 0
        for j in range(shard.data.shape[0]):
            c = int(counts[first + j])
            if c:
                out[first + j] = np.asarray(shard.data[j, :c])
    return out


def _collect_block_pairs(block: ShardBlock, pairs_dev,
                         counts: np.ndarray, out: set) -> None:
    """Transfer each shard's variable-length pair slice and map the packed
    (row, col) indices back to original ids."""
    for lk, local in _shard_prefixes(pairs_dev, counts).items():
        _emit_shard_pairs(block, lk, local, out)


def mr_cf_rs_join(R: SetCollection, S: SetCollection, t: float,
                  n_shards: int, strategy: str = "load_aware",
                  method: str = "popcount", mesh: Mesh | None = None,
                  axis: str | None = None, stats: dict | None = None,
                  emit: str = "pairs", pad: str | None = None,
                  pair_capacity: int | None = None,
                  measure: str = "jaccard", fault_plan=None,
                  checkpoint_dir: str | None = None,
                  schedule: str | None = None, plan=None) -> set:
    """Distributed candidate-free R-S join. Returns {(r_id, s_id)}.

    strategy: 'load_aware' (paper Eq. 2-3) | 'hash' (ablation baseline)
    method:   'auto' — the cost-model planner (core/planner.py,
              DESIGN.md §14) probes the routed inputs and picks per
              shard on the loop path (light small-universe shards run
              the bitmap popcount, heavy shards the planned LFVT walk)
              or one homogeneous method under a mesh; decisions land in
              ``stats["plan"]``/``stats["shard_methods"]`` and the
              chosen paths run through the same resilience ladder.
              Or force one of:
              'popcount' | 'onehot' | 'kernel_bitmap' | 'kernel_onehot'
              (shard-local tile joins over bitmap blocks) | 'lfvt' /
              'lfvt_ref' — each shard's S partition is compiled to a
              ``FlatLFVT`` and shipped as plain int32 arrays (DESIGN.md
              §9); nothing |S|·W-shaped is materialized, so it serves
              universes where the bitmap packing is infeasible. 'lfvt'
              reduces through the live row-tiled walk kernel on the
              loop path (DESIGN.md §10, walk stats mirrored) and — with
              a mesh — through the bucketed sentinel-padded shard_map
              path (DESIGN.md §11), where per-shard flat tables are
              pow-2 grouped and padded so shards share compiled shapes.
              'lfvt_ref' keeps the PR-4 whole-block jnp walk (loop path
              only; pass method='lfvt' for the mesh path).
    measure:  'jaccard' | 'cosine' | 'dice' | 'overlap' — qualify
              predicate, per-shard windows and map-phase R replication all
              specialize per measure (DESIGN.md §8)
    mesh:     if given, reduce runs under shard_map on ``axis`` (whose size
              must equal ``n_shards``); otherwise a sequential shard loop.
    emit:     'pairs' (default) — compaction happens inside the shard-local
              body: each shard ships a fixed-capacity (cap, 2) pair buffer
              + exact count (regrown on overflow, power-of-two protocol);
              the dense (n_shards, m_max, n_max) stack is never built and
              ``reduce_bytes`` counts compacted buffers (the paper's Fig. 8
              model). 'mask' — dense fallback: every per-shard boolean
              mask is transferred and scanned on host.
    pad:      'auto' (bucket on the loop and mesh-lfvt paths, global for
              stacked-bitmap shard_map) | 'global' | 'bucket' — see
              ``shard_blocks``; defaults to ``global_config.pad_mode``.
    pair_capacity: initial per-shard pair-buffer capacity hint for
              emit='pairs'; regrown automatically on overflow.
    fault_plan: a ``resilience.FaultPlan`` (or spec string, or "" for an
              explicitly-armed empty plan) enabling the per-task
              retry/degradation ladder (DESIGN.md §12); defaults to
              ``REPRO_FAULT`` from the environment via ``build_resilience``.
    checkpoint_dir: directory for the shard task ledger; completed shard
              tasks are checkpointed and skipped on resume (bit-identical
              output, ``stats['tasks_resumed']`` counts the skips).
    schedule: mesh-lfvt shard-body tile schedule — 'planned' (default
              via ``global_config.walk_schedule``) device-computes the
              live row-tile plan inside each traced shard body so dead
              tiles cost zero walk steps; 'static' keeps the all-tiles
              baseline. Bit-identical results either way; only the
              ``walk_steps``/``live_tiles`` stats (and wall clock) move.
              Ignored off the mesh-lfvt path (the loop path already
              plans live tiles on host).

    ``axis``, ``pad`` and ``schedule`` default to ``global_config``
    (core/config.py) when None. ``plan`` optionally injects a
    precomputed ``core.planner.JoinPlan`` (the ``repro.join`` front
    door's override path); when None the kwargs above are validated and
    resolved through ``core.planner.build_plan``.
    """
    axis = axis or global_config.mesh_axis
    pad_explicit = pad is not None
    pad = pad or global_config.pad_mode
    # the full kwarg lattice is validated in one place since ISSUE 10
    # (core/planner.py); PlannerError subclasses ValueError and keeps
    # the historical message texts, plus named errors for combinations
    # this driver used to accept silently
    validate_join_args(driver="mr", method=method, emit=emit, pad=pad,
                       pad_explicit=pad_explicit, schedule=schedule,
                       pair_capacity=pair_capacity,
                       has_mesh=mesh is not None)
    R.validate()
    S.validate()
    res = build_resilience(checkpoint_dir, fault_plan)
    if not len(R) or not len(S):
        if global_config.strict_validation:
            side = "R" if not len(R) else "S"
            raise EmptyCollectionError(
                f"empty {side} collection (strict_validation is on)")
        if stats is not None:  # consumers index these unconditionally
            stats.update(
                n_shards=0, emit=emit, measure=measure, result_pairs=0,
                pair_bytes=0,
                reduce_bytes=0, dense_mask_bytes=0, regrows=0,
                reduce_intermediate_peak_bytes=0, reduce_mask_peak_bytes=0,
                shuffle_bytes=0, shard_loads=[], max_load=0,
                r_replication=0.0, shard_block_bytes=0,
                shard_block_bytes_per_shard=0.0, pad_waste_max=0.0,
                pad_waste_mean=0.0, pad=pad, n_buckets=0, intervals=[],
                psi=0.0,
                plan=build_plan(R, S, t, driver="mr", method=method,
                                measure=measure, emit=emit,
                                has_mesh=mesh is not None, pad=pad,
                                pad_explicit=pad_explicit,
                                schedule=schedule,
                                pair_capacity=pair_capacity).to_dict())
            resilience_stats(stats, res)
        return set()
    # int32 exactness guard for the device predicate (DESIGN.md §8)
    get_measure(measure).validate(
        t, max(int(R.sizes().max(initial=0)), int(S.sizes().max(initial=0))))
    part = (load_aware_partition if strategy == "load_aware" else hash_partition)(
        R, S, t, n_shards, measure=measure)
    # resolve method='auto' (ISSUE 10): the cost model scores the routed
    # inputs — per shard on the loop path, one homogeneous pick under a
    # mesh (stacked shard_map shapes can't mix rep families)
    if plan is None:
        plan = build_plan(R, S, t, driver="mr", method=method,
                          measure=measure, emit=emit,
                          has_mesh=mesh is not None, pad=pad,
                          pad_explicit=pad_explicit, schedule=schedule,
                          pair_capacity=pair_capacity, part=part)
    method = plan.method
    shard_methods = plan.shard_methods
    if stats is not None:
        stats["plan"] = plan.to_dict()
        if shard_methods is not None:
            stats["shard_methods"] = list(shard_methods)
    if res is not None and res.ledger.dir:
        res.ledger.open_run({
            "version": 1, "driver": "mr_cf_rs_join", "t": float(t),
            "n_shards": int(n_shards), "strategy": strategy,
            "method": method, "emit": emit, "measure": measure,
            "pad": pad, "R": collection_digest(R),
            "S": collection_digest(S)})
    if shard_methods is not None and mesh is None:
        if len(set(shard_methods)) > 1:
            # heterogeneous plan: per-shard dispatch on the loop path
            return _lfvt_loop_join(R, S, t, part, emit=emit,
                                   pair_capacity=pair_capacity,
                                   measure=measure, stats=stats,
                                   impl="kernel", res=res,
                                   shard_methods=shard_methods)
        # homogeneous per-shard pick: reuse the plain single-method paths
        method = shard_methods[0]
    if method in ("lfvt", "lfvt_ref"):
        if mesh is not None:
            # lfvt_ref + mesh already rejected by validate_join_args
            assert mesh.shape[axis] == part.n_shards, (mesh.shape,
                                                       part.n_shards)
            pad_mode = pad if pad != "auto" else "bucket"
            return _lfvt_mesh_join(R, S, t, part, mesh, axis, emit=emit,
                                   pad=pad_mode,
                                   pair_capacity=pair_capacity,
                                   measure=measure, stats=stats, res=res,
                                   schedule=schedule)
        return _lfvt_loop_join(R, S, t, part, emit=emit,
                               pair_capacity=pair_capacity, measure=measure,
                               stats=stats,
                               impl="ref" if method == "lfvt_ref" else
                               "kernel", res=res)
    pad_mode = pad if pad != "auto" else ("global" if mesh is not None
                                          else "bucket")
    if mesh is not None and pad_mode != "global":
        raise ValueError("shard_map path requires pad='global'")
    blocks, route_stats = shard_blocks(R, S, part, t, pad=pad_mode)
    if mesh is not None:
        assert mesh.shape[axis] == part.n_shards, (mesh.shape, part.n_shards)

    pairs: set = set()
    dense_bytes = sum(b.n_local * b.m_pad * b.n_pad for b in blocks)
    cap_hint = pair_capacity if pair_capacity else PAIR_CAP_GRAIN
    kernel_loop = (mesh is None and emit == "pairs"
                   and method in ("kernel_bitmap", "kernel_onehot"))

    def zero_block_acc() -> dict:
        return {"reduce": 0, "result": 0, "regrows": 0, "peak_mask": 0,
                "peak_inter": 0, "live": 0, "total_tiles": 0}

    acc = zero_block_acc()
    reduce_devices: set = set()

    def run_block(block, acc: dict, out_pairs: set, use_mesh) -> None:
        """One ShardBlock's reduce + emit (primary / loop rung body)."""
        if kernel_loop:
            per_shard, counts, out_b, rg, lv, tt, staged = (
                _kernel_block_pairs(block, t=t, method=method,
                                    cap_hint=pair_capacity, measure=measure))
            for lk, local in enumerate(per_shard):
                _emit_shard_pairs(block, lk, local, out_pairs)
            acc["reduce"] += out_b
            acc["regrows"] += rg
            acc["live"] += lv
            acc["total_tiles"] += tt
            acc["result"] += int(counts.sum())
            # the staged (L, TM, TN) live-tile masks are what resides on
            # device — tile padding can exceed the shard's m_pad*n_pad
            acc["peak_mask"] = max(acc["peak_mask"], staged)
            acc["peak_inter"] = max(acc["peak_inter"], staged)
        elif emit == "pairs":
            pairs_dev, counts, cap, rg = _block_pairs_reduce(
                block, t=t, method=method, cap_hint=cap_hint,
                mesh=use_mesh, axis=axis, measure=measure)
            _collect_block_pairs(block, pairs_dev, counts, out_pairs)
            reduce_devices.update(_devices(pairs_dev))
            # variable-length reduce output: each shard ships its exact
            # slice + one count; the cap buffer never leaves the device
            acc["reduce"] += int(counts.sum()) * 8 + block.n_local * 4
            acc["regrows"] += rg
            acc["result"] += int(counts.sum())
            # one shard-local mask (per map step / per device) + the
            # compacted per-shard output buffers
            acc["peak_mask"] = max(acc["peak_mask"],
                                   block.m_pad * block.n_pad)
            acc["peak_inter"] = max(
                acc["peak_inter"],
                block.m_pad * block.n_pad + block.n_local * (cap * 8 + 4))
        else:
            if use_mesh is not None:
                masks_dev = _shard_map_reduce(block.arrays, use_mesh, axis,
                                              t=t, method=method,
                                              measure=measure)
            else:
                masks_dev = _loop_reduce(
                    tuple(jnp.asarray(a) for a in block.arrays),
                    t=t, method=method, measure=measure)
            reduce_devices.update(_devices(masks_dev))
            masks = np.asarray(masks_dev)
            for lk in range(block.n_local):
                rr, ss = np.nonzero(masks[lk])
                out_pairs.update(
                    (int(block.r_ids[lk, i]), int(block.s_ids[lk, j]))
                    for i, j in zip(rr, ss)
                    if block.r_ids[lk, i] >= 0 and block.s_ids[lk, j] >= 0
                )
            acc["reduce"] += masks.size
            acc["peak_mask"] = max(acc["peak_mask"], masks.size)
            acc["peak_inter"] = max(acc["peak_inter"], masks.size)

    if res is None:
        for block in blocks:
            run_block(block, acc, pairs, mesh)
    else:
        # resilience ladder per block (DESIGN.md §12): primary reduce ->
        # single-device loop rerun (mesh runs only) -> host oracle over
        # the shards' original sets (ids mapped back through R.ids/S.ids)
        from .join import brute_force_join
        r_rowmap = {int(v): i for i, v in enumerate(np.asarray(R.ids))}
        s_rowmap = {int(v): i for i, v in enumerate(np.asarray(S.ids))}

        for bi, block in enumerate(blocks):
            def primary(use_mesh, block=block):
                def run():
                    sub_acc, sub_pairs = zero_block_acc(), set()
                    run_block(block, sub_acc, sub_pairs, use_mesh)
                    return sorted_pairs(sub_pairs), sub_acc
                return run

            def oracle(block=block):
                sub_acc, sub_pairs = zero_block_acc(), set()
                for lk in range(block.n_local):
                    rrows = np.asarray(
                        [r_rowmap[int(v)] for v in block.r_ids[lk] if v >= 0],
                        np.int64)
                    srows = np.asarray(
                        [s_rowmap[int(v)] for v in block.s_ids[lk] if v >= 0],
                        np.int64)
                    got = brute_force_join(_sub_collection(R, rrows),
                                           _sub_collection(S, srows), t,
                                           measure=measure)
                    sub_pairs.update(got)
                if emit == "pairs":
                    sub_acc["result"] = len(sub_pairs)
                return sorted_pairs(sub_pairs), sub_acc

            tid = f"block_join/{method}/{emit}/{measure}/block={bi}"
            rungs = [("mesh" if mesh is not None else method, primary(mesh))]
            if mesh is not None:
                rungs.append(("loop", primary(None)))
            rungs.append(("oracle", oracle))
            got, delta = res.run(tid, rungs)
            pairs.update((int(a), int(b)) for a, b in got)
            _fold_delta(acc, delta)

    n_result = len(pairs) if emit == "mask" else acc["result"]
    if stats is not None:
        stats.update(route_stats)
        stats["intervals"] = part.intervals
        stats["psi"] = part.psi
        stats["n_shards"] = part.n_shards
        stats["emit"] = emit
        stats["measure"] = measure
        stats["result_pairs"] = n_result
        # compacted result bytes: 2 int32 ids per qualifying pair — the
        # quantity the paper's shuffle/disk accounting charges the reduce
        # output with (vs the dense per-shard masks)
        stats["pair_bytes"] = n_result * 8
        stats["reduce_bytes"] = acc["reduce"]
        stats["dense_mask_bytes"] = dense_bytes
        stats["reduce_intermediate_peak_bytes"] = acc["peak_inter"]
        # largest boolean mask ever resident at once: one shard's
        # (m_pad, n_pad) for emit='pairs', the whole stacked bucket for
        # emit='mask' — the assertion target for "no dense stack"
        stats["reduce_mask_peak_bytes"] = acc["peak_mask"]
        stats["regrows"] = acc["regrows"]
        if kernel_loop:
            stats["live_tiles"] = acc["live"]
            stats["total_tiles"] = acc["total_tiles"]
        else:
            stats["reduce_devices"] = len(reduce_devices)
        if mesh is not None:
            stats["mesh_devices"] = int(mesh.shape[axis])
        resilience_stats(stats, res)
    return pairs
