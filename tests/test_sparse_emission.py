"""Sparse on-device pair emission + live-tile scheduling (DESIGN.md §6).

Covers: compacted-pair parity vs the dense mask and vs the host FVT
oracle, the overflow/regrow protocol, live-tile grid construction, the
device-resident S-representation cache, window_bounds edge cases, and the
output-traffic accounting (bytes ~ result size, not O(m*n)).
"""
import time

import numpy as np
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import tile_join
from repro.core.distributed import mr_cf_rs_join
from repro.core.join import brute_force_join, cf_rs_join_fvt
from repro.core.sets import SetCollection
from repro.core.tile_join import cf_rs_join_device, window_bounds
from repro.kernels import ops
from repro.kernels.ref import join_ref


def _rand(rng, n, universe, max_len):
    return SetCollection.from_ragged(
        [rng.choice(universe, size=rng.integers(1, max_len), replace=False)
         for _ in range(n)],
        universe=universe,
    )


def _random_problem(rng, m, n, universe):
    W = max((universe + 31) // 32, 1)
    r_bm = rng.integers(0, 2**32, (m, W), dtype=np.uint32)
    s_bm = rng.integers(0, 2**32, (n, W), dtype=np.uint32)
    tail = universe % 32
    if tail:
        mask = np.uint32((1 << tail) - 1)
        r_bm[:, -1] &= mask
        s_bm[:, -1] &= mask
    r_sizes = np.bitwise_count(r_bm).sum(1).astype(np.int32)
    s_sizes = np.bitwise_count(s_bm).sum(1).astype(np.int32)
    return r_bm, r_sizes, s_bm, s_sizes


# ---------------------------------------------------------------------- #
# kernel-level parity: packed pairs == nonzero(dense mask)
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("kernel", ["bitmap", "onehot"])
@pytest.mark.parametrize("m,n,universe", [(1, 1, 7), (3, 5, 33),
                                          (17, 140, 257), (40, 260, 96)])
@pytest.mark.parametrize("t", [0.25, 0.625])
def test_pairs_match_dense_mask(kernel, m, n, universe, t):
    rng = np.random.default_rng(m * 101 + n + universe)
    r_bm, r_sz, s_bm, s_sz = _random_problem(rng, m, n, universe)
    lo = rng.integers(0, max(n, 1), m).astype(np.int32)
    hi = np.minimum(lo + rng.integers(0, max(n, 1), m), n).astype(np.int32)
    args = tuple(map(jnp.asarray, (r_bm, r_sz, s_bm, s_sz, lo, hi)))
    expected = set(zip(*np.nonzero(np.asarray(join_ref(*args, t)))))
    stats = {}
    pairs, n_pairs = ops.join_pairs(kernel, *args, t, stats=stats)
    packed = np.asarray(pairs)
    got = set(map(tuple, packed[:n_pairs].tolist()))
    assert got == expected
    assert n_pairs == len(expected) == stats["pair_count"]
    # capacity padding is exactly (-1, -1)
    assert (packed[n_pairs:] == -1).all()


def test_live_tile_schedule_skips_tiles():
    """Live-tile list == complement of the skip mask; result unchanged."""
    # skewed sizes: S spans 1..260 elements (size-sorted), R rows are
    # small, so the Lemma-3.1 windows land on the tail column tiles only
    universe = 300
    W = (universe + 31) // 32
    s_sz = np.sort(1 + (np.arange(512) % 260))[::-1].astype(np.int32)
    r_sz = (4 + np.arange(32) % 5).astype(np.int32)

    def first_bits(count):
        full, rem = divmod(int(count), 32)
        row = np.zeros(W, np.uint32)
        row[:full] = np.uint32(0xFFFFFFFF)
        if rem:
            row[full] = np.uint32((1 << rem) - 1)
        return row

    s_bm = np.stack([first_bits(c) for c in s_sz])
    r_bm = np.stack([first_bits(c) for c in r_sz])
    lo, hi = window_bounds(r_sz, s_sz, 0.5)
    lo, hi = lo.astype(np.int32), hi.astype(np.int32)
    args = tuple(map(jnp.asarray, (r_bm, r_sz, s_bm, s_sz, lo, hi)))
    tiles = (8, 128, 2)
    stats = {}
    pairs, n_pairs = ops.bitmap_join_pairs(*args, 0.5, tiles=tiles,
                                           stats=stats)
    # the schedule must launch strictly fewer grid steps than the dense
    # grid for a windowed problem of this shape...
    assert 0 < stats["live_tiles"] < stats["total_tiles"]
    # ...and agree with the host-side skip mask exactly
    TM, TN, _ = tiles
    lo_p = np.pad(lo, (0, (-32) % TM))
    hi_p = np.pad(hi, (0, (-32) % TM))
    skip = np.asarray(ops._tile_skip_mask(
        jnp.asarray(lo_p), jnp.asarray(hi_p), len(lo_p) // TM,
        512 // TN, TM, TN))
    assert stats["live_tiles"] == int((skip == 0).sum())
    expected = set(zip(*np.nonzero(np.asarray(ops.bitmap_join(
        *args, 0.5, tiles=tiles)))))
    assert set(map(tuple, np.asarray(pairs)[:n_pairs].tolist())) == expected


def test_overflow_regrow_protocol():
    """A too-small capacity hint regrows exactly once, losing nothing."""
    # 24 identical singleton sets on both sides: 576 qualifying pairs,
    # well past the too-small hint AND past one capacity grain
    m = n = 24
    r_bm = np.ones((m, 1), np.uint32)
    s_bm = np.ones((n, 1), np.uint32)
    sz = np.ones(m, np.int32)
    lo = np.zeros(m, np.int32)
    hi = np.full(m, n, np.int32)
    args = tuple(map(jnp.asarray, (r_bm, sz, s_bm, sz, lo, hi)))
    stats = {}
    pairs, n_pairs = ops.bitmap_join_pairs(*args, 0.5, capacity=8,
                                           stats=stats)
    assert n_pairs == m * n
    assert stats["regrows"] == 1
    assert pairs.shape[0] == ops.round_capacity(m * n) >= m * n
    got = set(map(tuple, np.asarray(pairs)[:n_pairs].tolist()))
    assert got == {(i, j) for i in range(m) for j in range(n)}
    # ample capacity: no regrow, same result
    stats2 = {}
    pairs2, n2 = ops.bitmap_join_pairs(*args, 0.5, capacity=1024,
                                       stats=stats2)
    assert stats2["regrows"] == 0 and n2 == n_pairs


def test_round_capacity():
    assert ops.round_capacity(0) == 0
    assert ops.round_capacity(1) == ops.PAIR_CAP_GRAIN
    assert ops.round_capacity(ops.PAIR_CAP_GRAIN) == ops.PAIR_CAP_GRAIN
    assert ops.round_capacity(ops.PAIR_CAP_GRAIN + 1) == 2 * ops.PAIR_CAP_GRAIN
    # power-of-two multiples only -> O(log) distinct jit signatures
    caps = {ops.round_capacity(k) for k in range(1, 5000)}
    assert len(caps) <= 7


# ---------------------------------------------------------------------- #
# dense-mask compaction: tile_join.compact_mask == np.nonzero, exactly
# ---------------------------------------------------------------------- #
def _sparse_mask(rng, shape, n_true):
    flat = np.zeros(int(np.prod(shape)), bool)
    flat[rng.choice(flat.size, n_true, replace=False)] = True
    return flat.reshape(shape)


def _last_only(rng, shape, _):
    mask = np.zeros(shape, bool)
    mask[(-1,) * len(shape)] = True
    return mask


@pytest.mark.parametrize("make,shape,n_true,size", [
    (_sparse_mask, (6, 2600), 40, 128),         # n not a multiple of C
    (_sparse_mask, (9, 300), 25, 128),          # n < C
    (_sparse_mask, (1, 5000), 30, 128),         # a single row
    (_sparse_mask, (16, 2100), 500, 256),       # size < total: a prefix
    (_sparse_mask, (37, 1000), 50, 128),        # size > total
    (_sparse_mask, (3, 7, 260), 40, 128),       # a shard stack -> (size, 3)
    (_sparse_mask, (32, 100_000), 300, 512),    # a batch cell's row width
    (_last_only, (4, 1500), 1, 128),            # the very last element
    (lambda rng, shape, _: np.zeros(shape, bool), (8, 3000), 0, 128),
    (lambda rng, shape, _: np.ones(shape, bool), (5, 300), 1500, 2048),
    (lambda rng, shape, _: np.ones(shape, bool), (4, 1100), 4400, 256),
    (lambda rng, shape, _: np.zeros(shape, bool), (0, 5), 0, 128),  # empty
    # dense regrows: totals far above 2,048, the chunk narrowed with size
    (_sparse_mask, (64, 5000), 96_000, 1 << 17),  # C = 2, size > total
    (_sparse_mask, (48, 3000), 40_000, 1 << 14),  # C = 8, a prefix
    (lambda rng, shape, _: np.ones(shape, bool), (3, 40, 700), 84_000,
     1 << 17),                                    # C = 1, size > m * n
])
def test_compact_mask_matches_nonzero(make, shape, n_true, size):
    rng = np.random.default_rng(int(np.prod(shape)) + n_true)
    mask = make(rng, shape, n_true)
    packed, total, live = tile_join._compact_mask(jnp.asarray(mask),
                                                  size=size)
    idx = np.stack(np.nonzero(mask), axis=1)
    expected = np.full((size, mask.ndim), -1, np.int64)
    expected[:min(size, len(idx))] = idx[:size]
    packed = np.asarray(packed)
    assert packed.dtype == np.int32 and packed.shape == expected.shape
    np.testing.assert_array_equal(packed, expected)  # pairs, order, fill
    assert int(total) == int(mask.sum()) == n_true
    # live chunks: C-wide chunks of the rows (leading dims folded), the
    # row padded with False to a multiple of C
    n = shape[-1]
    rows = mask.reshape(-1, n)
    c = tile_join._compact_chunk(rows.shape[0], n, size)
    assert size * c <= max(rows.size, size)  # the select stays mask-sized
    rows = np.pad(rows, ((0, 0), (0, (-n) % c)))
    chunk_counts = rows.reshape(rows.shape[0], -(-n // c), c).sum(-1)
    assert int(live) == int((chunk_counts > 0).sum())


def test_device_popcount_regrow_counts_live_chunks():
    """A forced overflow regrows the dense-mask compaction and keeps the
    pair set exact; the live-chunk counter is summed into the stats."""
    rng = np.random.default_rng(21)
    sets = [rng.choice(120, size=rng.integers(2, 10), replace=False)
            for _ in range(200)]
    R = SetCollection.from_ragged(sets, universe=120)
    S = SetCollection.from_ragged(sets, universe=120)
    expected = cf_rs_join_fvt(R, S, 0.5)
    assert len(expected) > tile_join.PAIR_CAP_GRAIN
    stats = {}
    t0 = time.perf_counter_ns()
    got = cf_rs_join_device(R, S, 0.5, method="popcount", pair_capacity=1,
                            stats=stats)
    assert got == expected
    assert stats["regrows"] >= 1
    assert 0 < stats["compact_live_chunks"] <= stats["pair_count"]
    # each block's repro.gather span carries its own count
    gathers = [g for g in obs.recent("repro.gather", obs.RING_ROOTS)
               if g.start_ns >= t0]
    assert len(gathers) == stats["r_blocks"]
    assert sum(g.attrs["compact_live_chunks"] for g in gathers) == (
        stats["compact_live_chunks"])


# ---------------------------------------------------------------------- #
# end-to-end: sparse path bit-identical to the host FVT oracle
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("method", ["popcount", "onehot", "kernel_bitmap",
                                    "kernel_onehot"])
def test_device_sparse_matches_fvt_oracle(method):
    rng = np.random.default_rng(3)
    R = _rand(rng, 40, 150, 20)
    S = _rand(rng, 50, 150, 20)
    for t in (0.25, 0.5, 0.75):
        expected = cf_rs_join_fvt(R, S, t)
        assert expected == brute_force_join(R, S, t)
        stats = {}
        got = cf_rs_join_device(R, S, t, method=method, stats=stats,
                                emit="pairs")
        assert got == expected
        assert stats["emit"] == "pairs"
        # dense fallback agrees too
        assert cf_rs_join_device(R, S, t, method=method, emit="mask") == expected


def test_device_sparse_output_bytes_scale_with_result():
    """Output traffic ~ pairs shipped, and << the dense mask for sparse
    results; tight pair_capacity regrows transparently."""
    rng = np.random.default_rng(11)
    R = _rand(rng, 300, 4000, 12)
    S = _rand(rng, 900, 4000, 12)
    stats = {}
    got = cf_rs_join_device(R, S, 0.8, method="popcount", stats=stats)
    assert stats["output_bytes"] <= (
        8 * tile_join.round_capacity(max(stats["pair_count"], 1))
        + 4 * stats["r_blocks"])
    assert stats["output_bytes"] < stats["dense_mask_bytes"]
    # forcing a tiny capacity regrows without changing the result
    assert cf_rs_join_device(R, S, 0.8, method="popcount",
                             pair_capacity=1) == got


def test_s_rep_cache_reused_across_calls():
    rng = np.random.default_rng(5)
    R1 = _rand(rng, 20, 100, 15)
    R2 = _rand(rng, 25, 100, 15)
    S = _rand(rng, 30, 100, 15)
    tile_join.clear_s_rep_cache()
    s1, s2, s3 = {}, {}, {}
    cf_rs_join_device(R1, S, 0.5, method="popcount", stats=s1)
    cf_rs_join_device(R2, S, 0.5, method="popcount", stats=s2)  # same S
    cf_rs_join_device(R2, S, 0.5, method="onehot", stats=s3)    # new family
    assert s1["s_rep_cache_hit"] is False
    assert s2["s_rep_cache_hit"] is True
    assert s3["s_rep_cache_hit"] is False
    # correctness with the cache hot
    assert (cf_rs_join_device(R2, S, 0.5, method="onehot")
            == brute_force_join(R2, S, 0.5))


# ---------------------------------------------------------------------- #
# distributed: variable-length pair buffers + compacted-byte accounting
# ---------------------------------------------------------------------- #
def test_mr_join_sparse_reduce_parity_and_bytes():
    rng = np.random.default_rng(9)
    R = _rand(rng, 60, 200, 25)
    S = _rand(rng, 80, 200, 25)
    for t in (0.4, 0.7):
        expected = brute_force_join(R, S, t)
        sp, dm = {}, {}
        assert mr_cf_rs_join(R, S, t, 4, stats=sp) == expected
        assert mr_cf_rs_join(R, S, t, 4, stats=dm, emit="mask") == expected
        assert sp["result_pairs"] == len(expected)
        assert sp["pair_bytes"] == 8 * len(expected)
        assert sp["reduce_bytes"] < dm["reduce_bytes"] == dm["dense_mask_bytes"]


# ---------------------------------------------------------------------- #
# window_bounds edge cases
# ---------------------------------------------------------------------- #
def test_window_bounds_t_one():
    """t=1 admits only |S| == |R| (Jaccard 1 requires equality of sizes)."""
    s_desc = np.array([9, 7, 5, 5, 3, 1], np.int32)
    lo, hi = window_bounds(np.array([5, 2, 9], np.int32), s_desc, 1.0)
    assert (lo[0], hi[0]) == (2, 4)   # exactly the two size-5 rows
    assert lo[1] == hi[1]             # size 2 absent -> empty window
    assert (lo[2], hi[2]) == (0, 1)


def test_window_bounds_t_small_covers_everything():
    s_desc = np.array([40, 17, 9, 2, 1], np.int32)
    lo, hi = window_bounds(np.array([3, 40], np.int32), s_desc, 0.01)
    assert (lo == 0).all() and (hi == len(s_desc)).all()


def test_window_bounds_all_equal_sizes():
    s_desc = np.full(7, 4, np.int32)
    lo, hi = window_bounds(np.array([4], np.int32), s_desc, 0.9)
    assert (lo[0], hi[0]) == (0, 7)
    lo, hi = window_bounds(np.array([8], np.int32), s_desc, 0.9)
    assert lo[0] == hi[0]  # 4 outside [ceil(7.2), floor(8/0.9)] -> empty


def test_window_bounds_empty_sides():
    lo, hi = window_bounds(np.zeros(0, np.int32), np.array([3], np.int32), 0.5)
    assert lo.shape == (0,) and hi.shape == (0,)
    lo, hi = window_bounds(np.array([3], np.int32), np.zeros(0, np.int32), 0.5)
    assert (lo[0], hi[0]) == (0, 0)


def test_empty_collections_sparse_path():
    rng = np.random.default_rng(2)
    S = _rand(rng, 5, 20, 6)
    E = SetCollection.from_ragged([], universe=20)
    assert cf_rs_join_device(E, S, 0.5) == set()
    assert cf_rs_join_device(S, E, 0.5) == set()
    assert mr_cf_rs_join(E, S, 0.5, 2) == set()
    assert mr_cf_rs_join(S, E, 0.5, 2) == set()
