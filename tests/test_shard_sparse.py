"""Shard-sparse MR reduce (DESIGN.md §7).

Covers: in-shard pair compaction parity vs the FVT oracle and vs the
dense emit='mask' fallback under both the sequential loop and a real
multi-device shard_map mesh; the per-shard overflow/regrow protocol;
all-empty-shard edge cases; the vectorized/bucketed shard packing
(gather/scatter parity with a naive reference, padding-waste stats);
the no-dense-stack guarantee (peak reduce intermediate bytes); and the
double-buffered R-block streaming of the single-device driver.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import tile_join
from repro.core.distributed import mr_cf_rs_join, shard_blocks
from repro.core.join import brute_force_join, cf_rs_join_fvt
from repro.core.partition import hash_partition, load_aware_partition, route
from repro.core.sets import SetCollection
from repro.core.tile_join import cf_rs_join_device


def _rand(rng, n, universe, max_len):
    return SetCollection.from_ragged(
        [rng.choice(universe, size=rng.integers(1, max_len), replace=False)
         for _ in range(n)],
        universe=universe,
    )


def _skewed(rng, n, universe):
    """Zipf-ish set sizes: many tiny sets, a few huge ones."""
    sizes = np.concatenate([
        rng.integers(1, 4, n - n // 10),
        rng.integers(universe // 4, universe // 2, n // 10),
    ])
    return SetCollection.from_ragged(
        [rng.choice(universe, size=int(s), replace=False) for s in sizes],
        universe=universe,
    )


# ---------------------------------------------------------------------- #
# parity: shard-sparse reduce vs FVT oracle and vs dense fallback
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("strategy", ["load_aware", "hash"])
@pytest.mark.parametrize("pad", ["global", "bucket"])
def test_shard_sparse_matches_oracle_and_mask(strategy, pad):
    rng = np.random.default_rng(17)
    R = _rand(rng, 50, 180, 22)
    S = _rand(rng, 60, 180, 22)
    for t in (0.3, 0.6):
        expected = cf_rs_join_fvt(R, S, t)
        assert expected == brute_force_join(R, S, t)
        sp, dm = {}, {}
        got = mr_cf_rs_join(R, S, t, 5, strategy=strategy, stats=sp, pad=pad)
        assert got == expected
        assert mr_cf_rs_join(R, S, t, 5, strategy=strategy, stats=dm,
                             emit="mask", pad=pad) == expected
        assert sp["result_pairs"] == len(expected)
        assert sp["emit"] == "pairs" and sp["pad"] == pad


def test_no_dense_stack_for_pairs():
    """emit='pairs' never materializes the (n_shards, m, n) mask stack:
    the largest resident mask is one shard's, not the whole stack."""
    rng = np.random.default_rng(23)
    R = _rand(rng, 80, 250, 30)
    S = _rand(rng, 90, 250, 30)
    sp, dm = {}, {}
    expected = brute_force_join(R, S, 0.5)
    assert mr_cf_rs_join(R, S, 0.5, 6, stats=sp, pad="global") == expected
    assert mr_cf_rs_join(R, S, 0.5, 6, stats=dm, emit="mask",
                         pad="global") == expected
    n_shards = sp["n_shards"]
    assert n_shards > 1
    # dense fallback holds the full stack; sparse holds one shard's mask
    assert dm["reduce_mask_peak_bytes"] == sp["reduce_mask_peak_bytes"] * n_shards
    assert sp["reduce_mask_peak_bytes"] * n_shards == sp["dense_mask_bytes"]
    # reduce output: compacted buffers, not O(shards*m*n)
    assert sp["reduce_bytes"] < dm["reduce_bytes"] == dm["dense_mask_bytes"]


def test_per_shard_overflow_regrow():
    """A 1-pair capacity hint forces the per-shard buffers to regrow
    (power-of-two protocol) without losing pairs."""
    # dense result: everything matches everything within a shard
    sets = [np.arange(6) for _ in range(30)]
    R = SetCollection.from_ragged(sets, universe=64)
    S = SetCollection.from_ragged(sets, universe=64)
    expected = brute_force_join(R, S, 0.9)
    assert len(expected) == 900
    stats = {}
    got = mr_cf_rs_join(R, S, 0.9, 2, stats=stats, pair_capacity=1)
    assert got == expected
    assert stats["regrows"] >= 1
    # ample capacity: no regrow, same answer
    stats2 = {}
    assert mr_cf_rs_join(R, S, 0.9, 2, stats=stats2,
                         pair_capacity=1024) == expected
    assert stats2["regrows"] == 0


@pytest.mark.parametrize("cap", [128, 1024])
def test_shard_pairs_body_matches_nonzero_on_skewed_stack(cap):
    """The in-shard compaction (the dense-mask helper of DESIGN.md §6)
    gives the pairs and exact count of the ``jnp.nonzero`` form it
    replaced, shard by shard, over a stack of skewed shard masks."""
    import jax
    import jax.numpy as jnp
    from repro.core.distributed import _shard_pairs_body

    rng = np.random.default_rng(31)
    m, n = 24, 1300
    stack = np.stack([rng.random((m, n)) < p
                      for p in (0.0, 0.0005, 0.004, 0.05, 0.5, 1.0)])
    stack[1, -1, -1] = True  # a shard whose last element qualifies

    def old_body(mask):
        rr, cc = jnp.nonzero(mask, size=cap, fill_value=-1)
        return (jnp.stack([rr, cc], axis=1).astype(jnp.int32),
                jnp.sum(mask, dtype=jnp.int32))

    def per_shard(body):  # the lax.map of the MR loop path
        return jax.jit(lambda s: jax.lax.map(body, s))(jnp.asarray(stack))

    pairs, counts = per_shard(lambda mk: _shard_pairs_body(mk, cap))
    ref_pairs, ref_counts = per_shard(old_body)
    np.testing.assert_array_equal(np.asarray(pairs), np.asarray(ref_pairs))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(ref_counts))
    assert list(np.asarray(counts)) == [int(s.sum()) for s in stack]
    assert np.asarray(counts).max() > cap  # the dense shards truncate


def test_all_empty_and_partial_shards():
    """Shards with no R rows, no S rows, or neither must contribute
    nothing and not disturb packing/compaction."""
    rng = np.random.default_rng(5)
    # S occupies exactly one length -> with many shards most are empty
    S = SetCollection.from_ragged([rng.choice(100, size=7, replace=False)
                                   for _ in range(12)], universe=100)
    R = _rand(rng, 25, 100, 30)
    for t in (0.4, 0.9):
        expected = brute_force_join(R, S, t)
        for pad in ("global", "bucket"):
            stats = {}
            assert mr_cf_rs_join(R, S, t, 8, stats=stats, pad=pad) == expected
    # R outside every window: no shard has work
    tiny = SetCollection.from_ragged([np.arange(1) for _ in range(4)],
                                     universe=100)
    huge = SetCollection.from_ragged([np.arange(90) for _ in range(4)],
                                     universe=100)
    stats = {}
    assert mr_cf_rs_join(tiny, huge, 0.9, 3, stats=stats) == set()
    assert stats["result_pairs"] == 0


# ---------------------------------------------------------------------- #
# vectorized shard packing
# ---------------------------------------------------------------------- #
def _reference_blocks(R, S, part, t):
    """The pre-vectorization per-shard packing loop (global padding)."""
    s_rows, r_rows, _ = route(R, S, part)
    n_shards = part.n_shards
    universe = max(R.universe, S.universe)
    W = max((universe + 31) // 32, 1)
    m_max = max(1, max((len(x) for x in r_rows), default=1))
    n_max = max(1, max((len(x) for x in s_rows), default=1))
    r_bm = np.zeros((n_shards, m_max, W), np.uint32)
    s_bm = np.zeros((n_shards, n_max, W), np.uint32)
    r_sz = np.zeros((n_shards, m_max), np.int32)
    s_sz = np.zeros((n_shards, n_max), np.int32)
    lo = np.zeros((n_shards, m_max), np.int32)
    hi = np.zeros((n_shards, m_max), np.int32)
    r_ids = np.full((n_shards, m_max), -1, np.int64)
    s_ids = np.full((n_shards, n_max), -1, np.int64)
    for k in range(n_shards):
        if len(s_rows[k]):
            sub = SetCollection([S.sets[i] for i in s_rows[k]], universe,
                                S.ids[s_rows[k]]).sort_by_size()
            ns = len(sub)
            s_bm[k, :ns] = sub.bitmaps(W)
            s_sz[k, :ns] = sub.sizes()
            s_ids[k, :ns] = sub.ids
        if len(r_rows[k]):
            subr = SetCollection([R.sets[i] for i in r_rows[k]], universe,
                                 R.ids[r_rows[k]])
            mr = len(subr)
            r_bm[k, :mr] = subr.bitmaps(W)
            sizes = subr.sizes()
            r_sz[k, :mr] = sizes
            r_ids[k, :mr] = subr.ids
            if len(s_rows[k]):
                l, h = tile_join.window_bounds(
                    sizes, s_sz[k, : len(s_rows[k])], t)
                lo[k, :mr] = l
                hi[k, :mr] = h
    return (r_bm, r_sz, s_bm, s_sz, lo, hi), (r_ids, s_ids)


@pytest.mark.parametrize("strategy", ["load_aware", "hash"])
def test_vectorized_packing_matches_reference(strategy):
    rng = np.random.default_rng(31)
    R = _rand(rng, 40, 150, 25)
    S = _rand(rng, 55, 150, 25)
    t = 0.5
    part = (load_aware_partition if strategy == "load_aware"
            else hash_partition)(R, S, t, 4)
    blocks, stats = shard_blocks(R, S, part, t, pad="global")
    assert len(blocks) == 1
    blk = blocks[0]
    ref_arrays, (ref_r_ids, ref_s_ids) = _reference_blocks(R, S, part, t)
    for got, ref in zip(blk.arrays, ref_arrays):
        np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(blk.r_ids, ref_r_ids)
    np.testing.assert_array_equal(blk.s_ids, ref_s_ids)
    # the fixed byte stat: total (not per-shard int division)
    assert stats["shard_block_bytes"] == blk.arrays[0].nbytes + blk.arrays[2].nbytes
    assert 0.0 <= stats["pad_waste_mean"] <= stats["pad_waste_max"] <= 1.0


def test_bucketed_packing_covers_all_shards_and_cuts_waste():
    rng = np.random.default_rng(41)
    R = _skewed(rng, 60, 300)
    S = _skewed(rng, 60, 300)
    t = 0.5
    part = load_aware_partition(R, S, t, 6)
    g_blocks, g_stats = shard_blocks(R, S, part, t, pad="global")
    b_blocks, b_stats = shard_blocks(R, S, part, t, pad="bucket")
    covered = np.sort(np.concatenate([b.shard_ids for b in b_blocks]))
    np.testing.assert_array_equal(covered, np.arange(part.n_shards))
    # skewed partitions: bucketed padding must not allocate more than the
    # global-max packing, and should waste strictly less on this skew
    assert b_stats["shard_block_bytes"] <= g_stats["shard_block_bytes"]
    if b_stats["n_buckets"] > 1:
        assert b_stats["pad_waste_mean"] < g_stats["pad_waste_mean"]
    # every packed id appears exactly as in the global packing
    def id_multiset(blocks, attr):
        out = []
        for b in blocks:
            ids = getattr(b, attr)
            out.extend(ids[ids >= 0].tolist())
        return sorted(out)
    assert id_multiset(b_blocks, "r_ids") == id_multiset(g_blocks, "r_ids")
    assert id_multiset(b_blocks, "s_ids") == id_multiset(g_blocks, "s_ids")


def test_skew_bucket_padding_beats_global_end_to_end():
    rng = np.random.default_rng(43)
    R = _skewed(rng, 80, 300)
    S = _skewed(rng, 80, 300)
    expected = brute_force_join(R, S, 0.5)
    gs, bs = {}, {}
    assert mr_cf_rs_join(R, S, 0.5, 6, stats=gs, pad="global") == expected
    assert mr_cf_rs_join(R, S, 0.5, 6, stats=bs, pad="bucket") == expected
    assert bs["reduce_mask_peak_bytes"] <= gs["reduce_mask_peak_bytes"]
    assert bs["shard_block_bytes"] <= gs["shard_block_bytes"]


# ---------------------------------------------------------------------- #
# double-buffered R-block streaming (single-device driver)
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("method", ["popcount", "kernel_bitmap"])
def test_double_buffer_parity(method):
    rng = np.random.default_rng(13)
    R = _rand(rng, 70, 160, 18)
    S = _rand(rng, 50, 160, 18)
    expected = brute_force_join(R, S, 0.5)
    db, sb = {}, {}
    got = cf_rs_join_device(R, S, 0.5, method=method, r_block=16, stats=db)
    assert got == expected
    assert db["double_buffered"] is True and db["r_blocks"] > 1
    assert cf_rs_join_device(R, S, 0.5, method=method, r_block=16, stats=sb,
                             double_buffer=False) == expected
    assert sb["double_buffered"] is False
    assert db["pair_count"] == sb["pair_count"] == len(expected)


def test_double_buffer_regrow_per_block():
    """Blocks whose speculative capacity overflows regrow exactly and
    lose nothing."""
    sets = [np.arange(8) for _ in range(40)]
    C = SetCollection.from_ragged(sets, universe=32)
    stats = {}
    got = cf_rs_join_device(C, C, 0.9, r_block=20, stats=stats)
    assert got == {(i, j) for i in range(40) for j in range(40)}
    assert stats["regrows"] >= 1  # 20*40=800 pairs/block > 128 grain


def test_r_block_rep_cache_across_calls():
    rng = np.random.default_rng(19)
    R = _rand(rng, 40, 120, 15)
    S1 = _rand(rng, 30, 120, 15)
    S2 = _rand(rng, 35, 120, 15)
    tile_join.clear_r_block_cache()
    s1, s2 = {}, {}
    cf_rs_join_device(R, S1, 0.5, r_block=16, stats=s1)
    assert s1["r_rep_cache_hits"] == 0
    # same R, same blocking, different S/threshold -> uploads reused
    cf_rs_join_device(R, S2, 0.4, r_block=16, stats=s2)
    assert s2["r_rep_cache_hits"] == s2["r_blocks"] > 0
    # correctness with a hot cache
    assert (cf_rs_join_device(R, S2, 0.4, r_block=16)
            == brute_force_join(R, S2, 0.4))


def test_set_collection_rep_memoization():
    rng = np.random.default_rng(29)
    C = _rand(rng, 10, 64, 9)
    assert C.bitmaps(2) is C.bitmaps(2)
    assert C.bitmaps(2) is not C.bitmaps(3)  # keyed by word width
    assert C.padded()[0] is C.padded()[0]
    assert C.sizes() is C.sizes()
    assert not C.bitmaps(2).flags.writeable


# ---------------------------------------------------------------------- #
# real multi-device shard_map (subprocess: needs its own XLA device count)
# ---------------------------------------------------------------------- #
_SHARD_SPARSE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
from repro.core.distributed import mr_cf_rs_join
from repro.core.join import brute_force_join
from repro.core.sets import SetCollection

assert jax.device_count() == 4
rng = np.random.default_rng(2)
mk = lambda n: SetCollection.from_ragged(
    [rng.choice(200, size=rng.integers(1, 30), replace=False) for _ in range(n)],
    universe=200)
R, S = mk(60), mk(70)
mesh = jax.make_mesh((4,), ("data",))
for t in (0.3, 0.7):
    expected = brute_force_join(R, S, t)
    sp, dm = {}, {}
    got = mr_cf_rs_join(R, S, t, 4, mesh=mesh, stats=sp)
    assert got == expected, t
    assert mr_cf_rs_join(R, S, t, 4, mesh=mesh, stats=dm,
                         emit="mask") == expected, t
    n = sp["n_shards"]
    # each device compacts in-shard: the resident mask is per-device
    assert sp["reduce_mask_peak_bytes"] * n == dm["reduce_mask_peak_bytes"]
    assert sp["reduce_bytes"] != dm["reduce_bytes"]
# overflow/regrow under shard_map (hash keeps 4 shards for 1 length)
sets = [np.arange(6) for _ in range(24)]
D = SetCollection.from_ragged(sets, universe=200)
st = {}
got = mr_cf_rs_join(D, D, 0.9, 4, mesh=mesh, stats=st, pair_capacity=1,
                    strategy="hash")
assert got == {(i, j) for i in range(24) for j in range(24)}
assert st["regrows"] >= 1
print("SHARD_SPARSE_OK")
"""


def test_shard_sparse_under_shard_map_4_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _SHARD_SPARSE_SCRIPT],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARD_SPARSE_OK" in out.stdout
