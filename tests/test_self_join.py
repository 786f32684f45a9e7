"""``repro.self_join`` (the tile join's self mode, DESIGN.md §2) against
the plain oracle ``brute_force_self_join``, the column band of the
dense-mask methods (``tile_join.block_band``) in self and R-S joins, and
the span budget a many-block call needs.
"""
import time

import numpy as np
import pytest

import repro
from repro import obs
from repro.core.config import global_config
from repro.core.join import brute_force_join, brute_force_self_join
from repro.core.planner import PlannerError
from repro.core.sets import SetCollection
from repro.core.tile_join import block_band, cf_rs_join_device, window_bounds

DENSE = ("popcount", "onehot")


def spread(n, seed=0, universe=160, lo=1, hi=40):
    """Sets of sizes spread over [lo, hi), a tenth of them near-copies
    (a copy, or a copy less one element) of other rows."""
    rng = np.random.default_rng(seed)
    sets = [rng.choice(universe, int(rng.integers(lo, hi)), replace=False)
            for _ in range(n - n // 10)]
    for k in range(n // 10):
        base = sets[int(rng.integers(len(sets)))]
        sets.append(base.copy() if k % 2 or len(base) < 5
                    else np.delete(base, 0))
    order = rng.permutation(len(sets))
    return SetCollection.from_ragged([sets[i] for i in order],
                                     universe=universe)


def ties(n, seed=1, universe=64):
    """Sets of three sizes only, many exact duplicates (J = 1): equal-size
    runs cross every block boundary."""
    rng = np.random.default_rng(seed)
    pool = [rng.choice(universe, int(rng.choice([8, 9, 10])), replace=False)
            for _ in range(n // 3)]
    return SetCollection.from_ragged(
        [pool[int(rng.integers(len(pool)))] for _ in range(n)],
        universe=universe)


def expected_band_cells(C, t, measure, method, r_block):
    """Rows x band columns summed over the blocks of a self-join."""
    n = len(C)
    sizes = C.sort_by_size().sizes()
    lo, hi = window_bounds(sizes, sizes, t, measure)
    lo = np.maximum(lo, np.arange(1, n + 1))
    cells = 0
    for a in range(0, n, r_block):
        b = min(a + r_block, n)
        cols = (block_band(lo[a:b], hi[a:b], n)[1] if method in DENSE
                else n)
        cells += (b - a) * cols
    return cells


CASES = [
    # (corpus, measure, method, r_block, threshold)
    ("spread", "jaccard", "popcount", 16, 0.8),
    ("spread", "cosine", "popcount", 16, 0.8),
    ("spread", "dice", "popcount", 16, 0.8),
    ("spread", "overlap", "popcount", 16, 0.8),
    ("spread", "jaccard", "onehot", 32, 0.6),
    ("spread", "jaccard", "auto", 64, 0.8),
    ("spread", "jaccard", "popcount", 1024, 0.8),  # one block, band n
    ("spread_small", "jaccard", "lfvt", 16, 0.7),
    ("spread_small", "dice", "kernel_bitmap", 32, 0.7),
    ("ties", "jaccard", "popcount", 16, 0.8),
    ("ties", "overlap", "onehot", 16, 0.9),
    ("ties", "jaccard", "lfvt_ref", 16, 1.0),
    ("empty", "jaccard", "popcount", 16, 0.8),
    ("singleton", "jaccard", "popcount", 16, 0.8),
]
CORPORA = {
    "spread": lambda: spread(700),
    "spread_small": lambda: spread(120, seed=2),
    "ties": lambda: ties(400),
    "empty": lambda: SetCollection.from_ragged([], universe=8),
    "singleton": lambda: SetCollection.from_ragged([[1, 2, 3]], universe=8),
}


@pytest.mark.parametrize("corpus,measure,method,r_block,t", CASES,
                         ids=["-".join(map(str, c)) for c in CASES])
def test_self_join_matches_the_oracle(monkeypatch, corpus, measure, method,
                                      r_block, t):
    monkeypatch.setattr(global_config, "r_block", r_block)
    C = CORPORA[corpus]()
    res = repro.self_join(C, t, measure=measure, method=method)
    want = brute_force_self_join(C, t, measure)
    pairs = list(res.pairs)
    assert res.pairs == want
    assert all(a < b for a, b in pairs)
    assert len(set(pairs)) == len(pairs) == res.stats["pair_count"]
    if len(C):
        assert res.stats["band_cells"] == expected_band_cells(
            C, t, measure, res.plan.method, r_block)
    else:
        assert res.stats["band_cells"] == 0
    if corpus == "ties":
        assert any(len(C.sets[a]) == len(C.sets[b]) and
                   np.array_equal(C.sets[a], C.sets[b]) for a, b in want)


@pytest.mark.parametrize("measure", ["jaccard", "overlap"])
def test_bands_narrow_and_reach_n_within_one_corpus(monkeypatch, measure):
    """On a size-spread corpus in small blocks the Jaccard bands are
    narrower than S, and under overlap (whose window is the whole row)
    the first block's band is all of S: the unbanded program."""
    monkeypatch.setattr(global_config, "r_block", 16)
    C = spread(700)
    t0 = time.perf_counter_ns()
    repro.self_join(C, 0.8, measure=measure, method="popcount")
    root = [r for r in obs.recent("repro.join", obs.RING_ROOTS)
            if r.start_ns >= t0][-1]
    bands = [(s.attrs["band_start"], s.attrs["band_cols"])
             for s in root.spans if s.name == "repro.dispatch"]
    n = len(C)
    assert len(bands) == -(-n // 16)
    assert all(0 <= a and a + c <= n for a, c in bands)
    if measure == "overlap":
        assert bands[0] == (0, n)
    else:
        assert max(c for _, c in bands) < n


def test_block_band_covers_every_window_in_few_widths():
    rng = np.random.default_rng(5)
    n = 100_000
    widths = set()
    for _ in range(300):
        lo = np.sort(rng.integers(0, n, 64))
        hi = np.minimum(lo + rng.integers(0, 30_000, 64), n)
        start, cols = block_band(lo, hi, n)
        live = lo < hi
        assert 0 <= start and start + cols <= n
        assert (lo[live] >= start).all() and (hi[live] <= start + cols).all()
        assert cols == n or cols % 128 == 0
        widths.add(cols)
    assert len(widths) <= 17
    # no live row: one grain, in range
    assert block_band(np.array([n]), np.array([n]), n) == (0, 6272)


def test_sorted_multi_block_rs_join_is_banded_and_exact(monkeypatch):
    """R-S semantics with the band: a size-sorted R in small blocks."""
    R = spread(300, seed=3).sort_by_size()
    S = spread(500, seed=4)
    stats = {}
    got = cf_rs_join_device(R, S, 0.7, method="popcount", r_block=16,
                            stats=stats)
    assert got == brute_force_join(R, S, 0.7)
    assert stats["band_cells"] < len(R) * len(S)
    res = repro.join(R, S, 0.7, method="onehot", r_block=32, emit="mask")
    assert res.pairs == got
    assert res.stats["band_cells"] < len(R) * len(S)


def test_self_join_root_and_block_spans(monkeypatch):
    monkeypatch.setattr(global_config, "r_block", 64)
    C = spread(300)
    t0 = time.perf_counter_ns()
    res = repro.self_join(C, 0.8)
    (root,) = [r for r in obs.recent("repro.join", obs.RING_ROOTS)
               if r.start_ns >= t0]
    assert root.attrs["self_join"] is True
    assert root.attrs["m"] == root.attrs["n"] == len(C)
    names = [s.name for s in root.spans]
    blocks = res.stats["r_blocks"]
    assert names.count("repro.dispatch") == names.count("repro.gather") \
        == blocks
    assert "repro.r_rep" not in names  # R blocks are slices of S's rep
    assert sum(s.attrs["band_cols"] for s in root.spans
               if s.name == "repro.dispatch") * 64 >= res.stats["band_cells"]


def test_a_hundred_block_call_keeps_every_span(monkeypatch):
    monkeypatch.setattr(global_config, "r_block", 16)
    C = spread(1600, seed=6, universe=400, hi=30)
    t0 = time.perf_counter_ns()
    res = repro.self_join(C, 0.8, method="popcount")
    assert res.stats["r_blocks"] == 100
    (root,) = [r for r in obs.recent("repro.join", obs.RING_ROOTS)
               if r.start_ns >= t0]
    assert root.dropped == 0
    assert len(root.spans) >= 4 * 100
    assert obs.MAX_SPANS >= len(root.spans)


def test_self_join_refuses_what_it_does_not_offer():
    C = spread(20)
    for name, value in (("mesh", object()), ("n_shards", 2),
                        ("fault_plan", ""), ("checkpoint_dir", "/x")):
        with pytest.raises(PlannerError, match=f"takes no {name}="):
            repro.self_join(C, 0.8, **{name: value})
    with pytest.raises(TypeError, match="bogus"):
        repro.self_join(C, 0.8, bogus=1)
    with pytest.raises(PlannerError, match="unknown method"):
        repro.self_join(C, 0.8, method="nope")
    with pytest.raises(ValueError, match="pass it as both R and S"):
        cf_rs_join_device(C, spread(20), 0.8, self_join=True)
    with pytest.raises(ValueError, match="takes no fault_plan"):
        cf_rs_join_device(C, C, 0.8, self_join=True, fault_plan="")


def test_self_join_accepts_raw_sets_and_reuses_the_s_rep():
    sets = [[1, 2, 3, 4, 5], [1, 2, 3, 4], [7, 8], [7, 8], [9]]
    assert repro.self_join(sets, 0.8).pairs == {(0, 1), (2, 3)}
    C = spread(200)
    first = repro.self_join(C, 0.8)
    again = repro.self_join(C, 0.8)
    assert again.pairs == first.pairs
    assert again.stats["s_rep_cache_hit"] is True
