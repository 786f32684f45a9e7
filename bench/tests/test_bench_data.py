"""The benchmark's generator and plain reference, on the CPU at tiny sizes."""
from __future__ import annotations

import os
import sys
from fractions import Fraction

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import gen  # noqa: E402
from reference import Reference  # noqa: E402

KOSARAK = {"universe": 41270, "draw_mean_len": 9.0, "max_len": 2498,
           "zipf_a": 1.6, "len_sigma": 0.9}
DBLP = {"universe": 6864, "draw_mean_len": 86.1, "max_len": 1625,
        "zipf_a": 1.3, "len_sigma": 0.35}
BIG_SEED = 2**31 + 12345


def _sets(c):
    return [gen.row(c, i) for i in range(len(c[1]) - 1)]


@pytest.mark.parametrize("prof", [KOSARAK, DBLP], ids=["kosarak", "dblp"])
def test_generator_repeats_from_a_seed(prof):
    a = gen.distinct_sets(prof, 3000, gen.rng_for(BIG_SEED, 1))
    b = gen.distinct_sets(prof, 3000, gen.rng_for(BIG_SEED, 1))
    c = gen.distinct_sets(prof, 3000, gen.rng_for(BIG_SEED + 1, 1))
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0][:1000], c[0][:1000])
    r1, p1 = gen.plant(a, c, prof["universe"], 0.1, gen.rng_for(BIG_SEED, 3))
    r2, p2 = gen.plant(a, c, prof["universe"], 0.1, gen.rng_for(BIG_SEED, 3))
    assert np.array_equal(r1[0], r2[0]) and np.array_equal(p1, p2)


@pytest.mark.parametrize("prof", [KOSARAK, DBLP], ids=["kosarak", "dblp"])
def test_sets_are_sorted_distinct_and_in_profile(prof):
    c = gen.distinct_sets(prof, 5000, gen.rng_for(7, 1))
    sets = _sets(c)
    assert len(sets) == 5000
    assert all(len(s) >= 1 and (np.diff(s) > 0).all() for s in sets)
    assert c[0].min() >= 0 and c[0].max() < prof["universe"]
    assert max(len(s) for s in sets) <= prof["max_len"]
    assert len({s.tobytes() for s in sets}) == len(sets)


def test_disjoint_split_is_seeded_and_shares_no_set():
    s, r = gen.disjoint(KOSARAK, 3000, 500, gen.rng_for(BIG_SEED, 1))
    s2, r2 = gen.disjoint(KOSARAK, 3000, 500, gen.rng_for(BIG_SEED, 1))
    assert np.array_equal(s[0], s2[0]) and np.array_equal(r[0], r2[0])
    assert len(s[1]) - 1 == 3000 and len(r[1]) - 1 == 500
    s_sets = {x.tobytes() for x in _sets(s)}
    r_sets = {x.tobytes() for x in _sets(r)}
    assert len(s_sets) == 3000 and len(r_sets) == 500
    assert not s_sets & r_sets


def test_boundary_copies_sit_at_four_fifths():
    rng = gen.rng_for(3)
    for k in range(4, 120):
        s = np.sort(rng.choice(3600, k, replace=False)).astype(np.int32)
        b = gen.boundary_copy(s, 3600, rng)
        if b is None:
            continue
        inter = len(np.intersect1d(s, b))
        assert Fraction(inter, len(s) + len(b) - inter) == Fraction(4, 5)


def _brute(r_sets, s_sets, t=Fraction(4, 5)):
    out = set()
    for i, r in enumerate(r_sets):
        for j, s in enumerate(s_sets):
            f = len(np.intersect1d(r, s))
            if f and Fraction(f, len(r) + len(s) - f) >= t:
                out.add((i, j))
    return out


def test_reference_agrees_with_repro_join_and_brute_force():
    import repro
    prof = dict(KOSARAK, universe=300, max_len=40)
    s = gen.distinct_sets(prof, 400, gen.rng_for(BIG_SEED, 1))
    r, planted = gen.plant(gen.distinct_sets(prof, 200, gen.rng_for(5, 2)),
                           s, 300, 0.3, gen.rng_for(5, 3))
    ref = Reference(s, 300, "0.8")
    want = {(i, int(j)) for i, a in enumerate(_sets(r))
            for j in ref.matches(a)}
    assert want == _brute(_sets(r), _sets(s))
    got = set(repro.join(_sets(r), _sets(s), 0.8).pairs)
    assert got == want
    # every planted row finds its source; the float32 control misreads
    # some of the pairs planted at exactly 4/5
    assert {i for i, _ in want} >= set(planted.tolist())
    f32 = {(i, int(j)) for i, a in enumerate(_sets(r))
           for j in ref.matches(a, float32=True)}
    assert f32 != want
