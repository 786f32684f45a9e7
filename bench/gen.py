"""Seeded set collections for the benchmark, in CSR form.

A collection is ``(elems, offs)``: ``elems`` int32, every set sorted and
free of duplicates, set ``i`` being ``elems[offs[i]:offs[i + 1]]``.

The profile sampling follows ``repro.data.synth._sample_sets``: set
lengths are log-normal (mean ``draw_mean_len``, spread ``len_sigma``)
clipped to ``[1, min(max_len, universe)]``, and elements follow a Zipf
law of exponent ``zipf_a`` over the universe. A set shorter than 64 is a
weighted draw without replacement (here: the first distinct values of an
i.i.d. weighted sequence, which is the same law, done for all sets at
once); a longer one takes the distinct values of ``2 * len`` weighted
draws and, where those are too few, tops them up uniformly from the rest
of the universe.

Planting follows ``chip_smoke.profile``: a seeded share of R rows is
replaced by near-duplicates of S rows. Half of them are a copy minus
one element (a copy for sets under five); the other half sit at
Jaccard exactly 4/5: ``a`` elements removed and ``b`` new ones added
with ``|s| = 5a + 4b``. A float predicate misreads most such pairs.
"""
from __future__ import annotations

import numpy as np

LONG = 64  # sets at least this long take the with-replacement draw


def _lengths(p: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if p["draw_mean_len"] <= 1.0:
        return np.ones(n, np.int64)
    mu = np.log(p["draw_mean_len"]) - p["len_sigma"] ** 2 / 2
    return np.clip(rng.lognormal(mu, p["len_sigma"], n).astype(np.int64), 1,
                   min(p["max_len"], p["universe"]))


def _cdf(p: dict) -> np.ndarray:
    w = np.arange(1, p["universe"] + 1, dtype=np.float64) ** -p["zipf_a"]
    c = np.cumsum(w)
    return c / c[-1]


def _draw(cdf: np.ndarray, k: int, rng: np.random.Generator) -> np.ndarray:
    return np.minimum(np.searchsorted(cdf, rng.random(k), side="right"),
                      len(cdf) - 1)


def _distinct(draws, offs):
    """Per segment of ``draws``: ``(owner, value, first position)`` of each
    distinct value, grouped by segment and in draw order within it."""
    n = len(offs) - 1
    owner = np.repeat(np.arange(n, dtype=np.int64), np.diff(offs))
    if not len(draws):
        return owner, draws, owner
    span = int(draws.max(initial=0)) + 1
    total = len(draws)
    # one sort of (segment, value, position) packed into an int64
    comp = np.sort((owner * span + draws) * total + np.arange(total))
    key = comp // total
    head = np.concatenate([[True], key[1:] != key[:-1]])
    pos = np.sort(comp[head] % total)
    return owner[pos], draws[pos], pos


def _first_distinct(draws, offs, want):
    """Per segment: how many distinct values it holds, and the first
    ``want`` of them in draw order, sorted -> (got, values, owners)."""
    n = len(offs) - 1
    own, val, _ = _distinct(draws, offs)
    got = np.bincount(own, minlength=n)
    rank = np.arange(len(own)) - np.searchsorted(own, np.arange(n))[own]
    keep = rank < want[own]
    span = int(val.max(initial=0)) + 1
    key = np.sort(own[keep] * span + val[keep])
    return got, (key % span).astype(np.int32), key // span


def sample_sets(p: dict, n: int, rng: np.random.Generator):
    """``n`` sets of profile ``p`` -> ``(elems, offs)``."""
    U = int(p["universe"])
    cdf = _cdf(p)
    lens = _lengths(p, n, rng)
    offs = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    elems = np.empty(int(offs[-1]), np.int32)
    # short sets: i.i.d. weighted draws, two per wanted element plus
    # eight; a set that found too few distinct values draws more (its
    # earlier draws kept first), in rounds of all such sets at once
    rows = np.flatnonzero(lens < LONG)
    want = lens[rows]
    per = 2 * want + 8
    seq_offs = np.concatenate([[0], np.cumsum(per)])
    draws = _draw(cdf, int(seq_offs[-1]), rng)
    while len(rows):
        got, vals, own = _first_distinct(draws, seq_offs, want)
        done = got >= want
        dst = np.repeat(offs[rows[done]], want[done])
        dst += np.arange(len(dst)) - np.repeat(
            np.cumsum(want[done]) - want[done], want[done])
        elems[dst] = vals[done[own]]
        more = np.flatnonzero(~done)
        if not len(more):
            break
        old = [draws[seq_offs[j]:seq_offs[j + 1]] for j in more]
        rows, want, per = rows[more], want[more], 2 * per[more]
        extra = _draw(cdf, int(per.sum()), rng)
        cut = np.cumsum(per)[:-1]
        draws = np.concatenate([x for pair in zip(old, np.split(extra, cut))
                                for x in pair])
        lens_j = np.array([len(a) for a in old]) + per  # old, then extra
        seq_offs = np.concatenate([[0], np.cumsum(lens_j)])
    # long sets: the distinct values of 2*len weighted draws; a random
    # len of them if there are enough, else all of them topped up by the
    # first new values of uniform draws (uniform over the rest of the
    # universe), in rounds of all such sets at once
    rows = np.flatnonzero(lens >= LONG)
    want = lens[rows]
    seq_offs = np.concatenate([[0], np.cumsum(2 * want)])
    own, val, _ = _distinct(_draw(cdf, int(seq_offs[-1]), rng), seq_offs)
    order = np.lexsort((rng.random(len(own)), own))
    own, val = own[order], val[order]
    start = np.searchsorted(own, np.arange(len(rows)))
    got = np.diff(np.concatenate([start, [len(own)]]))
    full = got >= want
    keep = (np.arange(len(own)) - start[own] < want[own]) & full[own]
    key = np.sort(own[keep] * U + val[keep])
    g = key // U
    pos = np.arange(len(g)) - np.searchsorted(g, g)
    elems[offs[rows[g]] + pos] = key % U
    bad = np.flatnonzero(~full)
    rows, want = rows[bad], want[bad]
    seqs = [val[start[j]:start[j] + got[j]] for j in bad]
    per = 2 * (want - got[bad]) + 8
    while len(rows):
        extra = rng.integers(0, U, int(per.sum()))
        seqs = [x for pair in zip(seqs, np.split(extra, np.cumsum(per)[:-1]))
                for x in pair]
        seqs = [np.concatenate(seqs[k:k + 2]) for k in range(0, len(seqs), 2)]
        seq_offs = np.concatenate([[0], np.cumsum([len(x) for x in seqs])])
        got2, vals, own2 = _first_distinct(np.concatenate(seqs), seq_offs,
                                           want)
        done = got2 >= want
        dst = np.repeat(offs[rows[done]], want[done])
        dst += np.arange(len(dst)) - np.repeat(
            np.cumsum(want[done]) - want[done], want[done])
        elems[dst] = vals[done[own2]]
        more = np.flatnonzero(~done)
        rows, want, per = rows[more], want[more], 2 * per[more]
        seqs = [seqs[j] for j in more]
    return elems, offs


def _set_hash(c, weights) -> np.ndarray:
    elems, offs = c
    h = np.zeros(len(offs) - 1, np.uint64)
    nz = np.flatnonzero(np.diff(offs))
    h[nz] = np.add.reduceat(weights[elems], offs[nz])
    return h


def distinct_sets(p: dict, n: int, rng: np.random.Generator):
    """``n`` pairwise distinct sets of profile ``p`` (the source collections
    hold no duplicate sets): draw, drop repeats, draw the shortfall again.
    Sets are told apart by a sum of random 64-bit weights per element."""
    weights = rng.integers(0, 2**63, int(p["universe"]), dtype=np.uint64)
    c = sample_sets(p, n, rng)
    while True:
        h = _set_hash(c, weights)
        _, first = np.unique(h, return_index=True)
        c = take(c, np.sort(first)[:n])
        if len(first) >= n:
            return c
        c = concat([c, sample_sets(p, 2 * (n - len(first)) + 64, rng)])


def disjoint(p: dict, n_s: int, n_r: int, rng: np.random.Generator):
    """A seeded disjoint split of ``n_s + n_r`` pairwise distinct sets of
    profile ``p`` into ``(S, R)``, S in draw order, R shuffled."""
    c = distinct_sets(p, n_s + n_r, rng)
    perm = rng.permutation(n_s + n_r)
    return take(c, np.sort(perm[:n_s])), take(c, perm[n_s:])


def take(c, rows: np.ndarray):
    """The sets ``rows`` of ``c``, in that order."""
    elems, offs = c
    rows = np.asarray(rows, np.int64)
    ln = np.diff(offs)[rows]
    new_offs = np.concatenate([[0], np.cumsum(ln)]).astype(np.int64)
    src = np.repeat(offs[rows], ln) + (
        np.arange(int(new_offs[-1])) - np.repeat(new_offs[:-1], ln))
    return elems[src], new_offs


def concat(parts):
    elems = np.concatenate([e for e, _ in parts])
    sizes = np.concatenate([np.diff(o) for _, o in parts])
    return elems, np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)


def from_list(sets: list):
    """CSR form of a list of sorted element arrays."""
    sizes = np.fromiter(map(len, sets), np.int64, len(sets))
    elems = (np.concatenate(sets).astype(np.int32) if sets
             else np.zeros(0, np.int32))
    return elems, np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)


def row(c, i: int) -> np.ndarray:
    elems, offs = c
    return elems[offs[i]:offs[i + 1]]


def boundary_copy(s: np.ndarray, universe: int, rng) -> np.ndarray | None:
    """A set at Jaccard exactly 4/5 to ``s``, or None if |s| allows none."""
    k = len(s)
    a = k % 4  # k = 5a + 4b needs a = k (mod 4)
    if k < 4 or k - 5 * a < 0:
        return None
    b = (k - 5 * a) // 4
    kept = np.delete(s, rng.choice(k, a, replace=False)) if a else s
    if b:
        free = np.setdiff1d(np.arange(universe), s, assume_unique=True)
        if len(free) < b:
            return None
        kept = np.concatenate([kept, rng.choice(free, b, replace=False)])
    return np.sort(kept).astype(np.int32)


def plant(r, s, universe: int, share: float, rng: np.random.Generator):
    """Replace a seeded ``share`` of R's rows by near-duplicates of S rows.

    Returns ``(r', planted row indices)``."""
    n_r, n_s = len(r[1]) - 1, len(s[1]) - 1
    n_plant = max(int(n_r * share), 1) if n_r else 0
    rows = np.sort(rng.choice(n_r, n_plant, replace=False))
    src = rng.integers(0, n_s, n_plant)
    out = [row(r, i) for i in range(n_r)]
    for k, (i, j) in enumerate(zip(rows, src)):
        base = row(s, int(j))
        new = boundary_copy(base, universe, rng) if k % 2 else None
        if new is None:
            new = (np.delete(base, rng.integers(len(base)))
                   if len(base) >= 5 else base.copy())
        out[int(i)] = new
    return from_list(out), rows


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """One independent stream per (seed, purpose...) tuple."""
    return np.random.default_rng([int(seed) & (2**64 - 1), *stream])
