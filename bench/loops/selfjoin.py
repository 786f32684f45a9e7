"""Closed loop, one client: ``repro.self_join(C, t)`` over the whole
resident corpus, call after call.

The corpus is built once in set-up from the seed: pairwise distinct sets
of the profile, of which a seeded ``planted_share`` is replaced by
near-duplicates of other corpus sets (``gen.plant``: half a copy less
one element, half at Jaccard exactly 4/5), then shuffled by the seed.
Every call joins the same collection object, so its sorted device rep is
cached as the R-S cells' S is. ``join_rows_per_s`` counts the corpus
rows of each completed call.

The check: a seeded sample of corpus rows (half planted) over the
window's calls; each row's answer is its partners in the call's
unordered pairs, symmetrised, and the reference's is
``Reference.matches(row)`` less the row itself. Every returned pair with
``a >= b`` and every reversed duplicate also counts as an extra pair.

Parameters: ``planted_share``, ``trace_seconds``.
"""
from __future__ import annotations

import numpy as np

import gen
import work_self
from harness import collection, now, profile, span
from reference import Reference

WARM_CALLS = 2     # calls before the window (compiles, S upload, caches)
CHECK_ROWS = 1024  # corpus rows compared with the reference, half planted


def corpus(cfg, mix, seed):
    """The seeded corpus (CSR) and the indices of its planted rows."""
    n = int(cfg["corpus_sets"])
    c = gen.distinct_sets(profile(cfg), n, gen.rng_for(seed, 1))
    c, planted = gen.plant(c, c, int(cfg["universe"]),
                           float(mix["planted_share"]), gen.rng_for(seed, 3))
    perm = gen.rng_for(seed, 4).permutation(n)
    flag = np.zeros(n, bool)
    flag[planted] = True
    return gen.take(c, perm), np.flatnonzero(flag[perm])


def order_faults(pairs) -> int:
    """Pairs not in ``(a, b)``, ``a < b`` form, and reversed duplicates."""
    return sum(1 for a, b in pairs if a >= b or (b, a) in pairs)


def run(cfg, mix, seed, seconds, tracer, compiles, control, log, on_window):
    import repro
    if not hasattr(repro, "self_join"):
        raise SystemExit("selfjoin: the program has no repro.self_join")

    U, t = int(cfg["universe"]), str(cfg["threshold"])
    c, planted = corpus(cfg, mix, seed)
    C = collection(c, U)
    n, sizes = len(C), np.diff(c[1])
    for _ in range(WARM_CALLS):
        res = repro.self_join(C, float(t))
    log(f"plan method={res.plan.method} decided={res.plan.decided} "
        f"pairs={len(res)} sets={n} planted={len(planted)} "
        f"mean_len={float(sizes.mean())} "
        f"band_cells={res.stats['band_cells']}")

    results, durations, least, traced_calls, band_cells = [], [], 0, 0, 0
    setup = on_window()
    compiles.on = True
    tracer.start()
    t0 = now()
    while now() - t0 < seconds:
        traced = tracer.active
        tc = now()
        with span("bench.join_call"):
            res = repro.self_join(C, float(t))
        durations.append(now() - tc)
        results.append(res.pairs)
        band_cells += res.stats["band_cells"]
        if traced:
            traced_calls += 1
            least += work_self.self_join_bytes(sizes, U, len(res))
        t0 += tracer.poll()
    window = now() - t0
    compiles.on = False
    tracer.stop()
    rec = {"setup_s": setup, "window_s": window,
           "attempted": n * len(results), "failed": 0,
           "calls": len(results), "compiles": compiles.count,
           "band_cells": band_cells, "full_cells": n * n * len(results),
           "least_bytes": {"bench.join_call": least},
           "end_to_end": {"join_rows_per_s": n * len(results) / window}}
    log(f"window calls={len(results)} rows={rec['attempted']} "
        f"seconds={window} compiles={compiles.count} "
        f"gc_pause_max_s={compiles.gc_pause_max_s} "
        f"slowest_call_s={max(durations, default=0.0)} "
        f"traced_calls={traced_calls}")

    def check():
        rng = gen.rng_for(seed, 5)
        flag = np.zeros(n, bool)
        flag[planted] = True
        half = CHECK_ROWS // 2
        rows = [int(i) for group in (np.flatnonzero(flag),
                                     np.flatnonzero(~flag))
                for i in rng.choice(group, min(half, len(group)),
                                    replace=False)]
        call_of = rng.integers(0, len(results), len(rows))
        partners: dict = {}
        for k in set(call_of.tolist()):
            mine = {i for i, j in zip(rows, call_of) if j == k}
            got = partners.setdefault(k, {})
            for a, b in results[k]:
                for x, y in ((a, b), (b, a)):
                    if x in mine:
                        got.setdefault(x, set()).add(y)
        ref = Reference(c, U, t)
        missing = extra = ref_pairs = 0
        for i, k in zip(rows, call_of):
            row = gen.row(c, i)
            want = set(ref.matches(row).tolist()) - {i}
            answer = (set(ref.matches(row, float32=True).tolist()) - {i}
                      if control else partners[int(k)].get(i, set()))
            ref_pairs += len(want)
            missing += len(want - answer)
            extra += len(answer - want)
        extra += sum(order_faults(p) for p in results)
        return {"checked_sets": len(rows), "reference_pairs": ref_pairs,
                "missing_pairs": missing, "extra_pairs": extra}

    rec["check"] = check
    return rec
