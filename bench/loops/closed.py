"""Closed loop, one client: ``repro.join(R, S, t)`` call after call.

S is built once in set-up and stays resident, as a curated corpus does.
S and the R rows are a seeded disjoint split of one collection of the
profile, as the source's R-S joins sample both sides from one data set.
Each call gets a new R collection (a fresh object, so no cache keyed on
it can serve it) from a pool made in set-up; a seeded share of its rows
are planted near-duplicates of S rows.

Parameters: ``rows_per_call``, ``planted_share``, ``trace_seconds``.
"""
from __future__ import annotations

import numpy as np

import gen
import work
from harness import check_answers, collection, now, profile, span
from reference import Reference

POOL_CALLS = 8    # distinct R batches made in set-up; calls cycle over them
WARM_CALLS = 4    # calls before the window (compiles, S upload, caches)
CHECK_ROWS = 1024  # R rows compared with the reference, half of them planted


def run(cfg, mix, seed, seconds, tracer, compiles, control, log, on_window):
    import repro

    U, t = int(cfg["universe"]), str(cfg["threshold"])
    n_rows = int(mix["rows_per_call"])
    s, r_all = gen.disjoint(profile(cfg), int(cfg["corpus_sets"]),
                            POOL_CALLS * n_rows, gen.rng_for(seed, 1))
    S = collection(s, U)
    s_sizes = np.diff(s[1])
    pool = []
    for i in range(POOL_CALLS):
        r = gen.take(r_all, np.arange(i * n_rows, (i + 1) * n_rows))
        pool.append(gen.plant(r, s, U, float(mix["planted_share"]),
                              gen.rng_for(seed, 3, i)))
    for i in range(WARM_CALLS):
        res = repro.join(collection(pool[i % len(pool)][0], U), S, float(t))
    log(f"plan method={res.plan.method} decided={res.plan.decided} "
        f"pairs={len(res)} r_rows={n_rows} s_sets={len(S)} "
        f"s_mean_len={float(s_sizes.mean())} "
        f"r_mean_len={float(np.diff(r_all[1]).mean())}")

    calls, durations, least, traced_calls = [], [], 0, 0
    setup = on_window()
    compiles.on = True
    tracer.start()
    t0 = now()
    while now() - t0 < seconds:
        traced = tracer.active
        k = len(calls)
        r, planted = pool[k % len(pool)]
        with span("bench.r_prep"):
            R = collection(r, U)
        tc = now()
        with span("bench.join_call"):
            res = repro.join(R, S, float(t))
        durations.append(now() - tc)
        calls.append((k % len(pool), res.pairs))
        if traced:
            traced_calls += 1
            least += work.join_bytes(np.diff(r[1]), s_sizes, U, t, len(res))
        t0 += tracer.poll()
    window = now() - t0
    compiles.on = False
    tracer.stop()
    rec = {"setup_s": setup, "window_s": window,
           "attempted": n_rows * len(calls), "failed": 0,
           "calls": len(calls), "compiles": compiles.count,
           "least_bytes": {"bench.join_call": least},
           "end_to_end": {"join_rows_per_s": n_rows * len(calls) / window}}
    log(f"window calls={len(calls)} rows={rec['attempted']} "
        f"seconds={window} compiles={compiles.count} "
        f"gc_pause_max_s={compiles.gc_pause_max_s} "
        f"slowest_call_s={max(durations, default=0.0)} "
        f"traced_calls={traced_calls}")

    def check():
        # a seeded sample: half planted rows, half others, over all calls
        rng = gen.rng_for(seed, 5)
        half = CHECK_ROWS // 2
        planted, others = [], []
        for k, (p, _) in enumerate(calls):
            flag = np.zeros(n_rows, bool)
            flag[pool[p][1]] = True
            planted += [(k, int(i)) for i in np.flatnonzero(flag)]
            others += [(k, int(i)) for i in np.flatnonzero(~flag)]
        take = [group[j] for group in (planted, others)
                for j in rng.choice(len(group), min(half, len(group)),
                                    replace=False)]
        rows_of: dict = {}
        for k, i in take:
            rows_of.setdefault(k, set()).add(i)
        answers: dict = {}
        for k, rows in rows_of.items():
            for a, b in calls[k][1]:
                if a in rows:
                    answers.setdefault((k, a), set()).add(b)
        asked = [gen.row(pool[calls[k][0]][0], i) for k, i in take]
        got = [answers.get(key, set()) for key in take]
        return check_answers(Reference(s, U, t), asked, got, control)

    rec["check"] = check
    return rec
