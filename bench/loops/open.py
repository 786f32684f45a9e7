"""Open loop: dedup requests arrive on a seeded schedule and one thread
serves them through ``DedupServeEngine``.

The schedule is a Poisson process at ``rate_per_s`` conditioned on its
count, so every run offers the same number of requests. The thread
submits what is due, calls ``step()`` while the queue holds work, and
sleeps to the next due time otherwise. A latency runs from the
request's due time to the end of the ``step()`` that answered it. The corpus and the requests are a seeded disjoint
split of one collection of the profile; a share of the requests are
planted near-duplicates of corpus sets.

Parameters: ``rate_per_s``, ``planted_share``, ``trace_seconds``.
"""
from __future__ import annotations

import time

import numpy as np

import gen
import work
from harness import check_answers, collection, now, profile, span
from reference import Reference


def _lane_ladder(grain: int, longest: int) -> list[int]:
    lanes, lane = [], grain
    while True:
        lanes.append(lane)
        if lane >= longest:
            return lanes
        lane <<= 1


def run(cfg, mix, seed, seconds, tracer, compiles, control, log, on_window):
    from repro.core.config import global_config
    from repro.serve.dedup import DedupServeEngine

    U, t = int(cfg["universe"]), str(cfg["threshold"])
    n_req = int(round(float(mix["rate_per_s"]) * seconds))
    s, q = gen.disjoint(profile(cfg), int(cfg["corpus_sets"]), n_req,
                        gen.rng_for(seed, 1))
    s_sizes = np.diff(s[1])
    q, _ = gen.plant(q, s, U, float(mix["planted_share"]),
                     gen.rng_for(seed, 3))
    due = np.sort(gen.rng_for(seed, 4).uniform(0.0, seconds, n_req))
    reqs = [gen.row(q, i) for i in range(n_req)]
    engine = DedupServeEngine(collection(s, U), threshold=float(t),
                              admit="none")
    # warm-up: one full micro-batch per lane width the traffic can reach,
    # with copies of corpus sets so the pair compaction runs, and one of
    # random sets, which finds no pair
    rng = gen.rng_for(0, 6)
    grain = int(global_config.serve_lane_grain)
    for _ in range(engine.micro_batch):
        engine.submit(rng.choice(U, grain, replace=False))
    engine.step()
    longest = max(int(s_sizes.max()), max(map(len, reqs), default=1))
    for lane in _lane_ladder(grain, longest):
        ln = min(lane, longest)
        fit = np.flatnonzero(s_sizes <= ln)
        engine.submit(rng.choice(U, ln, replace=False))
        for _ in range(engine.micro_batch - 1):
            engine.submit(gen.row(s, int(rng.choice(fit))) if len(fit)
                          else rng.choice(U, ln, replace=False))
        engine.step()
    engine.results()
    base = dict(engine.stats)
    log(f"corpus s_sets={len(s_sizes)} s_mean_len={float(s_sizes.mean())} "
        f"req_mean_len={float(np.mean([len(r) for r in reqs]))} "
        f"longest={longest}")

    emitted = np.full(n_req, np.nan)
    started = np.full(n_req, np.nan)
    submitted = np.full(n_req, np.nan)
    answers: list = [None] * n_req
    fills, least, n_steps, traced_steps, slowest = [], 0, 0, 0, 0.0
    rid_of: dict = {}
    setup = on_window()
    compiles.on = True
    tracer.start()
    t0 = now()
    i = 0
    while i < n_req or engine.queue_depth:
        if i < n_req and due[i] <= now() - t0:
            with span("bench.r_prep"):
                while i < n_req and due[i] <= now() - t0:
                    rid_of[engine.submit(reqs[i])] = i
                    submitted[i] = now() - t0
                    i += 1
        if engine.queue_depth:
            traced = tracer.active
            ts = now() - t0
            with span("bench.serve_step"):
                out = engine.step()
            te = now() - t0
            n_steps += 1
            slowest = max(slowest, te - ts)
            rows = [rid_of[res.rid] for res in out]
            for k, res in zip(rows, out):
                emitted[k], started[k] = te, ts
                answers[k] = set(int(x) for x in res.matches)
            if out:
                fills.append(out[0].stats["batch_fill"])
            if traced:
                traced_steps += 1
                least += work.join_bytes([len(reqs[k]) for k in rows],
                                         s_sizes, U, t,
                                         sum(len(answers[k]) for k in rows))
        elif i < n_req:
            with span("bench.wait_arrival"):
                time.sleep(max(0.0, due[i] - (now() - t0)))
        t0 += tracer.poll()
    window = now() - t0
    compiles.on = False
    tracer.stop()
    lat = (emitted - due) * 1e3
    late = (submitted - due) * 1e3
    rec = {"setup_s": setup, "window_s": window, "attempted": n_req,
           "failed": int(np.isnan(emitted).sum()), "steps": n_steps,
           "compiles": compiles.count,
           "least_bytes": {"bench.serve_step": least},
           "queue_wait_ms": (started - due) * 1e3,
           "batch_fill": fills,
           "walk_steps": engine.stats["walk_steps"] - base["walk_steps"],
           "requests": engine.stats["requests"] - base["requests"],
           "end_to_end": {"serve_p99_ms": float(np.nanpercentile(lat, 99)),
                          "serve_p50_ms": float(np.nanpercentile(lat, 50))}}
    log(f"window requests={n_req} steps={n_steps} seconds={window} "
        f"compiles={compiles.count} slowest_step_s={slowest} "
        f"gc_pause_max_s={compiles.gc_pause_max_s} "
        f"traced_steps={traced_steps} "
        f"rate_per_s={mix['rate_per_s']} generator_late_p99_ms="
        f"{float(np.nanpercentile(late, 99))} generator_late_max_ms="
        f"{float(np.nanmax(late))} walk_impl={engine.stats['walk_impl']}")

    def check():
        got = [a if a is not None else set() for a in answers]
        return check_answers(Reference(s, U, t), reqs, got, control)

    rec["check"] = check
    return rec
