#!/usr/bin/env python3
"""Run ``repro.join`` and the dedup service once on a TPU chip.

    python chip_smoke.py              # one chip: bitmap, lfvt and serve phases
    python chip_smoke.py --chips 4    # only the four-chip mesh phase

The data are the repo's Table-1 profiles (``repro.data.synth.DATASETS``
at scale 1.0), made from a seed. A seeded tenth of each R collection is
replaced by near-duplicates of S rows (a copy, minus one element for sets
of five or more), so every phase has pairs to find at t = 0.8.

Each join prints one line: the method asked for, the method the plan
ran, the walk implementation that ran, the pair count, walk steps and
host wall time. The wall time includes compilation and data upload; it
is not a device measurement. Every method of a phase must return the
same pair set, and that set must equal the host oracle
``brute_force_join`` on a seeded sample of R rows (half of them planted
near-duplicates). Any mismatch or exception exits non-zero.

The script checks the device before anything else: without a TPU it
exits non-zero and runs no join. The last line, printed only when every
phase passed, is one JSON object naming the device as JAX reports it.

JAX's persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, or else to ``.jax_cache`` beside this file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
THRESHOLD = 0.8
SEED = 0
PLANTED_FRACTION = 0.1
ORACLE_ROWS = 256  # also the number of requests the serve phase sends


def device_or_exit(chips: int) -> dict:
    """The device JAX found; exits non-zero unless it is ``chips`` TPUs."""
    import jax
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    print(f"device platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        sys.exit("chip_smoke: no TPU found; no join was run")
    if dev["count"] < chips:
        sys.exit(f"chip_smoke: {chips} chips asked for, {dev['count']} found")
    return dev


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---------------------------------------------------------------------- #
# data
# ---------------------------------------------------------------------- #
def profile(name: str, scale: float = 1.0):
    """(R, S, oracle sample rows, the oracle's pairs on them) for one
    profile."""
    from repro.core.sets import SetCollection
    from repro.data.synth import make_join_dataset

    R, S = make_join_dataset(name, scale=scale, seed=SEED)
    rng = np.random.default_rng(SEED)
    n_plant = max(int(len(R) * PLANTED_FRACTION), 1)
    planted = np.sort(rng.choice(len(R), n_plant, replace=False))
    sources = rng.choice(len(S), n_plant, replace=False)
    r_sets = list(R.sets)
    for i, j in zip(planted, sources):
        s = S.sets[j]
        r_sets[i] = (np.delete(s, rng.integers(len(s))) if len(s) >= 5
                     else s.copy())
    R = SetCollection.from_ragged(r_sets, universe=R.universe)
    others = np.setdiff1d(np.arange(len(R)), planted)
    half = ORACLE_ROWS // 2
    sample = np.sort(np.concatenate([
        rng.choice(planted, min(half, len(planted)), replace=False),
        rng.choice(others, min(half, len(others)), replace=False)]))
    print(f"data {name}: |R|={len(R)} |S|={len(S)} "
          f"sum|S|={int(S.sizes().sum())} U={S.universe} "
          f"max|S|={int(S.sizes().max())} planted={n_plant}", flush=True)
    return R, S, sample, oracle_pairs(R, S, sample)


def oracle_pairs(R, S, rows) -> set:
    """``brute_force_join`` of the sampled R rows (original ids kept)."""
    from repro.core.join import brute_force_join
    from repro.core.sets import SetCollection

    sub = SetCollection([R.sets[int(i)] for i in rows], R.universe,
                        R.ids[rows].astype(np.int32))
    return brute_force_join(sub, S, THRESHOLD)


def check(name: str, results: dict, R, rows, want: set) -> None:
    """Every method gave one pair set, equal to the sampled oracle."""
    methods = list(results)
    first = results[methods[0]]
    for m in methods[1:]:
        if results[m] != first:
            raise AssertionError(
                f"{name}: {m} gave {len(results[m])} pairs, {methods[0]} "
                f"gave {len(first)}; they differ in "
                f"{len(results[m] ^ first)}")
    ids = {int(i) for i in R.ids[rows]}
    got = {p for p in first if p[0] in ids}
    if got != want:
        raise AssertionError(
            f"{name}: {len(got)} pairs on {len(rows)} sampled rows, the "
            f"oracle has {len(want)}; they differ in {len(got ^ want)}")
    if not want:
        raise AssertionError(f"{name}: the sampled oracle found no pairs")
    print(f"check {name}: {len(methods)} methods agree on {len(first)} "
          f"pairs; sampled oracle {len(want)} pairs on {len(rows)} rows "
          "equal", flush=True)


def _field(value) -> str:
    return "-" if value is None else str(value)


def report(phase: str, method: str, plan: str, stats, n_pairs: int,
           wall: float) -> None:
    print(f"phase={phase} method={method} plan={plan} "
          f"walk_impl={_field(stats.get('walk_impl'))} pairs={n_pairs} "
          f"walk_steps={_field(stats.get('walk_steps'))} "
          f"host_wall_s_incl_compile={wall}", flush=True)


# ---------------------------------------------------------------------- #
# phases
# ---------------------------------------------------------------------- #
def join_phase(phase: str, R, S, rows, want, methods, **kw) -> None:
    """``repro.join`` once per method, then the agreement check."""
    import repro

    results = {}
    for method in methods:
        t0 = time.perf_counter()
        res = repro.join(R, S, THRESHOLD, method=method, **kw)
        wall = time.perf_counter() - t0
        report(phase, method, res.plan.method, res.stats, len(res), wall)
        results[method] = set(res.pairs)
    check(phase, results, R, rows, want)


def serve_phase(R, S, rows, want) -> None:
    """A ``DedupServeEngine`` over S answers the sampled R rows."""
    from repro.serve.dedup import DedupServeEngine

    t0 = time.perf_counter()
    engine = DedupServeEngine(S, threshold=THRESHOLD, admit="none")
    rids = [engine.submit(R.sets[int(i)]) for i in rows]
    served = engine.drain()
    wall = time.perf_counter() - t0
    if sorted(r.rid for r in served) != rids:
        raise AssertionError(
            f"serve: {len(served)} results for {len(rids)} requests")
    row_of = dict(zip(rids, rows))
    pairs = {(int(R.ids[row_of[r.rid]]), int(s))
             for r in served for s in r.matches}
    report("serve", "DedupServeEngine", "lfvt", engine.stats, len(pairs),
           wall)
    check("serve", {"DedupServeEngine": pairs}, R, rows, want)


def mesh_phase(R, S, rows, want, n_chips: int) -> None:
    """The MapReduce join on a ``n_chips``-device mesh against the loop
    path and the oracle, with the reduce spread over every chip."""
    import jax
    import repro

    mesh = jax.make_mesh((n_chips,), ("data",))
    results = {}
    for method in ("lfvt", "popcount"):
        for label, kw in (("mesh", {"mesh": mesh}), ("loop", {})):
            t0 = time.perf_counter()
            res = repro.join(R, S, THRESHOLD, method=method,
                             n_shards=n_chips, **kw)
            wall = time.perf_counter() - t0
            report(f"mesh{n_chips}", f"{method}/{label}", res.plan.method,
                   res.stats, len(res), wall)
            if label == "mesh":
                st = res.stats
                print(f"mesh{n_chips} {method}: mesh_devices="
                      f"{st['mesh_devices']} reduce_devices="
                      f"{st['reduce_devices']}", flush=True)
                if (st["mesh_devices"] != n_chips
                        or st["reduce_devices"] != n_chips):
                    raise AssertionError(
                        f"mesh {method}: the reduce ran on "
                        f"{st['reduce_devices']} of {n_chips} chips")
            results[f"{method}/{label}"] = set(res.pairs)
    check(f"mesh{n_chips}", results, R, rows, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip mesh phase")
    args = ap.parse_args(argv)
    device = device_or_exit(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    print(f"compile cache: {use_compile_cache()}", flush=True)

    if args.chips == 4:
        mesh_phase(*profile("dblp"), 4)
    else:
        join_phase("bitmap/enron", *profile("enron"),
                   ("auto", "popcount", "kernel_bitmap"))
        dblp = profile("dblp")
        join_phase("lfvt/dblp", *dblp, ("auto", "lfvt"))
        serve_phase(*dblp)
        join_phase("lfvt/orkut", *profile("orkut"), ("auto", "lfvt"))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
