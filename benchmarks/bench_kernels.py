"""Join-kernel microbench: CPU wall time of the XLA-compiled device paths
(popcount vs one-hot) + analytic TPU roofline per kernel variant.

Pallas interpret mode is a correctness harness, not a timing one; on this
CPU container the *compiled* jnp twins of the kernels are what we time.
The TPU projection uses per-tile byte/flop counts of each kernel design
(DESIGN.md §5): popcount moves 16x fewer HBM bytes, one-hot rides the MXU.

The roofline now includes the *output traffic* term (DESIGN.md §6): the
dense path writes+ships the O(m·n) boolean mask, the sparse path ships
per-tile counts + packed (r, s) pairs — bytes proportional to the result.
Both are reported, alongside measured result density and the host↔device
bytes each emission mode moves on this container.

``--method lfvt`` (or ``all``) adds the §9 method axis: a
bitmap-vs-onehot-vs-lfvt memory/time comparison on synthetic datasets
including a large-universe case (W >= 2^16 words) where the flat-LFVT
walk's S-side bytes scale with Σ|seq| (sparse entry table, never O(U))
while the bitmap path's dense (mb, n, W) popcount intermediate is
infeasible at the default block size.

``--impl kernel|ref|all`` (with ``--method lfvt``) picks the walk
execution layer: ``kernel`` is the ISSUE-5 live row-tiled walk
(``method='lfvt'`` — its compiled jnp twin on every backend,
DESIGN.md §10) with walk_steps/early_stops stats and the
``kernel_vs_ref_walk_ratio`` the CI regression gate tracks; ``ref`` is
the PR-4 whole-block jnp walk (``method='lfvt_ref'``).

CLI: ``python -m benchmarks.bench_kernels [--measure ...] [--method
bitmap onehot lfvt | all] [--impl kernel ref | all] [--smoke]
[--out F.json] [--append]`` — ``--out`` writes the consolidated
``{config, method, impl, metrics}`` row artifact (BENCH_pr8.json);
``--append`` extends an existing artifact (one file across benches).
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.join import brute_force_join
from repro.core.sets import SetCollection
from repro.core.tile_join import (_compact_mask, _onehot_qualify,
                                  _popcount_qualify, cf_rs_join_device,
                                  popcount_row_block, round_capacity,
                                  window_bounds)
from repro.data.synth import make_join_dataset
from repro.launch.analysis import HBM_BW, PEAK_FLOPS

from .common import bench_row, emit, timed, write_bench_json

T = 0.5

# feasibility budget for the dense popcount intermediate on this container
INTERMEDIATE_BUDGET = 1 << 30


def _prep(R, S, measure="jaccard"):
    Ss = S.sort_by_size()
    universe = max(R.universe, S.universe)
    W = (universe + 31) // 32
    lo, hi = window_bounds(R.sizes(), Ss.sizes(), T, measure)
    return (jnp.asarray(R.bitmaps(W)), jnp.asarray(R.sizes()),
            jnp.asarray(Ss.bitmaps(W)), jnp.asarray(Ss.sizes()),
            jnp.asarray(lo), jnp.asarray(hi), universe, Ss)


def tpu_projection(m, n, universe, skip_frac=0.0, pairs=None):
    """Roofline seconds per R-S block for each kernel design.

    With ``pairs`` given, output traffic models the sparse emission path
    as implemented (DESIGN.md §6): the live-tiled kernel still writes its
    per-tile bool masks to HBM and the on-device compaction re-reads
    them (2x the live region), plus the per-tile counts and the packed
    pair array that actually cross the host boundary. Without ``pairs``,
    the dense (m, n) bool mask write + host transfer.
    """
    W = (universe + 31) // 32
    live = 1.0 - skip_frac
    n_tiles = max(int(np.ceil(m / 256) * np.ceil(n / 256)), 1)
    if pairs is None:
        out_bytes = m * n                    # dense bool mask
    else:
        staged = 2 * live * m * n            # HBM-staged masks, write+read
        out_bytes = int(staged) + 8 * round_capacity(pairs) + 4 * int(
            live * n_tiles)
    in_bytes = (m * W + n * W) * 4
    # popcount: VPU ops ~ 2 per word-pair on live tiles
    pop_ops = 2.0 * m * n * W * live
    # one-hot: same bitmap bytes in; MXU flops = 2*m*n*(32W)
    oh_flops = 2.0 * m * n * (32 * W) * live
    return {
        "popcount_s": max((in_bytes + out_bytes) / HBM_BW,
                          pop_ops / (PEAK_FLOPS / 64)),
        "onehot_s": max((in_bytes + out_bytes) / HBM_BW,
                        oh_flops / PEAK_FLOPS),
        "out_bytes": out_bytes,
    }


def main(measures=("jaccard",)) -> dict:
    """Kernel microbench; ``measures`` adds a similarity-measure axis
    (per-measure windows change the skip fraction, the predicate itself
    is a handful of int32 VPU ops either way)."""
    out = {}
    for ds, measure in itertools.product(("dblp", "enron"), measures):
        R, S = make_join_dataset(ds, scale=0.04, seed=6)
        tag = ds if measure == "jaccard" else f"{ds}/{measure}"
        r_bm, r_sz, s_bm, s_sz, lo, hi, universe, Ss = _prep(R, S, measure)
        m, n = r_bm.shape[0], s_bm.shape[0]

        def pop():
            return _popcount_qualify(r_bm, r_sz, s_bm, s_sz, lo, hi, t=T,
                                     measure=measure).block_until_ready()

        pop()  # compile
        mask, t_pop = timed(pop, repeat=3)

        r_pad, _ = R.padded()
        s_pad, _ = Ss.padded()
        r_pad, s_pad = jnp.asarray(r_pad), jnp.asarray(s_pad)

        def oh():
            return _onehot_qualify(r_pad, r_sz, s_pad, s_sz, lo, hi, t=T,
                                   universe=universe, measure=measure
                                   ).block_until_ready()

        oh()
        _, t_oh = timed(oh, repeat=3)

        # sparse emission: count + on-device compaction + packed transfer
        n_pairs = int(_compact_mask(mask, size=0)[1])
        cap = round_capacity(n_pairs)

        def compact():
            if not cap:
                return np.zeros((0, 2), np.int32)
            return np.asarray(_compact_mask(mask, size=cap)[0])

        compact()  # compile
        _, t_compact = timed(compact, repeat=3)

        def dense_xfer():
            return np.asarray(mask)

        _, t_dense = timed(dense_xfer, repeat=3)

        density = n_pairs / max(m * n, 1)
        sparse_bytes = cap * 8 + 4
        dense_bytes = m * n

        # tile-skip fraction from the windows
        cols = np.arange(n)
        in_win = ((cols[None, :] >= np.asarray(lo)[:, None])
                  & (cols[None, :] < np.asarray(hi)[:, None]))
        skip = 1.0 - in_win.mean()
        proj_dense = tpu_projection(m, n, universe, skip)
        proj_sparse = tpu_projection(m, n, universe, skip, pairs=n_pairs)
        emit(f"kernel/{tag}/popcount_cpu", t_pop,
             f"tpu_proj_us={proj_dense['popcount_s']*1e6:.1f};skip={skip:.2f}")
        emit(f"kernel/{tag}/onehot_cpu", t_oh,
             f"tpu_proj_us={proj_dense['onehot_s']*1e6:.1f}")
        emit(f"kernel/{tag}/emit_sparse", t_compact,
             f"pairs={n_pairs};density={density:.2e}"
             f";bytes={sparse_bytes};tpu_proj_us="
             f"{proj_sparse['popcount_s']*1e6:.1f}")
        emit(f"kernel/{tag}/emit_dense", t_dense,
             f"bytes={dense_bytes};tpu_proj_us="
             f"{proj_dense['popcount_s']*1e6:.1f}")
        out[tag] = {
            "pop": t_pop, "oh": t_oh,
            "emit_sparse_s": t_compact, "emit_dense_s": t_dense,
            "result_pairs": n_pairs, "result_density": density,
            "output_bytes_sparse": sparse_bytes,
            "output_bytes_dense": dense_bytes,
            "popcount_s": proj_dense["popcount_s"],
            "onehot_s": proj_dense["onehot_s"],
            "popcount_sparse_s": proj_sparse["popcount_s"],
            "onehot_sparse_s": proj_sparse["onehot_s"],
        }
    return out


# ---------------------------------------------------------------------- #
# §9 method axis: bitmap vs one-hot vs flat-LFVT, memory + time
# ---------------------------------------------------------------------- #
def _zipf_collection(n: int, universe: int, mean_len: int,
                     rng: np.random.Generator) -> SetCollection:
    """Zipf(1.3) element popularity over an arbitrary universe: popular
    elements appear in most sets (deep shared LFVT chains), the tail
    exercises the sparse entry table."""
    sizes = np.clip(rng.poisson(mean_len, n), 1, max(universe // 2, 1))
    sets = [np.minimum(rng.zipf(1.3, size=int(s)).astype(np.int64) - 1,
                       universe - 1).astype(np.int32)
            for s in sizes]
    return SetCollection.from_ragged(sets, universe=universe)


def _perturbed_from(S: SetCollection, rng: np.random.Generator,
                    mean_len: int, frac: float = 0.3) -> SetCollection:
    """R side for the method axis: ``frac`` of the rows are near-copies
    of an S set (one element dropped), the rest fresh draws — so the
    join has real qualifying pairs at T instead of an empty result."""
    sets = []
    for i in range(len(S)):
        base = S.sets[i]
        if rng.random() < frac and len(base) > 1:
            sets.append(np.delete(base, rng.integers(len(base))))
        else:
            size = int(np.clip(rng.poisson(mean_len), 1, S.universe // 2))
            sets.append(np.minimum(
                rng.zipf(1.3, size=size).astype(np.int64) - 1,
                S.universe - 1).astype(np.int32))
    return SetCollection.from_ragged(sets, universe=S.universe)


def _popcount_intermediate_bytes(m: int, n: int, W: int, r_block: int) -> int:
    """Dense (mb, n, W) uint32 the popcount path stages per R block (the
    row-block inner intermediate of ``popcount_counts``, via the shared
    ``popcount_row_block`` so the model can't drift from the kernel)."""
    return popcount_row_block(min(m, r_block), n) * n * W * 4


def method_axis_sweep(smoke: bool = False,
                      impls=("kernel", "ref")) -> list:
    """bitmap-vs-onehot-vs-lfvt memory/time axis (DESIGN.md §9-§10).

    Two synthetic workloads: a mid-sized universe where every method runs
    (times + parity), and a large universe (W >= 2^16 words, i.e.
    >= 2^21 elements) where the bitmap sheet is |S|·W-shaped while the
    flat LFVT ships Σ|seq| tuples + O(U) entry rows. The bitmap path is
    measured there only at the reduced r_block that fits the
    intermediate budget — at the default block it is infeasible.

    ``impls`` picks the lfvt walk execution layers to time ('kernel' —
    the live row-tiled walk kernel — and/or 'ref' — the PR-4 whole-block
    jnp walk); when both run, the kernel row records
    ``kernel_vs_ref_walk_ratio`` (kernel seconds / ref seconds, < 1
    means the kernel wins), the gate-tracked metric.

    Returns consolidated-artifact rows (``common.bench_row``); smoke
    configs are suffixed ``[smoke]`` so the CI gate never diffs a smoke
    run against full-run baselines.
    """
    rows: list = []
    suffix = "[smoke]" if smoke else ""
    cases = [
        ("midW", 1 << 13, 64 if smoke else 320, 24),
        ("largeW", 1 << 21, 48 if smoke else 192, 32),
    ]
    for name, universe, n_sets, mean_len in cases:
        cfg = f"method_axis/{name}{suffix}"
        rng = np.random.default_rng(17)
        S = _zipf_collection(n_sets, universe, mean_len, rng)
        R = _perturbed_from(S, rng, mean_len)
        W = max((universe + 31) // 32, 1)
        m, n = len(R), len(S)
        oracle = brute_force_join(R, S, T)
        base = {"universe": universe, "w_words": W, "m": m, "n": n,
                "result_pairs": len(oracle)}

        # --- flat LFVT: always runs; S-side bytes ~ Σ|seq| + O(U) ----- #
        flat = S.sort_by_size().flat_lfvt()
        shared = {
            "seq_tuple_bytes": int(flat.seq_row.nbytes),
            "total_seq_tuples": len(flat.seq_row),
            "entry_rows": len(flat.entry_elem),
            "entry_table_bytes": int(flat.entry_elem.nbytes * 4),
        }
        method_of = {"kernel": "lfvt", "ref": "lfvt_ref"}
        stats_of: dict = {}
        for impl in impls:  # compile + parity before any clock starts
            lstats: dict = {}
            got = cf_rs_join_device(R, S, T, method=method_of[impl],
                                    stats=lstats)
            assert got == oracle, f"lfvt[{impl}] parity failed on {name}"
            stats_of[impl] = lstats
        # interleaved rounds: both impls see the same machine conditions,
        # so the kernel_vs_ref ratio is a paired comparison, not two
        # wall-clock phases a noisy runner can skew independently
        runs: dict = {impl: [] for impl in impls}
        for _ in range(5):
            for impl in impls:
                _, dt = timed(lambda m=method_of[impl]:
                              cf_rs_join_device(R, S, T, method=m))
                runs[impl].append(dt)
        times = {impl: min(rs) for impl, rs in runs.items()}
        for impl in impls:
            t_impl, lstats = times[impl], stats_of[impl]
            metrics = dict(base, seconds=t_impl, **shared,
                           s_rep_bytes=lstats["s_flat_bytes"],
                           s_flat_bytes=lstats["s_flat_bytes"],
                           s_bitmap_bytes_equiv=lstats[
                               "s_bitmap_bytes_equiv"])
            if impl == "kernel":
                metrics.update(
                    walk_steps=lstats["walk_steps"],
                    early_stops=lstats["early_stops"],
                    live_tiles=lstats["live_tiles"],
                    total_tiles=lstats["total_tiles"],
                    # lockstep upper bound the early exits undercut
                    walk_steps_bound=lstats["total_tiles"]
                    * flat.max_seq_len)
            rows.append(bench_row(cfg, "lfvt", impl, metrics))
            emit(f"method_axis/{name}/lfvt[{impl}]", t_impl,
                 f"s_rep_bytes={lstats['s_flat_bytes']}"
                 f";bitmap_equiv={lstats['s_bitmap_bytes_equiv']}"
                 f";pairs={len(got)}"
                 + (f";walk_steps={lstats['walk_steps']}"
                    f";early_stops={lstats['early_stops']}"
                    if impl == "kernel" else ""))
        if "kernel" in times and "ref" in times:
            # the ratio lands on the kernel row once both impls have run
            for r in rows:
                if (r["config"], r["method"], r["impl"]) == (
                        cfg, "lfvt", "kernel"):
                    r["metrics"]["kernel_vs_ref_walk_ratio"] = (
                        times["kernel"] / max(times["ref"], 1e-9))
            emit(f"method_axis/{name}/kernel_vs_ref", 0.0,
                 f"ratio={times['kernel'] / max(times['ref'], 1e-9):.3f}")
        t_lfvt = times.get("kernel", times.get("ref", 0.0))

        # --- bitmap popcount: feasibility-gated ----------------------- #
        s_bitmap_bytes = n * W * 4
        inter_default = _popcount_intermediate_bytes(m, n, W, 1024)
        feasible_default = inter_default <= INTERMEDIATE_BUDGET
        bm: dict = dict(base, s_rep_bytes=s_bitmap_bytes,
                        intermediate_bytes_default=inter_default,
                        feasible_at_default_block=feasible_default)
        # shrink r_block until the staged intermediate fits the budget
        r_block = 1024
        while (_popcount_intermediate_bytes(m, n, W, r_block)
               > INTERMEDIATE_BUDGET and r_block > 1):
            r_block //= 2
        bm["r_block_used"] = r_block
        if smoke and name == "largeW":
            # CI smoke never times the large-universe popcount: even a
            # budget-fitting block stages hundreds of MB of (mb, n, W)
            # intermediates on the runner — report the analytics only
            bm["seconds"] = None
            emit(f"method_axis/{name}/popcount", 0.0,
                 f"smoke_skip;inter_bytes_default={inter_default}"
                 f";feasible_default={feasible_default}")
        else:
            cf_rs_join_device(R, S, T, method="popcount", r_block=r_block)
            got_b, t_bm = timed(
                lambda: cf_rs_join_device(R, S, T, method="popcount",
                                          r_block=r_block),
                repeat=1 if name == "largeW" else 2)
            assert got_b == oracle, f"popcount parity failed on {name}"
            bm["seconds"] = t_bm
            bm["slowdown_vs_lfvt"] = t_bm / max(t_lfvt, 1e-9)
            emit(f"method_axis/{name}/popcount", t_bm,
                 f"s_rep_bytes={s_bitmap_bytes};r_block={r_block}"
                 f";feasible_default={feasible_default}")
        rows.append(bench_row(cfg, "bitmap", "jnp", bm))

        # --- one-hot MXU formulation: universe-scan gated ------------- #
        oh_blocks = -(-universe // 512)
        if name == "largeW":
            rows.append(bench_row(cfg, "onehot", "jnp", dict(
                base, seconds=None,
                skipped=f"scan over {oh_blocks} universe blocks",
                s_rep_bytes=s_bitmap_bytes)))
        else:
            cf_rs_join_device(R, S, T, method="onehot")
            got_o, t_oh = timed(
                lambda: cf_rs_join_device(R, S, T, method="onehot"),
                repeat=2)
            assert got_o == oracle, f"onehot parity failed on {name}"
            rows.append(bench_row(cfg, "onehot", "jnp", dict(
                base, seconds=t_oh, s_rep_bytes=s_bitmap_bytes)))
            emit(f"method_axis/{name}/onehot", t_oh,
                 f"s_rep_bytes={s_bitmap_bytes}")
    return rows


if __name__ == "__main__":
    import argparse

    from repro.core.measures import measure_names

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--measure", nargs="+", default=["jaccard"],
                    choices=list(measure_names()) + ["all"],
                    help="similarity-measure axis (or 'all')")
    ap.add_argument("--method", nargs="+", default=["bitmap", "onehot"],
                    choices=["bitmap", "onehot", "lfvt", "all"],
                    help="join-method axis; 'lfvt' adds the §9-§10 "
                         "bitmap-vs-onehot-vs-lfvt memory/time sweep")
    ap.add_argument("--impl", nargs="+", default=["kernel", "ref"],
                    choices=["kernel", "ref", "all"],
                    help="lfvt walk execution layer(s): the live "
                         "row-tiled walk kernel vs the PR-4 jnp walk")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sweep for CI (skips the infeasible cells)")
    ap.add_argument("--out", default=None,
                    help="write the consolidated row artifact here")
    ap.add_argument("--append", action="store_true",
                    help="extend an existing --out artifact instead of "
                         "overwriting (one BENCH json across benches)")
    args = ap.parse_args()
    ms = measure_names() if "all" in args.measure else tuple(args.measure)
    methods = ({"bitmap", "onehot", "lfvt"} if "all" in args.method
               else set(args.method))
    impls = (("kernel", "ref") if "all" in args.impl
             else tuple(args.impl))
    rows: list = []
    if methods & {"bitmap", "onehot"}:
        for tag, metrics in main(measures=ms).items():
            rows.append(bench_row(f"kernel/{tag}", "microbench", "jnp",
                                  metrics))
    if "lfvt" in methods:
        rows.extend(method_axis_sweep(smoke=args.smoke, impls=impls))
    if args.out:
        write_bench_json(args.out, rows, append=args.append)
