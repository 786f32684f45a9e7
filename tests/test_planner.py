"""Cost-model method dispatch (ISSUE 10): planner, kwarg lattice, stats.

  * differential: ``method='auto'`` is bit-identical (pairs and mask) to
    every forced method on both drivers, all four measures, thresholds
    including the exact 2/3 boundary, and the MR loop path — plus a
    4-forced-device mesh subprocess parity run;
  * monotonicity: the model flips bitmap -> lfvt as the universe grows
    (bitmap cost is linear in W, walk cost U-independent) and the
    crossover lands inside the BENCH-pinned 2^13..2^21 bracket;
  * the kwarg lattice: historical error texts preserved verbatim, and
    the combinations the drivers used to accept silently now raise the
    named :class:`PlannerError` — each pinned here;
  * calibration: per-family rescale from row-schema BENCH artifacts,
    clamping, null-seconds and legacy-schema skips, config pinning;
  * ``JoinPlan``/``JoinStats``: round trip + byte-compatible views.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import planner as P
from repro.core.config import global_config
from repro.core.distributed import mr_cf_rs_join
from repro.core.join import brute_force_join
from repro.core.measures import measure_names
from repro.core.partition import load_aware_partition
from repro.core.planner import (AUTO_CANDIDATES, DEFAULT_COEFFS, JoinPlan,
                                JoinStats, PlannerError, build_plan,
                                choose_method, load_calibration,
                                plan_shard_methods, probe_features,
                                score_methods, validate_join_args)
from repro.core.sets import SetCollection
from repro.core.tile_join import cf_rs_join_device

MEASURES = measure_names()


def random_collections(seed, m=14, n=16, universe=64, max_size=12):
    rng = np.random.default_rng(seed)

    def draw(k):
        sets = []
        for i in range(k):
            if rng.random() < 0.1:
                sets.append(np.zeros(0, np.int32))
            else:
                sets.append(rng.integers(0, universe,
                                         size=int(rng.integers(1, max_size))))
        return sets

    R = SetCollection.from_ragged(draw(m), universe=universe)
    S = SetCollection.from_ragged(draw(n), universe=universe)
    return R, S


def boundary_collections(universe=64):
    """Carry one exact Jaccard-2/3 pair: f=4, union=6 -> 4/6 == t."""
    rng = np.random.default_rng(3)
    sets_r = [rng.integers(0, universe, size=int(rng.integers(2, 10)))
              for _ in range(12)] + [[0, 1, 2, 3, 4]]
    sets_s = [rng.integers(0, universe, size=int(rng.integers(2, 10)))
              for _ in range(12)] + [[0, 1, 2, 3, 60]]
    return (SetCollection.from_ragged(sets_r, universe=universe),
            SetCollection.from_ragged(sets_s, universe=universe))


def heavy_collections(universe=1 << 18, seed=9):
    """Two length regimes so load-aware shards plan heterogeneously."""
    rng = np.random.default_rng(seed)
    sets_s = [np.unique(rng.integers(0, universe, size=k))
              for k in [6] * 24 + [400] * 8]
    sets_r = [np.unique(rng.integers(0, universe, size=k))
              for k in [6] * 16 + [400] * 6]
    return (SetCollection.from_ragged(sets_r, universe=universe),
            SetCollection.from_ragged(sets_s, universe=universe))


# ---------------------------------------------------------------------- #
# differential: auto == every forced method, both drivers
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("measure", MEASURES)
@pytest.mark.parametrize("t", [0.5, 2 / 3])
def test_auto_matches_forced_device(measure, t):
    R, S = boundary_collections()
    oracle = brute_force_join(R, S, t, measure=measure)
    if measure == "jaccard" and t == 2 / 3:
        assert any(a == 12 for a, _ in oracle)  # the boundary pair counts
    st_auto = {}
    auto = cf_rs_join_device(R, S, t, method="auto", measure=measure,
                             stats=st_auto)
    assert auto == oracle
    assert st_auto["plan"]["decided"] == "cost_model"
    assert st_auto["plan"]["requested"] == "auto"
    assert st_auto["method"] == st_auto["plan"]["method"]
    for forced in ("popcount", "onehot", "lfvt", "lfvt_ref"):
        st = {}
        assert cf_rs_join_device(R, S, t, method=forced, measure=measure,
                                 stats=st) == oracle, forced
        assert st["plan"]["decided"] == "forced"
        assert st["method"] == forced


@pytest.mark.parametrize("measure", MEASURES)
def test_auto_matches_forced_mr_loop(measure):
    t = 2 / 3
    R, S = boundary_collections()
    oracle = brute_force_join(R, S, t, measure=measure)
    st_auto = {}
    auto = mr_cf_rs_join(R, S, t, n_shards=3, method="auto",
                         measure=measure, stats=st_auto)
    assert auto == oracle
    assert st_auto["plan"]["requested"] == "auto"
    assert tuple(st_auto["shard_methods"]) == tuple(
        st_auto["plan"]["shard_methods"])
    assert len(st_auto["shard_methods"]) == 3
    for forced in ("popcount", "lfvt", "lfvt_ref"):
        assert mr_cf_rs_join(R, S, t, n_shards=3, method=forced,
                             measure=measure) == oracle, forced


@pytest.mark.parametrize("emit", ["pairs", "mask"])
def test_auto_heterogeneous_shards_bitwise(emit):
    """Mixed-length input: the plan assigns different families to
    different shards and the result stays bit-identical to the oracle
    and to every homogeneous forced run."""
    R, S = heavy_collections()
    t = 0.5
    oracle = brute_force_join(R, S, t)
    st = {}
    got = mr_cf_rs_join(R, S, t, n_shards=4, method="auto", emit=emit,
                        stats=st)
    assert got == oracle
    kinds = set(st["shard_methods"])
    assert kinds == {"popcount", "lfvt"}, st["shard_methods"]
    assert mr_cf_rs_join(R, S, t, n_shards=4, method="lfvt",
                         emit=emit) == oracle
    assert mr_cf_rs_join(R, S, t, n_shards=4, method="popcount",
                         emit=emit) == oracle


def test_auto_heterogeneous_resilience_ladder():
    """Planner-picked shards still run the DESIGN.md §12 ladder: a
    persistent walk fault degrades only the lfvt shards, bit-identically."""
    R, S = heavy_collections()
    oracle = brute_force_join(R, S, 0.5)
    st = {}
    got = mr_cf_rs_join(R, S, 0.5, n_shards=4, method="auto", stats=st,
                        fault_plan="walk_dispatch:persistent")
    assert got == oracle
    assert st["faults_injected"] >= 1
    assert any(d.startswith("auto_loop/lfvt/") for d in st["degradations"])


def test_auto_empty_collection_plan():
    R, S = random_collections(1)
    empty = SetCollection.from_ragged([], universe=R.universe)
    st = {}
    assert cf_rs_join_device(R, empty, 0.5, method="auto", stats=st) == set()
    assert st["plan"]["decided"] == "empty"
    assert st["plan"]["method"] in P.KNOWN_METHODS


# ---------------------------------------------------------------------- #
# real multi-device mesh (subprocess: needs its own XLA device count)
# ---------------------------------------------------------------------- #
_AUTO_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
from repro.core.distributed import mr_cf_rs_join
from repro.core.join import brute_force_join
from repro.core.sets import SetCollection

assert jax.device_count() == 4
mesh = jax.make_mesh((4,), ("data",))
rng = np.random.default_rng(7)
for U, want_method in ((512, "popcount"), (1 << 20, "lfvt")):
    sets_r = [np.unique(rng.integers(0, U, size=rng.integers(4, 24)))
              for _ in range(48)]
    sets_s = [np.unique(rng.integers(0, U, size=rng.integers(4, 24)))
              for _ in range(48)]
    R = SetCollection.from_ragged(sets_r, universe=U)
    S = SetCollection.from_ragged(sets_s, universe=U)
    t = 2 / 3
    oracle = brute_force_join(R, S, t)
    st = {}
    got = mr_cf_rs_join(R, S, t, n_shards=4, method="auto", mesh=mesh,
                        stats=st)
    assert got == oracle, U
    assert st["plan"]["decided"] == "cost_model", st["plan"]
    assert st["plan"]["shard_methods"] is None  # mesh pick is homogeneous
    assert st["n_shards"] == 4
    if U >= 1 << 20:
        assert st["plan"]["method"] == want_method, (U, st["plan"]["method"])
    forced = mr_cf_rs_join(R, S, t, n_shards=4,
                           method=st["plan"]["method"], mesh=mesh)
    assert forced == oracle, U
print("AUTO_MESH_OK")
"""


def test_auto_mesh_under_shard_map_4_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _AUTO_MESH_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "AUTO_MESH_OK" in out.stdout


# ---------------------------------------------------------------------- #
# the cost model: monotone bitmap -> lfvt flip, bracketed crossover
# ---------------------------------------------------------------------- #
def _fixed_sets(rng, k, lo, hi, span):
    return [np.unique(rng.integers(0, span, size=int(rng.integers(lo, hi))))
            for _ in range(k)]


def test_bitmap_to_lfvt_flip_is_monotone():
    """Same sets, growing universe: bitmap's predicted cost is linear in
    W while the walk's is U-independent, so once lfvt wins it never
    un-wins — and the flip lands inside the BENCH bracket 2^13..2^21."""
    rng = np.random.default_rng(4)
    sets_r = _fixed_sets(rng, 64, 8, 40, 4096)
    sets_s = _fixed_sets(rng, 64, 8, 40, 4096)
    picks = []
    for logu in range(12, 22):
        U = 1 << logu
        R = SetCollection.from_ragged(sets_r, universe=U)
        S = SetCollection.from_ragged(sets_s, universe=U)
        f = probe_features(R, S, 0.5)
        scores = score_methods(f, DEFAULT_COEFFS, ("popcount", "lfvt"))
        picks.append(choose_method(scores, ("popcount", "lfvt")))
    assert picks[0] == "popcount", picks
    assert picks[-1] == "lfvt", picks
    flip = picks.index("lfvt")
    assert all(p == "lfvt" for p in picks[flip:]), picks  # monotone
    assert 12 <= 12 + flip <= 21  # crossover inside the BENCH bracket


def test_auto_picks_families_on_bench_configs():
    """The acceptance configs: the planner must take the walk at
    U=2^21 and the bitmap family at the mid-width config, with whatever
    calibration the committed BENCH artifacts provide."""
    rng = np.random.default_rng(8)
    mid = [SetCollection.from_ragged(_fixed_sets(rng, 64, 8, 40, 4096),
                                     universe=8192) for _ in range(2)]
    plan = build_plan(mid[0], mid[1], 0.5, method="auto")
    assert P._FAMILY_OF[plan.method] in ("bitmap", "onehot"), plan.scores
    big = [SetCollection.from_ragged(_fixed_sets(rng, 64, 8, 40, 1 << 21),
                                     universe=1 << 21) for _ in range(2)]
    plan = build_plan(big[0], big[1], 0.5, method="auto")
    assert plan.method == "lfvt", plan.scores
    assert plan.scores["lfvt"]["seconds"] < plan.scores["popcount"]["seconds"]


def test_probe_features_exact_counts():
    R = SetCollection.from_ragged([[0, 1, 2], [1, 2]], universe=32)
    S = SetCollection.from_ragged([[1, 2, 9], [2], []], universe=32)
    f = probe_features(R, S, 0.5)
    assert (f["m"], f["n"]) == (2, 3)
    assert f["universe"] == 32 and f["w_words"] == 1
    assert f["r_elems"] == 5 and f["s_elems"] == 4
    # walk traffic: elem 1 -> 2*1, elem 2 -> 2*2, elem 9 in S only on R=0
    assert f["walk_units"] == 2 * 1 + 2 * 2
    assert f["distinct_s"] == 3
    assert 0.0 <= f["window_frac"] <= 1.0


def _probe_reference(R, S, t, measure="jaccard", r_rows=None, s_rows=None):
    """The probe as it was before the element-summary memo: every call
    concatenates and ``np.unique``s both sides and sorts S's sizes."""
    import math

    from repro.core.measures import get_measure

    def view(C, rows):
        if rows is None:
            return C.sets, C.sizes()
        rows = np.asarray(rows, dtype=np.int64)
        return [C.sets[int(i)] for i in rows], C.sizes()[rows]

    meas = get_measure(measure)
    r_sets, r_sizes = view(R, r_rows)
    s_sets, s_sizes = view(S, s_rows)
    m, n = len(r_sets), len(s_sets)
    universe = int(max(R.universe, S.universe, 1))
    r_elems = int(np.asarray(r_sizes).sum()) if m else 0
    s_elems = int(np.asarray(s_sizes).sum()) if n else 0
    feats = {
        "m": m, "n": n, "universe": universe,
        "w_words": (universe + 31) // 32,
        "r_elems": r_elems, "s_elems": s_elems,
        "max_r": int(np.asarray(r_sizes).max(initial=0)) if m else 0,
        "max_s": int(np.asarray(s_sizes).max(initial=0)) if n else 0,
        "density": s_elems / float(max(n, 1) * universe),
        "window_frac": meas.window_fraction(r_sizes, s_sizes, t),
        "walk_units": 0, "distinct_s": 0, "zipf_head_mass": 0.0,
    }
    if not s_elems:
        return feats
    vals_s, cnt_s = np.unique(
        np.concatenate([np.asarray(s) for s in s_sets if len(s)]),
        return_counts=True)
    feats["distinct_s"] = int(len(vals_s))
    k = max(1, math.ceil(global_config.planner_head_frac * len(vals_s)))
    k = min(k, len(vals_s))
    feats["zipf_head_mass"] = float(
        np.sort(cnt_s)[-k:].sum() / float(s_elems))
    if r_elems:
        vals_r, cnt_r = np.unique(
            np.concatenate([np.asarray(s) for s in r_sets if len(s)]),
            return_counts=True)
        idx = np.clip(np.searchsorted(vals_s, vals_r), 0, len(vals_s) - 1)
        hit = vals_s[idx] == vals_r
        feats["walk_units"] = int(
            (cnt_r[hit].astype(np.int64) * cnt_s[idx[hit]]).sum())
    return feats


def _probe_case(case):
    """(R, S, t, measure, r_rows, s_rows) for one probe-equality case."""
    kind, seed = case[0], case[1]
    if kind == "rs":
        _, _, measure, t = case
        R, S = random_collections(seed, m=40, n=60, universe=200,
                                  max_size=30)
        return R, S, t, measure, None, None
    if kind == "self":
        _, _, measure, t = case
        C = random_collections(seed, m=2, n=80, universe=150)[1]
        return C, C, t, measure, None, None
    if kind == "universes":   # R and S over universes of different sizes
        R = random_collections(seed, m=30, universe=1 << 12)[0]
        S = random_collections(seed + 1, n=50, universe=90)[1]
        return R, S, 0.6, "dice", None, None
    if kind == "empty_sets":  # every set of one side empty, or no sets
        R, S = random_collections(seed)
        empty = SetCollection.from_ragged([[]] * 5, universe=64)
        none = SetCollection.from_ragged([], universe=64)
        pick = {0: (empty, S), 1: (R, empty), 2: (R, none), 3: (none, S)}
        R, S = pick[seed]
        return R, S, 0.5, "jaccard", None, None
    # kind == "rows": the MR loop path's routed row subsets
    R, S = heavy_collections(seed=seed)
    part = load_aware_partition(R, S, 0.5, 4)
    s_rows, r_rows = part.shard_rows(R, S)
    k = max(range(part.n_shards), key=lambda i: len(r_rows[i]))
    return R, S, 0.5, "cosine", r_rows[k], s_rows[k]


_PROBE_CASES = (
    [("rs", 11 + i, meas, t) for i, meas in enumerate(MEASURES)
     for t in (0.5, 0.8)]
    + [("self", 21, "jaccard", 0.8), ("self", 22, "overlap", 0.7),
       ("universes", 31), ("universes", 32)]
    + [("empty_sets", i) for i in range(4)]
    + [("rows", 41), ("rows", 42)])


def _same_bits(a, b):
    if type(a) is not type(b):
        return False
    if isinstance(a, float):
        return np.float64(a).tobytes() == np.float64(b).tobytes()
    return a == b


@pytest.mark.parametrize("case", _PROBE_CASES,
                         ids=lambda c: "-".join(map(str, c)))
def test_probe_matches_reference_bit_for_bit(case, monkeypatch):
    """The memoized probe's features equal a from-scratch probe key for
    key and bit for bit, on the call that fills the memo and on the one
    that reads it; so the plan (method, features, scores) is unchanged."""
    R, S, t, measure, r_rows, s_rows = _probe_case(case)
    want = _probe_reference(R, S, t, measure, r_rows, s_rows)
    for _ in range(2):   # the first probe fills the memo, the second reads it
        got = probe_features(R, S, t, measure, r_rows=r_rows, s_rows=s_rows)
        assert list(got) == list(want)
        bad = {k: (got[k], want[k]) for k in want
               if not _same_bits(got[k], want[k])}
        assert not bad, bad
    if r_rows is None:
        plan = build_plan(R, S, t, method="auto", measure=measure).to_dict()
        monkeypatch.setattr(P, "probe_features", _probe_reference)
        assert plan == build_plan(R, S, t, method="auto",
                                  measure=measure).to_dict()


def _plan_span():
    from repro import obs
    (root,) = obs.recent("repro.join", 1)
    (sp,) = [s for s in root.spans if s.name == "repro.plan"]
    return sp


def test_probe_reads_the_memo_on_the_second_call():
    """A resident S is summarized once: the second join's probe reads
    S's memo (``probe_cached`` 1, R being a fresh object), and a
    self-join's second probe reads both sides (2)."""
    import repro

    R, S = random_collections(51, m=20, n=30)
    r_lists = [list(map(int, s)) for s in R.sets]
    counts = []
    for _ in range(2):   # raw lists: a fresh R collection every call
        repro.join(r_lists, S, 0.5)
        counts.append(_plan_span().attrs["probe_cached"])
    assert counts == [0, 1]
    C = random_collections(52, n=40)[1]
    counts = []
    for _ in range(2):
        repro.self_join(C, 0.5)
        counts.append(_plan_span().attrs["probe_cached"])
    assert counts == [0, 2]
    summary = S.element_summary()
    assert summary is S.element_summary()
    for arr in summary:
        assert not arr.flags.writeable
        with pytest.raises(ValueError):
            arr[...] = 0


def test_plan_shard_methods_tracks_partition():
    R, S = heavy_collections()
    part = load_aware_partition(R, S, 0.5, 4)
    picks = plan_shard_methods(R, S, 0.5, part, measure="jaccard",
                               coeffs=DEFAULT_COEFFS)
    assert len(picks) == part.n_shards
    assert set(picks) <= set(P.SHARD_CANDIDATES)
    assert len(set(picks)) > 1  # the two length regimes split


# ---------------------------------------------------------------------- #
# kwarg lattice: historical texts + previously-silent combinations
# ---------------------------------------------------------------------- #
def test_lattice_historical_messages_verbatim():
    with pytest.raises(ValueError, match="unknown emit mode"):
        validate_join_args(driver="device", method="popcount", emit="bogus")
    with pytest.raises(ValueError, match="unknown method"):
        validate_join_args(driver="device", method="bogus", emit="pairs")
    with pytest.raises(ValueError, match="unknown walk schedule"):
        validate_join_args(driver="mr", method="lfvt", emit="pairs",
                          schedule="bogus")
    with pytest.raises(ValueError, match="unknown pad mode"):
        validate_join_args(driver="mr", method="popcount", emit="pairs",
                          pad="bogus", pad_explicit=True)
    with pytest.raises(ValueError, match="use method='lfvt'"):
        validate_join_args(driver="mr", method="lfvt_ref", emit="pairs",
                          has_mesh=True)


def test_lattice_previously_silent_combos_now_raise():
    R, S = random_collections(2)
    # (1) schedule= with a method that never consults it (was silent)
    with pytest.raises(PlannerError, match="never consults it"):
        mr_cf_rs_join(R, S, 0.5, n_shards=2, method="popcount",
                      schedule="planned")
    # (2) explicit pad= on the loop lfvt path (was silently ignored)
    with pytest.raises(PlannerError, match="loop lfvt path"):
        mr_cf_rs_join(R, S, 0.5, n_shards=2, method="lfvt", pad="bucket")
    # (3) pair_capacity with emit='mask' (was silently ignored) — both
    # drivers route through the same check
    with pytest.raises(PlannerError, match="pair_capacity"):
        cf_rs_join_device(R, S, 0.5, emit="mask", pair_capacity=64)
    with pytest.raises(PlannerError, match="pair_capacity"):
        mr_cf_rs_join(R, S, 0.5, n_shards=2, emit="mask", pair_capacity=64)


def test_lattice_still_accepts_valid_combos():
    R, S = random_collections(5)
    oracle = brute_force_join(R, S, 0.5)
    # pad='auto' default stays accepted on every path
    assert mr_cf_rs_join(R, S, 0.5, n_shards=2, method="lfvt",
                         pad="auto") == oracle
    # schedule with method='auto' is legal: the planner may pick lfvt
    assert mr_cf_rs_join(R, S, 0.5, n_shards=2, method="auto",
                         schedule="static") == oracle


# ---------------------------------------------------------------------- #
# calibration from BENCH artifacts
# ---------------------------------------------------------------------- #
def _bench_doc(rows):
    return {"schema_version": 1, "rows": rows}


def _row(config, method, impl, metrics):
    return {"config": config, "method": method, "impl": impl,
            "metrics": metrics}


def test_load_calibration_rescales_and_clamps(tmp_path):
    met = {"m": 64, "n": 64, "universe": 8192, "seconds": None}
    W = 8192 // 32
    pred_default = (DEFAULT_COEFFS["bitmap"]["fixed"]
                    + DEFAULT_COEFFS["bitmap"]["per_byte"] * (128 * W * 4)
                    + DEFAULT_COEFFS["bitmap"]["per_unit"]
                    * (0.5 * 64 * 64 * W))
    p = tmp_path / "BENCH_prX.json"
    p.write_text(json.dumps(_bench_doc([
        _row("method_axis/midW", "bitmap", "jnp",
             dict(met, seconds=2.0 * pred_default)),
        _row("method_axis/midW", "bitmap", "jnp", dict(met)),  # null: skip
        _row("other/row", "bitmap", "jnp", dict(met, seconds=1.0)),
    ])))
    got = load_calibration([str(p)])
    assert got["bitmap"]["fixed"] == pytest.approx(
        2.0 * DEFAULT_COEFFS["bitmap"]["fixed"])
    # untouched families keep the defaults
    assert got["lfvt"] == DEFAULT_COEFFS["lfvt"]
    # clamp: an absurd artifact cannot invert a decision
    p2 = tmp_path / "BENCH_prY.json"
    p2.write_text(json.dumps(_bench_doc([
        _row("method_axis/midW", "bitmap", "jnp",
             dict(met, seconds=1e6 * pred_default))])))
    got2 = load_calibration([str(p2)])
    assert got2["bitmap"]["fixed"] == pytest.approx(
        global_config.planner_scale_max * DEFAULT_COEFFS["bitmap"]["fixed"])


def test_load_calibration_skips_legacy_and_garbage(tmp_path):
    legacy = tmp_path / "BENCH_pr2.json"
    legacy.write_text(json.dumps({"bitmap": {"seconds": 1.0}}))  # old schema
    junk = tmp_path / "BENCH_pr3.json"
    junk.write_text("{not json")
    assert load_calibration([str(legacy), str(junk),
                             str(tmp_path / "missing.json")]) \
        == DEFAULT_COEFFS


def test_effective_coeffs_pinned_off():
    old = global_config.planner_calibrate
    global_config.planner_calibrate = False
    try:
        assert P.effective_coeffs() is DEFAULT_COEFFS
    finally:
        global_config.planner_calibrate = old


# ---------------------------------------------------------------------- #
# JoinPlan / JoinStats views
# ---------------------------------------------------------------------- #
def test_join_plan_round_trip():
    R, S = heavy_collections()
    part = load_aware_partition(R, S, 0.5, 4)
    plan = build_plan(R, S, 0.5, driver="mr", method="auto", part=part)
    d = plan.to_dict()
    assert d["requested"] == "auto" and d["decided"] == "cost_model"
    back = JoinPlan.from_dict(json.loads(json.dumps(d)))
    assert back.method == plan.method
    assert back.shard_methods == plan.shard_methods
    assert isinstance(back.shard_methods, tuple)


def test_join_stats_byte_compatible_view():
    R, S = random_collections(6)
    raw = {}
    pairs = cf_rs_join_device(R, S, 0.5, stats=raw)
    st = JoinStats.from_dict(raw)
    # typed aliases resolve (device driver publishes pair_count)
    assert st.result_pairs == raw["pair_count"] == len(pairs)
    assert st.method == raw["method"]
    # Mapping protocol + byte-compatible dict view
    assert dict(st) == raw
    assert st.to_dict() == raw
    assert st["method"] == raw["method"]
    assert len(st) == len(raw)
