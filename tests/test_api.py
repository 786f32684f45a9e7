"""``repro.join`` front door (ISSUE 10): coercion, JoinResult, lazy
package exports, driver routing, error surface.
"""
import numpy as np
import pytest

import repro
from repro.core.join import brute_force_join
from repro.core.planner import JoinPlan, JoinStats, PlannerError
from repro.core.sets import SetCollection
from repro.data.synth import DATASETS, make_join_dataset


def sample_inputs(seed=5, n=28, universe=600):
    rng = np.random.default_rng(seed)
    base = [np.unique(rng.integers(0, universe, size=rng.integers(6, 20)))
            for _ in range(n)]
    # S shares a prefix verbatim + perturbs the rest: nonzero result set
    s = base[:n // 3] + [np.unique(np.concatenate(
        [b, rng.integers(0, universe, 2)])) for b in base[n // 3:]]
    return base, s


def test_lazy_package_exports():
    assert sorted(repro.__all__) == repro.__all__
    for name in repro.__all__:
        assert getattr(repro, name) is not None
    with pytest.raises(AttributeError, match="no attribute"):
        repro.not_a_symbol
    assert "join" in dir(repro)


def test_join_accepts_raw_sets_and_collections():
    r_sets, s_sets = sample_inputs()
    res = repro.join(r_sets, s_sets, 0.5)
    Rc = repro.as_collection(r_sets)
    Sc = repro.as_collection(s_sets)
    oracle = brute_force_join(Rc, Sc, 0.5)
    assert oracle  # the shared prefix guarantees hits
    assert set(res.pairs) == oracle
    assert set(repro.join(Rc, Sc, 0.5).pairs) == oracle
    # coercion dedups + sorts raw input and infers the universe
    dup = repro.as_collection([[3, 1, 3, 1], []])
    assert list(dup.sets[0]) == [1, 3] and dup.universe == 4


def test_join_result_surface():
    r_sets, s_sets = sample_inputs()
    res = repro.join(r_sets, s_sets, 0.5)
    assert isinstance(res, repro.JoinResult)
    assert isinstance(res.pairs, frozenset)
    assert isinstance(res.stats, JoinStats)
    assert isinstance(res.plan, JoinPlan)
    assert res.plan.requested == "auto"
    assert res.plan.decided == "cost_model"
    assert res.plan.method in res.plan.scores
    assert len(res) == len(res.pairs) == res.stats.result_pairs
    assert set(res) == set(res.pairs)
    assert res.sorted_pairs() == sorted(res.pairs)
    assert res.mask is None  # emit='pairs' default


def test_join_mask_emission_dense_matrix():
    r_sets, s_sets = sample_inputs()
    Rc, Sc = repro.as_collection(r_sets), repro.as_collection(s_sets)
    oracle = brute_force_join(Rc, Sc, 0.5)
    res = repro.join(Rc, Sc, 0.5, emit="mask")
    assert res.mask.shape == (len(Rc), len(Sc))
    want = np.zeros((len(Rc), len(Sc)), bool)
    for a, b in oracle:
        want[a, b] = True
    assert (res.mask == want).all()
    assert set(res.pairs) == oracle


def test_join_routes_to_mr_driver():
    r_sets, s_sets = sample_inputs()
    Rc, Sc = repro.as_collection(r_sets), repro.as_collection(s_sets)
    oracle = brute_force_join(Rc, Sc, 0.5)
    res = repro.join(Rc, Sc, 0.5, n_shards=3)
    assert set(res.pairs) == oracle
    assert res.plan.driver == "mr"
    assert res.stats["n_shards"] == 3
    assert res.plan.shard_methods is not None
    # mask reconstruction on the MR path too
    rm = repro.join(Rc, Sc, 0.5, n_shards=3, emit="mask")
    assert rm.mask.sum() == len(oracle)


def test_join_forced_method_passthrough():
    r_sets, s_sets = sample_inputs()
    Rc, Sc = repro.as_collection(r_sets), repro.as_collection(s_sets)
    oracle = brute_force_join(Rc, Sc, 0.5)
    for forced in ("popcount", "onehot", "lfvt", "lfvt_ref"):
        res = repro.join(Rc, Sc, 0.5, method=forced)
        assert set(res.pairs) == oracle, forced
        assert res.plan.decided == "forced"
        assert res.plan.method == forced
        assert res.stats.method == forced


@pytest.mark.parametrize("measure", ["jaccard", "cosine", "dice", "overlap"])
def test_join_measures_boundary(measure):
    """The exact 2/3 boundary pair survives the front door per measure."""
    r_sets = [[0, 1, 2, 3, 4]]
    s_sets = [[0, 1, 2, 3, 99]]
    res = repro.join(r_sets, s_sets, 2 / 3, measure=measure)
    oracle = brute_force_join(repro.as_collection(r_sets),
                              repro.as_collection(s_sets), 2 / 3,
                              measure=measure)
    assert set(res.pairs) == oracle


@pytest.mark.parametrize("name", sorted(DATASETS))
def test_join_auto_matches_oracle_on_dataset_profiles(name):
    """The front door's default (``method='auto'``) returns exactly the
    brute-force pairs on each synthetic dataset profile; S carries copies
    of four R rows so no profile's result is empty."""
    spec = DATASETS[name]
    R, S = make_join_dataset(name, scale=40 / spec.n_sets, seed=1)
    S = SetCollection.from_ragged(list(S.sets) + list(R.sets[:4]),
                                  universe=S.universe)
    oracle = brute_force_join(R, S, 0.5)
    assert len(oracle) >= 4
    res = repro.join(R, S, 0.5)
    assert res.plan.requested == "auto"
    assert res.pairs == oracle


def test_join_stats_sharing_and_byte_compat():
    r_sets, s_sets = sample_inputs()
    shared = {}
    res = repro.join(r_sets, s_sets, 0.5, stats=shared)
    assert res.stats.to_dict() == shared  # same mapping, byte-compatible
    assert shared["plan"] == res.plan.to_dict()
    assert dict(res.stats) == shared


def test_join_kwarg_errors_are_planner_errors():
    r_sets, s_sets = sample_inputs(n=6)
    with pytest.raises(PlannerError, match="unknown emit mode"):
        repro.join(r_sets, s_sets, 0.5, emit="bogus")
    with pytest.raises(PlannerError, match="unknown method"):
        repro.join(r_sets, s_sets, 0.5, method="bogus")
    # device-only knobs with the MR driver selected (and vice versa)
    with pytest.raises(PlannerError, match="tile driver"):
        repro.join(r_sets, s_sets, 0.5, n_shards=2, r_block=64)
    with pytest.raises(PlannerError, match="MapReduce driver"):
        repro.join(r_sets, s_sets, 0.5, schedule="planned")
    with pytest.raises(PlannerError, match="MapReduce driver"):
        repro.join(r_sets, s_sets, 0.5, pad="bucket")
    with pytest.raises(PlannerError, match="pair_capacity"):
        repro.join(r_sets, s_sets, 0.5, emit="mask", pair_capacity=32)


def test_join_device_tuning_overrides_land_in_plan():
    r_sets, s_sets = sample_inputs(n=10)
    res = repro.join(r_sets, s_sets, 0.5, method="lfvt", r_block=32,
                     row_tile=8, double_buffer=False)
    assert res.plan.r_block == 32
    assert res.plan.row_tile == 8
    assert res.plan.double_buffer is False
    oracle = brute_force_join(repro.as_collection(r_sets),
                              repro.as_collection(s_sets), 0.5)
    assert set(res.pairs) == oracle


def test_legacy_drivers_unchanged_defaults():
    """Back-compat: the legacy entry points still default to 'popcount'
    and a plain stats dict — only the front door defaults to 'auto'."""
    r_sets, s_sets = sample_inputs(n=8)
    Rc, Sc = repro.as_collection(r_sets), repro.as_collection(s_sets)
    st = {}
    got = repro.cf_rs_join_device(Rc, Sc, 0.5, stats=st)
    assert st["method"] == "popcount"
    assert st["plan"]["decided"] == "forced"
    assert got == brute_force_join(Rc, Sc, 0.5)
    st2 = {}
    repro.mr_cf_rs_join(Rc, Sc, 0.5, 2, stats=st2)
    assert st2["plan"]["method"] == "popcount"
