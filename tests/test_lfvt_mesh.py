"""Mesh-parallel LFVT (ISSUE 6): bucketed flat-array padding + the
shard_map join path.

Covers ``core/lfvt_flat.py`` sentinel padding and the
``core/distributed.py`` mesh route:

  * structural invariants on padded ``FlatLFVT`` tables — sentinel
    rows carry the documented values (int32-max entry elements, zero
    entry/set lengths, ``seq_next`` = -1) and are unreachable: every
    per-element walk and the full ``flat_join_mask`` are bit-identical
    to the unpadded tree, padded S columns never qualify;
  * ``entry_positions`` precomputation (walk starts survive padding),
    cap accounting (``flat_walk_caps``), no-shrink guard, and the
    ``max_seq_len``-only-raised rule;
  * bucket-vs-global pad waste: bucketed stacking never wastes more
    than a single global footprint;
  * a 4-device forced-host ``shard_map`` subprocess parity test vs the
    loop path and the brute-force oracle — all four measures at the
    exact 2/3 boundary, emit='pairs' and emit='mask', both pad modes,
    the per-shard overflow/regrow protocol, the planned-vs-static
    shard-body schedule (bit-identical pairs, ``live_tiles`` <=
    ``total_tiles``, named unknown-schedule error), and the named
    ``lfvt_ref`` mesh error (mirrors ``tests/test_shard_sparse.py``).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.config import global_config
from repro.core.join import brute_force_join
from repro.core.lfvt_flat import (FlatLFVT, entry_positions, flat_join_mask,
                                  flat_walk_caps, pad_flat_tables)
from repro.core.sets import SetCollection
from repro.core.tile_join import window_bounds


def random_collection(seed, n=20, universe=48, max_size=12, skew=False,
                      empty_frac=0.15) -> SetCollection:
    rng = np.random.default_rng(seed)
    sets = []
    for _ in range(n):
        if rng.random() < empty_frac:
            sets.append(np.zeros(0, np.int32))
            continue
        size = (int(min(max_size, rng.zipf(1.6))) if skew
                else int(rng.integers(1, max_size + 1)))
        sets.append(rng.integers(0, universe, size=size))
    return SetCollection.from_ragged(sets, universe=universe)


def padded_variant(flat: FlatLFVT, extra=7) -> FlatLFVT:
    caps = flat_walk_caps(flat)
    return pad_flat_tables(
        flat, n_nodes=caps["n_nodes"] + extra, n_seq=caps["n_seq"] + extra,
        n_entries=caps["n_entries"] + extra, n_sets=caps["n_sets"] + extra,
        max_seq_len=caps["max_seq_len"] + extra)


# ---------------------------------------------------------------------- #
# sentinel rows: documented values, unreachable by construction
# ---------------------------------------------------------------------- #
def test_padded_tables_sentinel_values():
    S = random_collection(11, n=18, skew=True)
    flat = S.sort_by_size().flat_lfvt()
    caps = flat_walk_caps(flat)
    pad = padded_variant(flat)
    E, T, n = caps["n_entries"], caps["n_seq"], caps["n_sets"]
    sentinel = np.int32(global_config.flat_pad_sentinel)
    assert np.all(pad.entry_elem[E:] == sentinel)
    assert np.all(pad.entry_len[E:] == 0)        # a lane dies instantly
    assert np.all(pad.entry_node[E:] == 0)
    assert np.all(pad.seq_next[T:] == -1)        # no hop chain enters
    assert np.all(pad.seq_row[T:] == 0)
    assert np.all(pad.s_sizes[n:] == 0)          # outside every window
    assert np.all(pad.s_ids[n:] == -1)           # host-side id filter
    assert np.all(pad.node_parent[caps["n_nodes"]:] == -1)
    # prefixes untouched, entry table still sorted (binary search safe)
    for name in ("entry_elem", "entry_node", "entry_off", "entry_len",
                 "seq_row", "seq_next", "s_ids", "s_sizes"):
        np.testing.assert_array_equal(
            getattr(pad, name)[:len(getattr(flat, name))],
            getattr(flat, name))
    assert np.all(np.diff(pad.entry_elem.astype(np.int64)) >= 0)
    # real element ids are < universe < sentinel: lookups can't alias
    assert flat.universe < int(sentinel)


def test_padded_tables_walks_bit_identical():
    for seed in (3, 9, 21):
        S = random_collection(seed, n=16, skew=seed % 2 == 0)
        flat = S.sort_by_size().flat_lfvt()
        pad = padded_variant(flat, extra=5 + seed)
        for a in range(flat.universe):
            assert list(pad.walk(a)) == list(flat.walk(a)), (seed, a)
        np.testing.assert_array_equal(
            entry_positions(pad)[:len(entry_positions(flat))],
            entry_positions(flat))


def test_padded_tables_join_mask_parity():
    """Device-side: padded tables produce the same qualifying mask on
    the original columns and an all-False tail on sentinel columns."""
    R = random_collection(5, n=12)
    S = random_collection(6, n=14)
    t = 2 / 3
    flat = S.sort_by_size().flat_lfvt()
    pad = padded_variant(flat)
    r_pad, r_sz = R.padded()
    lo, hi = window_bounds(r_sz, flat.s_sizes, t)
    lo_p, hi_p = window_bounds(r_sz, pad.s_sizes, t)
    mask = np.asarray(flat_join_mask(flat, r_pad, r_sz, lo, hi, t))
    mask_p = np.asarray(flat_join_mask(pad, r_pad, r_sz, lo_p, hi_p, t))
    n = flat.n_sets
    np.testing.assert_array_equal(mask_p[:, :n], mask)
    assert not mask_p[:, n:].any()      # sentinel columns never qualify
    got = {(int(R.ids[i]), int(pad.s_ids[j]))
           for i, j in zip(*np.nonzero(mask_p)) if pad.s_ids[j] >= 0}
    assert got == brute_force_join(R, S, t)


def test_pad_flat_tables_guards():
    S = random_collection(2, n=10)
    flat = S.sort_by_size().flat_lfvt()
    caps = flat_walk_caps(flat)
    # caps must not shrink any table (named FlatLFVTError since ISSUE 9:
    # the bare assert vanished under `python -O`)
    from repro.core.lfvt_flat import FlatLFVTError
    with pytest.raises(FlatLFVTError, match="n_entries"):
        pad_flat_tables(flat, n_entries=max(caps["n_entries"] - 1, 0))
    # max_seq_len is only ever raised, never lowered below the true bound
    same = pad_flat_tables(flat, max_seq_len=0)
    assert same.max_seq_len == caps["max_seq_len"]
    raised = pad_flat_tables(flat, max_seq_len=caps["max_seq_len"] + 9)
    assert raised.max_seq_len == caps["max_seq_len"] + 9
    # identity padding round-trips every table
    ident = pad_flat_tables(flat)
    for name in ("entry_elem", "seq_row", "seq_next", "s_ids", "s_sizes",
                 "node_seq_off", "node_seq_len", "node_parent"):
        np.testing.assert_array_equal(getattr(ident, name),
                                      getattr(flat, name))


# ---------------------------------------------------------------------- #
# real multi-device shard_map (subprocess: needs its own XLA device count)
# ---------------------------------------------------------------------- #
_LFVT_MESH_SCRIPT = r"""
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
U = 1 << 16
sets_r, sets_s = [], []
for _ in range(60):
    b = list(rng.choice(U, size=rng.integers(2, 16), replace=False))
    sets_r.append(b)
    dup = list(b)
    if len(dup) > 2 and rng.random() < 0.6:
        dup = dup[:-1]                      # near-duplicate partner
    sets_s.append(dup)
# exact Jaccard 2/3 boundary: f=4, union=6 -> 4/6 == t must qualify
sets_r.append([0, 1, 2, 3, 4])
sets_s.append([0, 1, 2, 3, 60000])
R = SetCollection.from_ragged(sets_r, universe=U)
S = SetCollection.from_ragged(sets_s, universe=U)
t = 2 / 3
waste = {}
for meas in ("jaccard", "cosine", "dice", "overlap"):
    oracle = brute_force_join(R, S, t, measure=meas)
    assert oracle, meas                     # boundary pair is in there
    loop = mr_cf_rs_join(R, S, t, n_shards=4, method="lfvt", measure=meas)
    assert loop == oracle, meas
    for emit in ("pairs", "mask"):
        for pad in ("bucket", "global"):
            st = {}
            got = mr_cf_rs_join(R, S, t, n_shards=4, method="lfvt",
                                mesh=mesh, emit=emit, pad=pad,
                                measure=meas, stats=st)
            assert got == oracle, (meas, emit, pad)
            assert st["mesh_devices"] == 4 and st["n_shards"] == 4
            # every shard walks on a device of its own, also when the
            # shards fall into several buckets
            assert st["reduce_devices"] == 4, (pad, st["n_buckets"])
            assert st["walk_steps"] > 0
            waste[pad] = st["flat_pad_waste"]
    print(meas, "OK", len(oracle))
# bucketed stacking never pads more than a single global footprint
assert 0.0 <= waste["bucket"] <= waste["global"] < 1.0, waste
print("WASTE_OK", round(waste["bucket"], 3), round(waste["global"], 3))
# device-planned vs static shard-body schedule: bit-identical pairs,
# per-shard live counts summed across the mesh, planned never walks
# more tiles/steps than static
ps, ss = {}, {}
oracle = brute_force_join(R, S, t)
p = mr_cf_rs_join(R, S, t, 4, method="lfvt", mesh=mesh, stats=ps,
                  schedule="planned")
s = mr_cf_rs_join(R, S, t, 4, method="lfvt", mesh=mesh, stats=ss,
                  schedule="static")
assert p == s == oracle
assert ps["walk_schedule"] == "planned" and ss["walk_schedule"] == "static"
assert 0 <= ps["live_tiles"] <= ps["total_tiles"]
assert ss["live_tiles"] == ss["total_tiles"] > 0
assert ps["total_tiles"] == ss["total_tiles"]
assert ps["walk_steps"] <= ss["walk_steps"]
try:
    mr_cf_rs_join(R, S, t, 4, method="lfvt", mesh=mesh, schedule="nope")
    raise SystemExit("expected ValueError for unknown schedule")
except ValueError as e:
    assert "schedule" in str(e), e
print("SCHED_OK", ps["live_tiles"], ps["total_tiles"])
# lfvt_ref has no mesh path: named error pointing at method='lfvt'
try:
    mr_cf_rs_join(R, S, 0.5, n_shards=4, method="lfvt_ref", mesh=mesh)
    raise SystemExit("expected ValueError for lfvt_ref on mesh")
except ValueError as e:
    assert "use method='lfvt'" in str(e), e
# per-shard overflow/regrow under shard_map (hash keeps 4 shards busy)
sets = [np.arange(6) for _ in range(24)]
D = SetCollection.from_ragged(sets, universe=U)
st = {}
got = mr_cf_rs_join(D, D, 0.9, 4, method="lfvt", mesh=mesh, stats=st,
                    pair_capacity=1, strategy="hash")
assert got == {(i, j) for i in range(24) for j in range(24)}
assert st["regrows"] >= 1, st["regrows"]
print("LFVT_MESH_OK")
"""


def test_lfvt_mesh_under_shard_map_4_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _LFVT_MESH_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "LFVT_MESH_OK" in out.stdout
    assert "WASTE_OK" in out.stdout
    assert "SCHED_OK" in out.stdout
