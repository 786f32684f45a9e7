"""The self-join cell (``dblp_dedup.self``, loop ``selfjoin``) on the CPU
at a small corpus, with the look for a chip skipped: correct as it
stands, its program-side metrics read in a traced run, and not correct
under the control or with the program broken underneath."""
from __future__ import annotations

import pytest

SEED = 2**31 + 41
CELL = "dblp_dedup.self"
# the per-layer metrics read from the program (the device-trace ones
# need a chip's trace)
PROGRAM_METRICS = ["band_frac.self", "block_host_ms.self",
                   "device_wait_ms.self", "compiles_in_window.self"]


@pytest.fixture
def small(bench_copy, monkeypatch):
    """The cell at 1,200 sets in 256-row blocks: five blocks a call."""
    from repro.core.config import global_config
    monkeypatch.setattr(global_config, "r_block", 256)
    bench_copy.update("bench/configs/dblp_dedup.json", corpus_sets=1200)
    return bench_copy


def test_traced_run_is_correct_and_reads_its_metrics(small, capsys):
    line = small.run(capsys, CELL, "--trace", "1", seed=SEED)
    assert line["correct"] is True
    assert line["attempted"] >= 1200 and line["failed"] == 0
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(PROGRAM_METRICS) <= set(got)
    assert 0 < got["band_frac.self"] < 1
    assert got["block_host_ms.self"] > 0 and got["device_wait_ms.self"] > 0
    assert got["compiles_in_window.self"] == 0


def test_untraced_run_reports_rows_per_second(small, capsys):
    line = small.run(capsys, CELL, seed=SEED)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"join_rows_per_s", "setup_s"}
    assert line["checks"]["extra_pairs"]["value"] == 0


def test_control_is_not_correct(small, capsys):
    line = small.run(capsys, CELL, "--control", seed=SEED)
    assert line["correct"] is False
    assert line["checks"]["missing_pairs"]["value"] > 0


def _reversed(pairs):
    return frozenset((b, a) for a, b in pairs)


def _both_orders(pairs):
    return pairs | _reversed(pairs)


def _drop_half(pairs):
    return frozenset(p for p in pairs if p[0] % 2)


@pytest.mark.parametrize("fault", [_reversed, _both_orders, _drop_half],
                         ids=["pairs_reversed", "both_orders",
                              "half_left_out"])
def test_broken_self_join_is_not_correct(small, capsys, monkeypatch, fault):
    import repro
    join = repro.self_join

    def broken(C, t, **kw):
        res = join(C, t, **kw)
        return type(res)(fault(res.pairs), res.mask, res.stats, res.plan)

    monkeypatch.setattr(repro, "self_join", broken)
    assert small.run(capsys, CELL, seed=SEED)["correct"] is False


def test_program_without_self_join_fails_before_building_data(
        small, monkeypatch):
    import repro
    import run
    monkeypatch.delattr(repro, "self_join", raising=False)
    monkeypatch.delitem(repro._EXPORTS, "self_join")
    with pytest.raises(SystemExit, match="no repro.self_join"):
        run.main(["--workload", CELL, "--seed", "1", "--seconds", "1"],
                 require_tpu=False, root=str(small.path))
