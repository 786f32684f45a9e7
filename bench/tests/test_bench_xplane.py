"""The trace reduction, checked on a trace recorded on one TPU v5 lite:
``dblp.batch`` (20,000 corpus sets, 8,192-row calls, ``--trace 1`` with a
one-second trace; that run printed busy_s 1.808704400000001 and
window_s 2.075469006)."""
from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import xplane  # noqa: E402

TRACE = os.path.join(BENCH, "tests", "data", "dblp_batch.xplane.pb.gz")


@pytest.fixture(scope="module")
def events():
    return xplane.read(xplane.load(TRACE))


def _naive_union(intervals):
    """Length of a union of intervals, by a plain sweep."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def test_trace_holds_one_device_and_the_benchmark_spans(events):
    devices, spans = events
    assert list(devices) == ["/device:TPU:0"]
    assert {n for n, _, _ in spans} == {"bench.r_prep", "bench.join_call"}
    # the profiler's one clock: every device op lies inside the spans
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    ops = devices["/device:TPU:0"]
    assert all(lo <= s and e <= hi for _, s, e in ops)


def test_reduction_matches_the_chip_run_and_a_plain_sweep(events):
    devices, spans = events
    got = xplane.reduce(devices, spans)
    assert got["window_s"] == pytest.approx(2.075469006, rel=1e-12)
    assert got["busy_s"] == pytest.approx(1.808704400000001, rel=1e-12)
    ops = devices["/device:TPU:0"]
    assert got["busy_s"] == pytest.approx(
        _naive_union([(s, e) for _, s, e in ops]), rel=1e-12)
    calls = [(s, e) for n, s, e in spans if n == "bench.join_call"]
    inside = [(max(s, a), min(e, b)) for n, s, e in ops for a, b in calls
              if min(e, b) > max(s, a) and not xplane.is_transfer(n)]
    assert got["span_device_s"]["bench.join_call"] == pytest.approx(
        _naive_union(inside), rel=1e-9)
    assert got["span_device_s"]["bench.r_prep"] == 0.0


def test_breakdown_is_ranked_and_labelled(events):
    got = xplane.reduce(*events)
    ops, gaps = got["device_ops"], got["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    assert [v for _, v in gaps] == sorted((v for _, v in gaps), reverse=True)
    # the dense-mask compaction is the costliest op of this cell
    assert "fusion(s32[20480000]" in ops[0][0]
    idle = got["window_s"] - got["busy_s"]
    assert sum(v for _, v in gaps) <= idle + 1e-9
    assert {label for label, _ in gaps} <= {
        "bench.join_call", "bench.r_prep", "outside_spans"}


def test_transfers_are_told_by_their_instruction_name():
    assert xplane.is_transfer("%copy-start.2 = (s32[8]) copy-start(...)")
    assert not xplane.is_transfer(
        "%fusion.1 = pred[8] fusion(s32[8] %copy-done.4)")


def test_no_device_ops_gives_nothing():
    assert xplane.reduce({}, [("bench.join_call", 0.0, 1.0)]) is None
