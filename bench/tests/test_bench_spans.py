"""The per-layer metrics that read the program's own spans: on the tiny
cells each returns a number in a traced run, and None where the ring
holds fewer roots than the window's calls or steps, or where the program
has no spans at all (as before it had them)."""
from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 23

BATCH = ["plan_ms.batch", "encode_ms.batch", "device_wait_ms.batch",
         "gather_ms.batch"]
SERVE = ["engine_queue_wait_p99_ms.serve", "step_host_ms.serve",
         "step_device_wait_ms.serve"]


def reader(name):
    import run
    return run.load_metric(name, os.path.dirname(BENCH))


@pytest.mark.parametrize("cell,names", [("kosarak.batch", BATCH),
                                        ("dblp.batch", BATCH),
                                        ("dblp.serve", SERVE)])
def test_traced_run_reports_every_span_metric(tiny, capsys, cell, names):
    line = tiny.run(capsys, cell, "--trace", "1", seed=SEED)
    assert line["correct"] is True
    for name in names:
        value = line["metrics"][name]["value"]
        assert value >= 0, name
    if cell.endswith("batch"):
        assert line["metrics"]["device_wait_ms.batch"]["value"] > 0


class FakeRun:
    def __init__(self, **record):
        self.record, self.trace, self.peaks = record, None, {}


@pytest.mark.parametrize("name", BATCH + SERVE)
def test_reader_is_none_when_the_ring_is_short(name):
    from repro import obs
    key = "calls" if name.endswith("batch") else "steps"
    run = FakeRun(**{key: obs.RING_ROOTS + 1})
    assert reader(name)(run) is None


@pytest.mark.parametrize("name", BATCH + SERVE)
def test_reader_is_none_without_program_spans(name, monkeypatch):
    key = "calls" if name.endswith("batch") else "steps"
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert reader(name)(FakeRun(**{key: 1})) is None
