"""The open-loop serve run on the CPU at a tiny size, with the look for a
chip skipped: correct as it stands, not correct under the control or
with the engine broken underneath."""
from __future__ import annotations

import pytest

SEED = 2**31 + 11


def test_serve_run_is_correct(tiny, capsys):
    line = tiny.run(capsys, "dblp.serve", seed=SEED)
    assert line["correct"] is True
    assert line["attempted"] == 40 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_p99_ms", "serve_p50_ms",
                                    "setup_s"}
    assert line["checks"]["missing_pairs"]["limit"] == 0


def test_serve_control_is_not_correct(tiny, capsys):
    assert tiny.run(capsys, "dblp.serve", "--control",
                    seed=SEED)["correct"] is False


def _alter_answer(step):
    def broken(self):
        import dataclasses
        return [dataclasses.replace(r, matches=tuple(m + 1 for m in r.matches)
                                    or (0,))
                for r in step(self)]
    return broken


def _drop_half(step):
    def broken(self):
        out = step(self)
        return out[: len(out) // 2]
    return broken


@pytest.mark.parametrize("fault", [_drop_half, _alter_answer],
                         ids=["half_of_batch_left_out", "answer_altered"])
def test_serve_fault_is_not_correct(tiny, capsys, monkeypatch, fault):
    from repro.serve.dedup import DedupServeEngine
    monkeypatch.setattr(DedupServeEngine, "step",
                        fault(DedupServeEngine.step))
    line = tiny.run(capsys, "dblp.serve", seed=SEED)
    assert line["correct"] is False

