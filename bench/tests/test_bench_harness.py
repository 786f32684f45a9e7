"""The harness on the CPU: found by name, no TPU no result, and the
comparison that decides ``correct`` fails under the control and under a
broken program. The batch loop runs at a tiny size in-process, with the
look for a chip skipped."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # in the checkout, and in a directory that holds only the benchmark
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    for cwd in (ROOT, str(tmp_path)):
        p = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", "kosarak.batch",
             "--seed", "1", "--seconds", "1", "--trace", "0"],
            cwd=cwd, env=env, capture_output=True, text=True, timeout=120)
        assert p.returncode != 0
        assert "{" not in p.stdout


def test_a_new_config_mix_and_metric_are_found_by_name(bench_copy, capsys):
    """A later PR adds a cell as new files plus entries: nothing that is
    there is edited."""
    c = bench_copy
    before = {p: p.read_bytes() for p in c.path.rglob("*") if p.is_file()}
    c.write("bench/configs/tiny.json",
            {**c.json("bench/configs/kosarak.json"),
             "corpus_sets": 1500, "universe": 2000})
    c.write("bench/traffic/tiny_batch.json",
            {**c.json("bench/traffic/batch.json"), "rows_per_call": 128})
    c.write("bench/metrics/calls_in_window.tiny.py",
            "def read(run):\n    return run.record['calls']\n")
    spec = c.json("BENCHMARK.json")
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": ["corpus_sets"], "why": "test"})
    spec["workloads"].append({"name": "tiny.batch", "config": "tiny",
                              "traffic": "tiny_batch", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "join_rows_per_s":
            m["workloads"].append("tiny.batch")
    spec["per_layer"].append({"name": "calls_in_window.tiny",
                              "unit": "calls", "better": "higher",
                              "source": "host_clock", "layer": "test",
                              "moves": "join_rows_per_s",
                              "workloads": ["tiny.batch"]})
    c.write("BENCHMARK.json", spec)

    import run
    got = run.load_spec("tiny.batch", str(c.path))
    assert got["config"]["corpus_sets"] == 1500
    assert got["traffic"]["rows_per_call"] == 128
    names = [m["name"] for m in got["per_layer"]]
    assert "calls_in_window.tiny" in names
    line = c.run(capsys, "tiny.batch", "--trace", "1")
    assert line["correct"] is True
    assert line["metrics"]["calls_in_window.tiny"]["value"] >= 1
    line = c.run(capsys, "tiny.batch")
    assert set(line["metrics"]) == {"join_rows_per_s", "setup_s"}
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data, f"{p} was edited"


# a load loop of a shape the benchmark does not have yet: bursts of joins,
# each a handful of back-to-back calls, then a pause
BURST_LOOP = '''
import time
import numpy as np
import gen
from harness import check_answers, collection, now, profile
from reference import Reference


def run(cfg, mix, seed, seconds, tracer, compiles, control, log, on_window):
    import repro
    U, t = int(cfg["universe"]), str(cfg["threshold"])
    s, r = gen.disjoint(profile(cfg), int(cfg["corpus_sets"]),
                        int(mix["rows_per_call"]), gen.rng_for(seed, 1))
    S, R = collection(s, U), collection(r, U)
    repro.join(R, S, float(t))
    setup = on_window()
    answers, t0 = [], now()
    while now() - t0 < seconds:
        for _ in range(int(mix["burst_calls"])):
            answers.append(repro.join(collection(r, U), S, float(t)).pairs)
        time.sleep(float(mix["pause_s"]))
    window = now() - t0
    rows = [gen.row(r, i) for i in range(len(r[1]) - 1)]

    def check():
        got = [{b for a, b in answers[-1] if a == i} for i in range(len(rows))]
        return check_answers(Reference(s, U, t), rows, got, control)

    n = len(answers) * len(rows)
    return {"setup_s": setup, "attempted": n, "failed": 0, "calls": len(answers),
            "end_to_end": {"join_rows_per_s": n / window}, "check": check}
'''


def test_a_new_load_loop_is_found_by_name(tiny, capsys):
    """A load shape the benchmark lacks is a new loop file, a traffic file
    that names it and an entry: no file that is there is edited."""
    c = tiny
    before = {p: p.read_bytes() for p in c.path.rglob("*") if p.is_file()}
    c.write("bench/loops/bursts.py", BURST_LOOP)
    c.write("bench/traffic/bursts.json", {"loop": "bursts",
                                          "rows_per_call": 64,
                                          "burst_calls": 3, "pause_s": 0.2,
                                          "trace_seconds": 1})
    spec = c.json("BENCHMARK.json")
    spec["workloads"].append({"name": "kosarak.bursts", "config": "kosarak",
                              "traffic": "bursts", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "join_rows_per_s":
            m["workloads"].append("kosarak.bursts")
    c.write("BENCHMARK.json", spec)
    line = c.run(capsys, "kosarak.bursts")
    assert line["correct"] is True and line["attempted"] >= 3 * 64
    assert set(line["metrics"]) == {"join_rows_per_s", "setup_s"}
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data, f"{p} was edited"


def test_batch_run_is_correct_and_names_its_device(tiny, capsys):
    line = tiny.run(capsys, "kosarak.batch")
    assert line["correct"] is True
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    assert line["metrics"]["join_rows_per_s"]["value"] > 0


def test_batch_control_is_not_correct(tiny, capsys):
    line = tiny.run(capsys, "kosarak.batch", "--control")
    assert line["correct"] is False
    assert line["checks"]["missing_pairs"]["value"] > 0


def _drop_half(join):
    def broken(R, S, t, **kw):
        res = join(R, S, t, **kw)
        pairs = frozenset(p for p in res.pairs if p[0] % 2 == 0)
        return type(res)(pairs, res.mask, res.stats, res.plan)
    return broken


def _alter_answer(join):
    def broken(R, S, t, **kw):
        res = join(R, S, t, **kw)
        first: dict = {}
        for r, s in sorted(res.pairs):
            first.setdefault(r, s)
        pairs = {(r, s + 1 if s + 1 < len(S) else s - 1)
                 if first[r] == s else (r, s) for r, s in res.pairs}
        return type(res)(frozenset(pairs), res.mask, res.stats, res.plan)
    return broken


@pytest.mark.parametrize("fault", [_drop_half, _alter_answer],
                         ids=["half_of_batch_left_out", "answer_altered"])
def test_batch_fault_is_not_correct(tiny, capsys, monkeypatch, fault):
    import repro
    monkeypatch.setattr(repro, "join", fault(repro.join))
    line = tiny.run(capsys, "kosarak.batch")
    assert line["correct"] is False
