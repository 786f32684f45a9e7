"""A copy of the benchmark (``BENCHMARK.json`` and ``bench/`` without its
tests) in a temporary directory, whose files a test may edit: tiny sizes
for the CPU, or new cells added as files and entries."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


class BenchCopy:
    def __init__(self, path):
        self.path = path
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), path)
        shutil.copytree(BENCH, path / "bench", ignore=shutil.ignore_patterns(
            "__pycache__", "tests"))

    def json(self, rel: str) -> dict:
        return json.loads((self.path / rel).read_text())

    def write(self, rel: str, data) -> None:
        (self.path / rel).parent.mkdir(parents=True, exist_ok=True)
        (self.path / rel).write_text(
            data if isinstance(data, str) else json.dumps(data))

    def update(self, rel: str, **changes) -> None:
        self.write(rel, {**self.json(rel), **changes})

    def run(self, capsys, workload: str, *extra: str, seed: int = 2**31 + 7,
            seconds: int = 1) -> dict:
        """One run of ``workload`` on the CPU, the look for a chip skipped;
        returns its result line."""
        import run
        argv = ["--workload", workload, "--seed", str(seed),
                "--seconds", str(seconds), "--trace", "0", *extra]
        assert run.main(argv, require_tpu=False, root=str(self.path)) == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture
def bench_copy(tmp_path):
    return BenchCopy(tmp_path)


@pytest.fixture
def tiny(bench_copy):
    """The committed cells at sizes the CPU runs in seconds."""
    c = bench_copy
    c.update("bench/configs/kosarak.json", corpus_sets=2000)
    c.update("bench/configs/dblp.json", corpus_sets=1000)
    c.update("bench/traffic/batch.json", rows_per_call=256)
    c.update("bench/configs/dblp_serve.json", corpus_sets=500)
    c.update("bench/traffic/serve.json", rate_per_s=40)
    return c
