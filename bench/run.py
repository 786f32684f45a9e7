#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` at the checkout's root. It
names a configuration (``bench/configs/<config>.json``: the deployment's
shapes and scale) and a traffic mix (``bench/traffic/<traffic>.json``: the
name of its load loop, ``bench/loops/<loop>.py``, and the loop's
parameters; see ``harness.py``). Per-layer metrics are readers of their
own, ``bench/metrics/<metric>.py``, each with a ``read(run)`` that
returns a number or None. All are found by name, so a new cell,
configuration, mix, loop or metric is a new file and an entry.

Steps: check for a TPU (none, or fewer chips than the cell asks for:
exit non-zero, no result); point JAX's compilation cache at a fixed
directory in the checkout (or ``JAX_COMPILATION_CACHE_DIR``); build the
data from ``--seed``; warm every shape; measure for ``--seconds``; read
peak device memory; compare a seeded sample of the window's answers with
the plain reference (``reference.py``); print the numbers compared beside
their limits as the last lines on standard error, and the result as the
last line on standard output. ``--trace 1`` traces the start of the
window and prints the per-layer metrics instead of the end-to-end ones.

``--control`` puts the reference's float32 predicate in the program's
place at the comparison (it must come out not correct).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import xplane  # noqa: E402

# the numbers compared and their limits: every one an exact count
LIMITS = {"missing_pairs": 0, "extra_pairs": 0, "unanswered": 0}


def _json(path: str):
    with open(path) as fh:
        return json.load(fh)


def load_spec(workload: str, root: str = ROOT) -> dict:
    """The cell, its configuration, its traffic and its per-layer metrics,
    all found by name."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg = _json(os.path.join(root, "bench", "configs",
                             cell["config"] + ".json"))
    mix = _json(os.path.join(root, "bench", "traffic",
                             cell["traffic"] + ".json"))
    reports = {m["name"] for m in bench["end_to_end"]
               if workload in m.get("workloads", [workload])}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in reports)]
    return {"bench": bench, "cell": cell, "config": cfg, "traffic": mix,
            "end_to_end": [m for m in bench["end_to_end"]
                           if m["name"] in reports],
            "per_layer": per_layer}


def load_metric(name: str, root: str = ROOT):
    return harness.load_module(os.path.join(root, "bench"), "metrics",
                               name).read


def device_or_exit(chips: int) -> dict:
    """The device JAX found; exits non-zero unless it is ``chips`` TPUs."""
    import jax
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu":
        raise SystemExit(f"run.py: no TPU found ({dev['platform']}); "
                         "no cell was run")
    if dev["count"] < chips:
        raise SystemExit(f"run.py: {chips} chips asked for, "
                         f"{dev['count']} found")
    return dev


def use_compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    # JAX's own threshold stands: programs that compile in under a second
    # are not kept, so a run's tiny programs compile alike in every run
    # and no run is sped up by what an earlier one happened to meet
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def peak_bytes(count: int) -> int | None:
    import jax
    peaks = []
    for d in jax.devices()[:count]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def peaks_for(kind: str) -> dict:
    table = _json(os.path.join(BENCH, "peaks.json"))
    if kind not in table["devices"]:
        raise SystemExit(f"run.py: no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table["devices"][kind]


class Run:
    """What a per-layer reader sees: the driver's record, the reduced
    trace (None when untraced or empty) and the chip's peaks."""

    def __init__(self, record: dict, trace: dict | None, peaks: dict):
        self.record, self.trace, self.peaks = record, trace, peaks


def main(argv=None, require_tpu: bool = True, root: str = ROOT) -> int:
    """``require_tpu=False`` and ``root`` are for the harness's own tests:
    they run it on the CPU and on a copy of the benchmark."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    spec = load_spec(args.workload, root)
    chips = int(spec["cell"]["chips"])
    if require_tpu:
        device = device_or_exit(chips)
    else:
        import jax
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": chips}
    peaks = {}
    if require_tpu:
        peaks = peaks_for(device["kind"])
        use_compile_cache()
    sys.path.insert(0, os.path.join(ROOT, "src"))

    def log(msg: str) -> None:
        print(f"[{args.workload}] {msg}", flush=True)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        tracer = harness.Tracer(trace_dir,
                                float(spec["traffic"]["trace_seconds"]))
        compiles = harness.Compiles()
        loop = harness.find_loop(spec["traffic"]["loop"],
                                 os.path.join(root, "bench"))
        record = loop.run(spec["config"], spec["traffic"], args.seed,
                      args.seconds, tracer, compiles, args.control, log,
                      lambda: time.perf_counter() - T_START)
        device["memory_peak_bytes"] = peak_bytes(chips)
        reduced = None
        if trace_dir:
            path = xplane.find(trace_dir)
            reduced = xplane.reduce_file(path) if path else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    checks = record["check"]()
    checks["unanswered"] = record["failed"]
    log("check " + " ".join(f"{k}={v}" for k, v in checks.items()))

    if args.trace:
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
        run = Run(record, reduced, peaks)
        metrics = {}
        for m in spec["per_layer"]:
            value = load_metric(m["name"], root)(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(record["end_to_end"], setup_s=record["setup_s"])
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    compared = {k: {"value": checks[k], "limit": lim}
                for k, lim in LIMITS.items()}
    correct = all(v["value"] <= v["limit"] for v in compared.values())
    out = {"correct": correct, "attempted": record["attempted"],
           "failed": record["failed"], "metrics": metrics, "device": device}
    if args.trace and reduced is not None:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = compared
    for k, v in compared.items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
