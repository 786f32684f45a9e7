"""Shared benchmark utilities: timing, CSV emission, the BENCH artifact.

Since ISSUE 5 every bench writes one consolidated artifact with a stable
top-level schema instead of accreting a JSON file per PR::

    {"schema_version": 1,
     "rows": [{"config": ..., "method": ..., "impl": ..., "metrics": {...}},
              ...]}

``config`` names the workload cell (e.g. "method_axis/largeW"),
``method`` the join family ("lfvt", "bitmap", "mr_cf", ...), ``impl``
the execution layer ("kernel" — the live row-tiled walk, run as its
compiled jnp twin — or "ref"/"jnp"), and ``metrics`` a flat name -> number
mapping. ``benchmarks/check_regression.py`` diffs two such files by
(config, method, impl) key; CI fails when a tracked metric regresses.
"""
from __future__ import annotations

import json
import time

import numpy as np

ROWS: list[str] = []

SCHEMA_VERSION = 1


def timed(fn, *args, repeat: int = 1, **kw):
    """Returns (result, best_seconds)."""
    best = float("inf")
    out = None
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        best = min(best, time.perf_counter() - t0)
    return out, best


def emit(name: str, seconds: float, derived: str = "") -> None:
    row = f"{name},{seconds * 1e6:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def bench_row(config: str, method: str, impl: str, metrics: dict) -> dict:
    """One artifact row; values coerced to plain JSON scalars."""
    def plain(v):
        if isinstance(v, (np.integer,)):
            return int(v)
        if isinstance(v, (np.floating,)):
            return float(v)
        return v
    return {"config": config, "method": method, "impl": impl,
            "metrics": {k: plain(v) for k, v in metrics.items()}}


def write_bench_json(path: str, rows: list, append: bool = False) -> None:
    """Write (or extend, with ``append=True``) a consolidated artifact."""
    if append:
        try:
            with open(path) as fh:
                doc = json.load(fh)
            rows = list(doc.get("rows", [])) + list(rows)
        except FileNotFoundError:
            pass
    with open(path, "w") as fh:
        json.dump({"schema_version": SCHEMA_VERSION, "rows": list(rows)},
                  fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"# wrote {path} ({len(rows)} rows)")


def load_bench_rows(path: str) -> dict:
    """-> {(config, method, impl): metrics} index of a consolidated
    artifact; raises on schema mismatch so the gate never silently
    compares incompatible files."""
    with open(path) as fh:
        doc = json.load(fh)
    if doc.get("schema_version") != SCHEMA_VERSION:
        raise ValueError(
            f"{path}: schema_version {doc.get('schema_version')!r} != "
            f"{SCHEMA_VERSION}")
    return {(r["config"], r["method"], r["impl"]): r["metrics"]
            for r in doc["rows"]}
