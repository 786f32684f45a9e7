"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Device planes are ``/device:<KIND>:<n>``; their op events are on the line
named ``XLA Ops`` (a ``while`` op's event spans its body's ops, so op
times in the breakdown nest; the unions below do not double count). Host spans are the benchmark's own
``jax.profiler.TraceAnnotation`` events, named ``bench.*``, on host
threads. Both are read from one file, on the profiler's one clock.

* window: from the first ``bench.*`` span's start to the last one's end.
* busy: the union of op intervals on a device, inside the window,
  averaged over the devices that ran any op.
* span device time: per span name, the union of op intervals that lie
  inside that span's instances, host<->device transfers left out.
* breakdown: the ops that took most device time, and the longest idle
  gaps, each labelled by the span the host was in at the gap's middle.
"""
from __future__ import annotations

import glob
import os

import numpy as np

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
# instructions that move data between host and device, by HLO name
TRANSFERS = ("infeed", "outfeed", "send", "recv", "copy-start",
             "copy-done")
TOP = 10


def find(directory: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (start, end) rows into disjoint sorted intervals."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.concatenate([[True], iv[1:, 0] > ends[:-1]])
    starts = iv[new, 0]
    stops = np.append(ends[np.flatnonzero(new)[1:] - 1], ends[-1])
    return np.stack([starts, stops], axis=1)


def _length(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two disjoint sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            out.append((lo, hi))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.array(out, np.float64).reshape(-1, 2)


def load(path: str):
    """The trace at ``path`` (``.xplane.pb``, or the same gzipped)."""
    import gzip

    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            return ProfileData.from_serialized_xspace(fh.read())
    return ProfileData.from_file(path)


def read(data) -> tuple[dict, list]:
    """-> ({device plane: [(name, start_s, end_s)]}, [(span, start, end)])."""
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events]
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return devices, spans


def reduce(devices: dict, spans: list) -> dict | None:
    """The numbers the per-layer metrics read; None without device ops
    or spans."""
    if not devices or not spans:
        return None
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    by_span: dict = {}
    for name, s, e in spans:
        by_span.setdefault(name, []).append((s, e))
    span_iv = {k: _union(np.array(v, np.float64)) for k, v in by_span.items()}
    busy, span_dev, op_time = [], {}, {}
    gaps = []
    for ops in devices.values():
        iv = _clip(np.array([(s, e) for _, s, e in ops], np.float64), lo, hi)
        merged = _union(iv)
        busy.append(_length(merged))
        compute = _union(_clip(np.array(
            [(s, e) for n, s, e in ops if not is_transfer(n)],
            np.float64).reshape(-1, 2), lo, hi))
        for k, siv in span_iv.items():
            span_dev[k] = span_dev.get(k, 0.0) + _length(
                _intersect(compute, siv))
        for n, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_time[n] = op_time.get(n, 0.0) + d
        edges = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
        for g0, g1 in edges:
            if g1 > g0:
                gaps.append((float(g1 - g0), _label(span_iv, (g0 + g1) / 2)))
    n_dev = len(devices)
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": float(hi - lo),
        "busy_s": float(sum(busy) / n_dev),
        "span_device_s": {k: v / n_dev for k, v in span_dev.items()},
        "device_ops": [[n, v / n_dev] for n, v in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[label, g] for g, label in gaps[:TOP]],
        "devices": n_dev,
    }


def is_transfer(op: str) -> bool:
    """``%copy-start.3 = ...`` names its instruction before `` = ``."""
    name = op.split(" = ", 1)[0].lstrip("%")
    return name.startswith(TRANSFERS)


def _label(span_iv: dict, at: float) -> str:
    inside = [k for k, iv in span_iv.items()
              if len(iv) and np.any((iv[:, 0] <= at) & (at < iv[:, 1]))]
    return "+".join(sorted(inside)) if inside else "outside_spans"


def reduce_file(path: str) -> dict | None:
    return reduce(*read(load(path)))
