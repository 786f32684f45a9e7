"""The program's own spans (``repro.obs``) over the measured window.

The window's calls or steps are the last roots of their name in the
program's ring: nothing calls the program after the window. Readers get
None where the program has no spans, where the ring holds fewer roots
than the window's calls or steps, or where a root dropped spans.
"""
from __future__ import annotations

import importlib


def window_roots(run, name: str, count_key: str):
    """The last ``run.record[count_key]`` roots named ``name``, or None."""
    try:
        obs = importlib.import_module("repro.obs")
    except ImportError:
        return None
    n = int(run.record[count_key])
    roots = obs.recent(name, n)
    if not n or len(roots) < n or any(r.dropped for r in roots):
        return None
    return roots


def ms_per_root(roots, names, self_time: bool = False):
    """Mean per root of the time its spans named ``names`` take, in ms
    (their self time with ``self_time``, so no interval counts twice)."""
    if roots is None:
        return None
    ns = sum(s.self_ns if self_time else s.duration_ns
             for r in roots for s in r.spans if s.name in names)
    return ns / len(roots) / 1e6
