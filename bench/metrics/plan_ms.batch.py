"""Planner probe, scoring and choice (``repro.plan``), mean ms a call."""
from spans import ms_per_root, window_roots


def read(run):
    return ms_per_root(window_roots(run, "repro.join", "calls"),
                       {"repro.plan"})
