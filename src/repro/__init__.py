"""Candidate-free R-S set similarity joins with filter-and-verification
trees (paper reproduction).

The public surface is the front door::

    import repro
    result = repro.join(R, S, 0.8)          # method='auto' cost model
    result.pairs, result.stats, result.plan
    repro.self_join(C, 0.8).pairs           # each unordered pair (a < b) once

Everything re-exported here resolves lazily (PEP 562) so ``import
repro`` stays free of jax/device initialization until a symbol is
actually touched — the subpackages (``repro.core``, ``repro.kernels``,
...) keep importing exactly as before.
"""
from __future__ import annotations

import importlib

_EXPORTS = {
    "join": "repro.api",
    "self_join": "repro.api",
    "JoinResult": "repro.api",
    "as_collection": "repro.api",
    "JoinPlan": "repro.core.planner",
    "JoinStats": "repro.core.planner",
    "PlannerError": "repro.core.planner",
    "build_plan": "repro.core.planner",
    "probe_features": "repro.core.planner",
    "SetCollection": "repro.core.sets",
    "cf_rs_join_device": "repro.core.tile_join",
    "mr_cf_rs_join": "repro.core.distributed",
    "global_config": "repro.core.config",
}

__all__ = sorted(_EXPORTS)


def __getattr__(name: str):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module 'repro' has no attribute {name!r}")
    val = getattr(importlib.import_module(mod), name)
    globals()[name] = val  # cache: next access skips the import machinery
    return val


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
