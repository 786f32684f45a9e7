"""Unified front door for the candidate-free R-S similarity join.

``repro.join(R, S, threshold)`` is the one entry point (ISSUE 10): it
defaults to the cost-model planner (``method='auto'``, core/planner.py,
DESIGN.md §14), routes to the single-device tile driver or the MapReduce
driver by whether ``n_shards``/``mesh`` are given, and returns a
:class:`JoinResult` carrying the pairs, optional dense mask, the typed
:class:`~repro.core.planner.JoinStats` view, and the resolved
:class:`~repro.core.planner.JoinPlan` so callers can inspect *why* a
path was taken. The legacy drivers (``cf_rs_join_device``,
``mr_cf_rs_join``) remain as thin, unchanged-signature delegates.

Inputs may be :class:`~repro.core.sets.SetCollection` instances or plain
sequences of integer element arrays (coerced with ``np.unique``, ids
``0..n-1``, universe inferred from the max element unless given).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterable, Mapping

import numpy as np

from . import obs
from .core.config import global_config
from .core.planner import JoinPlan, JoinStats, PlannerError, build_plan
from .core.sets import SetCollection

__all__ = ["join", "self_join", "JoinResult", "as_collection"]


def as_collection(sets, universe: int | None = None) -> SetCollection:
    """Coerce a front-door input into a :class:`SetCollection`.

    Passes ``SetCollection`` through untouched; otherwise treats ``sets``
    as an iterable of integer element arrays (deduped + sorted via
    ``np.unique``), ids ``0..n-1`` and ``universe = max element + 1``
    unless given explicitly.
    """
    if isinstance(sets, SetCollection):
        return sets
    arrs = [np.unique(np.asarray(s, dtype=np.int64)).astype(np.int32)
            for s in sets]
    if universe is None:
        universe = int(max((int(a[-1]) for a in arrs if len(a)),
                           default=0)) + 1
    return SetCollection(arrs, int(universe),
                         np.arange(len(arrs), dtype=np.int32))


@dataclasses.dataclass(frozen=True)
class JoinResult:
    """What ``repro.join`` returns.

    ``pairs`` is the exact result set ``{(r_id, s_id)}`` regardless of
    ``emit``; ``mask`` is the dense ``(|R|, |S|)`` bool matrix (input row
    order) only for ``emit='mask'``. ``stats`` wraps the driver's stats
    mapping (``.to_dict()`` is byte-compatible with the raw dict);
    ``plan`` is the resolved planner decision.
    """

    pairs: frozenset
    mask: np.ndarray | None
    stats: JoinStats
    plan: JoinPlan

    def sorted_pairs(self) -> list[tuple[int, int]]:
        return sorted(self.pairs)

    def __len__(self) -> int:
        return len(self.pairs)

    def __iter__(self):
        return iter(self.pairs)


def _dense_mask(pairs: Iterable[tuple[int, int]], R: SetCollection,
                S: SetCollection) -> np.ndarray:
    r_row = {int(i): k for k, i in enumerate(R.ids)}
    s_row = {int(i): k for k, i in enumerate(S.ids)}
    mask = np.zeros((len(R), len(S)), dtype=bool)
    for a, b in pairs:
        mask[r_row[int(a)], s_row[int(b)]] = True
    return mask


@obs.traced("repro.join")
def join(R, S, threshold: float, *, measure: str = "jaccard",
         method: str = "auto", emit: str = "pairs",
         n_shards: int | None = None, strategy: str = "load_aware",
         mesh=None, axis: str | None = None, pad: str | None = None,
         schedule: str | None = None, pair_capacity: int | None = None,
         r_block: int | None = None, row_tile: int | None = None,
         double_buffer: bool | None = None, fault_plan=None,
         checkpoint_dir: str | None = None,
         stats: dict | None = None) -> JoinResult:
    """Candidate-free R-S set similarity join (paper front door).

    threshold: similarity threshold ``t`` for ``measure`` ('jaccard' |
        'cosine' | 'dice' | 'overlap').
    method: 'auto' (default) — the cost model probes the inputs and
        picks the cheapest rep family per DESIGN.md §14 (per shard on
        the MR loop path); or force 'popcount' | 'onehot' |
        'kernel_bitmap' | 'kernel_onehot' | 'lfvt' | 'lfvt_ref'.
    emit: 'pairs' returns the compacted pair set only; 'mask' also
        materializes the dense ``(|R|, |S|)`` bool matrix in
        ``result.mask``.
    n_shards / strategy / mesh / axis / pad / schedule: MapReduce
        controls — any of them selects ``mr_cf_rs_join`` (``n_shards``
        defaults to the mesh axis size when only ``mesh`` is given);
        all None runs the single-device tile driver.
    r_block / row_tile / pair_capacity / double_buffer: device tuning
        overrides folded into the :class:`JoinPlan`.
    fault_plan / checkpoint_dir: resilience ladder + task ledger
        (DESIGN.md §12), forwarded verbatim.
    stats: optional dict to share the raw driver stats mapping with the
        caller (the same object wrapped by ``result.stats``).

    Every kwarg combination is validated up front through the planner's
    single lattice (:func:`repro.core.planner.validate_join_args`);
    invalid ones raise :class:`~repro.core.planner.PlannerError`.

    Each call is a ``repro.join`` root span (:mod:`repro.obs`), with the
    planner's ``repro.plan`` and the driver's spans below it.
    """
    R = as_collection(R)
    S = as_collection(S)
    obs.current().set(m=len(R), n=len(S), method=method)
    mr = n_shards is not None or mesh is not None
    if mr and n_shards is None:
        ax = axis or global_config.mesh_axis
        n_shards = int(mesh.shape[ax])
    if not mr:
        for name, val in (("strategy", strategy != "load_aware"),
                          ("pad", pad is not None),
                          ("schedule", schedule is not None)):
            if val:
                raise PlannerError(
                    f"{name} applies to the MapReduce driver; pass "
                    f"n_shards= (or mesh=) to select it")
    elif r_block is not None or row_tile is not None:
        raise PlannerError(
            "r_block/row_tile tune the single-device tile driver; drop "
            "n_shards/mesh to select it")

    raw: dict[str, Any] = stats if stats is not None else {}
    if not mr:
        from .core.tile_join import cf_rs_join_device

        with obs.span("repro.plan"):
            plan = build_plan(R, S, threshold, driver="device",
                              method=method, measure=measure, emit=emit,
                              r_block=r_block, row_tile=row_tile,
                              pair_capacity=pair_capacity,
                              double_buffer=double_buffer)
        pairs = cf_rs_join_device(R, S, threshold, stats=raw, emit=emit,
                                  pair_capacity=pair_capacity,
                                  measure=measure, fault_plan=fault_plan,
                                  checkpoint_dir=checkpoint_dir, plan=plan)
    else:
        from .core.distributed import mr_cf_rs_join

        pairs = mr_cf_rs_join(R, S, threshold, n_shards, strategy=strategy,
                              method=method, mesh=mesh, axis=axis,
                              stats=raw, emit=emit, pad=pad,
                              pair_capacity=pair_capacity, measure=measure,
                              fault_plan=fault_plan,
                              checkpoint_dir=checkpoint_dir,
                              schedule=schedule)
    plan_dict: Mapping[str, Any] | None = raw.get("plan")
    plan = (JoinPlan.from_dict(plan_dict) if plan_dict is not None
            else build_plan(driver="mr" if mr else "device", method=method,
                            measure=measure, emit=emit))
    mask = _dense_mask(pairs, R, S) if emit == "mask" else None
    return JoinResult(pairs=frozenset(pairs), mask=mask,
                      stats=JoinStats.from_dict(raw), plan=plan)


# what repro.join offers and self_join does not, with why
_SELF_JOIN_LACKS = {
    "mesh": "the MapReduce self-join is not implemented",
    "n_shards": "the MapReduce self-join is not implemented",
    "fault_plan": "the resilience ladder's oracle rung is an R-S join",
    "checkpoint_dir": "the resilience ladder's oracle rung is an R-S join",
}


@obs.traced("repro.join")
def self_join(C, threshold: float, *, measure: str = "jaccard",
              method: str = "auto", **unsupported) -> JoinResult:
    """Exact similarity self-join of one collection (near-duplicate dedup).

    ``result.pairs`` holds each unordered pair of ``C``'s ids whose
    similarity under ``measure`` is at least ``threshold`` once, as
    ``(a, b)`` with ``a < b``; no set is paired with itself. (``repro.join(C,
    C, t)`` keeps R-S semantics: every ``(a, a)`` and both orders.)

    It runs the single-device tile join's self mode
    (``cf_rs_join_device(..., self_join=True)``, DESIGN.md §2): the R
    blocks are row slices of ``C``'s cached sorted device rep, each row
    meets only later rows, and each block of the dense-mask methods
    covers only the columns its size windows reach. ``method`` is chosen
    as in :func:`join`. The call is a ``repro.join`` root span with
    ``self_join=True``.

    ``mesh``/``n_shards`` (a MapReduce self-join) and
    ``fault_plan``/``checkpoint_dir`` (the resilience ladder) are not
    offered: passing one raises :class:`PlannerError` naming it.
    """
    if unsupported:
        name = next(iter(unsupported))
        if name not in _SELF_JOIN_LACKS:
            raise TypeError(
                f"self_join() got an unexpected keyword argument {name!r}")
        raise PlannerError(f"self_join takes no {name}= "
                           f"({_SELF_JOIN_LACKS[name]})")
    C = as_collection(C)
    obs.current().set(m=len(C), n=len(C), method=method, self_join=True)
    from .core.tile_join import cf_rs_join_device

    with obs.span("repro.plan"):
        plan = build_plan(C, C, threshold, driver="device", method=method,
                          measure=measure)
    raw: dict[str, Any] = {}
    pairs = cf_rs_join_device(C, C, threshold, stats=raw, measure=measure,
                              plan=plan, self_join=True)
    return JoinResult(pairs=frozenset(pairs), mask=None,
                      stats=JoinStats.from_dict(raw),
                      plan=JoinPlan.from_dict(raw["plan"]))
