"""The plain reference: exact integer Jaccard on host numpy.

It imports nothing of the program and takes nothing it made. For an R
set ``r`` and threshold ``P/Q`` in lowest terms, ``s`` qualifies when

    (P + Q) * |r & s|  >=  P * (|r| + |s|)   and   |r & s| > 0,

which is ``|r & s| / |r | s| >= P/Q`` with integers only. Only S sets
whose size lies in ``[ceil(P|r|/Q), floor(Q|r|/P)]`` can qualify, so
those alone are counted.

``float32=True`` is the control: the same counts under the float32
predicate ``f * (1 + t) >= t * (|r| + |s|)``, the approximate answer the
exact guarantee rules out.
"""
from __future__ import annotations

from fractions import Fraction

import numpy as np


class Reference:
    """S, size-sorted once; answers one R set at a time."""

    def __init__(self, s, universe: int, threshold: str):
        elems, offs = s
        sizes = np.diff(offs)
        self.order = np.argsort(sizes, kind="stable")
        ln = sizes[self.order]
        self.sizes = ln
        self.offs = np.concatenate([[0], np.cumsum(ln)]).astype(np.int64)
        src = np.repeat(offs[self.order], ln) + (
            np.arange(int(self.offs[-1])) - np.repeat(self.offs[:-1], ln))
        self.elems = elems[src]
        self.universe = int(universe)
        frac = Fraction(threshold)
        self.p, self.q = frac.numerator, frac.denominator
        self.t = float(frac)

    def matches(self, r: np.ndarray, float32: bool = False) -> np.ndarray:
        """Sorted S row ids that qualify against the R set ``r``."""
        k = len(r)
        if not k:
            return np.zeros(0, np.int64)
        lo = -(-self.p * k // self.q)
        hi = self.q * k // self.p
        a, b = np.searchsorted(self.sizes, [lo, hi], side="left")[0], \
            np.searchsorted(self.sizes, hi, side="right")
        if a >= b:
            return np.zeros(0, np.int64)
        member = np.zeros(self.universe, np.int32)
        member[r] = 1
        e0, e1 = self.offs[a], self.offs[b]
        f = np.add.reduceat(member[self.elems[e0:e1]],
                            self.offs[a:b] - e0).astype(np.int64)
        z = self.sizes[a:b].astype(np.int64)
        if float32:
            t = np.float32(self.t)
            ok = (f.astype(np.float32) * (np.float32(1) + t)
                  >= t * (k + z).astype(np.float32))
        else:
            ok = (self.p + self.q) * f >= self.p * (k + z)
        ok &= f > 0
        return np.sort(self.order[a + np.flatnonzero(ok)])
