"""The least work a join can do, for the roofline shares.

A join has to read each R set and each S set that some R set's Lemma-3.1
size window admits, once, and write its result pairs. A set is read in
the smaller of its two forms: sparse (4 bytes an element) or bitmap
(4 bytes a 32-bit word of the universe). A pair is two int32 ids. This
count is the same whatever implements the join; divided by the chip's
HBM bandwidth it is the least time, so a share of it is an HBM bound.
The chip's integer VPU peak is not published, so no bound on operations
is used.
"""
from __future__ import annotations

from fractions import Fraction

import numpy as np


def set_bytes(sizes: np.ndarray, universe: int) -> np.ndarray:
    words = (int(universe) + 31) // 32
    return 4 * np.minimum(np.asarray(sizes, np.int64), words)


def admitted(r_sizes: np.ndarray, s_sizes: np.ndarray,
             threshold: str) -> np.ndarray:
    """Mask of the S sets whose size lies in some R set's Jaccard window
    ``[ceil(P|r|/Q), floor(Q|r|/P)]``."""
    frac = Fraction(threshold)
    p, q = frac.numerator, frac.denominator
    k = np.unique(np.asarray(r_sizes, np.int64))
    lo, hi = -(-p * k // q), q * k // p
    z = np.asarray(s_sizes, np.int64)
    top = int(max(z.max(initial=0), hi.max(initial=0))) + 2
    cover = np.zeros(top, np.int64)
    np.add.at(cover, np.minimum(lo, top - 1), 1)
    np.add.at(cover, np.minimum(hi + 1, top - 1), -1)
    return np.cumsum(cover)[z] > 0


def join_bytes(r_sizes, s_sizes, universe: int, threshold: str,
               n_pairs: int) -> int:
    """Least HBM bytes of one R-S join."""
    s_sizes = np.asarray(s_sizes)
    mask = admitted(r_sizes, s_sizes, threshold)
    return int(set_bytes(r_sizes, universe).sum()
               + set_bytes(s_sizes[mask], universe).sum() + 8 * n_pairs)
