"""The least work a self-join can do, for ``self_join_roofline``.

A self-join of a corpus has to read each of its sets once, in the
smaller of its two forms (``work.set_bytes``: sparse or bitmap), and
write its result pairs, two int32 ids each. The count is the same
whatever implements the join; at the chip's HBM bandwidth it is the
least time, so a share of it is an HBM bound.
"""
from __future__ import annotations

import work


def self_join_bytes(sizes, universe: int, n_pairs: int) -> int:
    """Least HBM bytes of one self-join of sets of ``sizes``."""
    return int(work.set_bytes(sizes, universe).sum() + 8 * n_pairs)
