"""The least bytes one coloring request must move through HBM.

Whatever implements it, a request has to read, for each active row, each
cell the row must differ from (its index and its color, 4 + 4 B), and
read and write the row's own color (4 + 4 B).  ``d1`` counts the real
distance-1 edges, ``d2`` the distinct distance-≤2 neighbors; padding is
never counted, and a masked request counts its active rows only.  At the
chip's peak HBM bandwidth these bytes give the least time of the local
step, the numerator of ``local_roofline``.
"""
from __future__ import annotations

import numpy as np

NEIGHBOR_BYTES = 8      # index + color of a cell a row must differ from
ROW_BYTES = 8           # the row's color, read and written


def least_bytes(hood_sizes: np.ndarray, mask: np.ndarray | None) -> int:
    """``hood_sizes`` from ``Reference.hood_sizes``; ``mask`` None = all."""
    sizes = hood_sizes if mask is None else hood_sizes[mask]
    return int(NEIGHBOR_BYTES * sizes.sum(dtype=np.int64)
               + ROW_BYTES * sizes.size)
