"""Distance-2 coloring: a cell differs from every cell within two hops."""
from __future__ import annotations

import numpy as np

from bench.references import PAD


def hood(ref) -> np.ndarray:
    """Distinct distance-1 and distance-2 neighbors of each row.

    Duplicates and the row itself are replaced by ``PAD``; the row is
    sorted so padding sorts first.
    """
    tab = ref.table
    n, w = tab.shape
    two = np.where(tab[:, :, None] >= 0, tab[np.maximum(tab, 0)], PAD)
    out = np.concatenate([tab, two.reshape(n, w * w)], axis=1)
    out = np.where(out == np.arange(n)[:, None], PAD, out)
    out.sort(axis=1)
    dup = np.zeros_like(out, bool)
    dup[:, 1:] = out[:, 1:] == out[:, :-1]
    out[dup] = PAD
    out.sort(axis=1)
    return out


def improper(ref, colors: np.ndarray) -> int:
    """Every distance-≤2 pair is two cells of one closed neighborhood:
    count the repeated colors in each."""
    tab = ref.table
    closed = np.concatenate([colors[:, None], np.where(
        tab >= 0, colors[np.maximum(tab, 0)], 0)], axis=1)
    closed.sort(axis=1)
    return int(np.count_nonzero((closed[:, 1:] == closed[:, :-1])
                                & (closed[:, 1:] > 0)))
