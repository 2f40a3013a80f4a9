"""Distance-1 coloring: a cell differs from each of its neighbors."""
from __future__ import annotations

import numpy as np


def hood(ref) -> np.ndarray:
    return ref.table


def improper(ref, colors: np.ndarray) -> int:
    a, b = colors[ref.g.src], colors[ref.g.dst]
    return int(np.count_nonzero((a == b) & (a > 0)))
