"""Distance-1 coloring: a cell differs from each of its neighbors."""
from __future__ import annotations

import numpy as np

from bench.references import CSR


def hood(ref) -> CSR:
    return ref.csr


def improper(ref, colors: np.ndarray) -> int:
    a, b = colors[ref.g.src], colors[ref.g.dst]
    return int(np.count_nonzero((a == b) & (a > 0)))
