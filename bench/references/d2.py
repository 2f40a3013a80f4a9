"""Distance-2 coloring: a cell differs from every cell within two hops."""
from __future__ import annotations

import numpy as np

from bench.references import CSR

# The distance-≤2 entries the reference will build: Σ deg² bounds them.
# 2**29 int32 entries are 2 GiB.  A hex mesh (Σ deg² = 36 a cell) passes
# up to 14.9M cells; a power-law graph is past it long before its d2
# coloring is a deployment (Graph500 scale 16: 1.2e9).
ENTRY_BUDGET = 1 << 29
# Candidate entries (one hop and two hops, repeats included) expanded at
# once while the neighborhoods are built.
BLOCK = 1 << 20


def hood(ref) -> CSR:
    """Distinct distance-1 and distance-2 neighbors of each row, sorted,
    built a block of rows at a time."""
    one = ref.csr
    n, deg = ref.g.n, one.sizes()
    estimate = int(deg @ deg)
    if estimate > ENTRY_BUDGET:
        raise ValueError(
            f"distance-2 neighborhoods of this graph may hold Σ deg² = "
            f"{estimate:,} entries, past the reference's budget of "
            f"{ENTRY_BUDGET:,} (bench/references/d2.py ENTRY_BUDGET)")
    two_hop = np.r_[0, np.cumsum(deg[one.indices])][one.indptr]
    before = np.r_[0, np.cumsum(deg + np.diff(two_hop))]
    sizes, cells, r0 = [], [], 0
    while r0 < n:
        r1 = int(np.searchsorted(before, before[r0] + BLOCK, "right")) - 1
        r1 = max(r1, r0 + 1)
        block = _block(one, deg, r0, r1)
        sizes.append(block.sizes())
        cells.append(block.indices)
        r0 = r1
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.concatenate(sizes), out=indptr[1:])
    return CSR(indptr, np.concatenate(cells))


def _block(one: CSR, deg: np.ndarray, r0: int, r1: int) -> CSR:
    """Rows ``r0:r1``'s distinct cells within two hops (rows from 0)."""
    mid = one.indices[one.indptr[r0]:one.indptr[r1]]
    row = np.repeat(np.arange(r1 - r0, dtype=np.int64), deg[r0:r1])
    lens = deg[mid]
    at = np.repeat(one.indptr[mid] - np.r_[0, np.cumsum(lens)[:-1]], lens)
    far = one.indices[at + np.arange(at.size)]
    row = np.concatenate([row, np.repeat(row, lens)])
    cell = np.concatenate([mid, far])
    keep = cell != row + r0
    n = deg.size
    key = np.unique(row[keep] * n + cell[keep])
    indptr = np.zeros(r1 - r0 + 1, np.int64)
    np.cumsum(np.bincount(key // n, minlength=r1 - r0), out=indptr[1:])
    return CSR(indptr, (key % n).astype(np.int32))


def improper(ref, colors: np.ndarray) -> int:
    """Every distance-≤2 pair is two cells of one closed neighborhood:
    count the repeated colors in each.

    One sort of ``row * k + color`` over the closed neighborhoods' entries
    (an uncolored cell counts as color 0, which repeats harmlessly).
    """
    one, n = ref.csr, ref.g.n
    k = int(colors.max(initial=0)) + 1
    dtype = np.int32 if n * k < 2**31 else np.int64
    key = np.concatenate([np.arange(n, dtype=dtype),
                          one.rows().astype(dtype, copy=False)])
    key *= k
    key += np.maximum(np.concatenate([colors, colors[one.indices]]),
                      0).astype(dtype, copy=False)
    key.sort()
    hit = key[1:] == key[:-1]
    hit &= key[1:] % k != 0
    return int(np.count_nonzero(hit))
