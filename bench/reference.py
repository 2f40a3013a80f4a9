"""The plain reference: a coloring's guarantees, checked from the graph's
own data and nothing the program made.

A configuration states its guarantees (``guarantees`` in its file):

* proper — no two cells within the problem's distance share a color
  (the problem's own module, ``bench/references/<problem>.py``, says
  which cells a row must differ from);
* complete — every cell holds a color (> 0);
* frozen — in a masked request every cell outside the mask keeps the
  color it had;
* first fit — no color exceeds the largest neighborhood + 1.

:func:`check` reads one answer against them and returns the numbers that
are compared, each with its limit.  :func:`control` is this module's own
coloring with its conflict resolution left out: every active cell takes
the lowest color its neighbors do not hold, all at once.  It breaks the
"proper" guarantee and is what the checks must catch.

Neighborhoods are CSR (:class:`bench.references.CSR`), so a degree-skewed
graph costs what its edges cost, not rows × largest degree.
"""
from __future__ import annotations

import numpy as np

from bench import references
from bench.references import CSR


def neighbor_csr(g) -> CSR:
    """Each row's distinct neighbors, self-loops left out.

    A row lists its neighbors in the order the edge list first names them:
    its edges as ``src``, then its edges as ``dst``.
    """
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    key = np.minimum(src, dst).astype(np.int64) * g.n + np.maximum(src, dst)
    key[src == dst] = -1
    _, first = np.unique(key, return_index=True)
    first = np.sort(first[key[first] >= 0])
    src, dst = src[first], dst[first]
    del key
    s = np.concatenate([src, dst])
    order = np.argsort(s, kind="stable")
    indptr = np.zeros(g.n + 1, np.int64)
    np.cumsum(np.bincount(s, minlength=g.n), out=indptr[1:])
    del s
    return CSR(indptr, np.concatenate([dst, src]).astype(np.int32)[order])


class Reference:
    """The reference's view of one graph under one problem."""

    def __init__(self, problem: str, g):
        self.problem, self.g = problem, g
        self._rules = references.load(problem)
        self._csr = self._hood = None

    @property
    def csr(self) -> CSR:
        """Distance-1 neighbors."""
        if self._csr is None:
            self._csr = neighbor_csr(self.g)
        return self._csr

    @property
    def hood(self) -> CSR:
        """The distinct cells each row must differ from."""
        if self._hood is None:
            self._hood = self._rules.hood(self)
        return self._hood

    def hood_sizes(self) -> np.ndarray:
        """Distinct cells each row must differ from."""
        return self.hood.sizes()

    def max_color_limit(self) -> int:
        return int(self.hood_sizes().max(initial=0)) + 1

    def improper(self, colors: np.ndarray) -> int:
        """Pairs within the problem's distance that share a color."""
        return self._rules.improper(self, colors)

    def check(self, colors, mask=None, colors0=None) -> dict:
        """The compared numbers of one answer (limits: :meth:`limits`)."""
        colors = np.asarray(colors)
        frozen = 0
        if mask is not None:
            base = (np.zeros_like(colors) if colors0 is None
                    else np.asarray(colors0))
            frozen = int(np.count_nonzero((colors != base) & ~mask))
        return {
            "improper": self.improper(colors),
            "uncolored": int(np.count_nonzero(colors <= 0)),
            "frozen_changed": frozen,
            "max_color": int(colors.max(initial=0)),
        }

    def limits(self) -> dict:
        return {"improper": 0, "uncolored": 0, "frozen_changed": 0,
                "max_color": self.max_color_limit()}

    def control(self, mask=None, colors0=None) -> np.ndarray:
        """First fit for every active cell at once, no conflict resolution.

        One sort of the active rows' distinct (row, held color) pairs: in a
        row's sorted colors ``1, 2, ..., k`` are held exactly where the j-th
        color is ``j``, so its first fit is one more than their count.
        """
        n = self.g.n
        c = (np.zeros(n, np.int32) if colors0 is None
             else np.array(colors0, np.int32))
        active = np.ones(n, bool) if mask is None else np.asarray(mask)
        c[active] = 0
        hood, sizes = self.hood, self.hood.sizes()
        row = np.repeat(np.arange(n, dtype=np.int32),
                        np.where(active, sizes, 0))
        held = c[hood.indices[np.repeat(active, sizes)]]
        row, held = row[held > 0], held[held > 0]
        width = int(held.max(initial=0)) + 1
        key = np.unique(row.astype(np.int64) * width + held)
        row = key // width
        rank = np.arange(key.size) - np.searchsorted(row, row) + 1
        first = 1 + np.bincount(row[key % width == rank], minlength=n)
        return np.where(active, first, c).astype(np.int32)


def passes(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)
