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
"""
from __future__ import annotations

import numpy as np

from bench import references
from bench.references import PAD


def neighbor_table(g) -> np.ndarray:
    """``(n, max degree)`` int32 neighbor ids, padded with ``PAD``."""
    s = np.concatenate([g.src, g.dst]).astype(np.int64)
    d = np.concatenate([g.dst, g.src]).astype(np.int32)
    order = np.argsort(s, kind="stable")
    s, d = s[order], d[order]
    deg = np.bincount(s, minlength=g.n)
    start = np.cumsum(deg) - deg
    tab = np.full((g.n, int(deg.max(initial=1))), PAD, np.int32)
    tab[s, np.arange(s.size) - start[s]] = d
    return tab


class Reference:
    """The reference's view of one graph under one problem."""

    def __init__(self, problem: str, g):
        self.problem, self.g = problem, g
        self._rules = references.load(problem)
        self._tab = self._hood = None

    @property
    def table(self) -> np.ndarray:
        """Distance-1 neighbor ids, ``PAD``-ed."""
        if self._tab is None:
            self._tab = neighbor_table(self.g)
        return self._tab

    @property
    def hood(self) -> np.ndarray:
        """The cells each row must differ from, ``PAD``-ed."""
        if self._hood is None:
            self._hood = self._rules.hood(self)
        return self._hood

    def hood_sizes(self) -> np.ndarray:
        """Distinct cells each row must differ from."""
        return (self.hood >= 0).sum(axis=1)

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
        """First fit for every active cell at once, no conflict resolution."""
        n = self.g.n
        c = (np.zeros(n, np.int32) if colors0 is None
             else np.array(colors0, np.int32))
        active = np.ones(n, bool) if mask is None else np.asarray(mask)
        c[active] = 0
        hood = self.hood
        held = np.where(hood >= 0, c[np.maximum(hood, 0)], 0)
        taken = np.zeros((n, max(int(held.max(initial=0)), 0) + 2), bool)
        taken[np.arange(n)[:, None], held] = True
        first = np.argmin(taken[:, 1:], axis=1) + 1
        return np.where(active, first, c).astype(np.int32)


def passes(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)
