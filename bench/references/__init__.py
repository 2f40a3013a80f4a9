"""What the reference knows of each problem, one module per configuration
``problem``.

A module provides, for a :class:`bench.reference.Reference` ``ref``:

* ``hood(ref)`` — a :class:`CSR`: the distinct cells each row must
  differ from, the row itself left out;
* ``improper(ref, colors)`` — the pairs within the problem's distance
  that share a color.

Everything else the reference checks is the same for every problem.
Nothing here sizes an array by the largest degree: a row's cells are a
slice of one flat list, so a hub of a power-law graph costs what its
edges cost.
"""
from __future__ import annotations

import importlib
from typing import NamedTuple

import numpy as np


class CSR(NamedTuple):
    """Row ``v``'s cells are ``indices[indptr[v]:indptr[v + 1]]``."""

    indptr: np.ndarray      # (n + 1,) int64
    indices: np.ndarray     # (nnz,) int32

    def sizes(self) -> np.ndarray:
        return np.diff(self.indptr)

    def rows(self) -> np.ndarray:
        """The row of each entry of ``indices``."""
        n = self.indptr.size - 1
        return np.repeat(np.arange(n, dtype=np.int32), self.sizes())


def load(problem: str):
    return importlib.import_module(f"bench.references.{problem}")
