"""What the reference knows of each problem, one module per configuration
``problem``.

A module provides, for a :class:`bench.reference.Reference` ``ref``:

* ``hood(ref)`` — ``(n, w)`` int32: the distinct cells each row must
  differ from, padded with ``PAD``;
* ``improper(ref, colors)`` — the pairs within the problem's distance
  that share a color.

Everything else the reference checks is the same for every problem.
"""
from __future__ import annotations

import importlib

PAD = -1


def load(problem: str):
    return importlib.import_module(f"bench.references.{problem}")
