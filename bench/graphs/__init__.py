"""Graph data generators, one module per ``graph.kind`` of a configuration.

A module provides ``make(spec: dict) -> GraphData``.  The benchmark makes
the graph's data; the program under test only builds its own tables from
it.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np


@dataclasses.dataclass(frozen=True)
class GraphData:
    """An undirected graph as the benchmark made it.

    ``src``/``dst`` list every undirected edge once.  ``grid`` is the
    row-major shape of the vertex ids where the graph is a mesh (vertex
    ``i`` sits at ``np.unravel_index(i, grid)``), else ``None``.
    """

    n: int
    src: np.ndarray
    dst: np.ndarray
    grid: tuple[int, ...] | None = None


def make(spec: dict) -> GraphData:
    """The graph of a configuration's ``graph`` entry, by its ``kind``."""
    return importlib.import_module(f"bench.graphs.{spec['kind']}").make(spec)
