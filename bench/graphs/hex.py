"""Hexahedral mesh: the cells of an ``nx × ny × nz`` grid, 6-point stencil.

The paper's weak-scaling input (average and maximum degree 6).  Cell ids
are x-major (``id = (x·ny + y)·nz + z``), so contiguous id blocks are the
x-slabs the paper partitions into.  Any symmetry of the box that keeps its
extents maps this graph onto itself, so there is one such mesh per
``extents``: the graph carries no seed.
"""
from __future__ import annotations

import numpy as np

from bench.graphs import GraphData


def make(spec: dict) -> GraphData:
    nx, ny, nz = (int(e) for e in spec["extents"])
    ids = np.arange(nx * ny * nz, dtype=np.int32).reshape(nx, ny, nz)
    src = np.concatenate([ids[:-1].ravel(), ids[:, :-1].ravel(),
                          ids[:, :, :-1].ravel()])
    dst = np.concatenate([ids[1:].ravel(), ids[:, 1:].ravel(),
                          ids[:, :, 1:].ravel()])
    return GraphData(n=ids.size, src=src, dst=dst, grid=(nx, ny, nz))
