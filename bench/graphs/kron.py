"""Graph500 Kronecker graph: ``2**scale`` vertices, ``edge_factor`` edges
a vertex drawn, a power-law degree distribution.

As the Graph500 specification's generator (graph500.org, its reference
``kronecker_generator``): each of ``edge_factor · 2**scale`` edges picks
one quadrant of the adjacency matrix per bit of the ids, with
probabilities ``initiator`` = A, B, C (D = 1 − A − B − C); the vertex
ids are then permuted at random.  The
benchmark keeps the undirected graph the kernels see: self-loops and
repeated edges dropped, isolated vertices kept.

Spec keys: ``scale``, ``edge_factor``, ``initiator`` ([A, B, C]) and
``seed``; the same spec gives the same arrays.
"""
from __future__ import annotations

import numpy as np

from bench.graphs import GraphData


def make(spec: dict) -> GraphData:
    scale, factor = int(spec["scale"]), int(spec["edge_factor"])
    a, b, c = (float(p) for p in spec["initiator"])
    if not 0 < scale <= 30:
        raise ValueError(f"kron scale {scale} is outside 1..30 (int32 ids)")
    n, m = 1 << scale, factor << scale
    rng = np.random.default_rng(int(spec["seed"]))
    i = np.zeros(m, np.int32)
    j = np.zeros(m, np.int32)
    a_norm, c_norm = a / (a + b), c / (1.0 - (a + b))
    for bit in range(scale):
        i_bit = rng.random(m, np.float32) > a + b
        j_bit = rng.random(m, np.float32) > np.where(i_bit, c_norm, a_norm)
        i |= i_bit.astype(np.int32) << bit
        j |= j_bit.astype(np.int32) << bit
        del i_bit, j_bit
    perm = rng.permutation(n).astype(np.int32)
    i, j = perm[i], perm[j]
    key = np.minimum(i, j).astype(np.int64) << scale
    key |= np.maximum(i, j)
    key = np.unique(key[i != j])
    del i, j
    return GraphData(n=n, src=(key >> scale).astype(np.int32),
                     dst=(key & (n - 1)).astype(np.int32))
