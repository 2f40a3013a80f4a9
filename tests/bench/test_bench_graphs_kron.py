"""The Graph500 Kronecker generator (``bench/graphs/kron.py``): seeded,
simple, skewed, and held by the reference without a max-degree table."""
import sys
import tracemalloc
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import graphs  # noqa: E402
from bench.reference import Reference  # noqa: E402


def kron(scale, seed=2**33 + 1, edge_factor=16):
    return graphs.make({"kind": "kron", "scale": scale,
                        "edge_factor": edge_factor,
                        "initiator": [0.57, 0.19, 0.19], "seed": seed})


def test_same_seed_same_arrays_other_seed_others():
    a, b, c = kron(10), kron(10), kron(10, seed=2**33 + 2)
    assert np.array_equal(a.src, b.src) and np.array_equal(a.dst, b.dst)
    assert a.src.size != c.src.size or not np.array_equal(a.src, c.src)


@pytest.mark.parametrize("scale", [1, 6, 11])
def test_vertex_count_is_two_to_the_scale(scale):
    g = kron(scale)
    assert g.n == 2**scale and g.grid is None
    assert g.src.dtype == g.dst.dtype == np.int32
    assert 0 <= min(g.src.min(), g.dst.min()) <= max(g.src.max(),
                                                     g.dst.max()) < g.n


def test_no_self_loops_or_repeated_edges():
    g = kron(12)
    assert np.all(g.src != g.dst)
    key = np.minimum(g.src, g.dst).astype(np.int64) * g.n + np.maximum(
        g.src, g.dst)
    assert np.unique(key).size == key.size
    # Repeats were dropped, not avoided: the draw names 16 edges a vertex.
    assert key.size < 16 * g.n


def test_degrees_are_skewed():
    g = kron(14)
    deg = np.bincount(np.r_[g.src, g.dst], minlength=g.n)
    assert deg.max() > 50 * deg.mean()
    assert np.count_nonzero(deg == 0) > 0            # isolated vertices kept
    # Ids are permuted: the hubs are not the ids with few set bits that
    # the draw favours (0, 1, 2, 4, ...).
    hubs = np.argsort(deg)[-10:]
    assert np.mean([bin(v).count("1") for v in hubs]) > 3


def test_reference_builds_without_a_max_degree_table():
    g = kron(16)
    tracemalloc.start()
    try:
        ref = Reference("d1", g)
        ref.limits()
        sizes = ref.hood_sizes()
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    csr = ref.csr.indptr.nbytes + ref.csr.indices.nbytes
    dense = g.n * int(sizes.max()) * 4          # (n, max degree) int32
    assert peak < 10 * csr < dense / 10
