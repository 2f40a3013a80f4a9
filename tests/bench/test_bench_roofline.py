"""The least-bytes function and the reference's neighborhoods, by hand."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.graphs import hex as hexmesh  # noqa: E402
from bench.reference import Reference  # noqa: E402
from bench.roofline import least_bytes  # noqa: E402


def sizes(problem, extents):
    g = hexmesh.make({"kind": "hex", "extents": extents})
    return Reference(problem, g).hood_sizes()


def test_d1_bytes_count_real_edges_only():
    # 3x1x1 path: degrees 1, 2, 1 -> 4 directed edges, 3 rows.
    assert least_bytes(sizes("d1", [3, 1, 1]), None) == 8 * 4 + 8 * 3
    # 2x2x2 cube: 8 cells of degree 3 -> 24 directed edges.
    assert least_bytes(sizes("d1", [2, 2, 2]), None) == 8 * 24 + 8 * 8


def test_d2_bytes_count_distinct_two_hop_neighbors():
    # 3x1x1 path: the ends reach 2 cells, the middle 2 -> 6, 3 rows.
    assert least_bytes(sizes("d2", [3, 1, 1]), None) == 8 * 6 + 8 * 3
    # 2x2x2 cube: each cell reaches 3 + 3 = 6 others (all but the
    # opposite corner) -> 48.
    assert least_bytes(sizes("d2", [2, 2, 2]), None) == 8 * 48 + 8 * 8
    # interior cell of a 5^3 mesh: 6 at distance 1, 18 at distance 2.
    s = sizes("d2", [5, 5, 5])
    assert s.reshape(5, 5, 5)[2, 2, 2] == 24 and s.max() == 24


def test_masked_request_counts_its_active_rows():
    s = sizes("d1", [3, 1, 1])
    mask = np.array([False, True, False])
    assert least_bytes(s, mask) == 8 * 2 + 8 * 1
    assert least_bytes(s, np.zeros(3, bool)) == 0


@pytest.mark.parametrize("problem,limit", [("d1", 7), ("d2", 25)])
def test_first_fit_limit_of_a_hex_mesh(problem, limit):
    g = hexmesh.make({"kind": "hex", "extents": [5, 5, 5]})
    assert Reference(problem, g).max_color_limit() == limit
