"""The reference's checks catch each broken guarantee on hand-made
colorings, and its control breaks 'proper'."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.graphs import hex as hexmesh  # noqa: E402
from bench.reference import Reference, passes  # noqa: E402
from bench.requests import Requests  # noqa: E402


def mesh(*extents):
    return hexmesh.make({"kind": "hex", "extents": list(extents)})


def parity(g):
    x, y, z = np.unravel_index(np.arange(g.n), g.grid)
    return ((x + y + z) % 2 + 1).astype(np.int32)


def test_d1_checks_on_a_two_coloring():
    g = mesh(4, 3, 2)
    ref = Reference("d1", g)
    good = parity(g)
    assert passes(ref.check(good), ref.limits())
    bad = good.copy()
    bad[0] = bad[1]
    assert ref.check(bad)["improper"] >= 1
    bad[0] = 0
    assert ref.check(bad)["uncolored"] == 1


def test_d2_catches_distance_two_but_not_distance_three_pairs():
    g = mesh(5, 1, 1)                     # a path of 5 cells
    ref = Reference("d2", g)
    assert ref.check(np.array([1, 2, 3, 1, 2]))["improper"] == 0
    assert ref.check(np.array([1, 2, 1, 3, 2]))["improper"] >= 1
    assert Reference("d1", g).check(np.array([1, 2, 1, 3, 2]))[
        "improper"] == 0


def test_frozen_cells_must_keep_their_color():
    g = mesh(4, 4, 1)
    ref = Reference("d1", g)
    before = parity(g)
    mask = np.zeros(g.n, bool)
    mask[:4] = True
    after = before.copy()
    after[:4] = 3 - before[:4]
    assert ref.check(after, mask, np.where(mask, 0, before))[
        "frozen_changed"] == 0
    after[10] = 5
    assert ref.check(after, mask, np.where(mask, 0, before))[
        "frozen_changed"] == 1


def test_first_fit_limit_is_compared():
    g = mesh(3, 3, 3)
    ref = Reference("d1", g)
    c = parity(g)
    c[13] = 9                             # the centre, degree 6
    nums = ref.check(c)
    assert nums["improper"] == 0 and nums["max_color"] > ref.limits()[
        "max_color"]


@pytest.mark.parametrize("problem", ["d1", "d2"])
def test_control_breaks_proper(problem):
    g = mesh(6, 6, 6)
    ref = Reference(problem, g)
    nums = ref.check(ref.control())
    assert nums["improper"] > 0 and nums["uncolored"] == 0
    gen = Requests(dict(MIX, warm_start=True), g, seed=2**40 + 1)
    req = gen.next(parity(g) if problem == "d1" else None)
    if req.colors0 is not None:
        assert ref.check(ref.control(req.mask, req.colors0), req.mask,
                         req.colors0)["frozen_changed"] == 0


MIX = {"mask": "box", "fraction": 0.1, "positions": 6, "set_seed": 12345}


def boxes(g, seed, n):
    gen = Requests(MIX, g, seed=seed)
    return [tuple(np.flatnonzero(gen.next(None).mask)[[0, -1]])
            for _ in range(n)]


def test_box_masks_hold_a_tenth_and_every_seed_gets_the_same_set():
    g = mesh(128, 128, 128)
    gen = Requests(MIX, g, seed=5)
    a, b = gen.next(None).mask, gen.next(None).mask
    assert a.sum() == b.sum() == 59 ** 3
    assert not np.array_equal(a, b)
    one, two = boxes(g, 5, 12), boxes(g, 2**40 + 3, 12)
    assert one == boxes(g, 5, 12)                   # same seed, same order
    assert one[:6] == one[6:] and len(set(one)) == 6  # a cycle of 6
    assert one != two and set(one) == set(two)      # other seed, same set


@pytest.mark.parametrize("problem", ["pd3", "nothing"])
def test_a_problem_without_a_reference_module_is_an_error(problem):
    with pytest.raises(ModuleNotFoundError):
        Reference(problem, mesh(2, 2, 2))


def test_a_mask_kind_without_a_module_is_an_error():
    with pytest.raises(ModuleNotFoundError):
        Requests({"mask": "scatter", "fraction": 0.1}, mesh(4, 4, 4), 1)


def test_an_unmasked_mix_colors_every_cell_from_zero():
    gen = Requests({"mask": "none", "warm_start": False}, mesh(4, 4, 4), 1)
    req = gen.next(parity(mesh(4, 4, 4)))
    assert req.mask is None and req.colors0 is None


# --- CSR neighborhoods against Python sets --------------------------------

from collections import Counter  # noqa: E402

from bench.graphs import GraphData  # noqa: E402
from bench.graphs import kron  # noqa: E402
from bench.reference import neighbor_csr  # noqa: E402
from bench.references import d2 as d2_rules  # noqa: E402


def kron_graph(scale, seed=3):
    return kron.make({"kind": "kron", "scale": scale, "edge_factor": 16,
                      "initiator": [0.57, 0.19, 0.19], "seed": seed})


def with_isolated():
    """40 vertices, 12 of them isolated; one edge named twice."""
    rng = np.random.default_rng(11)
    src = rng.integers(0, 28, 60)
    dst = (src + rng.integers(1, 28, 60)) % 28
    src, dst = np.r_[src, dst[0]], np.r_[dst, src[0]]
    return GraphData(n=40, src=src.astype(np.int32), dst=dst.astype(np.int32))


GRAPHS = {"hex": lambda: mesh(5, 4, 3), "path": lambda: mesh(9, 1, 1),
          "isolated": with_isolated, "kron10": lambda: kron_graph(10)}


def set_hoods(g, problem):
    """Each row's cells by Python sets of neighbors."""
    nb = [set() for _ in range(g.n)]
    for u, v in zip(g.src.tolist(), g.dst.tolist()):
        if u != v:
            nb[u].add(v)
            nb[v].add(u)
    if problem == "d1":
        return nb, nb
    return nb, [nb[v].union(*(nb[u] for u in nb[v])) - {v}
                for v in range(g.n)]


def set_improper(g, problem, colors, nb):
    if problem == "d1":
        return sum(colors[u] == colors[v] > 0
                   for u, v in zip(g.src.tolist(), g.dst.tolist()))
    total = 0
    for v in range(g.n):
        held = Counter(colors[u] for u in [v, *nb[v]] if colors[u] > 0)
        total += sum(m - 1 for m in held.values())
    return total


def first_fit(hoods, colors0, active):
    c = np.where(active, 0, colors0)
    out = c.copy()
    for v in np.flatnonzero(active):
        held = {c[u] for u in hoods[v]}
        out[v] = next(k for k in range(1, len(held) + 2) if k not in held)
    return out


def row(csr, v):
    return csr.indices[csr.indptr[v]:csr.indptr[v + 1]].tolist()


@pytest.mark.parametrize("problem", ["d1", "d2"])
@pytest.mark.parametrize("graph", sorted(GRAPHS))
def test_csr_reference_equals_python_sets(graph, problem):
    g = GRAPHS[graph]()
    ref = Reference(problem, g)
    nb, hoods = set_hoods(g, problem)
    hood = ref.hood
    assert hood.indptr.dtype == np.int64 and hood.indices.dtype == np.int32
    for v in range(g.n):
        cells = row(hood, v)
        assert len(cells) == len(set(cells)) and set(cells) == hoods[v], v
    sizes = np.array([len(h) for h in hoods])
    assert np.array_equal(ref.hood_sizes(), sizes)
    assert ref.limits()["max_color"] == sizes.max() + 1
    rng = np.random.default_rng(5)
    for top in (1, 3, 8, 40):
        colors = rng.integers(0, top + 1, g.n).astype(np.int32)
        assert ref.improper(colors) == set_improper(g, problem, colors, nb)
    greedy = np.zeros(g.n, np.int32)      # a sequential first fit is proper
    for v in range(g.n):
        held = {greedy[u] for u in hoods[v]}
        greedy[v] = next(k for k in range(1, g.n + 2) if k not in held)
    assert ref.improper(greedy) == 0 == set_improper(g, problem, greedy, nb)
    active = rng.random(g.n) < 0.4
    assert np.array_equal(ref.control(active, greedy),
                          first_fit(hoods, greedy, active))


def test_control_on_a_kron_graph_is_first_fit_and_improper():
    g = kron_graph(12)
    ref = Reference("d1", g)
    _, hoods = set_hoods(g, "d1")
    full = ref.control()
    assert np.array_equal(full, first_fit(hoods, np.zeros(g.n, np.int32),
                                          np.ones(g.n, bool)))
    assert ref.check(full)["improper"] > 0
    rng = np.random.default_rng(9)
    colors0 = rng.integers(1, 30, g.n).astype(np.int32)
    active = rng.random(g.n) < 0.3
    colors0[active] = 0
    part = ref.control(active, colors0)
    assert np.array_equal(part, first_fit(hoods, colors0, active))
    nums = ref.check(part, active, colors0)
    assert nums["improper"] > 0 and nums["frozen_changed"] == 0


def test_neighbor_csr_drops_self_loops_and_repeats_in_edge_order():
    g = GraphData(n=6, src=np.array([2, 0, 2, 3, 1, 4], np.int32),
                  dst=np.array([0, 1, 2, 2, 0, 3], np.int32))
    csr = neighbor_csr(g)
    assert [row(csr, v) for v in range(6)] == [[1, 2], [0], [0, 3], [2, 4],
                                               [3], []]


def test_d2_refuses_a_graph_past_its_entry_budget(monkeypatch):
    g = kron_graph(10)
    deg = Reference("d1", g).hood_sizes()
    estimate = int(deg @ deg)
    monkeypatch.setattr(d2_rules, "ENTRY_BUDGET", estimate - 1)
    with pytest.raises(ValueError, match=f"{estimate:,}"):
        Reference("d2", g).limits()
    monkeypatch.setattr(d2_rules, "ENTRY_BUDGET", estimate)
    assert Reference("d2", g).limits()["max_color"] > 0


def test_d2_blocks_give_the_same_hoods_as_one_block(monkeypatch):
    g = kron_graph(10)
    whole = Reference("d2", g).hood
    monkeypatch.setattr(d2_rules, "BLOCK", 1000)
    blocks = Reference("d2", g).hood
    assert np.array_equal(whole.indptr, blocks.indptr)
    assert np.array_equal(whole.indices, blocks.indices)
