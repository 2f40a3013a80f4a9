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
