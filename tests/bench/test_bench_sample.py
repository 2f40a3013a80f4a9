"""The window keeps a seeded sample of its answers for the checks
(``bench.run.Sample``, ``bench.run.CHECKED``) and drops every other."""
import collections
import gc
import weakref

import numpy as np
from toycells import run_toy

from bench import run


def kept(size, seed, n):
    s = run.Sample(size, seed)
    for i in range(n):
        s.offer(i, f"item{i}")
    return s.items()


def test_sample_keeps_all_of_a_short_window_in_order():
    assert kept(4, 7, 3) == [(0, "item0"), (1, "item1"), (2, "item2")]


def test_sample_is_drawn_from_the_seed():
    big = 2**31 + 11
    a, b = kept(8, big, 500), kept(8, big, 500)
    assert a == b and len(a) == 8
    assert [i for i, _ in a] == sorted({i for i, _ in a})
    assert a != kept(8, big + 1, 500)


def test_sample_is_uniform_over_the_window():
    counts = collections.Counter(
        i for seed in range(2000) for i, _ in kept(4, seed, 20))
    # each of the 20 items is kept with chance 4/20: in 400 of 2000 runs
    # (binomial, sd 17.9), so every count lies within 4 sd of 400
    assert set(counts) == set(range(20))
    assert all(330 < c < 470 for c in counts.values()), counts


def test_sample_drops_the_answers_it_does_not_keep():
    s, refs = run.Sample(2, 3), []
    for i in range(50):
        a = np.full(16, i)
        refs.append(weakref.ref(a))
        s.offer(i, a)
        del a
    gc.collect()
    assert sum(r() is not None for r in refs) == 2


def test_a_window_longer_than_its_sample_checks_the_sample(monkeypatch):
    monkeypatch.setattr(run, "CHECKED", 2)
    line = run_toy("hex128-d1-box10", seconds=0.5)
    assert line["attempted"] > 2 and line["window"]["checked"] == 2
    assert line["correct"] and line["failed"] == 0
    assert line["metrics"]["colors"]["value"] >= 1
    bad = run_toy("hex128-d1-box10", seconds=0.5, fault="altered")
    assert bad["window"]["checked"] == 2 and bad["failed"] == 2
    assert bad["correct"] is False
