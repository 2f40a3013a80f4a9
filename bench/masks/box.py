"""``box``: an axis-aligned box holding ``fraction`` of a mesh.

Each side is the mesh's extent × ``fraction``^(1/d), rounded.  The boxes
visit a fixed set of ``positions`` positions, drawn from the mix's
``set_seed`` and so the same for every run; the run's seed only orders
them, and the requests cycle through that order.  How many speculation
iterations a step takes depends on where its box lies, so boxes drawn
afresh from each seed would give each seed other work.  The masks are
built once, in set-up.
"""
from __future__ import annotations

import numpy as np


class Masks:
    def __init__(self, mix: dict, graph, rng):
        if graph.grid is None:
            raise ValueError("a box mask needs a mesh (graph.grid)")
        fraction = float(mix["fraction"])
        d = len(graph.grid)
        side = tuple(max(1, round(e * fraction ** (1 / d)))
                     for e in graph.grid)
        pick = np.random.default_rng(int(mix["set_seed"]))
        self.boxes = []
        for _ in range(int(mix["positions"])):
            lo = [int(pick.integers(0, e - s + 1))
                  for e, s in zip(graph.grid, side)]
            box = np.zeros(graph.grid, bool)
            box[tuple(slice(a, a + s) for a, s in zip(lo, side))] = True
            self.boxes.append(box.reshape(-1))
        self.order = rng.permutation(len(self.boxes))
        self.step = 0

    def next(self) -> np.ndarray:
        box = self.boxes[self.order[self.step % len(self.order)]]
        self.step += 1
        return box
