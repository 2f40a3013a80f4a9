"""The one request generator: a traffic mix's parameters → requests.

A mix (``bench/traffic/<name>.json``) is data only:

* ``mask`` — which cells a request recolors: the name of a mask kind,
  ``bench/masks/<mask>.py``, which reads the mix's other parameters
  (``none``: every cell; ``box``: a moving box, see its module);
* ``warm_start`` — ``true``: a request starts from the previous answer
  with its mask zeroed (a timestep; the first starts from a full coloring
  made in set-up), ``false``: from zero.

The loop is closed: the harness asks for the next request when the
previous one has returned.  Masks and their order come from ``seed`` and
the mix alone.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import masks


@dataclasses.dataclass
class Request:
    mask: np.ndarray | None         # (n,) bool, None = every cell
    colors0: np.ndarray | None      # (n,) int32, None = all zero


class Requests:
    def __init__(self, mix: dict, graph, seed: int):
        self.masks = masks.make(mix, graph, np.random.default_rng(seed))
        self.warm_start = bool(mix.get("warm_start", False))

    def next(self, prev: np.ndarray | None) -> Request:
        """The next request; ``prev`` is the previous answer's colors."""
        mask = self.masks.next()
        if not self.warm_start or prev is None:
            return Request(mask, None)
        colors0 = prev if mask is None else np.where(mask, 0, prev)
        return Request(mask, colors0.astype(np.int32, copy=False))
