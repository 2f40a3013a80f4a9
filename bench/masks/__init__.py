"""Mask kinds of a traffic mix, one module per ``mask`` name.

A module provides ``Masks(mix, graph, rng)``: ``next()`` gives the next
request's mask, an ``(n,)`` bool array of the cells it recolors, or
``None`` for every cell.  ``rng`` is drawn from the run's seed; what a
kind draws from it is the kind's own.
"""
from __future__ import annotations

import importlib


def make(mix: dict, graph, rng):
    """The masks of ``mix``, by its ``mask`` kind."""
    return importlib.import_module(f"bench.masks.{mix['mask']}").Masks(
        mix, graph, rng)
