"""Production mesh construction (task-mandated shapes).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (device count is locked at first use).
"""
from __future__ import annotations

import math
import os

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """The one mesh constructor: every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, whose sharding-in-
    types rules the GSPMD-style code here (``with_sharding_constraint``,
    ``shard_map`` with untyped collectives) is not written against.
    """
    from jax.sharding import AxisType

    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes present in a mesh ('pod' + 'data')."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def factor_parts(n_parts: int, node_size: int | None = None) -> tuple[int, int]:
    """``(n_nodes, node_size)`` factorization of the part count.

    The 2D (node, local) layout the hierarchical exchange assumes: parts
    ``A·node_size .. A·node_size + node_size - 1`` share node ``A``'s
    fast links; one leader per node crosses the slow axis.

    ``node_size=None`` reads ``REPRO_NODE_SIZE`` (0/unset = auto); auto
    picks the largest divisor of ``n_parts`` that is ``<= sqrt(n_parts)``
    (the squarest factorization, e.g. 4 → 2×2, 8 → 4×2, 12 → 4×3 nodes).
    A prime part count degrades to ``(n_parts, 1)`` — every part its own
    leader, so the hierarchy collapses to the flat point-to-point plan.
    """
    if n_parts < 1:
        raise ValueError(f"n_parts must be >= 1, got {n_parts}")
    if node_size is None:
        node_size = int(os.environ.get("REPRO_NODE_SIZE", "0")) or None
    if node_size is None:
        node_size = 1
        for d in range(1, int(math.isqrt(n_parts)) + 1):
            if n_parts % d == 0:
                node_size = d
    if node_size < 1 or n_parts % node_size:
        raise ValueError(
            f"node_size {node_size} must divide the part count {n_parts}")
    return n_parts // node_size, node_size


def make_two_level_mesh(n_parts: int, node_size: int | None = None):
    """A ``(node, local)`` mesh over the first ``n_parts`` devices.

    The hierarchical factorization as a real jax mesh (benches and
    multi-host launches); the coloring runtime's ``shard_map`` engine
    keeps its flat ``"p"`` axis — ``hier_delta`` derives the node
    structure from :func:`factor_parts`, so both views agree as long as
    devices enumerate node-major (the default on TPU slices).
    """
    n_nodes, node_size = factor_parts(n_parts, node_size)
    return make_mesh((n_nodes, node_size), ("node", "local"))
