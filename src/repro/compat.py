"""The one wrapper around ``jax.shard_map``.

``check_vma=False``: the coloring loop's ``lax.while_loop`` carries mix
device-varying tables with replicated scalars, and the loop drivers are
written against the untyped (pre-varying-axis) collective semantics.
"""
from __future__ import annotations

import jax

__all__ = ["shard_map"]


def shard_map(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
