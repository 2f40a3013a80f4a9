"""Jit'd wrappers composing the Pallas kernels into full coloring rounds.

``local_color_d1_pallas`` / ``local_color_d2_pallas`` are drop-in
replacements for ``repro.core.local.local_color_d1`` / ``local_color_d2``
built from the kernels: assignment + speculative-collision resolution
iterated to a fixed point.  d1 is ``fused_round.speculate`` (the
``vb_bit`` assign and ``resolve`` kernels, the loop every fused round
runs); d2 assigns through ``d2_forbidden``.  The distributed runtime
selects them through the pluggable backend layer —
``color_distributed(..., backend="pallas")`` routes every local-coloring
and conflict-detection step through these wrappers (see
``repro.core.backend.PallasBackend``); ``backend="reference"`` keeps the
pure-``jnp`` path.  Every wrapper's ``interpret`` flag defaults to
:func:`repro.kernels.default_interpret` — interpret mode (kernel bodies
as plain jax) off-TPU, Mosaic compilation on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.conflict import v_loses
from repro.core.local import pick_color
from repro.kernels import default_interpret
from repro.kernels.conflict import conflict_detect
from repro.kernels.d2_forbidden import d2_forbidden
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_round import DEFAULT_TILE, fused_round, speculate
from repro.kernels.scatter import pair_scatter
from repro.kernels.vb_bit import vb_bit_assign

__all__ = [
    "vb_bit_assign",
    "conflict_detect",
    "d2_forbidden",
    "flash_attention",
    "fused_round",
    "pair_scatter",
    "local_color_d1_pallas",
    "local_color_d2_pallas",
    "d2_assign_pallas",
]


def local_color_d1_pallas(
    adj_cidx, color_tab, active, deg_tab, gid_tab, *, diag=None,
    recolor_degrees: bool = True, max_iters: int = 512,
    interpret: bool | None = None, tile: int = DEFAULT_TILE,
):
    """Kernel-backed distance-1 local coloring (same contract as core.local,
    ``(table, iters)``): the ``fused_round.speculate`` fixed point over the
    one-hop rows, read along ``diag`` when given."""
    return speculate(adj_cidx, color_tab, active, deg_tab, gid_tab, diag=diag,
                     recolor_degrees=recolor_degrees, max_iters=max_iters,
                     tile=tile, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("partial_d2", "interpret", "tile")
)
def d2_assign_pallas(
    adj_cidx, ext_adj_cidx, color_tab, base, active, *,
    partial_d2: bool = False, interpret: bool | None = None, tile: int = 128,
):
    """One D2 assignment step: two-hop forbidden kernel + lowest-bit pick."""
    if interpret is None:
        interpret = default_interpret()
    n_loc = active.shape[0]
    colors = color_tab[:n_loc]
    forbidden = d2_forbidden(
        adj_cidx, base, active, colors, color_tab, ext_adj_cidx,
        partial_d2=partial_d2, tile=tile, interpret=interpret,
    )
    uncolored = active & (colors == 0)
    base_eff = jnp.where(uncolored, base, 1)
    cand, ok = pick_color(forbidden, base_eff)
    new_colors = jnp.where(uncolored & ok, cand, colors)
    new_base = jnp.where(uncolored & ~ok, base + 32, base)
    return new_colors, new_base


@functools.partial(
    jax.jit,
    static_argnames=("partial_d2", "recolor_degrees", "max_iters", "interpret", "tile"),
)
def local_color_d2_pallas(
    adj_cidx, two_hop_cidx, ext_adj_cidx, color_tab, active, deg_tab, gid_tab, *,
    partial_d2: bool = False, recolor_degrees: bool = True, max_iters: int = 1024,
    interpret: bool | None = None, tile: int = 128,
):
    """Kernel-backed distance-2 local coloring (same contract as core.local,
    ``(table, iters)``).

    Assignment runs through the ``d2_forbidden`` net-based kernel; the
    speculative-collision resolution is the identical Alg-4 loser rule over
    one-hop (unless ``partial_d2``) and two-hop neighborhoods, so the fixed
    point matches ``repro.core.local.local_color_d2`` exactly.
    """
    if interpret is None:
        interpret = default_interpret()
    n_loc = active.shape[0]
    base0 = jnp.ones((n_loc,), jnp.int32) + 0 * color_tab[:n_loc]
    deg_loc = deg_tab[:n_loc]
    gid_loc = gid_tab[:n_loc]
    # Resolution neighborhood, slot-major: two-hop, plus one-hop unless pd2.
    idx = (two_hop_cidx.T if partial_d2
           else jnp.concatenate([adj_cidx.T, two_hop_cidx.T], axis=0))

    def cond(st):
        tab, base, it = st
        return (it < max_iters) & jnp.any(active & (tab[:n_loc] == 0))

    def body(st):
        tab, base, it = st
        colors, base = d2_assign_pallas(
            adj_cidx, ext_adj_cidx, tab, base, active,
            partial_d2=partial_d2, tile=tile, interpret=interpret,
        )
        tab = tab.at[:n_loc].set(colors)
        lose = v_loses(
            colors[None], tab[idx], deg_loc[None], deg_tab[idx],
            gid_loc[None], gid_tab[idx], recolor_degrees=recolor_degrees,
        ).any(axis=0)
        tab = tab.at[:n_loc].set(jnp.where(active & lose, 0, colors))
        return tab, base, it + 1

    color_tab, _, iters = jax.lax.while_loop(
        cond, body, (color_tab, base0, jnp.int32(0)))
    return color_tab, iters
