"""Speculative local coloring — VB_BIT adapted to TPU (DESIGN.md §4.3).

Pure-``jnp`` reference implementation; ``repro.kernels.vb_bit`` is the
Pallas kernel with identical semantics (tested bit-exact against this).

Algorithm (one device, KokkosKernels VB_BIT re-derived for the VPU):
  repeat until no active vertex is uncolored:
    1. every uncolored active vertex builds a uint32 *forbidden mask* over
       its private color window ``[base_v, base_v + 32)`` from neighbor
       colors (one- or two-hop), takes the lowest clear bit; a full mask
       bumps the window;
    2. speculative assignment may collide; the Alg-4 loser rule
       (:func:`repro.core.conflict.v_loses`) uncolors the losers — lane-
       consistent, no atomics.

Ghost colors live in the color table and are simply forbidden; they are
never assigned here, so cross-device consistency is handled one level up.

Iteration caps are worst-case O(n): graphs with many equal-degree twin
vertices (mycielskians) resolve only one speculative collision per round
near the end.  The caps are while_loop bounds — no compile-time cost.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.conflict import v_loses

__all__ = ["local_color_d1", "local_color_d2", "forbidden_mask", "pick_color"]

UINT_FULL = jnp.uint32(0xFFFFFFFF)


def forbidden_mask(nbr_colors: jnp.ndarray, base: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """uint32 forbidden mask over the window ``[base, base+32)`` per vertex.

    nbr_colors: int32 neighbor colors with the neighbor slots on ``axis``
    (0 = uncolored/pad: never forbidden).  base: int32 window starts, the
    shape of ``nbr_colors`` without ``axis``.
    """
    axis = axis % nbr_colors.ndim
    rel = nbr_colors - jnp.expand_dims(base, axis)
    in_window = (nbr_colors > 0) & (rel >= 0) & (rel < 32)
    bits = jnp.where(in_window, jnp.uint32(1) << rel.astype(jnp.uint32), jnp.uint32(0))
    return jax.lax.reduce_or(bits, (axis,))


def pick_color(forbidden: jnp.ndarray, base: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Lowest allowed color in the window, and whether one exists.

    Returns ``(color, ok)``; color is valid only where ``ok``.
    Lowest-clear-bit trick: ``t = ~f & (f + 1)`` isolates the lowest zero
    bit; its index is ``popcount(t - 1)``.
    """
    t = (~forbidden) & (forbidden + jnp.uint32(1))
    ok = t != 0
    bitpos = jax.lax.population_count(t - jnp.uint32(1)).astype(jnp.int32)
    return base + jnp.where(ok, bitpos, 0), ok


def _speculate_round(
    color_tab, base, adj_cidx, active, deg_tab, gid_tab, two_hop_cidx, partial_d2, recolor_degrees
):
    """One speculate+resolve round. Returns (color_tab, base).

    Neighbor blocks are gathered slot-major, ``(K, Nv)``: with the vertex
    on the minor axis a narrow ELL width is not padded out to a full lane
    tile on TPU (an ``(Nv, 6)`` int32 block would take 21x its size).
    """
    n_loc = active.shape[0]
    colors_loc = color_tab[:n_loc]
    uncolored = active & (colors_loc == 0)

    # One- and two-hop (d2), two-hop only (pd2) or one-hop (d1) indices.
    if two_hop_cidx is None:
        idx = adj_cidx.T
    elif partial_d2:
        idx = two_hop_cidx.T
    else:
        idx = jnp.concatenate([adj_cidx.T, two_hop_cidx.T], axis=0)

    base_eff = jnp.where(uncolored, base, jnp.int32(1))
    mask = forbidden_mask(color_tab[idx], base_eff, axis=0)
    cand, ok = pick_color(mask, base_eff)
    new_colors = jnp.where(uncolored & ok, cand, colors_loc)
    new_base = jnp.where(uncolored & ~ok, base + 32, base)
    color_tab = color_tab.at[:n_loc].set(new_colors)

    # Speculative collision resolution (Alg 4 applied intra-device) over
    # the same neighborhood.
    lose = v_loses(
        new_colors[None], color_tab[idx], deg_tab[:n_loc][None], deg_tab[idx],
        gid_tab[:n_loc][None], gid_tab[idx], recolor_degrees=recolor_degrees,
    ).any(axis=0)
    color_tab = color_tab.at[:n_loc].set(jnp.where(active & lose, 0, new_colors))
    return color_tab, new_base


@partial(jax.jit, static_argnames=("recolor_degrees", "max_iters"))
def local_color_d1(
    adj_cidx: jnp.ndarray,       # (Nv, W) indices into the color table
    color_tab: jnp.ndarray,      # (Nt,) colors; [0:Nv] owned, rest ghosts+pad
    active: jnp.ndarray,         # (Nv,) bool — vertices to (re)color
    deg_tab: jnp.ndarray,        # (Nt,) degrees
    gid_tab: jnp.ndarray,        # (Nt,) global ids (pad: unique large)
    *,
    recolor_degrees: bool = True,
    max_iters: int = 512,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Distance-1 speculative local coloring.

    Returns ``(table, iters)``: the updated table and the number of
    speculate+resolve iterations the fixed point took (int32 scalar).
    """
    n_loc = active.shape[0]
    # ``+ 0 * color_tab`` ties the carry's varying-axis type to the data so
    # the same code works under shard_map (varying) and plain jit.
    base0 = jnp.ones((n_loc,), jnp.int32) + 0 * color_tab[:n_loc]

    def cond(st):
        color_tab, _, it = st
        return (it < max_iters) & jnp.any(active & (color_tab[:n_loc] == 0))

    def body(st):
        color_tab, base, it = st
        color_tab, base = _speculate_round(
            color_tab, base, adj_cidx, active, deg_tab, gid_tab,
            None, False, recolor_degrees,
        )
        return color_tab, base, it + 1

    color_tab, _, iters = jax.lax.while_loop(
        cond, body, (color_tab, base0, jnp.int32(0)))
    return color_tab, iters


@partial(jax.jit, static_argnames=("partial_d2", "recolor_degrees", "max_iters"))
def local_color_d2(
    adj_cidx: jnp.ndarray,        # (Nv, W)
    two_hop_cidx: jnp.ndarray,    # (Nv, H2) two-hop color-table indices
    color_tab: jnp.ndarray,
    active: jnp.ndarray,
    deg_tab: jnp.ndarray,
    gid_tab: jnp.ndarray,
    *,
    partial_d2: bool = False,
    recolor_degrees: bool = True,
    max_iters: int = 1024,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Distance-2 (or partial-distance-2) speculative local coloring;
    returns ``(table, iters)`` like :func:`local_color_d1`."""
    n_loc = active.shape[0]
    base0 = jnp.ones((n_loc,), jnp.int32) + 0 * color_tab[:n_loc]  # vma tie


    def cond(st):
        color_tab, _, it = st
        return (it < max_iters) & jnp.any(active & (color_tab[:n_loc] == 0))

    def body(st):
        color_tab, base, it = st
        color_tab, base = _speculate_round(
            color_tab, base, adj_cidx, active, deg_tab, gid_tab,
            two_hop_cidx, partial_d2, recolor_degrees,
        )
        return color_tab, base, it + 1

    color_tab, _, iters = jax.lax.while_loop(
        cond, body, (color_tab, base0, jnp.int32(0)))
    return color_tab, iters


def build_two_hop(adj_cidx: jnp.ndarray, full_adj_cidx: jnp.ndarray) -> jnp.ndarray:
    """Two-hop color-table indices: (Nv, W, W) flattened to (Nv, W*W).

    ``full_adj_cidx`` has one adjacency row per color-table entry (pad rows
    point at the pad slot), so ghosts' neighborhoods resolve too.
    """
    nv, w = adj_cidx.shape
    return full_adj_cidx[adj_cidx].reshape(nv, w * w)
