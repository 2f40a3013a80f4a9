"""``vb_bit`` Pallas kernel — windowed forbidden-bitmask color assignment.

TPU adaptation of KokkosKernels ``VB_BIT`` (Deveci et al. [2]):
GPU version: one warp per vertex walks a CSR row, ballot-builds a 64-bit
forbidden mask.  TPU version: the neighbor colors arrive as a dense
lane-major ``(W, tile)`` block (one vertex per lane, one ELL slot per
sublane row — the gather ran in XLA, since Mosaic has no in-kernel
table gather), and the ``uint32`` forbidden window is OR-accumulated row
by row with VPU bitwise ops — no ballots, no atomics.

VMEM working set per grid step: the ``(W, tile)`` color block plus four
``(1, tile)`` row vectors, double buffered; :func:`repro.kernels.lane_tile`
sizes ``tile`` so any ELL width fits.  The table itself stays in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.local import pick_color
from repro.kernels import (default_interpret, block_spec, lane_tile,
                           pad_lanes, row_spec)

DEFAULT_TILE = 2048

__all__ = ["vb_bit_assign", "assign_block", "forbidden_rows", "DEFAULT_TILE"]


def forbidden_rows(nc_ref, base_eff):
    """OR the window bits of every row of a ``(K, tile)`` color block.

    ``base_eff (1, tile)`` are the window starts; colors ``<= 0`` (pad,
    uncolored) never forbid.  Mirrors ``core.local.forbidden_mask``.
    """
    one, zero = jnp.uint32(1), jnp.uint32(0)

    def body(k, acc):
        c = nc_ref[pl.ds(k, 1), :]
        rel = c - base_eff
        in_w = (c > 0) & (rel >= 0) & (rel < 32)
        shift = jnp.where(in_w, rel, 0).astype(jnp.uint32)
        return acc | jnp.where(in_w, one << shift, zero)

    return jax.lax.fori_loop(0, nc_ref.shape[0], body,
                             jnp.zeros(base_eff.shape, jnp.uint32))


def _assign_kernel(nc_ref, colors_ref, base_ref, active_ref,
                   out_colors_ref, out_base_ref):
    colors = colors_ref[...]                 # (1, T)
    base = base_ref[...]
    uncolored = (active_ref[...] != 0) & (colors == 0)
    base_eff = jnp.where(uncolored, base, 1)
    cand, ok = pick_color(forbidden_rows(nc_ref, base_eff), base_eff)
    out_colors_ref[...] = jnp.where(uncolored & ok, cand, colors)
    out_base_ref[...] = jnp.where(uncolored & ~ok, base + 32, base)


def assign_block(nc_t, colors, base, active, *, tile, interpret):
    """Dense assignment over lane-padded inputs.

    ``nc_t (K, N)`` neighbor colors, ``colors/base/active (N,)`` int32,
    with ``N`` a multiple of ``tile``.  Returns ``(colors, base)``.
    """
    k, n = nc_t.shape
    row = lambda x: x.astype(jnp.int32).reshape(1, n)     # noqa: E731
    out = pl.pallas_call(
        _assign_kernel,
        grid=(n // tile,),
        in_specs=[block_spec(k, tile)] + [row_spec(tile)] * 3,
        out_specs=[row_spec(tile)] * 2,
        out_shape=[jax.ShapeDtypeStruct((1, n), jnp.int32)] * 2,
        interpret=interpret,
    )(nc_t, row(colors), row(base), row(active))
    return out[0][0], out[1][0]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def vb_bit_assign(
    adj_cidx: jnp.ndarray,    # (N, W) int32
    colors: jnp.ndarray,      # (N,)   int32 current colors of these vertices
    base: jnp.ndarray,        # (N,)   int32 window starts
    active: jnp.ndarray,      # (N,)   bool/int32
    color_tab: jnp.ndarray,   # (n_tab,) int32 colors of everything referenceable
    *,
    tile: int = DEFAULT_TILE,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pallas ``VB_BIT`` assignment step. Returns (new_colors, new_base)."""
    if interpret is None:
        interpret = default_interpret()
    n, w = adj_cidx.shape
    t = lane_tile(tile, n, w)
    n_pad = -(-n // t) * t
    idx_t = pad_lanes(adj_cidx.astype(jnp.int32).T, n_pad,
                      color_tab.shape[0] - 1)
    c, b = assign_block(
        color_tab.astype(jnp.int32)[idx_t],
        pad_lanes(colors.astype(jnp.int32), n_pad),
        pad_lanes(base.astype(jnp.int32), n_pad, 1),
        pad_lanes(active.astype(jnp.int32), n_pad),
        tile=t, interpret=interpret)
    return c[:n], b[:n]
