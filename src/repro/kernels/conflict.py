"""``conflict`` Pallas kernel — Algorithm-4 conflict detection over ELL tiles.

For every vertex in a tile, compare its color with every neighbor and apply
the paper's exact loser rule (recolorDegrees → rand(GID) → GID).  Emits the
vertex-side lose mask, the neighbor-side lose flags (scattered into the
ghost table by XLA — TPU Pallas has no efficient scatter), and a per-vertex
conflict count.

The neighbor colors, degrees and gids arrive as dense lane-major
``(W, tile)`` blocks gathered in XLA; the rule (``core.conflict.v_loses``,
shared with the jnp oracle) is evaluated row by row in VREGs — the TPU
equivalent of the paper's thread-per-vertex CUDA sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.conflict import v_loses
from repro.kernels import (default_interpret, block_spec, lane_tile,
                           pad_lanes, row_spec)

DEFAULT_TILE = 2048

__all__ = ["conflict_detect", "detect_block", "DEFAULT_TILE"]


def _detect_kernel(recolor_degrees, n_loc, n_tab,
                   idx_ref, nc_ref, nd_ref, ng_ref,
                   cv_ref, dv_ref, gv_ref, bnd_ref,
                   lose_v_ref, lose_o_ref, count_ref):
    cv, dv, gv = cv_ref[...], dv_ref[...], gv_ref[...]     # (1, T)
    rule = functools.partial(v_loses, recolor_degrees=recolor_degrees)

    def body(k, carry):
        lose, cnt = carry
        row = pl.ds(k, 1)
        a = idx_ref[row, :]
        co, do, go = nc_ref[row, :], nd_ref[row, :], ng_ref[row, :]
        ghost = (a >= n_loc) & (a < n_tab)
        vl = rule(cv, co, dv, do, gv, go) & ghost
        ol = rule(co, cv, do, dv, go, gv) & ghost
        lose_o_ref[row, :] = ol.astype(jnp.int32)
        return lose | vl.astype(jnp.int32), cnt + (vl | ol).astype(jnp.int32)

    zero = jnp.zeros(cv.shape, jnp.int32)
    lose, cnt = jax.lax.fori_loop(0, idx_ref.shape[0], body, (zero, zero))
    lose_v_ref[...] = jnp.where(bnd_ref[...] != 0, lose, 0)
    count_ref[...] = cnt


def detect_block(idx_t, nc_t, nd_t, ng_t, colors, deg, gid, is_boundary, *,
                 n_loc, n_tab, recolor_degrees, tile, interpret):
    """Dense Alg-4 sweep over lane-padded inputs.

    ``idx_t (K, N)`` color-table indices (ghosts are ``n_loc <= i <
    n_tab``), ``nc_t/nd_t/ng_t (K, N)`` their colors/degrees/gids, and
    ``(N,)`` row vectors, ``N`` a multiple of ``tile``.  Returns int32
    ``(lose_v (N,), lose_other (K, N), count (N,))``.
    """
    k, n = idx_t.shape
    row = lambda x: x.astype(jnp.int32).reshape(1, n)     # noqa: E731
    kernel = functools.partial(_detect_kernel, recolor_degrees, n_loc, n_tab)
    lose_v, lose_o, count = pl.pallas_call(
        kernel,
        grid=(n // tile,),
        in_specs=[block_spec(k, tile)] * 4 + [row_spec(tile)] * 4,
        out_specs=[row_spec(tile), block_spec(k, tile), row_spec(tile)],
        out_shape=[jax.ShapeDtypeStruct((1, n), jnp.int32),
                   jax.ShapeDtypeStruct((k, n), jnp.int32),
                   jax.ShapeDtypeStruct((1, n), jnp.int32)],
        interpret=interpret,
    )(idx_t, nc_t, nd_t, ng_t, row(colors), row(deg), row(gid),
      row(is_boundary))
    return lose_v[0], lose_o, count[0]


@functools.partial(jax.jit, static_argnames=("n_loc", "recolor_degrees", "tile", "interpret"))
def conflict_detect(
    adj_cidx: jnp.ndarray,      # (N, W)
    colors: jnp.ndarray,        # (N,) local colors
    deg: jnp.ndarray,           # (N,)
    gid: jnp.ndarray,           # (N,)
    is_boundary: jnp.ndarray,   # (N,) bool
    color_tab: jnp.ndarray,     # (n_tab,)
    deg_tab: jnp.ndarray,
    gid_tab: jnp.ndarray,
    n_loc: int,
    *,
    recolor_degrees: bool = True,
    tile: int = DEFAULT_TILE,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (lose_v (N,) bool, lose_other (W, N) bool, count scalar)."""
    if interpret is None:
        interpret = default_interpret()
    n, w = adj_cidx.shape
    n_tab = color_tab.shape[0] - 1  # last slot is pad
    t = lane_tile(tile, n, w)
    n_pad = -(-n // t) * t
    idx_t = pad_lanes(adj_cidx.astype(jnp.int32).T, n_pad, n_tab)
    lose_v, lose_o, count = detect_block(
        idx_t, color_tab.astype(jnp.int32)[idx_t],
        deg_tab.astype(jnp.int32)[idx_t], gid_tab.astype(jnp.int32)[idx_t],
        pad_lanes(colors.astype(jnp.int32), n_pad),
        pad_lanes(deg.astype(jnp.int32), n_pad),
        pad_lanes(gid.astype(jnp.int32), n_pad),
        pad_lanes(is_boundary.astype(jnp.int32), n_pad),
        n_loc=n_loc, n_tab=n_tab, recolor_degrees=recolor_degrees,
        tile=t, interpret=interpret)
    return lose_v[:n].astype(bool), lose_o[:, :n].astype(bool), count.sum()
