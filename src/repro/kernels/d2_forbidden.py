"""``d2_forbidden`` Pallas kernel — two-hop forbidden-mask accumulation.

TPU adaptation of KokkosKernels ``NB_BIT`` (Taş et al. [22], Deveci [2]):
instead of each vertex walking its two-hop neighborhood by pointer
chasing (GPU warp-per-vertex), the wrapper expands the net in XLA — the
one-hop ELL block indexes the extended adjacency, giving ``W²`` two-hop
table indices per vertex — and gathers their colors into one dense
lane-major ``(W + W², tile)`` block (``W²`` only for partial distance-2).
The kernel OR-accumulates the ``uint32`` forbidden window over the block's
rows (``vb_bit.forbidden_rows``); the ops.py wrapper combines it with the
lowest-clear-bit pick shared with ``vb_bit``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import (default_interpret, block_spec, lane_tile,
                           pad_lanes, row_spec)
from repro.kernels.vb_bit import forbidden_rows

DEFAULT_TILE = 1024

__all__ = ["d2_forbidden", "DEFAULT_TILE"]


def _d2_kernel(nc_ref, base_ref, active_ref, colors_ref, forbidden_ref):
    uncolored = (active_ref[...] != 0) & (colors_ref[...] == 0)
    base_eff = jnp.where(uncolored, base_ref[...], 1)
    forbidden_ref[...] = forbidden_rows(nc_ref, base_eff)


@functools.partial(jax.jit, static_argnames=("partial_d2", "tile", "interpret"))
def d2_forbidden(
    adj_cidx: jnp.ndarray,     # (N, W)
    base: jnp.ndarray,         # (N,)
    active: jnp.ndarray,       # (N,)
    colors: jnp.ndarray,       # (N,)
    color_tab: jnp.ndarray,    # (n_tab,)
    ext_adj_cidx: jnp.ndarray, # (n_tab, W) adjacency row per table entry
    *,
    partial_d2: bool = False,
    tile: int = DEFAULT_TILE,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """uint32 forbidden masks over the current window for each vertex."""
    if interpret is None:
        interpret = default_interpret()
    n, w = adj_cidx.shape
    # Slot-major throughout; the two-hop slots come out in (k2, k1) order,
    # which the OR-reduction over slots does not see.
    adj_t = adj_cidx.astype(jnp.int32).T
    two_hop_t = ext_adj_cidx.astype(jnp.int32).T[:, adj_t].reshape(w * w, n)
    idx = (two_hop_t if partial_d2
           else jnp.concatenate([adj_t, two_hop_t], axis=0))
    k = idx.shape[0]
    t = lane_tile(tile, n, k)
    n_pad = -(-n // t) * t
    idx_t = pad_lanes(idx, n_pad, color_tab.shape[0] - 1)
    row = lambda x, v=0: pad_lanes(x.astype(jnp.int32), n_pad, v).reshape(1, n_pad)  # noqa: E731
    forbidden = pl.pallas_call(
        _d2_kernel,
        grid=(n_pad // t,),
        in_specs=[block_spec(k, t)] + [row_spec(t)] * 3,
        out_specs=row_spec(t),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.uint32),
        interpret=interpret,
    )(color_tab.astype(jnp.int32)[idx_t], row(base, 1), row(active),
      row(colors))
    return forbidden[0, :n]
