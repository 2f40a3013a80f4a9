"""One fused detect→recolor inner round on dense lane-major kernels.

The decomposed round (``core.distributed._detect_part`` then
``_recolor_part``) is Alg-4 conflict detection against the freshly
exchanged ghosts, zeroing of the losers, and speculative recoloring of the
losers to a fixed point.  This module runs the same round with the
elementwise work in three Mosaic kernels and the neighbor reads either in
a fourth or in XLA, because Mosaic cannot gather from a table by a 2-D
index nor scatter inside a kernel:

  1. XLA reads neighbor colors / degrees / gids into lane-major
     ``(K, N)`` blocks (``K`` = the ELL slots the problem reads: ``W`` for
     d1, ``W + W²`` for d2, ``W²`` for pd2 — one concatenated index block
     covers both the one- and two-hop sweeps) and the ``detect`` kernel
     (``conflict.detect_block``) applies the Alg-4 loser rule.  Every
     block read is ``kernels.diagonals.read_neighbors``: a Mosaic kernel
     that reads the table along the index's diagonals where the plan
     found a diagonal layout (structured meshes), else XLA's scalar
     gather;
  2. XLA scatter-maxes the per-edge neighbor-side lose flags into the
     ghost lose table;
  3. :func:`speculate` iterates, to a fixed point, a neighbor-color read,
     the ``assign`` kernel (``vb_bit.assign_block``: windowed forbidden
     mask + lowest clear bit), a second read of the updated table, and
     the ``resolve`` kernel (intra-part Alg-4 collisions; losers zeroed).

The degree/gid reads are loop invariant and paid once per call.  The
kernels run a 1-D grid over ``tile``-lane row blocks
(``kernels.lane_tile``), so VMEM holds one ``(K, tile)`` block per operand
at any shard size; tables live in HBM.  The math is the jnp reference's
(``core.local._speculate_round`` / ``core.conflict.v_loses``), which keeps
the path bit-identical to the decomposed one — ``fused_round_ref`` in
``kernels/ref.py`` is the oracle and ``tests/test_kernels.py -k fused``
pins parity on d1/d2/pd2 including ragged tails.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.conflict import lose_table, v_loses
from repro.kernels import (default_interpret, block_spec, lane_tile,
                           pad_lanes, row_spec)
from repro.kernels.conflict import detect_block
from repro.kernels.diagonals import read_neighbors
from repro.kernels.vb_bit import assign_block

DEFAULT_TILE = 2048

__all__ = ["fused_round", "speculate", "neighbor_index", "DEFAULT_TILE"]


def neighbor_index(adj_cidx, two_hop_cidx, problem: str):
    """The ``(..., N, K)`` color-table indices ``problem`` reads per vertex
    (host arrays stay host arrays)."""
    if problem == "d1":
        return adj_cidx
    if two_hop_cidx is None:
        raise ValueError(f"problem={problem!r} requires two_hop_cidx")
    if problem == "pd2":
        return two_hop_cidx
    xp = np if isinstance(adj_cidx, np.ndarray) else jnp
    return xp.concatenate([adj_cidx, two_hop_cidx], axis=-1)


def _resolve_kernel(recolor_degrees, nc_ref, nd_ref, ng_ref,
                    cv_ref, dv_ref, gv_ref, act_ref, out_ref):
    cv, dv, gv = cv_ref[...], dv_ref[...], gv_ref[...]     # (1, T)

    def body(k, lose):
        row = pl.ds(k, 1)
        hit = v_loses(cv, nc_ref[row, :], dv, nd_ref[row, :], gv,
                      ng_ref[row, :], recolor_degrees=recolor_degrees)
        return lose | hit.astype(jnp.int32)

    lose = jax.lax.fori_loop(0, nc_ref.shape[0], body,
                             jnp.zeros(cv.shape, jnp.int32))
    out_ref[...] = jnp.where((act_ref[...] != 0) & (lose != 0), 0, cv)


def _resolve_block(nc_t, nd_t, ng_t, colors, deg, gid, active, *,
                   recolor_degrees, tile, interpret):
    """Zero the active rows that lose an Alg-4 collision to any neighbor."""
    k, n = nc_t.shape
    row = lambda x: x.reshape(1, n)                       # noqa: E731
    out = pl.pallas_call(
        functools.partial(_resolve_kernel, recolor_degrees),
        grid=(n // tile,),
        in_specs=[block_spec(k, tile)] * 3 + [row_spec(tile)] * 4,
        out_specs=row_spec(tile),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
    )(nc_t, nd_t, ng_t, row(colors), row(deg), row(gid), row(active))
    return out[0]


def _lane_layout(idx, n_tab, tile):
    """Lane tile, padded row count and the padded ``(K, N_pad)`` indices."""
    n, k = idx.shape
    t = lane_tile(tile, n, k)
    n_pad = -(-n // t) * t
    return t, n_pad, pad_lanes(idx.astype(jnp.int32).T, n_pad, n_tab - 1)


@functools.partial(jax.jit, static_argnames=(
    "recolor_degrees", "max_iters", "tile", "interpret"))
def speculate(idx, color_tab, active, deg_tab, gid_tab, *, diag=None,
              recolor_degrees: bool = True, max_iters: int = 512,
              tile: int = DEFAULT_TILE, interpret: bool | None = None):
    """Speculative local coloring of ``active`` rows to a fixed point.

    ``idx (N, K)`` are the color-table indices each row must differ from
    (``neighbor_index``); ``color_tab`` is the ``(n_tab,)`` table whose
    first ``N`` entries are the rows.  Same contract and results as
    ``core.local.local_color_d1`` (``idx = adj``) and ``local_color_d2``
    (``idx`` = one- and two-hop, or two-hop only for pd2): returns
    ``(table, iters)``, the updated table and the number of
    assign+resolve iterations the fixed point took.  ``diag`` is the
    diagonal layout of ``idx`` (``kernels.diagonals.find_diagonals``) when
    it has one: every neighbor block is then read along it.

    Each piece runs under a ``jax.named_scope`` (``spec.invariant``,
    ``spec.gather_assign``, ``spec.assign``, ``spec.gather_resolve``,
    ``spec.resolve``), so a profile names its device operations by
    layer whatever numbers XLA gives them.
    """
    if interpret is None:
        interpret = default_interpret()
    t, n_pad, idx_t = _lane_layout(idx, color_tab.shape[0], tile)
    with jax.named_scope("spec.invariant"):
        nd, ng = read_neighbors(idx_t, diag, deg_tab.astype(jnp.int32),
                                gid_tab.astype(jnp.int32), tile=t,
                                interpret=interpret)
    return _speculate(idx_t, diag, nd, ng, color_tab, active, deg_tab,
                      gid_tab, recolor_degrees=recolor_degrees,
                      max_iters=max_iters, tile=t, interpret=interpret)


def _speculate(idx_t, diag, nd, ng, color_tab, active, deg_tab, gid_tab, *,
               recolor_degrees, max_iters, tile, interpret):
    """:func:`speculate`'s fixed point over the laid-out index ``idx_t``
    and its loop-invariant degree / gid blocks ``nd`` / ``ng``."""
    n = active.shape[0]
    n_pad = idx_t.shape[1]
    color_tab = color_tab.astype(jnp.int32)
    rest = color_tab[n:]                 # ghosts + pad slot: never written
    dv = pad_lanes(deg_tab[:n].astype(jnp.int32), n_pad)
    gv = pad_lanes(gid_tab[:n].astype(jnp.int32), n_pad)
    act = pad_lanes(active.astype(jnp.int32), n_pad)
    kw = dict(tile=tile, interpret=interpret)

    def gather(colors):
        nbr, = read_neighbors(idx_t, diag,
                              jnp.concatenate([colors[:n], rest]), **kw)
        return nbr

    def cond(st):
        colors, _, it = st
        return (it < max_iters) & jnp.any((act != 0) & (colors == 0))

    def body(st):
        colors, base, it = st
        with jax.named_scope("spec.gather_assign"):
            nbr = gather(colors)
        with jax.named_scope("spec.assign"):
            newc, base = assign_block(nbr, colors, base, act, **kw)
        with jax.named_scope("spec.gather_resolve"):
            nbr = gather(newc)
        with jax.named_scope("spec.resolve"):
            colors = _resolve_block(nbr, nd, ng, newc, dv, gv, act,
                                    recolor_degrees=recolor_degrees, **kw)
        return colors, base, it + 1

    colors0 = pad_lanes(color_tab[:n], n_pad)
    base0 = jnp.ones((n_pad,), jnp.int32)
    colors, _, iters = jax.lax.while_loop(cond, body,
                                          (colors0, base0, jnp.int32(0)))
    return jnp.concatenate([colors[:n], rest]), iters


@functools.partial(jax.jit, static_argnames=(
    "problem", "recolor_degrees", "max_iters", "tile", "interpret"))
def fused_round(
    adj_cidx: jnp.ndarray,        # (N, W) int32 color-table indices
    colors: jnp.ndarray,          # (N,)   int32 current local colors
    ghost: jnp.ndarray,           # (G,)   int32 ghost colors (post-exchange)
    deg_tab: jnp.ndarray,         # (N+G+1,) int32 degrees (pad slot last)
    gid_tab: jnp.ndarray,         # (N+G+1,) int32 global ids
    is_boundary: jnp.ndarray,     # (N,)   bool
    two_hop_cidx: jnp.ndarray | None = None,   # (N, H2) for d2/pd2
    pair_slots: jnp.ndarray | None = None,     # (C,) optional ghost updates
    pair_colors: jnp.ndarray | None = None,    # (C,)
    *,
    diag=None,                                 # Diagonals of the index
    problem: str = "d1",
    recolor_degrees: bool = True,
    max_iters: int | None = None,
    tile: int = DEFAULT_TILE,
    interpret: bool | None = None,
):
    """One fused inner round: detect → zero losers → speculative recolor.

    Returns ``(new_colors (N,), lose_v (N,) bool, lose_ghost (G,) bool,
    n_conflicts scalar int32, iters scalar int32)`` — exactly the
    decomposed ``_detect_part`` + ``_recolor_part`` composition of the
    reference backend (``fused_round_ref`` is the pinned oracle); ``iters``
    is the :func:`speculate` iteration count of the losers' recolor.
    Optional ``(pair_slots, pair_colors)`` ghost updates (slots ``>= G``
    drop) are applied before detection, which runs under the
    ``round.detect`` named scope.  ``diag`` is the diagonal layout of the
    problem's neighbor index (:func:`neighbor_index`), when it has one.
    """
    if interpret is None:
        interpret = default_interpret()
    if problem not in ("d1", "d2", "pd2"):
        raise ValueError(f"fused_round does not support problem={problem!r}")
    if max_iters is None:
        max_iters = 512 if problem == "d1" else 1024
    n, g = colors.shape[0], ghost.shape[0]
    colors = colors.astype(jnp.int32)
    ghost = ghost.astype(jnp.int32)
    if pair_slots is not None:
        ghost = ghost.at[pair_slots].set(pair_colors.astype(jnp.int32),
                                         mode="drop")
    deg_tab = deg_tab.astype(jnp.int32)
    gid_tab = gid_tab.astype(jnp.int32)
    zero = jnp.zeros((1,), jnp.int32)
    tab = jnp.concatenate([colors, ghost, zero])

    # -- 1+2. Alg-4 owned-vs-ghost detection; ghost-side losers scattered.
    idx = neighbor_index(adj_cidx, two_hop_cidx, problem)
    t, n_pad, idx_t = _lane_layout(idx, n + g + 1, tile)
    with jax.named_scope("round.detect"):
        nbr, nd, ng = read_neighbors(idx_t, diag, tab, deg_tab, gid_tab,
                                     tile=t, interpret=interpret)
        lose_v, lose_o, count = detect_block(
            idx_t, nbr, nd, ng,
            pad_lanes(colors, n_pad), pad_lanes(deg_tab[:n], n_pad),
            pad_lanes(gid_tab[:n], n_pad),
            pad_lanes(is_boundary.astype(jnp.int32), n_pad),
            n_loc=n, n_tab=n + g, recolor_degrees=recolor_degrees,
            tile=t, interpret=interpret)
    lose_l = lose_v[:n] != 0
    lose_g = lose_table(idx_t, lose_o, n + g + 1)[n:n + g] != 0

    # -- 3. zero losers, speculate them to a fixed point (the degree and
    # gid blocks are detection's). --------------------------------------
    tab = jnp.concatenate([jnp.where(lose_l, 0, colors), ghost, zero])
    tab, iters = _speculate(idx_t, diag, nd, ng, tab, lose_l, deg_tab,
                            gid_tab, recolor_degrees=recolor_degrees,
                            max_iters=max_iters, tile=t,
                            interpret=interpret)
    return tab[:n], lose_l, lose_g, count.sum(), iters
