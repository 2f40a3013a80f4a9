"""Neighbor reads along the index's diagonals (the DIA layout of an ELL block).

Every ``(K, N)`` neighbor block of ``fused_round`` is ``table[idx_t]``:
slot ``k`` of row ``v`` reads ``table[idx_t[k, v]]``.  XLA:TPU runs that
gather one scalar at a time.  On a structured mesh numbered along its
axes, nearly every entry lies on a few *diagonals*: ``idx_t[k, v] - v``
takes one of a handful of constant offsets (``±1, ±nz, ±ny·nz`` for a hex
mesh's 6-point stencil; the 24 distance-≤2 offsets, plus the self entry
of the two-hop table, for d2; on a slab of a partitioned mesh also one or
two offsets for each face of ghosts).  For such an index the block is
built from shifts of the table instead: entry ``(k, v)`` takes
``table[v + off]`` for the listed offset that ``idx_t[k, v] - v`` equals.
A Mosaic kernel does it tile by tile: the window of one diagonal is one
or two aligned table blocks rotated together.  Entries on no listed
diagonal form the *residual*, read with XLA's scalar gather over a
compact ``(position, table index)`` list and written into the block.  The
result equals ``table[idx_t]`` element for element.

:func:`find_diagonals` finds the layout once, on the host, when a plan is
built; :func:`read_neighbors` is the one neighbor read of the fused round
and falls back to ``table[idx_t]`` when the index has no layout.  The
choice is made from what the analysis observes in the index, by the cost
rule stated at :data:`MAX_RESIDUAL`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import _VMEM_BUDGET, block_spec, pad_lanes

__all__ = ["Diagonals", "find_diagonals", "read_neighbors", "MAX_DIAGONALS",
           "MAX_RESIDUAL"]

# The cut-over rule.  The read kernel streams, per lane tile, the index
# block, the output block and one or two table blocks a diagonal, and
# spends a compare and a select per entry and diagonal: a few ps a row at
# HBM speed.  A residual entry costs a scalar gather and a scatter in XLA,
# ~16 ns (XLA:TPU gathers ~8 ns an element).  So a diagonal earns its
# place once it serves more than a few thousandths of the rows; diagonals
# serving fewer than N/2048 entries go to the residual.  At most
# MAX_DIAGONALS offsets bound the kernel's select chain, its VMEM and its
# compile; the path is taken only while the residual stays under
# MAX_RESIDUAL of the real entries, where its gathers cost at most a
# quarter of the whole-block gather.  Below that (skewed, random or
# relabeled graphs) the plain gather runs unchanged.
MAX_DIAGONALS = 32
MAX_RESIDUAL = 1 / 8
_MIN_DIAGONAL_ROWS = 2048
_SAMPLE_ROWS = 1 << 16


@jax.tree_util.register_pytree_node_class
class Diagonals:
    """The diagonal layout of one ``(N, K)`` neighbor index.

    ``offsets`` are the diagonals' offsets, ascending (static: part of the
    compiled program); ``res_pos`` / ``res_src`` (``(..., R)`` int32, a
    leading part axis where stacked) are the residual's flat positions
    ``row * K + slot`` in the row-major index and the table indices they
    read.  Unused residual entries hold ``N * K`` and the pad slot: they
    land on a lane-padding column, which reads the pad slot anyway, or
    are dropped.
    """

    def __init__(self, offsets, res_pos, res_src):
        self.offsets = offsets
        self.res_pos = res_pos
        self.res_src = res_src

    def tree_flatten(self):
        return (self.res_pos, self.res_src), self.offsets

    @classmethod
    def tree_unflatten(cls, offsets, children):
        return cls(offsets, *children)


def find_diagonals(idx, n_tab: int):
    """Host analysis of a ``(P, N, K)`` neighbor index stacked over parts.

    ``n_tab`` is the table length; index ``n_tab - 1`` is the pad slot.
    Returns ``(layout, share)``: a :class:`Diagonals` (the residual lists
    stacked over parts; ``None`` when the rule at :data:`MAX_RESIDUAL`
    keeps the gather) and the share of real (non-pad) entries the
    diagonals serve, 0 without a layout.

    The diagonals come from a sample of rows (every row up to 64k rows);
    one exact pass over the whole index, in cache-sized chunks, then lists
    the residual: every real entry on none of them.
    """
    idx = np.asarray(idx, np.int32)
    p, n, k = idx.shape
    pad = n_tab - 1
    if not p * n * k or (n + 1) * k >= 2 ** 31 or n_tab >= 2 ** 29:
        return None, 0.0
    # Offsets lie in -n < off < n_tab: bin off + n_tab, the pad slot's
    # entries in bin 0 (no offset lands there).
    width = 2 * n_tab
    shift = np.arange(-n_tab, n - n_tab, dtype=np.int32)[:, None]

    # The sample's rows are drawn at random: rows at a fixed stride can
    # fall in step with the mesh and never see one of its diagonals.
    m = max(1, _SAMPLE_ROWS // p)
    at = (np.arange(n) if n <= m else np.sort(
        np.random.default_rng(0).choice(n, m, replace=False)))
    sample = idx[:, at]
    b = sample - shift[at]
    b[sample == pad] = 0
    hist = np.bincount(b.ravel(), minlength=width)
    hist[0] = 0
    top = np.argsort(hist, kind="stable")[::-1][:MAX_DIAGONALS]
    top = top[hist[top] * _MIN_DIAGONAL_ROWS >= p * at.size]
    # Skip the exact pass where the sample already misses by far.
    if hist[top].sum() <= (1 - 2 * MAX_RESIDUAL) * np.count_nonzero(
            sample != pad):
        return None, 0.0
    on_diag = np.zeros(width, bool)
    on_diag[top] = True
    chunk = max(1, (1 << 18) // k)
    res, n_real = [], 0
    for part in range(p):
        found = []
        for r0 in range(0, n, chunk):
            block = idx[part, r0:r0 + chunk]
            real = block != pad
            n_real += np.count_nonzero(real)
            off_diag = ~on_diag[block - shift[r0:r0 + chunk]] & real
            found.append(np.flatnonzero(off_diag) + r0 * k)
        res.append(np.concatenate(found))
    n_res = sum(r.size for r in res)
    if not n_real or n_res > MAX_RESIDUAL * n_real:
        return None, 0.0
    r = max(x.size for x in res)
    res_pos = np.full((p, r), n * k, np.int32)
    res_src = np.full((p, r), pad, np.int32)
    for part, pos in enumerate(res):
        res_pos[part, :pos.size] = pos
        res_src[part, :pos.size] = idx[part].reshape(-1)[pos]
    offsets = tuple(sorted((top - n_tab).tolist()))
    return Diagonals(offsets, res_pos, res_src), 1.0 - n_res / n_real


def _read_kernel(offsets, windows, n_blocks, n_tab, tile, fill_ref, idx_ref,
                 *refs):
    """One lane tile of every table's block: a select over the diagonals'
    windows, each a rotation of one or two of the ``n_blocks`` aligned
    table blocks; the pad slot's entries take ``fill_ref``, its value."""
    blocks, outs = refs[:n_blocks], refs[n_blocks:]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    idx = idx_ref[...]
    diff = idx - (lane + pl.program_id(0) * tile)
    pad = idx == n_tab - 1
    for t, out_ref in enumerate(outs):
        out = jnp.where(pad, fill_ref[t:t + 1, :], 0)
        for off, (a, b, r) in zip(offsets, windows):
            win = blocks[a][t:t + 1, :]
            if r:
                win = jnp.where(lane < tile - r,
                                pltpu.roll(win, tile - r, 1),
                                pltpu.roll(blocks[b][t:t + 1, :],
                                           tile - r, 1))
            out = jnp.where(diff == off, win, out)
        out_ref[...] = out


def read_neighbors(idx_t, diag: Diagonals | None, *tables, tile: int,
                   interpret: bool):
    """``tuple(table[idx_t] for table in tables)`` for a lane-major
    ``(K, N_pad)`` index block, ``N_pad`` a multiple of ``tile``.

    With ``diag`` (the layout :func:`find_diagonals` found for the rows of
    ``idx_t``; lane-padding columns hold the pad slot), one Mosaic kernel
    builds every table's block, lane tile by lane tile: the window of
    diagonal ``off`` for the tile at ``i·T`` is the table's
    ``[i·T + off, i·T + off + T)``, two aligned blocks of the table
    rotated together; entries on a listed diagonal take it, the pad slot
    its value.  The residual is gathered and written in by XLA.  Without
    ``diag``, XLA's scalar gather.  The kernel's HLO instructions are
    named ``neighbor_read.N`` under the caller's scope.
    """
    if diag is None:
        return tuple(table[idx_t] for table in tables)
    k, n_pad = idx_t.shape
    n_tab = tables[0].shape[0]
    t = _read_tile(tile, k, len(tables), diag.offsets)
    n_blk = -(-n_tab // t)
    tab = jnp.stack([pad_lanes(x.astype(jnp.int32), n_blk * t)
                     for x in tables])
    # Each window reads table blocks ``i + q`` and, unless it is aligned,
    # ``i + q + 1``; the same block serves every window that needs it.
    # Blocks off either end are clamped: their entries are out of the
    # table, so no index selects them.
    shifts = sorted({q for off in diag.offsets
                     for q in ((off // t,) if off % t == 0
                               else (off // t, off // t + 1))})
    block_of = {q: j for j, q in enumerate(shifts)}
    windows = [(block_of[off // t],
                block_of.get(off // t + 1) if off % t else None, off % t)
               for off in diag.offsets]
    block = lambda q: pl.BlockSpec(                         # noqa: E731
        (len(tables), t), lambda i: (0, jnp.clip(i + q, 0, n_blk - 1)))
    outs = pl.pallas_call(
        functools.partial(_read_kernel, diag.offsets, windows, len(shifts),
                          n_tab, t),
        grid=(n_pad // t,),
        in_specs=[pl.BlockSpec((len(tables), t), lambda i: (0, 0)),
                  block_spec(k, t)] + [block(q) for q in shifts],
        out_specs=[block_spec(k, t)] * len(tables),
        out_shape=[jax.ShapeDtypeStruct((k, n_pad), jnp.int32)] * len(tables),
        interpret=interpret, name="neighbor_read",
    )(jnp.broadcast_to(tab[:, n_tab - 1:n_tab], (len(tables), t)), idx_t,
      *([tab] * len(shifts)))
    if diag.res_pos.shape[-1]:
        pos = diag.res_pos
        outs = [out.at[pos % k, pos // k].set(x[diag.res_src], mode="drop")
                for out, x in zip(outs, tab)]
    return tuple(outs)


def _read_tile(tile, k, n_tables, offsets):
    """The largest lane tile, ``tile`` halved, whose blocks fit the VMEM
    budget: the index and output blocks (``k`` rows) and two table
    blocks a diagonal, double buffered."""
    rows = -(-k // 8) * 8 * (1 + n_tables) + 2 * len(offsets) * 8
    while tile % 256 == 0 and 2 * rows * tile * 4 > _VMEM_BUDGET:
        tile //= 2
    return tile
