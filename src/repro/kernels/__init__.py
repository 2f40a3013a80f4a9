"""Pallas TPU kernels for the coloring hot spots.

The paper's compute hot spots are KokkosKernels' ``VB_BIT`` /
``NB_BIT`` loops and the conflict-detection sweep; these are the layers the
paper optimizes on GPU, so they get TPU kernels here:

* ``vb_bit``      -- windowed forbidden-bitmask color assignment
* ``conflict``    -- Algorithm-4 conflict detection over ELL tiles
* ``d2_forbidden``-- two-hop forbidden-mask accumulation
* ``scatter``     -- (slot, value) pair application for sparse exchanges
* ``fused_round`` -- one whole detect→recolor round (gathers in XLA,
  dense lane-major kernels for detect / assign / resolve)
* ``diagonals``   -- neighbor blocks read along the index's diagonals
  (shifted table windows), the fused round's reads on structured meshes

Mosaic has no in-kernel gather by a 2-D index, so every kernel consumes
*dense* neighbor blocks: the wrapper reads ``table[idx.T]`` (XLA's gather,
or along the diagonals) into a lane-major ``(K, N)`` array (neighbor slot
on sublanes, vertex on lanes)
and the kernel reduces over the ``K`` rows of a ``(K, tile)`` block.  Row
vectors travel as ``(1, N)``.  Each kernel ships ``<name>.py``
(``pl.pallas_call`` + ``BlockSpec`` grid), a jit'd wrapper re-exported by
``ops.py``, and a pure-jnp oracle in ``ref.py``.

Kernel wrappers take ``interpret=None`` and resolve it through
:func:`default_interpret`: compiled Mosaic kernels on TPU, the Pallas
interpreter on the CPU backend (tests), and an error anywhere else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["default_interpret", "LANES", "lane_tile", "pad_lanes",
           "block_spec", "row_spec"]

LANES = 128
# Per-step VMEM the lane tile is sized against (inputs + outputs, double
# buffered); well inside v5e's 16 MiB default scoped VMEM limit.
_VMEM_BUDGET = 8 << 20
# Operand blocks a kernel step holds at once (inputs + outputs).
_N_BLOCKS = 8


def default_interpret() -> bool:
    """Platform-derived default for kernel ``interpret`` flags.

    ``False`` (compiled Mosaic) on TPU, ``True`` (Pallas interpret mode)
    on the CPU backend.  Any other backend raises: the kernels lower only
    through Mosaic, and a silent interpreter there would hide that.
    Evaluated at trace time — the flag is a static argument of every
    kernel wrapper.
    """
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas coloring kernels compile only for TPU (interpret mode on "
        f"CPU); backend {platform!r} is not supported — use "
        f"backend='reference'")


def lane_tile(tile: int, n: int, k: int = 1) -> int:
    """Lane-block width for ``n`` vertices with ``k``-row neighbor blocks.

    A multiple of 128 lanes, no wider than the lane-padded row count, and
    halved until ``_N_BLOCKS`` double-buffered ``(k, tile)`` int32 blocks
    fit :data:`_VMEM_BUDGET` (sublanes pad ``k`` to a multiple of 8).
    """
    t = max(LANES, -(-tile // LANES) * LANES)
    t = min(t, max(LANES, -(-n // LANES) * LANES))
    rows = -(-max(k, 1) // 8) * 8
    while t > LANES and 2 * _N_BLOCKS * rows * t * 4 > _VMEM_BUDGET:
        t = max(LANES, (t // 2) // LANES * LANES)
    return t


def pad_lanes(x, n_pad: int, value=0):
    """Pad the last (lane) axis of ``x`` to ``n_pad`` with ``value``."""
    pad = n_pad - x.shape[-1]
    if not pad:
        return x
    cfg = ((0, 0),) * (x.ndim - 1) + ((0, pad),)
    return jnp.pad(x, cfg, constant_values=value)


def block_spec(k: int, tile: int):
    """``(k, tile)`` neighbor block of a lane-major ``(k, N)`` array."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec((k, tile), lambda i: (0, i))


def row_spec(tile: int):
    """``(1, tile)`` block of a ``(1, N)`` row vector."""
    return block_spec(1, tile)
