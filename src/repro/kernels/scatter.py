"""``pair_scatter`` Pallas kernel — apply (slot-id, value) pairs to a table.

The ``sparse_delta`` ghost exchange ships count-prefixed
``(send-slot-id, color)`` pairs; receivers must scatter them into their
per-owner slot tables.  TPU Pallas has no efficient scatter primitive, so
the kernel inverts the operation into a gather: a 2-D grid walks
``(table tile, pair chunk)`` blocks, broadcast-compares the lane-major
table positions ``(1, tile)`` against a sublane-major chunk of slot ids
``(chunk, 1)`` — ``(chunk, tile)`` elementwise work in VREGs — and
selects the paired value where a slot matches; the output tile stays
resident across the chunk axis.  Callers guarantee slot ids are unique;
padded pairs carry an out-of-range slot (>= table length) and fall
through to the old table value.  Work is ``O(N·C)``: meant for the small
per-peer slot tables of the sparse exchanges.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import default_interpret, lane_tile, pad_lanes

DEFAULT_TILE = 512
CHUNK = 256

__all__ = ["pair_scatter", "DEFAULT_TILE"]


def _pair_scatter_kernel(tile, table_ref, slots_ref, values_ref, out_ref):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[...] = table_ref[...]

    pos = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) + i * tile
    match = slots_ref[...] == pos                         # (chunk, tile)
    hit = jnp.max(match.astype(jnp.int32), axis=0, keepdims=True)
    val = jnp.sum(jnp.where(match, values_ref[...], 0), axis=0,
                  keepdims=True)                          # slots unique
    out_ref[...] = jnp.where(hit != 0, val, out_ref[...])


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def pair_scatter(
    table: jnp.ndarray,       # (N,) int32 slot table
    slots: jnp.ndarray,       # (C,) int32 slot ids; >= N means "dropped pad"
    values: jnp.ndarray,      # (C,) int32 paired values
    *,
    tile: int = DEFAULT_TILE,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Return ``table`` with ``table[slots[j]] = values[j]`` applied.

    Pairs whose slot id is ``>= len(table)`` are dropped (the count-prefix
    padding convention of ``repro.core.exchange.pack_pairs``).  Real slot
    ids must be unique.  Bit-exact against the jnp reference
    ``repro.kernels.ref.pair_scatter_ref``.
    """
    if interpret is None:
        interpret = default_interpret()
    n, c = table.shape[0], slots.shape[0]
    t = lane_tile(tile, n)
    n_pad = -(-n // t) * t
    c_pad = -(-c // CHUNK) * CHUNK
    # Out-of-range slots (pads) are remapped past every padded position.
    slots = jnp.where(slots.astype(jnp.int32) < n, slots, n_pad)
    col = lambda x, v: jnp.pad(x, (0, c_pad - c), constant_values=v).reshape(c_pad, 1)  # noqa: E731
    chunk_spec = pl.BlockSpec((CHUNK, 1), lambda i, j: (j, 0))
    tile_spec = pl.BlockSpec((1, t), lambda i, j: (0, i))
    out = pl.pallas_call(
        functools.partial(_pair_scatter_kernel, t),
        grid=(n_pad // t, c_pad // CHUNK),
        in_specs=[tile_spec, chunk_spec, chunk_spec],
        out_specs=tile_spec,
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        interpret=interpret,
    )(pad_lanes(table.astype(jnp.int32), n_pad).reshape(1, n_pad),
      col(slots.astype(jnp.int32), n_pad), col(values.astype(jnp.int32), 0))
    return out[0, :n]
