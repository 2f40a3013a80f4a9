"""Conflict-resolution rules (paper Algorithm 4, ``Check-Conflicts``).

The loser of a conflicting pair is decided by a *pure function* of
(color, degree, hash(GID), GID), so any two parties — lanes on one device or
two devices across the mesh — reach the same verdict with zero
communication.  This is the paper's consistency mechanism; we keep the rule
bit-identical to Algorithm 4:

  1. colors equal and nonzero, else no conflict;
  2. if ``recolor_degrees``: the *lower-degree* endpoint loses
     (it is cheaper to recolor — the paper's novel heuristic, §3.3);
  3. tie → the endpoint with the *higher* ``rand(GID)`` loses
     (Bozdağ et al. rule);
  4. tie → the endpoint with the higher GID loses.
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["gid_hash", "v_loses", "lose_table"]


def gid_hash(gid: jnp.ndarray) -> jnp.ndarray:
    """``rand(GID)``: deterministic avalanche hash (lowbias32 variant).

    Matches the paper's role for Bozdağ's per-vertex RNG: a fixed
    pseudo-random value derived from the global id only.
    """
    x = gid.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def v_loses(
    color_v: jnp.ndarray,
    color_u: jnp.ndarray,
    deg_v: jnp.ndarray,
    deg_u: jnp.ndarray,
    gid_v: jnp.ndarray,
    gid_u: jnp.ndarray,
    *,
    recolor_degrees: bool,
) -> jnp.ndarray:
    """True where vertex ``v`` must be uncolored in the pair ``(v, u)``.

    Vectorized Algorithm 4 from v's perspective.  ``u``'s owner evaluates
    the mirrored call and reaches the complementary verdict.  Self-pairs
    (``gid_v == gid_u``) are never conflicts.
    """
    conflict = (color_v == color_u) & (color_v > 0) & (gid_v != gid_u)
    hv, hu = gid_hash(gid_v), gid_hash(gid_u)
    # Pure boolean algebra (no select over bools), so the same rule lowers
    # inside the Mosaic kernels, where i1 selects are not supported.
    loses = (hv > hu) | ((hv == hu) & (gid_v > gid_u))
    if recolor_degrees:
        loses = (deg_v < deg_u) | ((deg_v == deg_u) & loses)
    return conflict & loses


def lose_table(idx: jnp.ndarray, flags: jnp.ndarray, size: int) -> jnp.ndarray:
    """int32 ``(size,)`` table: 1 at each ``idx`` entry whose flag is set.

    The ghost side of a conflict sweep: ``flags`` are the per-edge
    neighbor-side lose flags and ``idx`` their color-table indices (same
    shape).  Two XLA:TPU compile-time traps are avoided: the scatter-max
    runs on int32 (a bool one compiles ~25x slower), and ``idx`` takes on
    the batch axes of ``flags`` (``+ 0 * flags``) — under an outer
    ``vmap`` that batches only the flags, such as the serving slot
    engine's request axis, a scatter with unbatched indices compiles ~30x
    slower.
    """
    flags = flags.astype(jnp.int32)
    idx = idx + 0 * flags
    return jnp.zeros((size,), jnp.int32).at[idx.reshape(-1)].max(
        flags.reshape(-1))
