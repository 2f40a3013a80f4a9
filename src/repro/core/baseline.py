"""Bozdağ-style batched-boundary coloring — the paper's "Zoltan" baseline.

Zoltan's distributed coloring (Bozdağ et al. [3]) colors *interior* vertices
first, then boundary vertices in small batches with an exchange between
batches.  Lower concurrency → fewer conflicts → quality close to serial, at
the cost of more communication rounds.  The paper compares D1/D2 against
this; we implement it so EXPERIMENTS.md §Coloring-quality has its baseline
column (built on the same per-part step functions as the main runtime).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.conflict import gid_hash
from repro.core.distributed import (
    ColoringResult,
    _detect_part,
    _gather_colors,
    _recolor_part,
)
from repro.core.exchange import send_buffer
from repro.core.plan import cached_device_state
from repro.core.validate import num_colors
from repro.graph.partition import PartitionedGraph

__all__ = ["color_baseline"]


def color_baseline(
    pg: PartitionedGraph,
    *,
    problem: str = "d1",
    n_batches: int = 8,
    recolor_degrees: bool = False,
    max_rounds: int = 96,
) -> ColoringResult:
    """Batched-boundary distributed coloring (Bozdağ et al. / Zoltan).

    ``recolor_degrees=False`` matches Zoltan's first-fit conflict rule
    (random/GID tiebreaks only).
    """
    # Routed through the plan layer's host-state cache: repeated baseline
    # runs (and main-runtime plans) on one topology share the tables.
    st_np = cached_device_state(pg, problem)
    st = {k: jnp.asarray(v) for k, v in st_np.items()}
    recolor = jax.jit(jax.vmap(
        partial(_recolor_part, problem=problem, recolor_degrees=recolor_degrees)
    ))
    detect = jax.jit(jax.vmap(
        partial(_detect_part, problem=problem, recolor_degrees=recolor_degrees)
    ))
    sendbuf = jax.vmap(send_buffer)

    @jax.jit
    def exchange(colors):
        allbuf = sendbuf(colors, st)
        ghost = allbuf[st["ghost_part"], st["ghost_slot"]]
        return jnp.where(st["ghost_real"], ghost, 0)

    P, G = st_np["ghost_part"].shape
    nl = st_np["adj_cidx"].shape[1]
    active0 = st_np["active0"]
    boundary = st_np["is_boundary"] & active0
    interior = active0 & ~boundary
    # Deterministic batch assignment by GID hash.
    batch_of = np.asarray(
        gid_hash(jnp.asarray(st_np["gid_tab"][:, :nl]))
    ).astype(np.int64) % n_batches

    colors = jnp.zeros((P, nl), jnp.int32)
    zeros_g = jnp.zeros((P, G), jnp.int32)
    no_ghost_active = jnp.zeros_like(st["ghost_real"])

    # Phase 1: interior only — provably conflict-free (paper §3, Bozdağ).
    colors, _ = recolor(st, colors, zeros_g, jnp.asarray(interior), no_ghost_active)
    ghost = exchange(colors)

    rounds, total = 0, 0
    lose_l = jnp.zeros((P, nl), bool)
    # Phase 2: boundary in batches, exchanging between batches.
    for b in range(n_batches):
        active = jnp.asarray(boundary & (batch_of == b)) | lose_l
        colors = jnp.where(lose_l, 0, colors)
        colors, _ = recolor(st, colors, ghost, active, no_ghost_active)
        ghost = exchange(colors)
        lose_l, _, conf = detect(st, colors, ghost)
        total += int(conf.sum())
        rounds += 1
    # Phase 3: iterate remaining conflicts (like D1's loop).
    conf_g = int(np.asarray(lose_l).sum())
    while conf_g > 0 and rounds < max_rounds:
        colors = jnp.where(lose_l, 0, colors)
        colors, _ = recolor(st, colors, ghost, lose_l, no_ghost_active)
        ghost = exchange(colors)
        lose_l, _, conf = detect(st, colors, ghost)
        conf_g = int(conf.sum())
        total += conf_g
        rounds += 1

    gathered = _gather_colors(pg, np.asarray(colors))
    return ColoringResult(
        colors=gathered,
        rounds=rounds,
        converged=bool(conf_g == 0),
        n_colors=num_colors(gathered),
        total_conflicts=total,
        comm_bytes_per_round=P * pg.send_width * 4,
        problem=f"{problem}-baseline",
        n_parts=P,
    )
