"""Distributed speculate-and-iterate coloring (paper Algorithm 2).

Layered runtime: one *shared loop driver* (:func:`_make_loop`) executes
the speculate→exchange→detect round structure for both execution engines,
parameterized by a pluggable compute backend and exchange strategy:

* **engines** — ``shard_map`` (one XLA program over a device mesh axis
  ``"p"``, on-device ``lax.while_loop`` + ``psum`` convergence test — zero
  host round-trips) and ``simulate`` (the identical driver ``vmap``-ped
  over the part axis on one device).  Both call the same driver with the
  same per-part step functions, so they execute identical math
  (tested bit-for-bit).
* **backends** (``repro.core.backend``) — ``reference`` (pure ``jnp``)
  or ``pallas`` (TPU kernels: vb_bit / d2_forbidden / conflict).
* **exchange strategies** (``repro.core.exchange``) — ``all_gather``,
  ``halo`` (slab ppermute), ``delta`` (changed-colors-only accounting, the
  paper's communication-reduction direction), or ``sparse_delta`` (true
  sparse all-to-all: count-prefixed slot/color pairs routed over
  edge-colored ``ppermute`` phases); per-round payload bytes are
  *measured* and reported in ``ColoringResult.comm_bytes_by_round``.

Problems: ``d1``, ``d1_2gl``, ``d2``, ``pd2`` (paper §3.2-§3.6).

Execution is **compile-once**: :func:`color_distributed` routes through
``repro.core.plan`` — the static half (device state, exchange prepare,
the jitted loop program) is built once per topology/config key and
served from a keyed LRU cache; warm calls feed only per-request dynamic
inputs.  This module keeps the engine-agnostic pieces: the device-state
builder, the per-part step functions, and the shared loop driver.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backend import LocalBackend, ReferenceBackend
from repro.core.conflict import lose_table
from repro.core.exchange import ExchangeStrategy, level_split
from repro.graph.csr import SENTINEL, Graph
from repro.graph.partition import PAD_GID, PartitionedGraph, partition_graph

__all__ = [
    "ColoringResult",
    "color_distributed",
    "color_single_device",
    "build_device_state",
    "neighbor_diagonals",
]

PROBLEMS = ("d1", "d1_2gl", "d2", "pd2")

_REFERENCE = ReferenceBackend()


@dataclasses.dataclass
class ColoringResult:
    colors: np.ndarray          # (n_global,) gathered global coloring
    rounds: int                 # communication rounds after initial coloring
    converged: bool
    n_colors: int
    total_conflicts: int        # sum over rounds of detected conflicts
    comm_bytes_per_round: int   # mean measured payload per device per round
    problem: str
    n_parts: int
    backend: str = "reference"
    exchange: str = "all_gather"
    comm_bytes_total: int = 0   # sum of per-round measured payloads
    # (rounds+1,) measured payload per device for each exchange, starting
    # with the post-initial-coloring one.  None for runtimes that predate
    # measured accounting (baseline / Jones-Plassmann) and for results
    # merged across reduction passes (see ReductionResult.merged_result,
    # which keeps the per-pass split instead).
    comm_bytes_by_round: np.ndarray | None = None
    # (rounds+1, 2) [intra-node, inter-node] split of the same payloads.
    # Flat strategies book every byte as inter-node (any hop may cross
    # hosts); hier_delta measures the two levels separately.  None under
    # the same conditions as comm_bytes_by_round.
    comm_bytes_by_level: np.ndarray | None = None
    # Speculative iterations of the local coloring (the while loop of
    # ``fused_round.speculate`` / ``core.local``) summed over the
    # request's recolor and rounds; the most any part ran.  0 for
    # runtimes that do not count them (baseline / Jones-Plassmann).
    spec_iters: int = 0

    @property
    def comm_bytes_intra(self) -> int:
        """Total measured intra-node payload (0 when the split is absent)."""
        lv = self.comm_bytes_by_level
        return int(lv[:, 0].sum()) if lv is not None else 0

    @property
    def comm_bytes_inter(self) -> int:
        """Total measured inter-node payload (= total when split absent)."""
        lv = self.comm_bytes_by_level
        if lv is None:
            return int(self.comm_bytes_total)
        return int(lv[:, 1].sum())


# ---------------------------------------------------------------------------
# Device state construction (host-side, static per graph+partition).
# ---------------------------------------------------------------------------

def build_device_state(pg: PartitionedGraph, problem: str) -> dict[str, np.ndarray]:
    """Stacked (P, ...) arrays consumed by the SPMD program."""
    if problem not in PROBLEMS:
        raise ValueError(f"problem must be one of {PROBLEMS}")
    needs_l2 = problem in ("d1_2gl", "d2", "pd2")
    if needs_l2 and not pg.has_second_layer:
        raise ValueError(f"{problem} requires partition_graph(..., second_layer=True)")
    P, nl, G, W = pg.n_parts, pg.n_local, pg.n_ghost, pg.ell_width
    pad_cidx = nl + G

    gid_tab = np.concatenate(
        [pg.vertex_gid, pg.ghost_gid, np.full((P, 1), PAD_GID, np.int32)], axis=1
    )
    deg_tab = np.concatenate([pg.deg, pg.ghost_deg, np.zeros((P, 1), np.int32)], axis=1)

    state = {
        "adj_cidx": pg.adj_cidx.astype(np.int32),
        "deg_tab": deg_tab.astype(np.int32),
        "gid_tab": gid_tab.astype(np.int32),
        "send_idx": pg.send_idx.astype(np.int32),
        "send_mask": pg.send_mask,
        "ghost_part": pg.ghost_part.astype(np.int32),
        "ghost_slot": pg.ghost_slot.astype(np.int32),
        "ghost_real": (pg.ghost_gid != SENTINEL),
        "active0": (pg.vertex_gid != PAD_GID),
        "is_boundary": pg.is_boundary,
    }
    if needs_l2:
        # Extended adjacency: rows for locals, then ghosts, then a pad row.
        ext = np.concatenate(
            [pg.adj_cidx, pg.ghost_adj_cidx, np.full((P, 1, W), pad_cidx, np.int32)],
            axis=1,
        ).astype(np.int32)
        state["ext_adj_cidx"] = ext
        if problem in ("d2", "pd2"):
            # One vectorized gather over all parts (the former per-part
            # Python loop was the O(P·n·W²) host hot spot of plan builds).
            th = ext[np.arange(P)[:, None, None], pg.adj_cidx].reshape(P, nl, W * W)
            state["two_hop_cidx"] = th
            # Distance-2 boundary (paper Fig. 1): a vertex whose one- OR
            # two-hop neighborhood crosses the partition — strictly larger
            # than the distance-1 boundary used by D1.
            is_ghost = lambda ix: (ix >= nl) & (ix < pad_cidx)  # noqa: E731
            state["is_boundary"] = (
                is_ghost(pg.adj_cidx).any(axis=2)
                | is_ghost(th).any(axis=2)
            )
    return state


def neighbor_diagonals(st: dict[str, np.ndarray], problem: str):
    """Diagonal layout of the neighbor index ``problem``'s speculative
    coloring reads, over the stacked host state ``st``.

    Returns ``kernels.diagonals.find_diagonals``' ``(layout or None,
    share)``; the plan hands the layout to the backend as ``nbr_diag``.
    """
    from repro.kernels.diagonals import find_diagonals
    from repro.kernels.fused_round import neighbor_index

    n_tab = st["deg_tab"].shape[-1]
    if problem == "d1_2gl":
        idx = st["ext_adj_cidx"][:, : n_tab - 1]
    else:
        idx = neighbor_index(st["adj_cidx"], st.get("two_hop_cidx"), problem)
    return find_diagonals(idx, n_tab)


# ---------------------------------------------------------------------------
# Per-part step functions (pure; no collectives; backend-pluggable).
# ---------------------------------------------------------------------------

def _recolor_part(st, colors_loc, ghost_colors, active_loc, active_ghost, *,
                  problem: str, recolor_degrees: bool,
                  backend: LocalBackend | None = None):
    """Recolor active vertices of one part; returns ``(new local colors,
    speculative iterations)``."""
    backend = backend or _REFERENCE
    n_loc = colors_loc.shape[0]
    zero = jnp.zeros((1,), jnp.int32)
    color_tab = jnp.concatenate([colors_loc, ghost_colors, zero])
    diag = st.get("nbr_diag")
    if problem in ("d2", "pd2"):
        color_tab, iters = backend.color_d2(
            st["adj_cidx"], st["two_hop_cidx"], st["ext_adj_cidx"],
            color_tab, active_loc, st["deg_tab"], st["gid_tab"],
            partial_d2=(problem == "pd2"), recolor_degrees=recolor_degrees,
            diag=diag,
        )
        return color_tab[:n_loc], iters
    if problem == "d1_2gl":
        # Locals + conflicted ghosts recolor together over the extended
        # adjacency; ghosts' speculative colors inform locals (paper §3.4)
        # and are then discarded (restored from the next exchange).
        n_ghost = ghost_colors.shape[0]
        active_ext = jnp.concatenate([active_loc, active_ghost])
        tab = jnp.concatenate(
            [colors_loc, jnp.where(active_ghost, 0, ghost_colors), zero]
        )
        tab, iters = backend.color_d1(
            st["ext_adj_cidx"][: n_loc + n_ghost], tab, active_ext,
            st["deg_tab"], st["gid_tab"], recolor_degrees=recolor_degrees,
            diag=diag,
        )
        return tab[:n_loc], iters
    # plain d1
    color_tab, iters = backend.color_d1(
        st["adj_cidx"], color_tab, active_loc, st["deg_tab"], st["gid_tab"],
        recolor_degrees=recolor_degrees, diag=diag,
    )
    return color_tab[:n_loc], iters


def _detect_part(st, colors_loc, ghost_colors, *, problem: str,
                 recolor_degrees: bool, backend: LocalBackend | None = None):
    """Cross-partition conflict detection (Alg. 3 / Alg. 5).

    Returns (lose_loc (nl,), lose_ghost (G,), n_conflicts scalar).  Only
    owned-vs-ghost pairs are conflicts: local pairs are resolved by the
    local coloring.  Both endpoints' owners reach the same verdict because
    the loser rule is a pure function of replicated per-vertex data.
    """
    backend = backend or _REFERENCE
    n_loc = colors_loc.shape[0]
    n_ghost = ghost_colors.shape[0]
    pad_cidx = n_loc + n_ghost
    zero = jnp.zeros((1,), jnp.int32)
    color_tab = jnp.concatenate([colors_loc, ghost_colors, zero])

    lose_loc = jnp.zeros((n_loc,), bool)
    lose_tab = jnp.zeros((pad_cidx + 1,), jnp.int32)
    n_conf = jnp.int32(0)

    def sweep(adj, lose_loc, lose_tab, n_conf):
        vl, ol, c = backend.detect(
            adj, colors_loc, color_tab, st["deg_tab"], st["gid_tab"],
            st["is_boundary"], recolor_degrees=recolor_degrees,
        )
        lose_loc |= vl
        lose_tab = jnp.maximum(lose_tab,
                               lose_table(adj.T, ol, pad_cidx + 1))
        return lose_loc, lose_tab, n_conf + c

    if problem != "pd2":
        lose_loc, lose_tab, n_conf = sweep(st["adj_cidx"], lose_loc, lose_tab, n_conf)
    if problem in ("d2", "pd2"):
        lose_loc, lose_tab, n_conf = sweep(st["two_hop_cidx"], lose_loc, lose_tab, n_conf)

    return lose_loc, lose_tab[n_loc:pad_cidx] != 0, n_conf


def _round_part(st, colors_loc, ghost_colors, *, problem: str,
                recolor_degrees: bool, backend: LocalBackend | None = None):
    """One fused inner round of one part: detect → zero losers →
    speculative recolor for the next round (``LocalBackend.round``);
    returns ``(colors, lose_l, lose_g, n_conflicts, iters)``."""
    backend = backend or _REFERENCE
    return backend.round(st, colors_loc, ghost_colors, problem=problem,
                         recolor_degrees=recolor_degrees)


# ---------------------------------------------------------------------------
# Shared loop driver (engine-agnostic).
# ---------------------------------------------------------------------------

def _make_loop(recolor, round_fn, exchange, all_sum, *, max_rounds: int):
    """Build the speculate→exchange→round loop from engine primitives.

    Both engines call this with the *same* per-part step functions — the
    ``shard_map`` engine binds per-device state + ``lax`` collectives, the
    ``simulate`` engine binds ``vmap``-ped steps + a stacked gather — so
    they provably execute identical math.

      recolor(colors, ghost, active_local, active_ghost) -> (colors, iters)
      round_fn(colors, ghost) -> (colors, lose_local, lose_ghost, n_confl,
                                  iters)
      exchange(colors, ex_state) -> (ghost, payload_bytes, ex_state)
      all_sum(x) -> global scalar (psum / sum over the part axis)

    ``iters`` counts the speculative iterations of a part's local
    coloring; the loop sums them over the request's recolor and rounds
    in the ``iters`` carry entry, kept per part (no collective), and
    returns that sum last.  Every ``exchange`` call runs under the
    ``exchange`` named scope and every ``round_fn`` call under ``round``,
    so a profile attributes device time to those layers.

    ``round_fn`` fuses conflict detection with the *next* round's
    speculative recoloring (``LocalBackend.round``): detect round k and
    recolor round k+1 read the same (colors, ghost) tables, so fusing
    them halves table reads, whereas the former recolor→detect body was
    split by the exchange.  The rotation is bit-exact: at convergence
    the trailing recolor has an all-false active mask and is the
    identity, so the returned colors equal the unrotated loop's.
    """

    def exchange_(colors, ex_state):
        with jax.named_scope("exchange"):
            return exchange(colors, ex_state)

    def round_(colors, ghost):
        with jax.named_scope("round"):
            return round_fn(colors, ghost)

    def loop(colors0, zeros_ghost, active0, no_ghost_active, ex_state0):
        colors, iters0 = recolor(colors0, zeros_ghost, active0,
                                 no_ghost_active)
        ghost, nbytes, ex_state = exchange_(colors, ex_state0)
        colors, lose_l, lose_g, conf, iters = round_(colors, ghost)
        conf = all_sum(conf)
        # Byte history carries the [intra-node, inter-node] split per
        # round (flat strategies are booked as inter; see level_split).
        bytes_hist = jnp.zeros((max_rounds + 1, 2), jnp.int32)
        bytes_hist = bytes_hist.at[0].set(level_split(nbytes))
        carry = {
            "colors": colors, "ghost": ghost, "lose_l": lose_l,
            "lose_g": lose_g, "ex_state": ex_state, "conf": conf,
            "rounds": jnp.int32(0), "total": conf, "bytes": bytes_hist,
            "iters": iters0 + iters,
        }

        def cond(c):
            return (c["conf"] > 0) & (c["rounds"] < max_rounds)

        def body(c):
            ghost, nbytes, ex_state = exchange_(c["colors"], c["ex_state"])
            colors, lose_l, lose_g, conf, iters = round_(c["colors"], ghost)
            conf = all_sum(conf)
            rounds = c["rounds"] + 1
            return {
                "colors": colors, "ghost": ghost, "lose_l": lose_l,
                "lose_g": lose_g, "ex_state": ex_state, "conf": conf,
                "rounds": rounds, "total": c["total"] + conf,
                "bytes": c["bytes"].at[rounds].set(level_split(nbytes)),
                "iters": c["iters"] + iters,
            }

        # The batched recoloring service vmaps this loop over a request
        # axis; jax's while_loop batching rule keeps iterating until every
        # element's cond is false and select-masks the carries of finished
        # elements, so each request stays bit-identical to its solo run
        # (pinned by tests/test_plan.py::test_service_batch_bit_identical).
        out = jax.lax.while_loop(cond, body, carry)
        return (out["colors"], out["rounds"], out["conf"], out["total"],
                out["bytes"], out["iters"])

    return loop


# ---------------------------------------------------------------------------
# Public entry points.
# ---------------------------------------------------------------------------

def _gather_colors(pg: PartitionedGraph, stacked_colors: np.ndarray) -> np.ndarray:
    out = np.zeros(pg.n_global, dtype=np.int32)
    real = pg.vertex_gid != PAD_GID
    out[pg.vertex_gid[real]] = stacked_colors[real]
    return out


def color_distributed(
    pg: PartitionedGraph,
    *,
    problem: str = "d1",
    recolor_degrees: bool = True,
    backend: str | LocalBackend = "reference",
    exchange: str | ExchangeStrategy = "all_gather",
    max_rounds: int = 64,
    engine: str = "auto",
    mesh: jax.sharding.Mesh | None = None,
    color_mask: np.ndarray | None = None,
    cache=None,
    reduce_passes: int = 0,
    reduce_order: str = "reverse",
) -> ColoringResult:
    """Color a partitioned graph with the paper's distributed algorithm.

    Routed through the plan/executor layer (``repro.core.plan``): the
    static half — device-state tables, exchange prepare, and the jitted
    loop program — is built once per ``(topology, problem, recolor_degrees,
    backend, exchange, engine, max_rounds)`` and served from a keyed LRU
    cache, so repeated calls on the same topology (the paper's
    timestep-recoloring workload) pay only the cheap dynamic half.

    backend: "reference" (pure jnp) or "pallas" (TPU kernels; interpret
    mode on CPU) — see ``repro.core.backend``.  Both produce identical
    colorings and round counts.

    exchange: "all_gather", "halo" (slab partitions only), "delta"
    (changed-colors-only), or "sparse_delta" (true sparse a2a over
    ppermute phases) — see ``repro.core.exchange``.  Per-round payload
    bytes are measured and reported in the result.

    engine: "shard_map" (needs >= n_parts devices), "simulate" (vmap on one
    device), or "auto".

    color_mask: optional (n_global,) bool — restrict coloring to a vertex
    subset.  This implements the paper's stated FUTURE WORK for PD2
    ("modify PD2 to allow it to color only vertices of interest", §6):
    with the bipartite V_s mask, only the Jacobian's column set is
    colored, matching Zoltan's behavior.  A per-request dynamic input:
    changing it never retraces.

    cache: ``None`` → the process-wide default :class:`~repro.core.plan.
    PlanCache`; a ``PlanCache`` instance → that cache; ``False`` → build a
    fully cold plan for this call (fresh host state too).  Cached plans
    pin device state + executables until LRU-evicted; for sweeps over
    many large topologies use ``cache=False`` or clear the default cache.

    reduce_passes / reduce_order: optional post-coloring quality pass —
    run up to ``reduce_passes`` iterative color-reduction passes
    (``repro.core.reduce``) over the finished coloring, rebuilding its
    classes in ``reduce_order``.  The returned result folds the
    reduction in: final colors, summed rounds and measured comm bytes.
    Use :func:`repro.core.reduce.reduce_colors` directly for the full
    colors-by-pass trajectory.
    """
    from repro.core import plan as plan_mod

    plan = plan_mod.get_plan(
        pg, problem=problem, recolor_degrees=recolor_degrees,
        backend=backend, exchange=exchange, engine=engine,
        max_rounds=max_rounds, mesh=mesh, cache=cache,
    )
    res = plan.run(color_mask=color_mask)
    if reduce_passes > 0:
        from repro.core.reduce import reduce_colors

        red = reduce_colors(plan, res, passes=reduce_passes,
                            order=reduce_order, cache=cache,
                            color_mask=color_mask)
        res = red.merged_result(res)
    return res


def color_single_device(
    graph: Graph, *, problem: str = "d1", recolor_degrees: bool = True,
    backend: str | LocalBackend = "reference",
) -> ColoringResult:
    """Single-device speculate&iterate (the paper's 1-GPU baseline)."""
    pg = partition_graph(graph, 1, second_layer=problem != "d1")
    return color_distributed(
        pg, problem=problem, recolor_degrees=recolor_degrees,
        backend=backend, engine="simulate",
    )
