"""Pluggable ghost-exchange strategies for the distributed coloring loop.

The paper's MPI boundary exchange becomes one of four swappable
strategies, each implemented twice over the same index tables — once with
``lax`` collectives for the ``shard_map`` engine (per-device view) and
once as a stacked gather for the ``simulate`` engine (part axis leading):

* ``all_gather``   — every part broadcasts its send buffer; ghosts are a
  static ``(owner_part, send_slot)`` gather from the gathered table.
  Measured bytes/device/round: ``P·S·4``.
* ``halo``         — two-way ``ppermute`` for slab partitions (ghosts only
  on parts p±1).  Measured bytes/device/round: ``2·S·4``.
* ``delta``        — iterative-recoloring communication reduction (Sarıyüce
  et al.): after the first round only boundary vertices whose color
  *changed* are exchanged; receivers patch their ghost table.  Still rides
  all_gather mechanics under the hood — the byte count is the payload a
  mask+words wire format *would* move: ``4·(global changed) + P·⌈S/8⌉``.
* ``sparse_delta`` — the true sparse all-to-all: changed boundary colors
  are packed as count-prefixed ``(send-slot-id, color)`` pairs into
  fixed-capacity per-destination buffers (capacity = send width) and
  routed point-to-point with one ``lax.ppermute`` per phase of an
  edge-colored route plan (``core.a2a_schedule.exchange_route_plan`` —
  the runtime schedules its own communication with the paper's D1
  algorithm).  Receivers scatter the pairs into a per-owner slot table.
  Measured bytes/device/round: ``4·Σ_edges(1 + 2·sent) / P`` — this is
  the payload actually moved, not an estimate (under ``ppermute`` the
  fixed-capacity buffer occupies the wire, so wire bytes equal measured
  bytes exactly when buffers are full).  ``ragged=True`` replaces the
  phase loop with one single-shot ``lax.ragged_all_to_all`` that moves
  the measured count only (XLA:CPU does not implement it, so the loop is
  the default and the only transport the CPU backend runs).
* ``hier_delta`` — the two-level NCCL-style hierarchy over a
  ``(node, local)`` factorization of the part axis
  (``launch.mesh.factor_parts``): same-node pairs go point-to-point over
  the fast links (an edge-colored intra plan), cross-node pairs are
  aggregated per destination *node* (deduplicating same-node ghosters),
  gathered member→leader, shipped once per routed node edge
  leader→leader, and re-broadcast leader→members
  (``core.a2a_schedule.hierarchical_route_plan``).  On the wire, colors
  ride the narrowest dtype the palette bound admits and slot ids/counts
  the narrowest width the send capacity admits (:func:`wire_dtype`), so
  the measured bytes — split into intra-node vs inter-node totals — are
  derived from the *packed* widths.

Strategies carry loop state (``init_state``) through the round loop —
``delta`` keeps the previous send buffer and ghost table, the sparse
strategies the previous send buffer and the per-peer slot tables; the
static strategies carry nothing.  Strategies that need host-side setup
(route plans, per-destination need masks, wire dtypes) override
:meth:`prepare`.  Every strategy returns a *measured* per-round byte
count through the shared :func:`payload_bytes` schema — scalar, or a
shape-(2,) ``[intra-node, inter-node]`` split which :func:`level_split`
normalizes for the loop drivers — accumulated into
``ColoringResult.comm_bytes_by_round`` / ``comm_bytes_by_level`` (no
static estimates anywhere).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.registry import Registry

__all__ = [
    "ExchangeStrategy",
    "AllGatherExchange",
    "HaloExchange",
    "DeltaExchange",
    "SparseDeltaExchange",
    "HierDeltaExchange",
    "EXCHANGES",
    "get_exchange",
    "list_exchanges",
    "register_exchange",
    "send_buffer",
    "payload_bytes",
    "wire_dtype",
    "dtype_bytes",
    "level_split",
    "pack_pairs",
    "apply_pairs",
]

COLOR_DTYPE = jnp.int32            # in-memory dtype for colors/slots
COLOR_BYTES = np.dtype(np.int32).itemsize


def wire_dtype(bound: int):
    """Narrowest wire dtype that represents every value in ``0..bound``.

    The packed-wire-format selector: ``hier_delta`` calls it with the
    static palette bound (first-fit: ``Δ+1`` for D1-family problems,
    ``Δ²+1`` for the distance-2 family) to pick the color wire dtype and
    with the send capacity ``S`` (the pad sentinel — the largest slot id
    or count a buffer can carry) to pick the slot/count wire dtype.
    """
    if bound < 0:
        raise ValueError(f"wire bound must be >= 0, got {bound}")
    if bound <= np.iinfo(np.uint8).max:
        return jnp.uint8
    if bound <= np.iinfo(np.uint16).max:
        return jnp.uint16
    return COLOR_DTYPE


def dtype_bytes(dtype) -> int:
    """Bytes per element of a wire dtype (the one itemsize rule)."""
    return int(np.dtype(dtype).itemsize)


def send_buffer(colors_loc, st):
    """Pack the colors other parts need into the static send layout."""
    return jnp.where(st["send_mask"], colors_loc[st["send_idx"]], 0)


def payload_bytes(st, *, colors=0, masks=0, headers=0, pairs=0,
                  color_dtype=COLOR_DTYPE, slot_dtype=COLOR_DTYPE):
    """Measured payload bytes under one shared schema.

    ``colors`` counts bare color words (at ``color_dtype`` width),
    ``headers`` counts buffer count-prefix words (at ``slot_dtype``
    width), ``pairs`` counts ``(slot-id, color)`` tuples (one word of
    each dtype), and ``masks`` counts whole changed-bitmasks over the
    send width.  Every strategy computes its byte accounting through
    this helper with the wire dtypes it actually ships, so the width
    rule and the mask rounding live in exactly one place and measured
    bytes cannot drift between strategies that pack differently.
    """
    s = st["send_idx"].shape[-1]
    cb, sb = dtype_bytes(color_dtype), dtype_bytes(slot_dtype)
    total = (cb * colors + sb * headers + (cb + sb) * pairs
             + masks * ((s + 7) // 8))
    return jnp.asarray(total).astype(jnp.int32)


def level_split(nbytes):
    """Normalize a strategy's byte return to the ``[intra, inter]`` pair.

    Flat strategies return a scalar — booked entirely as *inter-node*
    (every hop may cross hosts); hierarchical strategies return the
    shape-(2,) ``[intra-node, inter-node]`` split directly.  The loop
    drivers route every exchange's return through this, so third-party
    strategies may use either form.
    """
    nbytes = jnp.asarray(nbytes)
    if nbytes.ndim == 0:
        return jnp.stack([jnp.zeros((), nbytes.dtype), nbytes])
    return nbytes


def pack_pairs(take, send):
    """Front-pack one destination's changed slots as (slot-id, color) pairs.

    Returns ``(slots, colors, count)`` with capacity ``S = take.shape[0]``:
    the first ``count`` entries are the selected slot ids in ascending
    order with their colors; padding carries the out-of-range sentinel
    slot ``S`` (dropped by :func:`apply_pairs`).  The sort key is fully
    deterministic (no reliance on sort stability).
    """
    s = take.shape[0]
    count = take.sum().astype(COLOR_DTYPE)
    key = jnp.where(take, 0, s + 1) + jnp.arange(s, dtype=COLOR_DTYPE)
    order = jnp.argsort(key).astype(COLOR_DTYPE)
    valid = jnp.arange(s) < count
    slots = jnp.where(valid, order, s).astype(COLOR_DTYPE)
    colors = jnp.where(valid, send[order], 0).astype(COLOR_DTYPE)
    return slots, colors, count


def apply_pairs(table, slots, colors, *, scatter: str = "reference"):
    """Scatter received (slot-id, color) pairs into a slot table.

    Padded pairs carry slot id >= len(table) and are dropped.  ``scatter``
    selects the jnp reference or the Pallas ``pair_scatter`` kernel
    (``repro.kernels.ops``) — both produce identical tables.
    """
    if scatter == "pallas":
        from repro.kernels.ops import pair_scatter

        return pair_scatter(table, slots, colors)
    return table.at[slots].set(colors, mode="drop")


def _route_pair_phases(plan, ghost_tab, counts, slots, colors, *, p, axis,
                       n_parts, scatter, slot_dtype=COLOR_DTYPE,
                       color_dtype=COLOR_DTYPE):
    """Execute a :class:`RoutePlan` over packed per-destination pair tables.

    ``counts (D,)``, ``slots (D, S)``, ``colors (D, S)`` are the sender's
    per-destination packed buffers (int32 in memory).  Each phase ships
    one count-prefixed header at ``slot_dtype`` and the colors at
    ``color_dtype`` — the packed wire format — to ``dst_of[k][p]``, and
    scatters arrivals into ``ghost_tab[src]``.  Shared by the flat
    ``sparse_delta`` loop (int32 wire) and ``hier_delta``'s intra stage
    (narrow wire); both parties of an edge agree on the static dtypes.
    """
    s = slots.shape[-1]
    arange_s = jnp.arange(s)
    for k, phase in enumerate(plan.phases):
        dst = jnp.asarray(plan.dst_of[k])[p]                  # -1 = idle
        src = jnp.asarray(plan.src_of[k])[p]
        d = jnp.clip(dst, 0, counts.shape[0] - 1)
        head = jnp.concatenate([counts[d][None], slots[d]]).astype(slot_dtype)
        cols = colors[d].astype(color_dtype)
        head = jnp.where(dst >= 0, head, 0)                   # idle sends 0
        cols = jnp.where(dst >= 0, cols, 0)
        r_head = jax.lax.ppermute(head, axis, list(phase))
        r_cols = jax.lax.ppermute(cols, axis, list(phase))
        r_count = r_head[0].astype(COLOR_DTYPE)
        r_slots = r_head[1:].astype(COLOR_DTYPE)
        valid = (arange_s < r_count) & (src >= 0)
        idx = jnp.where(valid, r_slots, s)                    # pad -> drop
        o = jnp.clip(src, 0, n_parts - 1)
        row = apply_pairs(ghost_tab[o], idx, r_cols.astype(COLOR_DTYPE),
                          scatter=scatter)
        ghost_tab = ghost_tab.at[o].set(
            jnp.where(src >= 0, row, ghost_tab[o]))
    return ghost_tab


def _stacked_pair_apply(ghost_tab, take, send, live, *, scatter):
    """Pack and deliver pair tables in the stacked (simulate) view.

    ``take (P, D, S)`` selects, owner-major, which send slots each of
    ``D`` destinations receives; ``send (P, S)`` are the owner send
    buffers; ``live (P, D)`` marks the edges that actually ship.
    Returns the receiver-major patched ``ghost_tab (D, P, S)`` plus the
    owner-major pair counts ``(P, D)`` for byte accounting.  This is the
    simulate-engine counterpart of :func:`_route_pair_phases` — same
    pack, same scatter, no wire, so the narrow dtypes need not apply.
    """
    s = take.shape[-1]
    slots, cols, counts = jax.vmap(
        lambda t_rows, s_row: jax.vmap(pack_pairs, in_axes=(0, None))(
            t_rows, s_row)
    )(take, send)                                             # [owner, dest]
    sl_t = jnp.swapaxes(slots, 0, 1)
    co_t = jnp.swapaxes(cols, 0, 1)
    cn_t = jnp.swapaxes(counts, 0, 1)
    lv_t = jnp.swapaxes(jnp.asarray(live), 0, 1)
    valid = (jnp.arange(s)[None, None, :] < cn_t[..., None]) & lv_t[..., None]
    idx = jnp.where(valid, sl_t, s)
    apply2 = jax.vmap(jax.vmap(
        lambda tab, ix, co: apply_pairs(tab, ix, co, scatter=scatter)))
    return apply2(ghost_tab, idx, co_t), counts


class ExchangeStrategy:
    """Interface: one ghost exchange per round, with measured byte count.

    ``device`` is the per-device (shard_map) implementation using ``lax``
    collectives over ``axis``; ``stacked`` is the part-axis-leading
    (simulate) implementation.  Both return ``(ghost, nbytes, state)``
    with identical values, so the engines execute identical math.
    """

    name: str = "abstract"
    requires_slab: bool = False

    def prepare(self, pg, st):
        """Host-side setup before the loop (static per graph+partition).

        Returns extra stacked ``(P, ...)`` arrays for the runtime to merge
        into the device state (sharded over the part axis like everything
        else).  Static strategies need none; ``sparse_delta`` builds its
        per-destination need masks and ppermute route plan here.
        """
        return {}

    def init_state(self, st):
        """Loop-carried exchange state (shapes follow ``st``'s layout)."""
        return ()

    def device(self, st, colors_loc, state, *, axis, n_parts):
        raise NotImplementedError

    def stacked(self, st, colors, state):
        raise NotImplementedError


class AllGatherExchange(ExchangeStrategy):
    name = "all_gather"

    def device(self, st, colors_loc, state, *, axis, n_parts):
        send = send_buffer(colors_loc, st)
        allbuf = jax.lax.all_gather(send, axis)                   # (P, S)
        ghost = allbuf[st["ghost_part"], st["ghost_slot"]]
        ghost = jnp.where(st["ghost_real"], ghost, 0)
        nbytes = payload_bytes(st, colors=n_parts * send.shape[0])
        return ghost, nbytes, state

    def stacked(self, st, colors, state):
        allbuf = jax.vmap(send_buffer)(colors, st)                # (P, S)
        ghost = allbuf[st["ghost_part"], st["ghost_slot"]]
        ghost = jnp.where(st["ghost_real"], ghost, 0)
        nbytes = payload_bytes(st, colors=allbuf.shape[0] * allbuf.shape[1])
        return ghost, nbytes, state


class HaloExchange(ExchangeStrategy):
    """Two-way slab halo: each part talks only to p-1 and p+1."""

    name = "halo"
    requires_slab = True

    def device(self, st, colors_loc, state, *, axis, n_parts):
        send = send_buffer(colors_loc, st)
        p = jax.lax.axis_index(axis)
        fwd = [(i, i + 1) for i in range(n_parts - 1)]            # recv from p-1
        bwd = [(i + 1, i) for i in range(n_parts - 1)]            # recv from p+1
        from_prev = jax.lax.ppermute(send, axis, fwd)
        from_next = jax.lax.ppermute(send, axis, bwd)
        ghost = jnp.where(
            st["ghost_part"] < p,
            from_prev[st["ghost_slot"]],
            from_next[st["ghost_slot"]],
        )
        ghost = jnp.where(st["ghost_real"], ghost, 0)
        nbytes = payload_bytes(st, colors=2 * send.shape[0])
        return ghost, nbytes, state

    def stacked(self, st, colors, state):
        # Slab validity is checked up front, so every ghost's owner is p±1
        # and the gathered values coincide with the ppermute pair; only the
        # byte accounting differs from all_gather.
        allbuf = jax.vmap(send_buffer)(colors, st)
        ghost = allbuf[st["ghost_part"], st["ghost_slot"]]
        ghost = jnp.where(st["ghost_real"], ghost, 0)
        nbytes = payload_bytes(st, colors=2 * allbuf.shape[1])
        return ghost, nbytes, state


class DeltaExchange(ExchangeStrategy):
    """Changed-colors-only exchange (communication-reducing recoloring).

    Round 0 ships every real send slot (all colors are new); afterwards a
    slot is shipped only if its color differs from the previous round, and
    receivers patch the stale entries of their ghost table.  The carried
    state is (previous send buffer, previous ghost table).
    """

    name = "delta"

    def init_state(self, st):
        return {
            "prev_send": jnp.zeros(st["send_idx"].shape, COLOR_DTYPE),
            "prev_ghost": jnp.zeros(st["ghost_part"].shape, COLOR_DTYPE),
        }

    def device(self, st, colors_loc, state, *, axis, n_parts):
        send = send_buffer(colors_loc, st)
        changed = st["send_mask"] & (send != state["prev_send"])
        payload = jnp.where(changed, send, 0)
        ch_all = jax.lax.all_gather(changed, axis)                # (P, S) bits
        pay_all = jax.lax.all_gather(payload, axis)
        ghost_new = ch_all[st["ghost_part"], st["ghost_slot"]] & st["ghost_real"]
        ghost = jnp.where(
            ghost_new, pay_all[st["ghost_part"], st["ghost_slot"]],
            state["prev_ghost"],
        )
        nbytes = payload_bytes(st, colors=ch_all.sum(), masks=n_parts)
        return ghost, nbytes, {"prev_send": send, "prev_ghost": ghost}

    def stacked(self, st, colors, state):
        send = jax.vmap(send_buffer)(colors, st)                  # (P, S)
        changed = st["send_mask"] & (send != state["prev_send"])
        payload = jnp.where(changed, send, 0)
        ghost_new = changed[st["ghost_part"], st["ghost_slot"]] & st["ghost_real"]
        ghost = jnp.where(
            ghost_new, payload[st["ghost_part"], st["ghost_slot"]],
            state["prev_ghost"],
        )
        nbytes = payload_bytes(st, colors=changed.sum(), masks=send.shape[0])
        return ghost, nbytes, {"prev_send": send, "prev_ghost": ghost}


class SparseDeltaExchange(ExchangeStrategy):
    """True sparse delta all-to-all over a ppermute route plan.

    Per round, each part packs the ``(send-slot-id, color)`` pairs of
    boundary vertices whose color changed since the previous round into a
    fixed-capacity count-prefixed buffer per destination (capacity = send
    width ``S``, so the shape is static) and ships each buffer
    point-to-point: one ``lax.ppermute`` per phase of the edge-colored
    route plan built by :func:`repro.core.a2a_schedule.exchange_route_plan`
    from the static owner→ghoster traffic graph.  Receivers scatter the
    pairs into a per-owner slot table (``ghost_tab[owner, slot]`` = last
    color heard) and gather ghosts from it, so the reconstruction is
    exact: identical colorings and round counts to ``all_gather``.

    Loop-carried state: the previous send buffer plus the per-peer slot
    tables — the buffers flow through ``_make_loop``'s carry like any
    other exchange state.  Measured bytes are the count-prefixed payload
    actually moved (``1 + 2·count`` words per routed edge), averaged per
    device.

    ``scatter`` selects how received pairs are applied: the jnp
    ``reference`` scatter or the ``pallas`` ``pair_scatter`` kernel.
    ``ragged`` selects the ``shard_map`` transport explicitly — never by
    platform: ``False`` (default) is the ``ppermute`` phase loop,
    ``True`` one single-shot ``lax.ragged_all_to_all`` that moves exactly
    the measured count (XLA:CPU cannot run it).  Both transports move
    the same payload, so measured bytes and results are identical; the
    stacked (simulate) view has no wire and ignores the choice.
    """

    name = "sparse_delta"

    def __init__(self, *, scatter: str = "reference", ragged: bool = False):
        if not isinstance(ragged, bool):
            raise ValueError(f"ragged must be True or False, got {ragged!r}")
        self.scatter = scatter
        self.ragged = ragged
        self._plan = None
        self._traffic = None

    def prepare(self, pg, st):
        from repro.core.a2a_schedule import exchange_route_plan
        from repro.graph.csr import SENTINEL

        p_, s_ = pg.n_parts, pg.send_width
        # need[owner, dest, slot]: dest ghosts the owner's send slot.
        need = np.zeros((p_, p_, s_), dtype=bool)
        for q in range(p_):
            real = pg.ghost_gid[q] != SENTINEL
            need[pg.ghost_part[q][real], q, pg.ghost_slot[q][real]] = True
        traffic = need.any(axis=2)
        self._plan = exchange_route_plan(traffic.astype(np.int64))
        self._traffic = traffic
        return {"peer_need": need}

    def init_state(self, st):
        if "peer_need" not in st:
            raise ValueError(
                "sparse_delta needs its prepare() tables; run it through "
                "color_distributed (or call prepare(pg, st) first)"
            )
        return {
            "prev_send": jnp.zeros(st["send_idx"].shape, COLOR_DTYPE),
            # Per-peer slot tables: device (P, S) = owner-major; stacked
            # (P, P, S) = receiver-major — both match peer_need's shape.
            "ghost_tab": jnp.zeros(st["peer_need"].shape, COLOR_DTYPE),
        }

    def device(self, st, colors_loc, state, *, axis, n_parts):
        s = st["send_idx"].shape[0]
        p = jax.lax.axis_index(axis)
        send = send_buffer(colors_loc, st)
        changed = st["send_mask"] & (send != state["prev_send"])
        # Pack one fixed-capacity buffer per destination: (P, S) each.
        take = changed[None, :] & st["peer_need"]
        slots, colors, counts = jax.vmap(pack_pairs, in_axes=(0, None))(
            take, send
        )
        # Measured payload: count header + (slot, color) pair per routed
        # edge, at int32 wire widths; global total averaged per device.
        traffic_row = jnp.asarray(self._traffic)[p]               # (P,)
        hdr = traffic_row.sum().astype(jnp.int32)
        prs = jnp.where(traffic_row, counts, 0).sum().astype(jnp.int32)
        hdr, prs = jax.lax.psum(jnp.stack([hdr, prs]), axis)
        nbytes = payload_bytes(st, headers=hdr, pairs=prs) // n_parts

        if self.ragged:
            ghost_tab = self._device_ragged(
                state["ghost_tab"], traffic_row, counts, slots, colors,
                p=p, axis=axis, n_parts=n_parts, s=s)
        else:
            ghost_tab = _route_pair_phases(
                self._plan, state["ghost_tab"], counts, slots, colors,
                p=p, axis=axis, n_parts=n_parts, scatter=self.scatter)
        ghost = ghost_tab[st["ghost_part"], st["ghost_slot"]]
        ghost = jnp.where(st["ghost_real"], ghost, 0)
        return ghost, nbytes, {"prev_send": send, "ghost_tab": ghost_tab}

    def _device_ragged(self, ghost_tab, traffic_row, counts, slots, colors,
                       *, p, axis, n_parts, s):
        """Single-shot transport: one ragged all-to-all replaces the loop.

        Per-source regions of fixed capacity ``1 + 2S`` words hold the
        count-prefixed rows ``[count, slot_0, color_0, slot_1, ...]``;
        ``send_sizes`` trims each to the measured ``1 + 2·count`` (0
        off-traffic), so exactly the counted payload crosses the wire —
        the pairs are interleaved so that prefix holds all of them.
        Receivers learn their ragged ``recv_sizes`` from an all-gather of
        the size columns (int32 metadata, not payload — NCCL exchanges the
        equivalent handshake).
        """
        width = 1 + 2 * s
        pairs = jnp.stack([slots, colors.astype(slots.dtype)], axis=-1)
        rows = jnp.concatenate([counts[:, None].astype(slots.dtype),
                                pairs.reshape(n_parts, 2 * s)], axis=1)
        rows = jnp.where(traffic_row[:, None], rows, 0)           # (P, 1+2S)
        send_sizes = jnp.where(traffic_row, 1 + 2 * counts, 0).astype(
            jnp.int32)
        recv_sizes = jax.lax.all_gather(send_sizes, axis)[:, p]
        recv = jax.lax.ragged_all_to_all(
            rows.reshape(-1),
            jnp.zeros((n_parts * width,), rows.dtype),
            jnp.arange(n_parts, dtype=jnp.int32) * width,
            send_sizes,
            jnp.full((n_parts,), p * width, jnp.int32),
            recv_sizes,
            axis_name=axis,
        ).reshape(n_parts, width)
        r_count = recv[:, 0]
        r_pairs = recv[:, 1:].reshape(n_parts, s, 2)
        valid = jnp.arange(s)[None, :] < r_count[:, None]
        idx = jnp.where(valid, r_pairs[..., 0], s)
        return jax.vmap(
            lambda tab, ix, co: apply_pairs(tab, ix, co, scatter=self.scatter)
        )(ghost_tab, idx, r_pairs[..., 1].astype(colors.dtype))

    def stacked(self, st, colors, state):
        p_ = st["send_idx"].shape[0]
        send = jax.vmap(send_buffer)(colors, st)                  # (P, S)
        changed = st["send_mask"] & (send != state["prev_send"])
        take = changed[:, None, :] & st["peer_need"]              # (P, P, S)
        # Receiver view: ghost_tab[r, o] patched with the pairs o -> r.
        ghost_tab, counts = _stacked_pair_apply(
            state["ghost_tab"], take, send, self._traffic,
            scatter=self.scatter)                                 # (P, P, S)
        traffic = jnp.asarray(self._traffic)
        hdr = traffic.sum().astype(jnp.int32)
        prs = jnp.where(traffic, counts, 0).sum().astype(jnp.int32)
        nbytes = payload_bytes(st, headers=hdr, pairs=prs) // p_
        ghost = jax.vmap(
            lambda tab, gp, gs, real: jnp.where(real, tab[gp, gs], 0)
        )(ghost_tab, st["ghost_part"], st["ghost_slot"], st["ghost_real"])
        return ghost, nbytes, {"prev_send": send, "ghost_tab": ghost_tab}


class HierDeltaExchange(ExchangeStrategy):
    """Two-level hierarchical sparse delta over a (node, local) factoring.

    The NCCL-style pattern for machines whose part axis factors into
    ``n_nodes`` nodes of ``node_size`` parts (``launch.mesh.factor_parts``;
    part ``p`` lives on node ``p // node_size``, part ``A·node_size`` is
    node ``A``'s leader).  Each round runs four stages over the schedules
    of :func:`repro.core.a2a_schedule.hierarchical_route_plan`:

    1. *direct* — same-node ``(slot, color)`` pairs go point-to-point over
       the edge-colored intra plan (fast links), exactly like
       ``sparse_delta`` restricted to same-node edges.
    2. *up* — each member ships its per-destination-**node** aggregated
       pair tables to its node leader (``node_size - 1`` phases).  The
       aggregation is the dedup win: a boundary slot ghosted by three
       parts of node B is packed once for B, not three times.
    3. *inter* — one leader→leader message per routed **node** edge
       (the node-level route plan): the block of ``node_size`` member
       tables destined to that node.  The only stage crossing the slow
       axis.
    4. *down* — the leader re-broadcasts the arrived tables to its
       members (``node_size - 1`` phases); every part then scatters all
       arrived pairs into its per-owner slot tables.  Unneeded entries
       land in table rows the ghost gather never reads, so the
       reconstruction is exact — bit-identical colorings and rounds to
       ``all_gather``.

    On the wire, colors ride the narrowest dtype the static palette
    bound admits (first-fit: ``Δ+1`` for the d1 family, ``Δ²+1`` for
    distance-2) and slot ids/counts the narrowest width the send
    capacity admits (:func:`wire_dtype`), so measured bytes come from
    the *packed* widths.  ``nbytes`` is the shape-(2,) ``[intra-node,
    inter-node]`` split: direct + up + down traffic on the fast axis,
    the leader→leader hop on the slow one.

    ``node_size=None`` defers to :func:`repro.launch.mesh.factor_parts`
    (env ``REPRO_NODE_SIZE``, else the squarest divisor).  A prime part
    count degrades to ``(P, 1)`` — pure packed point-to-point.
    """

    name = "hier_delta"

    def __init__(self, *, scatter: str = "reference",
                 node_size: int | None = None):
        self.scatter = scatter
        self.node_size = node_size
        self._hplan = None

    def prepare(self, pg, st):
        from repro.core.a2a_schedule import hierarchical_route_plan
        from repro.graph.csr import SENTINEL
        from repro.launch.mesh import factor_parts

        p_, s_ = pg.n_parts, pg.send_width
        # need[owner, dest, slot]: dest ghosts the owner's send slot.
        need = np.zeros((p_, p_, s_), dtype=bool)
        for q in range(p_):
            real = pg.ghost_gid[q] != SENTINEL
            need[pg.ghost_part[q][real], q, pg.ghost_slot[q][real]] = True
        traffic = need.any(axis=2)
        n_nodes, node_size = factor_parts(p_, self.node_size)
        self._n, self._l = n_nodes, node_size
        self._hplan = hierarchical_route_plan(
            traffic.astype(np.int64), node_size)
        node = np.arange(p_) // node_size
        same = node[:, None] == node[None, :]
        # agg_need[owner, B, slot]: some part of *other* node B ghosts it.
        agg_need = np.zeros((p_, n_nodes, s_), dtype=bool)
        for b in range(n_nodes):
            agg_need[:, b, :] = need[:, node == b, :].any(axis=1)
        agg_need[np.arange(p_), node, :] = False   # same node -> direct path
        self._intra_traffic = traffic & same                     # (P, P)
        self._agg_traffic = agg_need.any(axis=2)                 # (P, N)
        # reach[o, q]: q hears o's pairs (directly or via B's broadcast).
        self._reach_traffic = self._intra_traffic | self._agg_traffic[:, node]
        # Packed wire widths from static bounds: palette = first-fit bound
        # (colors are 0 = uncolored or 1..bound), slots/counts = send
        # capacity S (the pad sentinel is the largest value shipped).
        delta = int(np.max(pg.deg, initial=0))
        palette = delta * delta + 1 if "two_hop_cidx" in st else delta + 1
        self._color_dtype = wire_dtype(palette)
        self._slot_dtype = wire_dtype(s_)
        return {"hier_need": need & same[:, :, None],
                "hier_agg_need": agg_need}

    def init_state(self, st):
        if "hier_need" not in st:
            raise ValueError(
                "hier_delta needs its prepare() tables; run it through "
                "color_distributed (or call prepare(pg, st) first)"
            )
        return {
            "prev_send": jnp.zeros(st["send_idx"].shape, COLOR_DTYPE),
            # Per-owner slot tables, shaped like sparse_delta's: device
            # (P, S) owner-major; stacked (P, P, S) receiver-major.
            "ghost_tab": jnp.zeros(st["hier_need"].shape, COLOR_DTYPE),
        }

    def _split_bytes(self, st, intra_hdr, intra_prs, inter_hdr, inter_prs):
        """[intra, inter] payload at the packed widths (linear in counts,
        so per-part sums and global totals go through the same formula)."""
        kw = dict(color_dtype=self._color_dtype, slot_dtype=self._slot_dtype)
        return jnp.stack([
            payload_bytes(st, headers=intra_hdr, pairs=intra_prs, **kw),
            payload_bytes(st, headers=inter_hdr, pairs=inter_prs, **kw),
        ])

    def device(self, st, colors_loc, state, *, axis, n_parts):
        hp, s = self._hplan, st["send_idx"].shape[0]
        l, n_nodes = self._l, self._n
        p = jax.lax.axis_index(axis)
        my_node = p // l
        is_leader = (p % l) == 0
        send = send_buffer(colors_loc, st)
        changed = st["send_mask"] & (send != state["prev_send"])

        # Stage 1 — direct same-node pairs over the intra plan, at the
        # packed wire widths.
        take_d = changed[None, :] & st["hier_need"]               # (P, S)
        d_slots, d_cols, d_counts = jax.vmap(pack_pairs, in_axes=(0, None))(
            take_d, send)
        ghost_tab = _route_pair_phases(
            hp.intra, state["ghost_tab"], d_counts, d_slots, d_cols,
            p=p, axis=axis, n_parts=n_parts, scatter=self.scatter,
            slot_dtype=self._slot_dtype, color_dtype=self._color_dtype)

        # Per-destination-node aggregated tables (the dedup win).
        take_a = changed[None, :] & st["hier_agg_need"]           # (N, S)
        a_slots, a_cols, a_counts = jax.vmap(pack_pairs, in_axes=(0, None))(
            take_a, send)

        # Measured bytes: each agg table pays one up hop (members only),
        # one inter hop, and node_size-1 down hops — booked against its
        # originating owner; the global psum total is exact.
        intra_row = jnp.asarray(self._intra_traffic)[p]           # (P,)
        agg_row = jnp.asarray(self._agg_traffic)[p]               # (N,)
        d_hdr = intra_row.sum().astype(jnp.int32)
        d_prs = jnp.where(intra_row, d_counts, 0).sum().astype(jnp.int32)
        a_hdr = agg_row.sum().astype(jnp.int32)
        a_prs = jnp.where(agg_row, a_counts, 0).sum().astype(jnp.int32)
        up_down = jnp.where(is_leader, 0, 1) + (l - 1)
        nbytes = jax.lax.psum(
            self._split_bytes(st, d_hdr + up_down * a_hdr,
                              d_prs + up_down * a_prs, a_hdr, a_prs),
            axis) // n_parts

        # Stage 2 — up: members gather their typed agg tables at the
        # leader (row 0 = own tables; row j = member A·L+j's).
        head0 = jnp.concatenate(
            [a_counts[:, None], a_slots], axis=1).astype(self._slot_dtype)
        cols0 = a_cols.astype(self._color_dtype)
        up_head = jnp.zeros((l,) + head0.shape, head0.dtype).at[0].set(head0)
        up_cols = jnp.zeros((l,) + cols0.shape, cols0.dtype).at[0].set(cols0)
        for j, perm in enumerate(hp.up, start=1):
            up_head = up_head.at[j].set(
                jax.lax.ppermute(head0, axis, list(perm)))
            up_cols = up_cols.at[j].set(
                jax.lax.ppermute(cols0, axis, list(perm)))

        # Stage 3 — inter: one leader→leader block (node_size member
        # sub-tables) per routed node edge, accumulated owner-major.
        arr_head = jnp.zeros((n_parts, 1 + s), self._slot_dtype)
        arr_cols = jnp.zeros((n_parts, s), self._color_dtype)
        for k, phase in enumerate(hp.node.phases):
            part_perm = [(a * l, b * l) for a, b in phase]
            dstn = jnp.asarray(hp.node.dst_of[k])[my_node]        # -1 = idle
            srcn = jnp.asarray(hp.node.src_of[k])[my_node]
            db = jnp.clip(dstn, 0, n_nodes - 1)
            live_send = is_leader & (dstn >= 0)
            blk_head = jnp.where(live_send, up_head[:, db], 0)    # (L, 1+S)
            blk_cols = jnp.where(live_send, up_cols[:, db], 0)
            r_head = jax.lax.ppermute(blk_head, axis, part_perm)
            r_cols = jax.lax.ppermute(blk_cols, axis, part_perm)
            sb = jnp.clip(srcn, 0, n_nodes - 1)
            live_recv = is_leader & (srcn >= 0)
            upd_head = jax.lax.dynamic_update_slice(
                arr_head, r_head, (sb * l, 0))
            upd_cols = jax.lax.dynamic_update_slice(
                arr_cols, r_cols, (sb * l, 0))
            arr_head = jnp.where(live_recv, upd_head, arr_head)
            arr_cols = jnp.where(live_recv, upd_cols, arr_cols)

        # Stage 4 — down: the leader re-broadcasts the arrivals.
        down_head, down_cols = arr_head, arr_cols
        for j, perm in enumerate(hp.down, start=1):
            r_head = jax.lax.ppermute(arr_head, axis, list(perm))
            r_cols = jax.lax.ppermute(arr_cols, axis, list(perm))
            is_me = (p % l) == j
            down_head = jnp.where(is_me, r_head, down_head)
            down_cols = jnp.where(is_me, r_cols, down_cols)

        # Apply every arrived row; pairs this part never ghosts land in
        # table entries the ghost gather never reads (and carry the
        # owner's true colors regardless), so extra writes are harmless.
        arr_cnt = down_head[:, 0].astype(COLOR_DTYPE)             # (P,)
        arr_slots = down_head[:, 1:].astype(COLOR_DTYPE)          # (P, S)
        valid = jnp.arange(s)[None, :] < arr_cnt[:, None]
        idx = jnp.where(valid, arr_slots, s)
        ghost_tab = jax.vmap(
            lambda tab, ix, co: apply_pairs(tab, ix, co, scatter=self.scatter)
        )(ghost_tab, idx, down_cols.astype(COLOR_DTYPE))
        ghost = ghost_tab[st["ghost_part"], st["ghost_slot"]]
        ghost = jnp.where(st["ghost_real"], ghost, 0)
        return ghost, nbytes, {"prev_send": send, "ghost_tab": ghost_tab}

    def stacked(self, st, colors, state):
        p_ = st["send_idx"].shape[0]
        l, n_nodes = self._l, self._n
        node = np.arange(p_) // l
        same = node[:, None] == node[None, :]
        send = jax.vmap(send_buffer)(colors, st)                  # (P, S)
        changed = st["send_mask"] & (send != state["prev_send"])
        # Who hears which slots: direct need on same-node edges, the
        # node-aggregated need everywhere else — one pack+scatter pass
        # reproduces all four device stages' net effect.
        reach = jnp.where(jnp.asarray(same)[:, :, None], st["hier_need"],
                          st["hier_agg_need"][:, node, :])
        take = changed[:, None, :] & reach                        # (P, P, S)
        ghost_tab, counts = _stacked_pair_apply(
            state["ghost_tab"], take, send, self._reach_traffic,
            scatter=self.scatter)
        # Byte split identical to device's psum: reach counts restricted
        # to same-node edges are the direct counts; the leader column of
        # each other node carries that node's agg count.
        intra_t = jnp.asarray(self._intra_traffic)
        agg_t = jnp.asarray(self._agg_traffic)
        d_hdr = intra_t.sum().astype(jnp.int32)
        d_prs = jnp.where(intra_t, counts, 0).sum().astype(jnp.int32)
        leaders = np.arange(n_nodes) * l
        cnt_a = counts[:, leaders]                                # (P, N)
        a_hdr_o = agg_t.sum(axis=1).astype(jnp.int32)             # (P,)
        a_prs_o = jnp.where(agg_t, cnt_a, 0).sum(axis=1).astype(jnp.int32)
        member = np.arange(p_) % l != 0
        up_down = jnp.asarray(member.astype(np.int32) + (l - 1))
        nbytes = self._split_bytes(
            st, d_hdr + (up_down * a_hdr_o).sum(),
            d_prs + (up_down * a_prs_o).sum(),
            a_hdr_o.sum(), a_prs_o.sum()) // p_
        ghost = jax.vmap(
            lambda tab, gp, gs, real: jnp.where(real, tab[gp, gs], 0)
        )(ghost_tab, st["ghost_part"], st["ghost_slot"], st["ghost_real"])
        return ghost, nbytes, {"prev_send": send, "ghost_tab": ghost_tab}


EXCHANGES: Registry = Registry(
    "exchange",
    {
        "all_gather": AllGatherExchange,
        "halo": HaloExchange,
        "delta": DeltaExchange,
        "sparse_delta": SparseDeltaExchange,
        "hier_delta": HierDeltaExchange,
    },
    instance_of=ExchangeStrategy,
    instantiate=True,
    default="all_gather",
)


def register_exchange(name: str, cls: type[ExchangeStrategy]) -> None:
    """Register a third-party :class:`ExchangeStrategy` under ``name``."""
    EXCHANGES.register(name, cls)


def list_exchanges() -> list[str]:
    """Sorted registered exchange names (drives the CLI choices)."""
    return EXCHANGES.names()


def get_exchange(exchange: str | ExchangeStrategy | None) -> ExchangeStrategy:
    """Resolve ``exchange`` (name, instance, or None → all_gather)."""
    return EXCHANGES.resolve(exchange)
