"""Pluggable local-compute backends for the distributed coloring runtime.

Following KokkosKernels' pluggable-algorithm design (Deveci et al.), the
per-part compute steps of the speculate-and-iterate loop — speculative
local (re)coloring and cross-partition conflict detection — are behind a
small :class:`LocalBackend` interface with two implementations:

* ``reference`` — the pure-``jnp`` path (``repro.core.local``), runs
  everywhere, serves as the correctness oracle;
* ``pallas``    — the TPU kernel path (``repro.kernels.ops``): ``vb_bit``
  assignment, ``d2_forbidden`` two-hop accumulation, and the ``conflict``
  kernel for detection.  Interpret mode on CPU, Mosaic-compiled on TPU;
* ``pallas_fused`` — one jitted round on the ``fused_round`` kernels.

Both backends implement the *same math* (the kernels are tested bit-exact
against the jnp oracles), so swapping backends changes neither colorings
nor round counts — ``tests/test_kernels.py::test_backend_parity_*`` pins
this.  Select with ``color_distributed(..., backend="pallas")`` or
``--backend`` on the CLI.  Third-party backends can be added with
:func:`register_backend`.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.conflict import v_loses
from repro.core.local import local_color_d1, local_color_d2
from repro.core.registry import Registry

__all__ = [
    "LocalBackend",
    "ReferenceBackend",
    "PallasBackend",
    "PallasFusedBackend",
    "BACKENDS",
    "get_backend",
    "list_backends",
    "register_backend",
]


class LocalBackend:
    """Interface for per-part compute steps (no collectives).

    All methods take/return the part-local layout used by the runtime:
    ``color_tab`` is the (n_local + n_ghost + 1,) color table (owned
    vertices, then ghosts, then one pad slot); adjacency arrays hold
    color-table indices.
    """

    name: str = "abstract"

    def reads_diagonals(self, problem: str) -> bool:
        """Whether ``problem``'s neighbor blocks are read through
        ``kernels.diagonals.read_neighbors``: a plan then looks for the
        diagonal layout of the problem's neighbor index and hands it in
        as ``diag`` (the state's ``nbr_diag``)."""
        return False

    def color_d1(self, adj_cidx, color_tab, active, deg_tab, gid_tab, *,
                 recolor_degrees: bool, diag=None):
        """Distance-1 speculative coloring of ``active`` rows; returns
        ``(color_table, iters)``: the updated table and the number of
        speculative iterations its fixed point took.  ``diag`` is the
        diagonal layout of ``adj_cidx`` when the plan found one; it never
        changes the result."""
        raise NotImplementedError

    def color_d2(self, adj_cidx, two_hop_cidx, ext_adj_cidx, color_tab, active,
                 deg_tab, gid_tab, *, partial_d2: bool, recolor_degrees: bool,
                 diag=None):
        """Distance-2 / partial-distance-2 speculative coloring; returns
        ``(color_table, iters)`` like :meth:`color_d1` (``diag``: the
        layout of the one- plus two-hop index, two-hop only for pd2)."""
        raise NotImplementedError

    def detect(self, adj_cidx, colors_loc, color_tab, deg_tab, gid_tab,
               is_boundary, *, recolor_degrees: bool):
        """Alg-4 owned-vs-ghost conflict sweep over one adjacency block.

        Returns ``(lose_v, lose_o, count)``: per-row lose mask (already
        boundary-masked), per-edge neighbor-side lose flags slot-major —
        ``(W, N)``, the transpose of ``adj_cidx``, scattered into the ghost
        table by the caller — and the conflict count.
        """
        raise NotImplementedError

    def round(self, st, colors_loc, ghost_colors, *, problem: str,
              recolor_degrees: bool):
        """One fused inner round: detect conflicts against the freshly
        exchanged ghosts, zero the losers, and speculatively recolor them
        for the next round.

        Returns ``(new_colors (nl,), lose_loc (nl,) bool, lose_ghost (G,)
        bool, n_conflicts scalar int32, iters scalar int32)``, ``iters``
        being the recolor's speculative iterations.  The default
        implementation is the decomposed ``_detect_part`` →
        ``_recolor_part`` composition, so ``reference`` and plain
        ``pallas`` stay bit-identical oracles for backends that override
        this with a fused kernel (``pallas_fused``).
        """
        from repro.core.distributed import _detect_part, _recolor_part

        kw = dict(problem=problem, recolor_degrees=recolor_degrees,
                  backend=self)
        lose_l, lose_g, conf = _detect_part(st, colors_loc, ghost_colors,
                                            **kw)
        colors = jnp.where(lose_l, 0, colors_loc)
        colors, iters = _recolor_part(st, colors, ghost_colors, lose_l,
                                      lose_g, **kw)
        return colors, lose_l, lose_g, conf, iters


class ReferenceBackend(LocalBackend):
    """Pure-``jnp`` backend (``repro.core.local`` + ``v_loses``)."""

    name = "reference"

    def color_d1(self, adj_cidx, color_tab, active, deg_tab, gid_tab, *,
                 recolor_degrees, diag=None):
        return local_color_d1(adj_cidx, color_tab, active, deg_tab, gid_tab,
                              recolor_degrees=recolor_degrees)

    def color_d2(self, adj_cidx, two_hop_cidx, ext_adj_cidx, color_tab, active,
                 deg_tab, gid_tab, *, partial_d2, recolor_degrees, diag=None):
        return local_color_d2(adj_cidx, two_hop_cidx, color_tab, active,
                              deg_tab, gid_tab, partial_d2=partial_d2,
                              recolor_degrees=recolor_degrees)

    def detect(self, adj_cidx, colors_loc, color_tab, deg_tab, gid_tab,
               is_boundary, *, recolor_degrees):
        n_loc = colors_loc.shape[0]
        n_tab = color_tab.shape[0] - 1      # last slot is pad
        idx = adj_cidx.T                    # slot-major, see core.local
        is_ghost = (idx >= n_loc) & (idx < n_tab)
        co = color_tab[idx]
        do = deg_tab[idx]
        go = gid_tab[idx]
        cv = colors_loc[None]
        dv, gv = deg_tab[:n_loc][None], gid_tab[:n_loc][None]
        vl = v_loses(cv, co, dv, do, gv, go,
                     recolor_degrees=recolor_degrees) & is_ghost
        ol = v_loses(co, cv, do, dv, go, gv,
                     recolor_degrees=recolor_degrees) & is_ghost
        lose_v = vl.any(axis=0) & is_boundary
        return lose_v, ol, (vl | ol).sum().astype(jnp.int32)


class PallasBackend(LocalBackend):
    """TPU-kernel backend (``repro.kernels.ops`` wrappers).

    ``interpret=None`` resolves through
    :func:`repro.kernels.default_interpret`: compiled Mosaic kernels on
    TPU, the Pallas interpreter on the CPU backend, an error elsewhere.
    """

    name = "pallas"

    def __init__(self, *, interpret: bool | None = None,
                 tile_d1: int = 2048, tile_d2: int = 1024):
        if interpret is None:
            from repro.kernels import default_interpret

            interpret = default_interpret()
        self.interpret = interpret
        self.tile_d1 = tile_d1
        self.tile_d2 = tile_d2

    def reads_diagonals(self, problem):
        return problem in ("d1", "d1_2gl")      # d2 is d2_forbidden's

    def color_d1(self, adj_cidx, color_tab, active, deg_tab, gid_tab, *,
                 recolor_degrees, diag=None):
        from repro.kernels.ops import local_color_d1_pallas

        return local_color_d1_pallas(
            adj_cidx, color_tab, active, deg_tab, gid_tab, diag=diag,
            recolor_degrees=recolor_degrees,
            interpret=self.interpret, tile=self.tile_d1,
        )

    def color_d2(self, adj_cidx, two_hop_cidx, ext_adj_cidx, color_tab, active,
                 deg_tab, gid_tab, *, partial_d2, recolor_degrees, diag=None):
        from repro.kernels.ops import local_color_d2_pallas

        return local_color_d2_pallas(
            adj_cidx, two_hop_cidx, ext_adj_cidx, color_tab, active,
            deg_tab, gid_tab, partial_d2=partial_d2,
            recolor_degrees=recolor_degrees,
            interpret=self.interpret, tile=self.tile_d2,
        )

    def detect(self, adj_cidx, colors_loc, color_tab, deg_tab, gid_tab,
               is_boundary, *, recolor_degrees):
        from repro.kernels.ops import conflict_detect

        n_loc = colors_loc.shape[0]
        lose_v, lose_o, count = conflict_detect(
            adj_cidx, colors_loc, deg_tab[:n_loc], gid_tab[:n_loc],
            is_boundary, color_tab, deg_tab, gid_tab, n_loc,
            recolor_degrees=recolor_degrees, interpret=self.interpret,
        )
        return lose_v, lose_o, count.astype(jnp.int32)


class PallasFusedBackend(PallasBackend):
    """Fused-round backend: ``kernels.fused_round``.

    Overrides :meth:`LocalBackend.round` with ``fused_round`` — Alg-4
    detection, loser zeroing and speculative recoloring in one jitted
    round whose gathers run in XLA and whose elementwise work runs in
    three dense Mosaic kernels (detect / assign / resolve).  The d1
    speculative coloring is inherited: ``pallas`` already runs it through
    the same ``speculate`` fixed point.  The d2 / pd2 one overrides the
    chained ``d2_forbidden`` assignment with ``speculate`` over the
    gathered one- and two-hop block, so the initial coloring and every
    round's recolor run one loop.  ``d1_2gl`` recolors ghosts over the
    extended adjacency and keeps the decomposed round (``speculate`` plus
    the ``conflict`` kernel).  Bit-identical to ``reference`` /
    ``pallas`` by construction (``tests/test_kernels.py -k fused``).
    """

    name = "pallas_fused"

    def __init__(self, *, interpret: bool | None = None,
                 tile_round: int | None = None):
        super().__init__(interpret=interpret)
        from repro.kernels.fused_round import DEFAULT_TILE

        self.tile_round = tile_round or DEFAULT_TILE

    def reads_diagonals(self, problem):
        return True

    def color_d2(self, adj_cidx, two_hop_cidx, ext_adj_cidx, color_tab, active,
                 deg_tab, gid_tab, *, partial_d2, recolor_degrees, diag=None):
        from repro.kernels.fused_round import neighbor_index, speculate

        idx = neighbor_index(adj_cidx, two_hop_cidx,
                             "pd2" if partial_d2 else "d2")
        return speculate(idx, color_tab, active, deg_tab, gid_tab, diag=diag,
                         recolor_degrees=recolor_degrees, max_iters=1024,
                         tile=self.tile_round, interpret=self.interpret)

    def round(self, st, colors_loc, ghost_colors, *, problem: str,
              recolor_degrees: bool):
        if problem == "d1_2gl":
            return super().round(st, colors_loc, ghost_colors,
                                 problem=problem,
                                 recolor_degrees=recolor_degrees)
        from repro.kernels.fused_round import fused_round

        return fused_round(
            st["adj_cidx"], colors_loc, ghost_colors, st["deg_tab"],
            st["gid_tab"], st["is_boundary"],
            two_hop_cidx=(st["two_hop_cidx"] if problem in ("d2", "pd2")
                          else None),
            diag=st.get("nbr_diag"), problem=problem,
            recolor_degrees=recolor_degrees, tile=self.tile_round,
            interpret=self.interpret,
        )


BACKENDS: Registry = Registry(
    "backend",
    {
        "reference": ReferenceBackend,
        "pallas": PallasBackend,
        "pallas_fused": PallasFusedBackend,
    },
    instance_of=LocalBackend,
    instantiate=True,
    default="reference",
)


def register_backend(name: str, cls: type[LocalBackend]) -> None:
    """Register a third-party :class:`LocalBackend` under ``name``."""
    BACKENDS.register(name, cls)


def list_backends() -> list[str]:
    """Sorted registered backend names (drives the CLI choices)."""
    return BACKENDS.names()


def get_backend(backend: str | LocalBackend | None) -> LocalBackend:
    """Resolve ``backend`` (name, instance, or None → reference)."""
    return BACKENDS.resolve(backend)
