"""Kernel microbenchmarks: Pallas (interpret) vs pure-jnp reference.

Interpret mode runs the kernel body in Python on CPU — the timing column
is NOT a TPU number; the purpose here is (a) correctness at bench scale
and (b) the op-level call graph for the roofline discussion.  ``derived``
= checksum equality with the oracle.

Beyond the raw kernels, the ``backend/*`` rows time the *composed*
per-part steps (full local-coloring fixed point + conflict sweep) through
the ``LocalBackend`` interface — the unit the distributed loop actually
dispatches per round — for reference, pallas, and ``pallas_fused``; the
``roofline/*`` rows report the *lowered one-round programs* of the chained
and fused pallas paths as HBM bytes summed over the optimized HLO
(``repro.roofline.analysis.hlo_totals``).  Those are counts of the CPU
compiler's program (the kernels in interpret mode), not a chip
measurement.  ``toy=True`` (the CI ``kernels_smoke`` suite) shrinks the
graph but keeps every row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, timed
from repro.core.backend import get_backend
from repro.core.distributed import build_device_state
from repro.graph.generators import rmat
from repro.graph.partition import partition_graph
from repro.kernels import ops, ref
from repro.roofline.analysis import hlo_totals


def run(toy: bool = False) -> list[str]:
    rows = []
    g = rmat(8, 6, seed=3) if toy else rmat(10, 8, seed=3)
    pg = partition_graph(g, 2, second_layer=True)
    st = build_device_state(pg, "d2")
    nl = pg.n_local
    rng = np.random.default_rng(0)
    tab = jnp.asarray(np.concatenate(
        [rng.integers(0, 9, nl + pg.n_ghost).astype(np.int32), [0]]))
    base = jnp.ones(nl, jnp.int32)
    active = jnp.asarray(st["active0"][0])
    adj = jnp.asarray(st["adj_cidx"][0])
    deg_tab = jnp.asarray(st["deg_tab"][0])
    gid_tab = jnp.asarray(st["gid_tab"][0])
    ext = jnp.asarray(st["ext_adj_cidx"][0])
    two_hop = jnp.asarray(st["two_hop_cidx"][0])
    boundary = jnp.asarray(st["is_boundary"][0])

    (kc, kb), us_k = timed(lambda: ops.vb_bit_assign(adj, tab[:nl], base, active, tab))
    (rc, rb), us_r = timed(lambda: ref.vb_bit_assign_ref(adj, tab[:nl], base, active, tab))
    ok = bool((np.asarray(kc) == np.asarray(rc)).all())
    rows.append(row("kernel/vb_bit/pallas_interp", us_k, f"match_ref={ok}"))
    rows.append(row("kernel/vb_bit/jnp_ref", us_r, "oracle"))

    out_k, us_k = timed(lambda: ops.conflict_detect(
        adj, tab[:nl], deg_tab[:nl], gid_tab[:nl],
        boundary, tab, deg_tab, gid_tab, nl))
    out_r, us_r = timed(lambda: ref.conflict_detect_ref(
        adj, tab[:nl], deg_tab[:nl], gid_tab[:nl],
        boundary, tab, deg_tab, gid_tab, nl))
    ok = bool((np.asarray(out_k[0]) == np.asarray(out_r[0])).all())
    rows.append(row("kernel/conflict/pallas_interp", us_k, f"match_ref={ok}"))
    rows.append(row("kernel/conflict/jnp_ref", us_r, "oracle"))

    f_k, us_k = timed(lambda: ops.d2_forbidden(adj, base, active, tab[:nl], tab, ext))
    f_r, us_r = timed(lambda: ref.d2_forbidden_ref(adj, base, active, tab[:nl], tab, ext))
    ok = bool((np.asarray(f_k) == np.asarray(f_r)).all())
    rows.append(row("kernel/d2_forbidden/pallas_interp", us_k, f"match_ref={ok}"))
    rows.append(row("kernel/d2_forbidden/jnp_ref", us_r, "oracle"))

    # pair_scatter: the sparse_delta exchange's receive-side apply step.
    table = jnp.asarray(rng.integers(0, 9, 512).astype(np.int32))
    n_pairs = 96
    slots = jnp.asarray(np.concatenate(
        [rng.permutation(512)[:n_pairs], np.full(512 - n_pairs, 512)]
    ).astype(np.int32))
    vals = jnp.asarray(rng.integers(1, 9, 512).astype(np.int32))
    s_k, us_k = timed(lambda: ops.pair_scatter(table, slots, vals))
    s_r, us_r = timed(lambda: ref.pair_scatter_ref(table, slots, vals))
    ok = bool((np.asarray(s_k) == np.asarray(s_r)).all())
    rows.append(row("kernel/pair_scatter/pallas_interp", us_k, f"match_ref={ok}"))
    rows.append(row("kernel/pair_scatter/jnp_ref", us_r, "oracle"))

    # Fused round vs the decomposed oracle (d1 boundary/state).
    bnd1 = jnp.asarray(pg.is_boundary[0])
    colors0 = tab[:nl]
    ghost0 = tab[nl:nl + pg.n_ghost]
    fr_k, us_k = timed(lambda: ops.fused_round(
        adj, colors0, ghost0, deg_tab, gid_tab, bnd1, problem="d1"))
    fr_r, us_r = timed(lambda: ref.fused_round_ref(
        adj, colors0, ghost0, deg_tab, gid_tab, bnd1, problem="d1"))
    ok = all(bool((np.asarray(a) == np.asarray(b)).all())
             for a, b in zip(fr_k, fr_r))
    rows.append(row("kernel/fused_round/pallas_interp", us_k, f"match_ref={ok}"))
    rows.append(row("kernel/fused_round/jnp_ref", us_r, "oracle"))

    # Composed backend steps (the distributed loop's per-round unit).
    st0 = {"adj_cidx": adj, "deg_tab": deg_tab, "gid_tab": gid_tab,
           "is_boundary": bnd1}
    tab0 = jnp.zeros_like(tab)
    outs = {}
    rounds = {}
    for name in ("reference", "pallas", "pallas_fused"):
        b = get_backend(name)
        (colored, _), us_c = timed(lambda b=b: b.color_d1(
            adj, tab0, active, deg_tab, gid_tab, recolor_degrees=True))
        outs[name] = np.asarray(colored)
        rows.append(row(f"backend/{name}/color_d1", us_c,
                        f"colors={int(np.unique(outs[name][outs[name] > 0]).size)}"))
        _, us_d = timed(lambda b=b: b.detect(
            adj, tab[:nl], tab, deg_tab, gid_tab, boundary,
            recolor_degrees=True))
        rows.append(row(f"backend/{name}/detect", us_d, "alg4_sweep"))
        (c2, _), us_2 = timed(lambda b=b: b.color_d2(
            adj, two_hop, ext, tab0, active, deg_tab, gid_tab,
            partial_d2=False, recolor_degrees=True))
        rows.append(row(f"backend/{name}/color_d2", us_2,
                        f"colors={int(np.unique(np.asarray(c2)[np.asarray(c2) > 0]).size)}"))
        rnd, us_rd = timed(lambda b=b: b.round(
            st0, colors0, ghost0, problem="d1", recolor_degrees=True))
        rounds[name] = [np.asarray(x) for x in rnd]
        rows.append(row(f"backend/{name}/round_d1", us_rd,
                        f"conflicts={int(rounds[name][3])}"))
    ok = bool((outs["reference"] == outs["pallas"]).all()
              & (outs["reference"] == outs["pallas_fused"]).all())
    rows.append(row("backend/parity/color_d1", 0, f"identical={ok}"))
    ok = all(bool((rounds["reference"][i] == rounds[name][i]).all())
             for name in ("pallas", "pallas_fused") for i in range(5))
    rows.append(row("backend/parity/round_d1", 0, f"identical={ok}"))

    # HBM bytes of the *lowered* one-round programs: both are jitted over
    # the same closed-over part-0 state, lowered, compiled, and their
    # optimized HLO summed by hlo_totals — while-loop bodies scaled by
    # their trip-count bound.
    hbytes = {}
    for name in ("pallas", "pallas_fused"):
        b = get_backend(name)

        def one_round(c, gh, b=b):
            return b.round(st0, c, gh, problem="d1", recolor_degrees=True)

        text = jax.jit(one_round).lower(colors0, ghost0).compile().as_text()
        hbytes[name] = int(hlo_totals(text)["hlo_bytes_per_dev"])
        rows.append(row(f"roofline/round_d1/{name}", 0,
                        f"hlo_bytes_per_round={hbytes[name]}"))
    rows.append(row(
        "roofline/round_d1/fused_vs_chained", 0,
        f"fused={hbytes['pallas_fused']} chained={hbytes['pallas']} "
        f"ratio={hbytes['pallas_fused'] / hbytes['pallas']:.4f}"))
    return rows
