"""Pure-jnp oracles for the Pallas kernels (bit-exact references).

These re-derive each kernel's math with plain jnp ops; the kernel tests
sweep shapes/dtypes and assert exact equality (integer kernels — no
tolerance needed; ``assert_allclose`` with rtol=0).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.conflict import v_loses
from repro.core.local import forbidden_mask, pick_color


def vb_bit_assign_ref(adj_cidx, colors, base, active, color_tab):
    """Oracle for kernels.vb_bit.vb_bit_assign."""
    colors = colors.astype(jnp.int32)
    base = base.astype(jnp.int32)
    uncolored = (active.astype(jnp.int32) != 0) & (colors == 0)
    base_eff = jnp.where(uncolored, base, 1)
    nbr_colors = color_tab.astype(jnp.int32)[adj_cidx]
    mask = forbidden_mask(nbr_colors, base_eff)
    cand, ok = pick_color(mask, base_eff)
    new_colors = jnp.where(uncolored & ok, cand, colors)
    new_base = jnp.where(uncolored & ~ok, base + 32, base)
    return new_colors, new_base


def conflict_detect_ref(adj_cidx, colors, deg, gid, is_boundary,
                        color_tab, deg_tab, gid_tab, n_loc, *,
                        recolor_degrees=True):
    """Oracle for kernels.conflict.conflict_detect."""
    colors = colors.astype(jnp.int32)
    n_tab = color_tab.shape[0] - 1
    co = color_tab.astype(jnp.int32)[adj_cidx]
    do = deg_tab.astype(jnp.int32)[adj_cidx]
    go = gid_tab.astype(jnp.int32)[adj_cidx]
    is_ghost = (adj_cidx >= n_loc) & (adj_cidx < n_tab)
    vl = v_loses(colors[:, None], co, deg.astype(jnp.int32)[:, None], do,
                 gid.astype(jnp.int32)[:, None], go,
                 recolor_degrees=recolor_degrees) & is_ghost
    ol = v_loses(co, colors[:, None], do, deg.astype(jnp.int32)[:, None],
                 go, gid.astype(jnp.int32)[:, None],
                 recolor_degrees=recolor_degrees) & is_ghost
    lose_v = vl.any(axis=1) & is_boundary.astype(bool)
    count = (vl | ol).sum().astype(jnp.int32)
    return lose_v, ol.T, count          # neighbor flags slot-major (W, N)


def d2_forbidden_ref(adj_cidx, base, active, colors, color_tab, ext_adj_cidx,
                     *, partial_d2=False):
    """Oracle for kernels.d2_forbidden.d2_forbidden."""
    colors = colors.astype(jnp.int32)
    base = base.astype(jnp.int32)
    uncolored = (active.astype(jnp.int32) != 0) & (colors == 0)
    base_eff = jnp.where(uncolored, base, 1)
    tab = color_tab.astype(jnp.int32)
    n, w = adj_cidx.shape
    two_hop = ext_adj_cidx[adj_cidx].reshape(n, w * w)
    if partial_d2:
        all_colors = tab[two_hop]
    else:
        all_colors = jnp.concatenate([tab[adj_cidx], tab[two_hop]], axis=1)
    return forbidden_mask(all_colors, base_eff)


def pair_scatter_ref(table, slots, values):
    """Oracle for kernels.scatter.pair_scatter (drop out-of-range slots)."""
    return table.astype(jnp.int32).at[slots].set(
        values.astype(jnp.int32), mode="drop")


def fused_round_ref(adj_cidx, colors, ghost, deg_tab, gid_tab, is_boundary,
                    two_hop_cidx=None, pair_slots=None, pair_colors=None,
                    ext_adj_cidx=None, *, problem="d1", recolor_degrees=True):
    """Oracle for kernels.fused_round.fused_round.

    The decomposed composition the fused round reproduces: optional
    ``pair_scatter`` into the ghost segment, then the reference
    ``_detect_part`` sweep, then zero-losers + ``_recolor_part``.
    ``ext_adj_cidx`` is only threaded through for the d2 recolor
    signature (the reference backend ignores it).
    """
    from repro.core.distributed import _detect_part, _recolor_part

    if pair_slots is not None:
        ghost = pair_scatter_ref(ghost, pair_slots, pair_colors)
    st = {"adj_cidx": adj_cidx, "deg_tab": deg_tab, "gid_tab": gid_tab,
          "is_boundary": is_boundary}
    if two_hop_cidx is not None:
        st["two_hop_cidx"] = two_hop_cidx
        st["ext_adj_cidx"] = (ext_adj_cidx if ext_adj_cidx is not None
                              else adj_cidx)
    kw = dict(problem=problem, recolor_degrees=recolor_degrees)
    lose_l, lose_g, conf = _detect_part(st, colors, ghost, **kw)
    new_colors, iters = _recolor_part(st, jnp.where(lose_l, 0, colors),
                                      ghost, lose_l, lose_g, **kw)
    return new_colors, lose_l, lose_g, conf, iters


def flash_attention_ref(q, k, v, *, causal=True):
    """Oracle for kernels.flash_attention (dense fp32 attention)."""
    from repro.models.layers import _gqa_out, _gqa_scores, _mask_bias

    lq, lk = q.shape[1], k.shape[1]
    s = _gqa_scores(q, k) + _mask_bias(
        jnp.arange(lq), jnp.arange(lk), causal=causal, window=0)
    p = jax.nn.softmax(s, axis=-1)
    return _gqa_out(p, v)
