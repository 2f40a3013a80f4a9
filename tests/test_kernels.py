"""Pallas kernel sweeps: shapes × dtypes × flags vs the jnp oracles.

Integer kernels — equality is exact (assert_allclose with zero tolerance).
Interpret mode executes kernel bodies on CPU (TPU is the target; the
Mosaic compiles are pinned by tests/test_tpu_compile.py). Tiles are
lane counts: multiples of 128.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref
from repro.kernels.fused_round import DEFAULT_TILE


def _mk_inputs(n, w, n_ghost, n_colors, seed, deg_max=50):
    rng = np.random.default_rng(seed)
    n_tab = n + n_ghost + 1
    adj = rng.integers(0, n_tab, (n, w)).astype(np.int32)
    tab = np.concatenate([
        rng.integers(0, n_colors + 1, n + n_ghost), [0]]).astype(np.int32)
    base = rng.integers(1, 40, n).astype(np.int32)
    active = (rng.random(n) < 0.8)
    deg_tab = np.concatenate([
        rng.integers(0, deg_max, n + n_ghost), [0]]).astype(np.int32)
    gid_tab = np.concatenate([
        rng.permutation(10 * (n + n_ghost))[: n + n_ghost], [2**31 - 2]
    ]).astype(np.int32)
    bd = rng.random(n) < 0.5
    return (jnp.asarray(adj), jnp.asarray(tab), jnp.asarray(base),
            jnp.asarray(active), jnp.asarray(deg_tab), jnp.asarray(gid_tab),
            jnp.asarray(bd))


SHAPES = [(16, 3, 8), (100, 7, 40), (256, 1, 1), (515, 12, 200), (64, 33, 9)]


@pytest.mark.parametrize("n,w,g", SHAPES)
@pytest.mark.parametrize("tile", [128, 256])
def test_vb_bit_sweep(n, w, g, tile):
    adj, tab, base, active, _, _, _ = _mk_inputs(n, w, g, 60, seed=n + tile)
    got = ops.vb_bit_assign(adj, tab[:n], base, active, tab, tile=tile)
    want = ref.vb_bit_assign_ref(adj, tab[:n], base, active, tab)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=0)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), rtol=0)


@pytest.mark.parametrize("n,w,g", SHAPES)
@pytest.mark.parametrize("rd", [True, False])
def test_conflict_sweep(n, w, g, rd):
    adj, tab, base, active, deg_tab, gid_tab, bd = _mk_inputs(n, w, g, 6, seed=n)
    got = ops.conflict_detect(adj, tab[:n], deg_tab[:n], gid_tab[:n], bd,
                              tab, deg_tab, gid_tab, n, recolor_degrees=rd)
    want = ref.conflict_detect_ref(adj, tab[:n], deg_tab[:n], gid_tab[:n], bd,
                                   tab, deg_tab, gid_tab, n, recolor_degrees=rd)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert int(got[2]) == int(want[2])


@pytest.mark.parametrize("n,w,g", [(16, 3, 8), (64, 5, 30), (130, 9, 60)])
@pytest.mark.parametrize("partial_d2", [False, True])
def test_d2_forbidden_sweep(n, w, g, partial_d2):
    adj, tab, base, active, _, _, _ = _mk_inputs(n, w, g, 20, seed=n * 7)
    rng = np.random.default_rng(n)
    ext = jnp.asarray(
        rng.integers(0, n + g + 1, (n + g + 1, w)).astype(np.int32))
    got = ops.d2_forbidden(adj, base, active, tab[:n], tab, ext,
                           partial_d2=partial_d2)
    want = ref.d2_forbidden_ref(adj, base, active, tab[:n], tab, ext,
                                partial_d2=partial_d2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(n=st.integers(4, 120), w=st.integers(1, 16), seed=st.integers(0, 99))
@settings(max_examples=15, deadline=None)
def test_vb_bit_property(n, w, seed):
    adj, tab, base, active, _, _, _ = _mk_inputs(n, w, 10, 50, seed)
    got = ops.vb_bit_assign(adj, tab[:n], base, active, tab)
    want = ref.vb_bit_assign_ref(adj, tab[:n], base, active, tab)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    # Invariant: assigned color is never a neighbor's color.
    colors = np.asarray(got[0])
    tabn = np.asarray(tab)
    newly = (np.asarray(tab[:n]) == 0) & (colors > 0) & np.asarray(active)
    nbr = tabn[np.asarray(adj)]
    clash = (nbr == colors[:, None]) & (colors[:, None] > 0)
    assert not (clash.any(axis=1) & newly).any()


@pytest.mark.parametrize("n,c", [(16, 5), (100, 100), (257, 64), (512, 1)])
@pytest.mark.parametrize("tile", [128, 256])
def test_pair_scatter_sweep(n, c, tile):
    rng = np.random.default_rng(n + c + tile)
    table = rng.integers(0, 99, n).astype(np.int32)
    k = int(rng.integers(0, min(n, c) + 1))
    slots = np.full(c, n, np.int32)          # pad sentinel = table length
    slots[:k] = rng.permutation(n)[:k]
    vals = rng.integers(1, 50, c).astype(np.int32)
    got = ops.pair_scatter(jnp.asarray(table), jnp.asarray(slots),
                           jnp.asarray(vals), tile=tile)
    want = ref.pair_scatter_ref(jnp.asarray(table), jnp.asarray(slots),
                                jnp.asarray(vals))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(n=st.integers(4, 200), c=st.integers(1, 64), seed=st.integers(0, 99))
@settings(max_examples=10, deadline=None)
def test_pair_scatter_property(n, c, seed):
    """Pairs land, pads drop, untouched slots keep their value."""
    rng = np.random.default_rng(seed)
    table = rng.integers(0, 99, n).astype(np.int32)
    k = int(rng.integers(0, min(n, c) + 1))
    slots = np.full(c, n, np.int32)
    slots[:k] = rng.permutation(n)[:k]
    vals = rng.integers(1, 50, c).astype(np.int32)
    got = np.asarray(ops.pair_scatter(
        jnp.asarray(table), jnp.asarray(slots), jnp.asarray(vals), tile=128))
    want = table.copy()
    want[slots[:k]] = vals[:k]
    np.testing.assert_array_equal(got, want)


def test_pallas_local_color_matches_core():
    from repro.core.distributed import build_device_state
    from repro.core.local import local_color_d1
    from repro.graph.generators import rmat
    from repro.graph.partition import partition_graph

    g = rmat(7, 5, seed=9)
    pg = partition_graph(g, 2)
    st_ = build_device_state(pg, "d1")
    nl, gh = pg.n_local, pg.n_ghost
    tab0 = jnp.zeros(nl + gh + 1, jnp.int32)
    args = (jnp.asarray(st_["adj_cidx"][0]), tab0,
            jnp.asarray(st_["active0"][0]), jnp.asarray(st_["deg_tab"][0]),
            jnp.asarray(st_["gid_tab"][0]))
    (a, ia), (b, ib) = local_color_d1(*args), ops.local_color_d1_pallas(*args)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(ia) == int(ib) > 0


def test_pallas_local_color_d2_matches_core():
    from repro.core.distributed import build_device_state
    from repro.core.local import local_color_d2
    from repro.graph.generators import rmat
    from repro.graph.partition import partition_graph

    g = rmat(7, 5, seed=11)
    pg = partition_graph(g, 2, second_layer=True)
    st_ = build_device_state(pg, "d2")
    nl, gh = pg.n_local, pg.n_ghost
    for partial_d2 in (False, True):
        tab0 = jnp.zeros(nl + gh + 1, jnp.int32)
        a, ia = local_color_d2(
            jnp.asarray(st_["adj_cidx"][0]), jnp.asarray(st_["two_hop_cidx"][0]),
            tab0, jnp.asarray(st_["active0"][0]), jnp.asarray(st_["deg_tab"][0]),
            jnp.asarray(st_["gid_tab"][0]), partial_d2=partial_d2)
        b, ib = ops.local_color_d2_pallas(
            jnp.asarray(st_["adj_cidx"][0]), jnp.asarray(st_["two_hop_cidx"][0]),
            jnp.asarray(st_["ext_adj_cidx"][0]), tab0,
            jnp.asarray(st_["active0"][0]), jnp.asarray(st_["deg_tab"][0]),
            jnp.asarray(st_["gid_tab"][0]), partial_d2=partial_d2)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(ia) == int(ib) > 0


# ---------------------------------------------------------------------------
# Fused round: parity with the decomposed oracle composition.
# ---------------------------------------------------------------------------

def _part0_state(problem, seed=3, parts=3):
    """Part-0 device arrays of a real partitioned graph + random colors."""
    from repro.core.distributed import build_device_state
    from repro.graph.generators import bipartite_random, rmat
    from repro.graph.partition import partition_graph

    if problem == "pd2":
        g = bipartite_random(70, 35, 3, seed=seed)
    else:
        g = rmat(7, 5, seed=seed)
    pg = partition_graph(g, parts, strategy="edge_balanced",
                         second_layer=problem != "d1")
    st_ = build_device_state(pg, problem)
    rng = np.random.default_rng(seed + 1)
    nl, gh = pg.n_local, pg.n_ghost
    out = {k: jnp.asarray(v[0]) for k, v in st_.items()}
    out["colors"] = jnp.asarray(rng.integers(0, 7, nl).astype(np.int32))
    out["ghost"] = jnp.asarray(rng.integers(0, 7, gh).astype(np.int32))
    out["n_ghost"] = gh
    return out


def _fused_vs_ref(s, problem, tile, pair_slots=None, pair_colors=None):
    th = s.get("two_hop_cidx")
    got = ops.fused_round(
        s["adj_cidx"], s["colors"], s["ghost"], s["deg_tab"], s["gid_tab"],
        s["is_boundary"], two_hop_cidx=th, pair_slots=pair_slots,
        pair_colors=pair_colors, problem=problem, tile=tile)
    want = ref.fused_round_ref(
        s["adj_cidx"], s["colors"], s["ghost"], s["deg_tab"], s["gid_tab"],
        s["is_boundary"], two_hop_cidx=th, pair_slots=pair_slots,
        pair_colors=pair_colors, ext_adj_cidx=s.get("ext_adj_cidx"),
        problem=problem)
    names = ("colors", "lose_l", "lose_g", "conf", "iters")
    assert len(got) == len(want) == len(names)
    for g_, w_, name in zip(got, want, names):
        np.testing.assert_array_equal(np.asarray(g_), np.asarray(w_),
                                      err_msg=f"{problem}/{name}")


@pytest.mark.parametrize("problem", ["d1", "d2", "pd2"])
@pytest.mark.parametrize("tile", [128, 256, 512])
def test_fused_round_parity(problem, tile):
    """Fused round == decomposed oracle, incl. ragged tails (nl % tile != 0)."""
    _fused_vs_ref(_part0_state(problem), problem, tile)


@pytest.mark.parametrize("problem", ["d1", "d2"])
def test_fused_round_pairs_d1_d2(problem):
    """Inline pair scatter: (slot, color) updates land before detection."""
    s = _part0_state(problem, seed=5)
    rng = np.random.default_rng(11)
    gh = s["n_ghost"]
    c = max(gh // 2, 1)
    slots = np.full(c, gh, np.int32)              # pad sentinel drops
    k = c // 2
    slots[:k] = rng.permutation(gh)[:k]
    vals = rng.integers(1, 7, c).astype(np.int32)
    _fused_vs_ref(s, problem, 128, pair_slots=jnp.asarray(slots),
                  pair_colors=jnp.asarray(vals))


def test_fused_round_zero_ghost_d1():
    """Single part: G == 0 exercises the dummy-ghost input path."""
    _fused_vs_ref(_part0_state("d1", parts=1), "d1", 128)


def test_fused_round_rejects_d1_2gl():
    s = _part0_state("d1")
    with pytest.raises(ValueError, match="d1_2gl"):
        ops.fused_round(s["adj_cidx"], s["colors"], s["ghost"],
                        s["deg_tab"], s["gid_tab"], s["is_boundary"],
                        problem="d1_2gl")


@given(seed=st.integers(0, 10_000), parts=st.integers(1, 4))
@settings(max_examples=8, deadline=None)
def test_fused_backend_round_property_d1(seed, parts):
    """Property: PallasFusedBackend.round == the reference decomposed round
    on random partitioned graphs (random topology, partition count, colors)."""
    from repro.core.backend import PallasFusedBackend, ReferenceBackend
    from repro.core.distributed import build_device_state
    from repro.graph.generators import erdos_renyi
    from repro.graph.partition import partition_graph

    rng = np.random.default_rng(seed)
    g = erdos_renyi(int(rng.integers(20, 90)), int(rng.integers(1, 5)),
                    seed=seed)
    pg = partition_graph(g, parts)
    st_ = build_device_state(pg, "d1")
    s = {k: jnp.asarray(v[0]) for k, v in st_.items()}
    colors = jnp.asarray(rng.integers(0, 6, pg.n_local).astype(np.int32))
    ghost = jnp.asarray(rng.integers(0, 6, pg.n_ghost).astype(np.int32))
    kw = dict(problem="d1", recolor_degrees=True)
    got = PallasFusedBackend(interpret=True).round(s, colors, ghost, **kw)
    want = ReferenceBackend().round(s, colors, ghost, **kw)
    assert len(got) == len(want) == 5
    for g_, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g_), np.asarray(w_))


# ---------------------------------------------------------------------------
# Backend layer: reference and pallas must be interchangeable — identical
# colorings AND identical round counts through the full distributed loop.
# ---------------------------------------------------------------------------

def test_backend_registry():
    from repro.core.backend import (
        BACKENDS, PallasBackend, PallasFusedBackend, ReferenceBackend,
        get_backend)

    assert set(BACKENDS) >= {"reference", "pallas", "pallas_fused"}
    assert isinstance(get_backend("reference"), ReferenceBackend)
    assert isinstance(get_backend("pallas"), PallasBackend)
    assert isinstance(get_backend("pallas_fused"), PallasFusedBackend)
    assert get_backend(None).name == "reference"
    inst = PallasBackend(interpret=True)
    assert get_backend(inst) is inst
    with pytest.raises(ValueError, match="unknown backend"):
        get_backend("cuda")


@pytest.mark.parametrize("problem", ["d1", "d1_2gl", "d2", "pd2"])
def test_backend_parity_distributed(problem):
    from repro.core.distributed import color_distributed
    from repro.core.validate import is_proper_d1, is_proper_d2, is_proper_pd2
    from repro.graph.generators import bipartite_random, rmat
    from repro.graph.partition import partition_graph

    if problem == "pd2":
        g = bipartite_random(90, 45, 3, seed=5)
        check = is_proper_pd2
    else:
        g = rmat(7, 5, seed=3)
        check = is_proper_d2 if problem == "d2" else is_proper_d1
    pg = partition_graph(g, 3, strategy="edge_balanced",
                         second_layer=problem != "d1")
    ref = color_distributed(pg, problem=problem, engine="simulate",
                            backend="reference")
    pal = color_distributed(pg, problem=problem, engine="simulate",
                            backend="pallas")
    assert ref.converged and pal.converged
    assert check(g, pal.colors)
    assert (ref.colors == pal.colors).all(), problem
    assert ref.rounds == pal.rounds, problem
    assert ref.backend == "reference" and pal.backend == "pallas"


@pytest.mark.parametrize("problem", ["d1", "d1_2gl", "d2", "pd2"])
def test_fused_backend_parity_distributed(problem):
    """pallas_fused through the full loop: identical colors, round counts,
    conflict totals, AND per-round comm-bytes accounting vs reference.
    (``d1_2gl`` exercises the decomposed-round fallback.)"""
    from repro.core.distributed import color_distributed
    from repro.graph.generators import bipartite_random, rmat
    from repro.graph.partition import partition_graph

    if problem == "pd2":
        g = bipartite_random(90, 45, 3, seed=5)
    else:
        g = rmat(7, 5, seed=3)
    pg = partition_graph(g, 3, strategy="edge_balanced",
                         second_layer=problem != "d1")
    ref_ = color_distributed(pg, problem=problem, engine="simulate",
                             backend="reference")
    fus = color_distributed(pg, problem=problem, engine="simulate",
                            backend="pallas_fused")
    assert ref_.converged and fus.converged
    assert (ref_.colors == fus.colors).all(), problem
    assert ref_.rounds == fus.rounds, problem
    assert ref_.total_conflicts == fus.total_conflicts, problem
    np.testing.assert_array_equal(ref_.comm_bytes_by_round,
                                  fus.comm_bytes_by_round)
    assert fus.backend == "pallas_fused"


def test_backend_parity_single_device():
    from repro.core.distributed import color_single_device
    from repro.graph.generators import rmat

    g = rmat(7, 6, seed=8)
    ref = color_single_device(g, backend="reference")
    pal = color_single_device(g, backend="pallas")
    assert (ref.colors == pal.colors).all()
    assert ref.rounds == pal.rounds


# ---------------------------------------------------------------------------
# Diagonal neighbor reads (kernels.diagonals): the blocks, and with them
# colors, iterations, lose flags and conflict counts, equal the gather's.
# ---------------------------------------------------------------------------

def _hex_graph(spec, extra=0, seed=0):
    """An x-major hex mesh, plus ``extra`` random edges: entries on no
    diagonal, so the layout keeps a residual."""
    from repro.graph.csr import build_graph
    from repro.graph.generators import hex_mesh

    g = hex_mesh(*spec)
    if not extra:
        return g
    rng = np.random.default_rng(seed)
    src = np.repeat(np.arange(g.n, dtype=np.int32), np.diff(g.offsets))
    keep = src < g.targets
    return build_graph(
        np.concatenate([src[keep], rng.integers(0, g.n, extra)]),
        np.concatenate([g.targets[keep], rng.integers(0, g.n, extra)]),
        g.n, name=f"hex_plus_{extra}")


def _hex_state(spec, parts, problem, extra=0):
    """Stacked state of a (perturbed) hex mesh and its diagonal layout."""
    from repro.core.distributed import build_device_state, neighbor_diagonals
    from repro.graph.partition import partition_graph

    pg = partition_graph(_hex_graph(spec, extra), parts,
                         second_layer=problem != "d1")
    st_ = build_device_state(pg, problem)
    diag, share = neighbor_diagonals(st_, problem)
    assert diag is not None
    assert (diag.res_pos.shape[-1] > 0) == (share < 1.0) == (extra > 0)
    return st_, diag


def _hex_part(spec, parts, problem, extra=0, seed=0):
    """One part's arrays (an inner one where there are several), random
    colors and ghost colors, and the part's slice of the layout."""
    st_, diag = _hex_state(spec, parts, problem, extra)
    part = min(1, parts - 1)
    s = {k: jnp.asarray(v[part]) for k, v in st_.items()}
    rng = np.random.default_rng(seed)
    n, g = s["is_boundary"].shape[0], s["ghost_real"].shape[0]
    s["colors"] = jnp.asarray(rng.integers(0, 7, n).astype(np.int32))
    s["ghost"] = jnp.asarray(rng.integers(0, 7, g).astype(np.int32))
    return s, jax.tree_util.tree_map(lambda x: jnp.asarray(x[part]), diag)


# (graph, parts, problem, extra edges).  One part of a hex mesh has every
# entry on a diagonal, and four x-slabs their ghosts too (one or two
# offsets a face); the extra random edges leave a residual.  No row count
# is a multiple of the 128-lane tile (ragged tails).
HEX_CASES = [((12, 10, 9), 1, "d1", 0), ((9, 8, 7), 1, "d2", 0),
             ((9, 8, 7), 1, "pd2", 0), ((16, 12, 10), 4, "d1", 0),
             ((16, 12, 10), 4, "d1", 40), ((16, 10, 9), 4, "d2", 3)]
HEX_IDS = [f"{p}-{n}part" + ("-residual" if x else "")
           for _, n, p, x in HEX_CASES]


@pytest.mark.parametrize("spec,parts,problem,extra", HEX_CASES, ids=HEX_IDS)
@pytest.mark.parametrize("tile", [128, 384])
def test_read_neighbors_matches_gather(spec, parts, problem, extra, tile):
    """The diagonal kernel's blocks == ``X[idx_t]``, two tables at once."""
    from repro.kernels.diagonals import read_neighbors
    from repro.kernels.fused_round import _lane_layout, neighbor_index

    st_, diag = _hex_state(spec, parts, problem, extra)
    idx = neighbor_index(st_["adj_cidx"], st_.get("two_hop_cidx"), problem)
    n_tab = st_["deg_tab"].shape[-1]
    rng = np.random.default_rng(tile)
    for part in range(parts):
        tab = jnp.asarray(rng.integers(0, 1 << 30, n_tab).astype(np.int32))
        t, _, idx_t = _lane_layout(jnp.asarray(idx[part]), n_tab, tile)
        d = jax.tree_util.tree_map(lambda x: jnp.asarray(x[part]), diag)
        got = read_neighbors(idx_t, d, tab, tab[::-1], tile=t,
                             interpret=True)
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(tab[idx_t]))
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(tab[::-1][idx_t]))


@pytest.mark.parametrize("spec,parts,problem,extra", HEX_CASES, ids=HEX_IDS)
def test_fused_round_diagonal_parity(spec, parts, problem, extra):
    """Diagonal reads == the gather path == the decomposed oracle."""
    s, diag = _hex_part(spec, parts, problem, extra, seed=parts)
    th = s.get("two_hop_cidx")
    args = (s["adj_cidx"], s["colors"], s["ghost"], s["deg_tab"],
            s["gid_tab"], s["is_boundary"])
    got = ops.fused_round(*args, two_hop_cidx=th, diag=diag,
                          problem=problem, tile=128)
    gather = ops.fused_round(*args, two_hop_cidx=th, problem=problem,
                             tile=128)
    want = ref.fused_round_ref(*args, two_hop_cidx=th,
                               ext_adj_cidx=s.get("ext_adj_cidx"),
                               problem=problem)
    for name, g_, a_, w_ in zip(("colors", "lose_l", "lose_g", "conf",
                                 "iters"), got, gather, want):
        np.testing.assert_array_equal(np.asarray(g_), np.asarray(a_),
                                      err_msg=name)
        np.testing.assert_array_equal(np.asarray(g_), np.asarray(w_),
                                      err_msg=name)


@pytest.mark.parametrize("case", [4, 5], ids=[HEX_IDS[4], HEX_IDS[5]])
def test_fused_round_diagonal_pairs(case):
    """Ghost updates (``pair_slots``) land before the diagonal reads."""
    spec, parts, problem, extra = HEX_CASES[case]
    s, diag = _hex_part(spec, parts, problem, extra, seed=9)
    gh = s["ghost"].shape[0]
    rng = np.random.default_rng(13)
    slots = np.full(gh // 2, gh, np.int32)          # pad sentinel drops
    slots[: gh // 4] = rng.permutation(gh)[: gh // 4]
    pairs = dict(pair_slots=jnp.asarray(slots), pair_colors=jnp.asarray(
        rng.integers(1, 7, slots.size).astype(np.int32)))
    args = (s["adj_cidx"], s["colors"], s["ghost"], s["deg_tab"],
            s["gid_tab"], s["is_boundary"])
    th = s.get("two_hop_cidx")
    got = ops.fused_round(*args, two_hop_cidx=th, diag=diag,
                          problem=problem, tile=128, **pairs)
    want = ops.fused_round(*args, two_hop_cidx=th, problem=problem,
                           tile=128, **pairs)
    assert int(got[3]) > 0                          # conflicts to resolve
    for g_, w_ in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g_), np.asarray(w_))


@pytest.mark.parametrize("spec,parts,problem,extra", HEX_CASES[:3],
                         ids=HEX_IDS[:3])
def test_speculate_diagonal_parity_masked(spec, parts, problem, extra):
    """A masked request (a random third of the rows active, the rest
    frozen at their colors): same table and iteration count."""
    from repro.kernels.fused_round import neighbor_index, speculate

    s, diag = _hex_part(spec, parts, problem, extra, seed=4)
    idx = neighbor_index(s["adj_cidx"], s.get("two_hop_cidx"), problem)
    n = s["colors"].shape[0]
    active = jnp.asarray(np.random.default_rng(5).random(n) < 1 / 3)
    tab = jnp.concatenate([jnp.where(active, 0, s["colors"]), s["ghost"],
                           jnp.zeros((1,), jnp.int32)])
    kw = dict(tile=128, max_iters=1024)
    got = speculate(idx, tab, active, s["deg_tab"], s["gid_tab"], diag=diag,
                    **kw)
    want = speculate(idx, tab, active, s["deg_tab"], s["gid_tab"], **kw)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert int(got[1]) == int(want[1]) > 0


def test_find_diagonals_sees_a_diagonal_in_step_with_the_mesh():
    """hex:256,256,2 has 131,072 rows, more than the analysis samples; the
    ``-1`` diagonal lies on odd rows only, so a sample of every other row
    would miss it (a tenth of the entries)."""
    from repro.core.distributed import build_device_state, neighbor_diagonals
    from repro.graph.generators import hex_mesh
    from repro.graph.partition import partition_graph

    st_ = build_device_state(partition_graph(hex_mesh(256, 256, 2), 1), "d1")
    diag, share = neighbor_diagonals(st_, "d1")
    assert diag.offsets == (-512, -2, -1, 1, 2, 512)
    assert share == 1.0 and diag.res_pos.shape == (1, 0)


def _relabeled_hex(spec, seed):
    from repro.graph.csr import build_graph
    from repro.graph.generators import hex_mesh

    g = hex_mesh(*spec)
    perm = np.random.default_rng(seed).permutation(g.n).astype(np.int32)
    src = np.repeat(np.arange(g.n, dtype=np.int32), np.diff(g.offsets))
    return build_graph(perm[src], perm[g.targets], g.n, name="hex_relabeled")


@pytest.mark.parametrize("graph", ["rmat", "er", "hex_relabeled", "hex"])
def test_plan_reads_along_diagonals_only_where_observed(graph):
    """The plan's counter says which path each graph took; both paths
    color like the reference, full and masked."""
    from repro.core.plan import build_plan
    from repro.graph.generators import erdos_renyi, hex_mesh, rmat
    from repro.graph.partition import partition_graph

    g = {"rmat": lambda: rmat(8, 6, seed=2),
         "er": lambda: erdos_renyi(300, 5, seed=3),
         "hex_relabeled": lambda: _relabeled_hex((8, 7, 6), 4),
         "hex": lambda: hex_mesh(8, 7, 6)}[graph]()
    pg = partition_graph(g, 1)
    kw = dict(problem="d1", engine="simulate", exchange="sparse_delta")
    plan = build_plan(pg, backend="pallas_fused", **kw)
    stats = plan.stats
    if graph == "hex":
        assert (stats.diagonals, stats.diagonal_share) == (6, 1.0)
        assert "nbr_diag" in plan.state
    else:
        assert (stats.diagonals, stats.diagonal_share) == (0, 0.0)
        assert "nbr_diag" not in plan.state
    oracle = build_plan(pg, backend="reference", **kw)
    assert (oracle.stats.diagonals, oracle.stats.diagonal_share) == (0, 0.0)
    mask = np.random.default_rng(6).random(g.n) < 0.3
    full = oracle.run()
    for run_kw in ({}, dict(color_mask=mask, colors0=full.colors)):
        a, b = plan.run(**run_kw), oracle.run(**run_kw)
        assert (a.colors == b.colors).all()
        assert (a.rounds, a.total_conflicts, a.spec_iters) == (
            b.rounds, b.total_conflicts, b.spec_iters)


@pytest.mark.parametrize("problem,least,share", [("d1", 6, 0.98),
                                                 ("d2", 25, 0.95)])
def test_plan_counts_ghost_diagonals_on_four_parts(problem, least, share):
    """Four x-slabs: the owned diagonals plus the ghosts' offsets (d2's
    ghosts of ghosts add more than the kernel takes); counted when the
    plan is built, before any run."""
    from repro.core.plan import build_plan
    from repro.graph.generators import hex_mesh
    from repro.graph.partition import partition_graph

    pg = partition_graph(hex_mesh(16, 12, 10), 4,
                         second_layer=problem != "d1")
    plan = build_plan(pg, problem=problem, backend="pallas_fused",
                      engine="simulate", exchange="sparse_delta")
    assert plan.stats.diagonals >= least
    assert plan.stats.diagonal_share >= share
