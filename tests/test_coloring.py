"""Coloring correctness: unit + hypothesis property tests.

The system invariant (paper §2): every run produces a PROPER coloring of
its variant, regardless of graph, partition count, or strategy; interior
vertices are never recolored after their initial assignment.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.baseline import color_baseline
from repro.core.distributed import (
    build_device_state,
    color_distributed,
    color_single_device,
)
from repro.core.greedy import greedy_d1, greedy_d2, greedy_pd2
from repro.core.validate import (
    is_proper_d1,
    is_proper_d2,
    is_proper_pd2,
    num_colors,
)
from repro.graph.csr import build_graph
from repro.graph.generators import (
    bipartite_random,
    erdos_renyi,
    grid_2d,
    hex_mesh,
    mycielskian,
    rmat,
)
from repro.graph.partition import PAD_GID, partition_graph


@pytest.mark.parametrize("order", ["natural", "largest_first", "smallest_last"])
def test_serial_greedy_proper(order):
    g = rmat(8, 6, seed=1)
    assert is_proper_d1(g, greedy_d1(g, order))


def test_serial_greedy_d2_pd2_proper():
    g = hex_mesh(6, 6, 6)
    assert is_proper_d2(g, greedy_d2(g))
    b = bipartite_random(80, 40, 3, seed=1)
    assert is_proper_pd2(b, greedy_pd2(b))


def test_greedy_bounded_by_maxdeg_plus_one():
    for seed in range(3):
        g = erdos_renyi(300, 8.0, seed=seed)
        assert num_colors(greedy_d1(g)) <= g.max_degree + 1


GRAPHS = {
    "hex": lambda: hex_mesh(8, 6, 6),
    "grid": lambda: grid_2d(20, 20),
    "rmat": lambda: rmat(8, 6, seed=3),
    "myc": lambda: mycielskian(8),
}


@pytest.mark.parametrize("gname", list(GRAPHS))
@pytest.mark.parametrize("parts", [1, 3, 8])
@pytest.mark.parametrize("problem", ["d1", "d1_2gl", "d2"])
def test_distributed_proper(gname, parts, problem):
    g = GRAPHS[gname]()
    pg = partition_graph(g, parts, strategy="edge_balanced",
                         second_layer=problem != "d1")
    res = color_distributed(pg, problem=problem, engine="simulate")
    assert res.converged, (gname, parts, problem)
    check = is_proper_d2 if problem == "d2" else is_proper_d1
    assert check(g, res.colors), (gname, parts, problem)


@pytest.mark.parametrize("parts", [2, 5])
def test_pd2_proper(parts):
    b = bipartite_random(120, 60, 3, seed=2)
    pg = partition_graph(b, parts, second_layer=True)
    res = color_distributed(pg, problem="pd2", engine="simulate")
    assert res.converged
    assert is_proper_pd2(b, res.colors)


def test_baseline_proper_and_lower_concurrency():
    g = rmat(9, 8, seed=4)
    pg = partition_graph(g, 8, strategy="edge_balanced")
    fast = color_distributed(pg, problem="d1", engine="simulate")
    slow = color_baseline(pg, n_batches=8)
    assert is_proper_d1(g, slow.colors)
    assert slow.rounds >= fast.rounds  # batching trades rounds for quality


def test_recolor_degrees_quality_on_skewed():
    """Paper §3.3: recolorDegrees reduces colors (holds on skewed/
    adversarial graphs; validated on the paper's own stress family)."""
    wins = 0
    for gname, gfn in [("rmat", lambda: rmat(9, 8, seed=1)),
                       ("myc", lambda: mycielskian(9))]:
        g = gfn()
        pg = partition_graph(g, 8, strategy="edge_balanced")
        rd = color_distributed(pg, problem="d1", recolor_degrees=True,
                               engine="simulate")
        nord = color_distributed(pg, problem="d1", recolor_degrees=False,
                                 engine="simulate")
        wins += int(rd.n_colors <= nord.n_colors)
    assert wins == 2


def test_interior_never_recolored():
    """Paper invariant: interior vertices keep their initial colors."""
    import jax.numpy as jnp
    from functools import partial
    import jax
    from repro.core import distributed as D
    from repro.core.exchange import send_buffer

    g = hex_mesh(10, 6, 6)
    pg = partition_graph(g, 4)
    st_np = D.build_device_state(pg, "d1")
    st = {k: jnp.asarray(v) for k, v in st_np.items()}
    recolor = jax.vmap(partial(D._recolor_part, problem="d1", recolor_degrees=True))
    detect = jax.vmap(partial(D._detect_part, problem="d1", recolor_degrees=True))
    sendbuf = jax.vmap(send_buffer)
    P_, G = st_np["ghost_part"].shape
    colors, _ = recolor(st, jnp.zeros((P_, pg.n_local), jnp.int32),
                        jnp.zeros((P_, G), jnp.int32), st["active0"],
                        jnp.zeros_like(st["ghost_real"]))
    interior = st_np["active0"] & ~st_np["is_boundary"]
    snapshot = np.asarray(colors)[interior]
    for _ in range(4):
        allbuf = sendbuf(colors, st)
        ghost = jnp.where(st["ghost_real"],
                          allbuf[st["ghost_part"], st["ghost_slot"]], 0)
        lose, lose_g, _ = detect(st, colors, ghost)
        colors = jnp.where(lose, 0, colors)
        colors, _ = recolor(st, colors, ghost, lose, lose_g)
    assert (np.asarray(colors)[interior] == snapshot).all()


@given(
    n=st.integers(8, 80),
    deg=st.integers(1, 6),
    parts=st.integers(1, 6),
    seed=st.integers(0, 10_000),
    rd=st.booleans(),
)
@settings(max_examples=20, deadline=None)
def test_property_d1_proper_any_graph(n, deg, parts, seed, rd):
    rng = np.random.default_rng(seed)
    m = n * deg
    g = build_graph(rng.integers(0, n, m), rng.integers(0, n, m), n)
    pg = partition_graph(g, parts, strategy="random", seed=seed)
    res = color_distributed(pg, problem="d1", recolor_degrees=rd,
                            engine="simulate")
    assert res.converged
    assert is_proper_d1(g, res.colors)
    # Determinism: same inputs -> same coloring.
    res2 = color_distributed(pg, problem="d1", recolor_degrees=rd,
                             engine="simulate")
    assert (res.colors == res2.colors).all()


@given(
    n=st.integers(8, 40),
    deg=st.integers(1, 4),
    parts=st.integers(1, 4),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=10, deadline=None)
def test_property_d2_proper_any_graph(n, deg, parts, seed):
    rng = np.random.default_rng(seed)
    g = build_graph(rng.integers(0, n, n * deg), rng.integers(0, n, n * deg), n)
    pg = partition_graph(g, parts, strategy="random", seed=seed,
                         second_layer=True)
    res = color_distributed(pg, problem="d2", engine="simulate")
    assert res.converged
    assert is_proper_d2(g, res.colors)


@pytest.mark.parametrize("problem", ["d1", "d2"])
def test_delta_exchange_matches_all_gather(problem):
    """`delta` ships only changed boundary colors, yet must reconstruct the
    identical ghost tables — same colors, same rounds, measured payload
    strictly below all_gather's from round 1 on (slab-partitioned hex)."""
    g = hex_mesh(12, 8, 8)
    pg = partition_graph(g, 4, second_layer=problem != "d1")  # block slabs
    ag = color_distributed(pg, problem=problem, engine="simulate")
    de = color_distributed(pg, problem=problem, engine="simulate",
                           exchange="delta")
    assert de.converged
    assert (ag.colors == de.colors).all()
    assert ag.rounds == de.rounds
    assert de.exchange == "delta" and ag.exchange == "all_gather"
    # Measured accounting: one entry per exchange, strictly cheaper than
    # the full gather once only conflict deltas move.
    assert len(de.comm_bytes_by_round) == de.rounds + 1
    assert len(ag.comm_bytes_by_round) == ag.rounds + 1
    assert all(d < a for d, a in zip(de.comm_bytes_by_round[1:],
                                     ag.comm_bytes_by_round[1:]))
    assert de.comm_bytes_total < ag.comm_bytes_total
    assert ag.comm_bytes_total == sum(ag.comm_bytes_by_round)


@pytest.mark.parametrize("problem", ["d1", "d2", "pd2"])
def test_sparse_delta_matches_all_gather(problem):
    """The true sparse a2a — count-prefixed (slot, color) pairs over
    edge-colored ppermute phases — must reconstruct the identical ghost
    tables: same colorings, same rounds, and a measured payload (the pairs
    actually moved) strictly below all_gather's full-buffer broadcast."""
    g = hex_mesh(12, 8, 8)
    pg = partition_graph(g, 4, second_layer=problem != "d1")
    ag = color_distributed(pg, problem=problem, engine="simulate")
    sd = color_distributed(pg, problem=problem, engine="simulate",
                           exchange="sparse_delta")
    assert sd.converged
    assert (ag.colors == sd.colors).all()
    assert ag.rounds == sd.rounds
    assert sd.exchange == "sparse_delta"
    assert len(sd.comm_bytes_by_round) == sd.rounds + 1
    assert sd.comm_bytes_total < ag.comm_bytes_total
    # After round 0 only conflict deltas ride the wire.
    assert all(d < a for d, a in zip(sd.comm_bytes_by_round[1:],
                                     ag.comm_bytes_by_round[1:]))


def test_sparse_delta_pallas_scatter_path():
    """The Pallas pair_scatter receive path is bit-identical to the jnp
    reference scatter through the full distributed loop."""
    from repro.core.exchange import SparseDeltaExchange

    g = hex_mesh(10, 6, 6)
    pg = partition_graph(g, 4)
    a = color_distributed(pg, problem="d1", engine="simulate",
                          exchange="sparse_delta")
    b = color_distributed(pg, problem="d1", engine="simulate",
                          exchange=SparseDeltaExchange(scatter="pallas"))
    assert (a.colors == b.colors).all()
    assert a.rounds == b.rounds
    assert list(a.comm_bytes_by_round) == list(b.comm_bytes_by_round)


@given(
    n=st.integers(8, 40),
    deg=st.integers(1, 4),
    parts=st.integers(1, 4),
    seed=st.integers(0, 10_000),
)
@settings(max_examples=6, deadline=None)
def test_property_exchange_parity_all_strategies(n, deg, parts, seed):
    """Every registered exchange strategy is a pure transport: on random
    partitioned graphs all of them yield byte-identical final colorings
    and round counts across d1/d2/pd2 (slab-only strategies skipped where
    the partition is not slab-legal)."""
    from repro.core.exchange import EXCHANGES, get_exchange

    rng = np.random.default_rng(seed)
    m = n * deg
    g = build_graph(rng.integers(0, n, m), rng.integers(0, n, m), n)
    pg = partition_graph(g, parts, strategy="random", seed=seed,
                         second_layer=True)
    for problem in ("d1", "d2", "pd2"):
        ref = color_distributed(pg, problem=problem, engine="simulate")
        for name in EXCHANGES:
            if (get_exchange(name).requires_slab
                    and not pg.halo_neighbors_ok()):
                continue
            res = color_distributed(pg, problem=problem, engine="simulate",
                                    exchange=name)
            assert (res.colors == ref.colors).all(), (name, problem)
            assert res.rounds == ref.rounds, (name, problem)


def test_exchange_registry_and_validation():
    from repro.core.exchange import (
        EXCHANGES, DeltaExchange, SparseDeltaExchange, get_exchange)

    assert set(EXCHANGES) >= {"all_gather", "halo", "delta", "sparse_delta"}
    assert get_exchange(None).name == "all_gather"
    inst = DeltaExchange()
    assert get_exchange(inst) is inst
    with pytest.raises(ValueError, match="unknown exchange"):
        get_exchange("rdma")
    # halo still rejects non-slab partitions.
    g = rmat(7, 5, seed=1)
    pg = partition_graph(g, 4, strategy="random")
    with pytest.raises(ValueError, match="slab"):
        color_distributed(pg, problem="d1", exchange="halo")
    # sparse_delta refuses to run without its prepare() tables.
    with pytest.raises(ValueError, match="prepare"):
        SparseDeltaExchange().init_state({"send_idx": np.zeros((2, 3))})


def test_single_device_matches_quality_band():
    """1-device speculative run lands near serial greedy (paper Fig 2b)."""
    g = rmat(9, 8, seed=6)
    res = color_single_device(g)
    greedy = num_colors(greedy_d1(g))
    assert res.n_colors <= int(greedy * 1.5) + 2
