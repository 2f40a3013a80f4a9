"""Hierarchical two-level exchange + packed wire format (ISSUE-8).

Covers the tentpole contracts: ``hier_delta`` is bit-identical to
``all_gather`` across problems and backends, its measured bytes carry
the ``[intra-node, inter-node]`` split, wire widths are the narrowest
the static bounds admit, and the sparse transport is an explicit
choice.  The shard_map-engine legs live in test_multidevice.py.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.distributed import build_device_state, color_distributed
from repro.core.exchange import (
    COLOR_DTYPE,
    HierDeltaExchange,
    SparseDeltaExchange,
    dtype_bytes,
    get_exchange,
    level_split,
    list_exchanges,
    payload_bytes,
    wire_dtype,
)
from repro.core.validate import is_proper_d1, is_proper_d2
from repro.graph.generators import erdos_renyi, hex_mesh, rmat
from repro.graph.partition import partition_graph, two_level_partition
from repro.launch.mesh import factor_parts

GRAPH = hex_mesh(12, 6, 6)
PG = two_level_partition(GRAPH, 2, 2, second_layer=True)


# ---------------------------------------------------------------------------
# Packed wire format: dtype selection + the shared payload schema.
# ---------------------------------------------------------------------------

def test_wire_dtype_thresholds():
    assert wire_dtype(0) == jnp.uint8
    assert wire_dtype(255) == jnp.uint8
    assert wire_dtype(256) == jnp.uint16
    assert wire_dtype(65535) == jnp.uint16
    assert wire_dtype(65536) == COLOR_DTYPE
    with pytest.raises(ValueError):
        wire_dtype(-1)


def test_dtype_bytes():
    assert dtype_bytes(jnp.uint8) == 1
    assert dtype_bytes(jnp.uint16) == 2
    assert dtype_bytes(COLOR_DTYPE) == 4


def test_payload_bytes_schema():
    st = {"send_idx": np.zeros((4, 10), np.int32)}
    # Default widths are the in-memory int32.
    assert int(payload_bytes(st, colors=3)) == 12
    assert int(payload_bytes(st, headers=2, pairs=5)) == 2 * 4 + 5 * 8
    # Packed widths flow through every term.
    got = payload_bytes(st, colors=3, headers=2, pairs=5,
                        color_dtype=jnp.uint8, slot_dtype=jnp.uint16)
    assert int(got) == 3 * 1 + 2 * 2 + 5 * (1 + 2)
    # Masks are whole bitmasks over the send width, rounded up to bytes.
    assert int(payload_bytes(st, masks=2)) == 2 * ((10 + 7) // 8)


def test_level_split_normalizes():
    flat = level_split(jnp.asarray(40, jnp.int32))
    assert flat.shape == (2,) and list(np.asarray(flat)) == [0, 40]
    pair = level_split(jnp.asarray([7, 9], jnp.int32))
    assert list(np.asarray(pair)) == [7, 9]


# ---------------------------------------------------------------------------
# (node, local) factorization.
# ---------------------------------------------------------------------------

def test_factor_parts_auto_squarest():
    assert factor_parts(1) == (1, 1)
    assert factor_parts(4) == (2, 2)
    assert factor_parts(8) == (4, 2)
    assert factor_parts(12) == (4, 3)
    assert factor_parts(7) == (7, 1)       # prime -> degenerate hierarchy


def test_factor_parts_explicit_and_env(monkeypatch):
    assert factor_parts(8, 4) == (2, 4)
    monkeypatch.setenv("REPRO_NODE_SIZE", "4")
    assert factor_parts(8) == (2, 4)
    monkeypatch.setenv("REPRO_NODE_SIZE", "0")   # 0 = auto
    assert factor_parts(8) == (4, 2)
    with pytest.raises(ValueError):
        factor_parts(8, 3)
    with pytest.raises(ValueError):
        factor_parts(0)


def _owned(pg, p):
    from repro.graph.partition import PAD_GID

    gids = pg.vertex_gid[p]
    return {int(v) for v in gids[gids != PAD_GID]}


def test_two_level_partition_layout():
    assert PG.n_parts == 4
    assert "2lvl2x2" in PG.name
    sizes = [len(_owned(PG, p)) for p in range(4)]
    assert sum(sizes) == GRAPH.n and all(s > 0 for s in sizes)
    # Node-major: parts {0,1} and {2,3} subdivide contiguous node slabs,
    # so each pair's owned-vertex set is exactly one flat 2-part slab.
    flat = partition_graph(GRAPH, 2, strategy="block", second_layer=True)
    for node in (0, 1):
        two = _owned(PG, node * 2) | _owned(PG, node * 2 + 1)
        assert two == _owned(flat, node)


# ---------------------------------------------------------------------------
# hier_delta parity: bit-identical to all_gather, problems x backends.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("problem", ["d1", "d2", "pd2"])
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_hier_delta_matches_all_gather(problem, backend):
    ag = color_distributed(PG, problem=problem, backend=backend,
                           engine="simulate", cache=False)
    hd = color_distributed(PG, problem=problem, backend=backend,
                           engine="simulate", exchange="hier_delta",
                           cache=False)
    assert (hd.colors == ag.colors).all()
    assert hd.rounds == ag.rounds
    assert hd.converged
    if problem != "pd2":
        check = is_proper_d2 if problem == "d2" else is_proper_d1
        assert check(GRAPH, hd.colors)
    # Byte accounting: per-round [intra, inter] split sums to the round
    # totals, the properties sum the columns, and the win is real.
    lv = hd.comm_bytes_by_level
    assert lv is not None and lv.shape == (hd.rounds + 1, 2)
    assert list(lv.sum(axis=1)) == list(hd.comm_bytes_by_round)
    assert hd.comm_bytes_intra + hd.comm_bytes_inter == hd.comm_bytes_total
    assert hd.comm_bytes_intra > 0 and hd.comm_bytes_inter > 0
    assert hd.comm_bytes_total < ag.comm_bytes_total


def test_comm_ordering_hier_sparse_all_gather():
    """The tentpole ordering on the two-level partition."""
    res = {ex: color_distributed(PG, problem="d1", engine="simulate",
                                 exchange=ex, cache=False)
           for ex in ("all_gather", "sparse_delta", "hier_delta")}
    ag, sd, hd = res["all_gather"], res["sparse_delta"], res["hier_delta"]
    assert (sd.colors == ag.colors).all() and (hd.colors == ag.colors).all()
    assert sd.rounds == ag.rounds == hd.rounds
    assert hd.comm_bytes_total < sd.comm_bytes_total < ag.comm_bytes_total
    # Flat strategies book everything as inter-node.
    assert sd.comm_bytes_intra == 0
    assert sd.comm_bytes_inter == sd.comm_bytes_total


def test_hier_delta_flat_partition_and_explicit_node_size():
    """hier_delta needs no special partition, and node_size=1 (the prime
    degeneration) collapses to pure packed point-to-point: all bytes
    intra-free, still bit-identical."""
    g = rmat(8, 6, seed=5)
    pg = partition_graph(g, 4, strategy="edge_balanced", second_layer=True)
    ag = color_distributed(pg, problem="d1", engine="simulate", cache=False)
    hd = color_distributed(pg, problem="d1", engine="simulate",
                           exchange=HierDeltaExchange(node_size=2),
                           cache=False)
    assert (hd.colors == ag.colors).all() and hd.rounds == ag.rounds
    flat = color_distributed(pg, problem="d1", engine="simulate",
                             exchange=HierDeltaExchange(node_size=1),
                             cache=False)
    assert (flat.colors == ag.colors).all()
    assert flat.comm_bytes_intra == 0        # every part its own leader


def test_hier_delta_requires_prepare_tables():
    ex = HierDeltaExchange()
    with pytest.raises(ValueError, match="prepare"):
        ex.init_state({"send_idx": np.zeros((4, 8), np.int32)})


def test_registry_has_hier_delta():
    assert "hier_delta" in list_exchanges()
    assert isinstance(get_exchange("hier_delta"), HierDeltaExchange)


# ---------------------------------------------------------------------------
# Packed-width boundary cases: palettes crossing 255 / 65535, wide slots.
# ---------------------------------------------------------------------------

def _prepared(pg, problem):
    ex = HierDeltaExchange()
    st = build_device_state(pg, problem)
    st.update(ex.prepare(pg, st))
    return ex


def test_wire_widths_cross_uint8_palette():
    """One graph, both families: rmat(8,6) has 16 < max-degree < 255, so
    the d1 palette packs to uint8 while the d2 palette crosses 255 into
    uint16 — and the parity holds at both widths."""
    g = rmat(8, 6, seed=5)
    delta = g.max_degree
    assert 16 < delta < 255 < delta * delta + 1 <= 65535
    pg = partition_graph(g, 4, strategy="edge_balanced", second_layer=True)
    assert _prepared(pg, "d1")._color_dtype == jnp.uint8
    assert _prepared(pg, "d2")._color_dtype == jnp.uint16
    assert _prepared(pg, "d1")._slot_dtype == wire_dtype(pg.send_width)
    for problem in ("d1", "d2"):
        ag = color_distributed(pg, problem=problem, engine="simulate",
                               cache=False)
        hd = color_distributed(pg, problem=problem, engine="simulate",
                               exchange="hier_delta", cache=False)
        assert (hd.colors == ag.colors).all() and hd.rounds == ag.rounds


def test_wire_widths_cross_uint16_palette():
    """A dense graph (max degree > 255): d1 colors need uint16 and the
    d2 palette bound overflows 65535 back to the in-memory int32."""
    g = erdos_renyi(600, 400)
    delta = g.max_degree
    assert 255 < delta <= 65535 < delta * delta + 1
    pg = partition_graph(g, 4, strategy="edge_balanced", second_layer=True)
    assert _prepared(pg, "d1")._color_dtype == jnp.uint16
    assert _prepared(pg, "d2")._color_dtype == COLOR_DTYPE
    ag = color_distributed(pg, problem="d1", engine="simulate", cache=False)
    hd = color_distributed(pg, problem="d1", engine="simulate",
                           exchange="hier_delta", cache=False)
    assert (hd.colors == ag.colors).all() and hd.rounds == ag.rounds
    assert is_proper_d1(g, hd.colors)


def test_wire_widths_wide_send_slots():
    """A random partition ghosts nearly everything: send width > 255, so
    slot ids/counts pack to uint16 and the pad sentinel (= S) still
    round-trips."""
    g = hex_mesh(12, 8, 8)
    pg = partition_graph(g, 2, strategy="random", second_layer=True)
    assert pg.send_width > 255
    assert _prepared(pg, "d1")._slot_dtype == jnp.uint16
    ag = color_distributed(pg, problem="d1", engine="simulate", cache=False)
    hd = color_distributed(pg, problem="d1", engine="simulate",
                           exchange="hier_delta", cache=False)
    assert (hd.colors == ag.colors).all() and hd.rounds == ag.rounds
    assert is_proper_d1(g, hd.colors)


# ---------------------------------------------------------------------------
# Persistent compilation cache wiring.
# ---------------------------------------------------------------------------

def test_compilation_cache_wiring(monkeypatch, tmp_path):
    """The cache goes where JAX_COMPILATION_CACHE_DIR says; unset, to the
    one fixed directory inside the checkout — never a temp/pid path."""
    import os

    import jax

    from repro.launch import cache as cache_mod

    old_dir = jax.config.jax_compilation_cache_dir
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert cache_mod.DEFAULT_DIR == os.path.join(repo, ".jax_cache")
        assert cache_mod.enable_compilation_cache() == cache_mod.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == cache_mod.DEFAULT_DIR
        assert os.path.isdir(cache_mod.DEFAULT_DIR)
        # Idempotent, and the same fixed path on every call.
        assert cache_mod.enable_compilation_cache() == cache_mod.DEFAULT_DIR
        # An outside placement wins and is left exactly as given.
        outside = str(tmp_path / "cc")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        assert cache_mod.enable_compilation_cache() == outside
        assert jax.config.jax_compilation_cache_dir == outside
        # The suite's own switch (conftest.py) is never flipped back on.
        assert jax.config.jax_enable_compilation_cache is False
    finally:
        jax.config.update("jax_compilation_cache_dir", old_dir)


# ---------------------------------------------------------------------------
# Sparse transport: an explicit choice, never switched by platform.
# ---------------------------------------------------------------------------

def test_ragged_transport_gate():
    assert SparseDeltaExchange().ragged is False          # phase loop
    assert SparseDeltaExchange(ragged=True).ragged is True
    with pytest.raises(ValueError, match="ragged"):
        SparseDeltaExchange(ragged="auto")


def test_ragged_auto_falls_back_bit_identical():
    """The simulate view has no wire: both transport choices (and the
    default) give the same colors, rounds and measured bytes."""
    loop = color_distributed(PG, problem="d1", engine="simulate",
                             exchange=SparseDeltaExchange(ragged=False),
                             cache=False)
    rag = color_distributed(PG, problem="d1", engine="simulate",
                            exchange=SparseDeltaExchange(ragged=True),
                            cache=False)
    dflt = color_distributed(PG, problem="d1", engine="simulate",
                             exchange="sparse_delta", cache=False)
    for other in (rag, dflt):
        assert (other.colors == loop.colors).all()
        assert other.rounds == loop.rounds
        assert other.comm_bytes_total == loop.comm_bytes_total
