"""Mosaic/XLA:TPU compiles of the main-path kernels at real shard widths.

Nothing runs: each test lowers a kernel (or the ``simulate`` loop program)
for a described ``v5e:2x2`` topology with ``interpret=False`` and compiles
it with the TPU compiler, which refuses what Mosaic cannot lower and what
does not fit the chip's memory.  Shapes are handed in as
``ShapeDtypeStruct``s on the described device.  The topology is described
inside a fixture, never at import: only one process may load the TPU
library, and every test worker imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

I32 = jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Compiles for a described chip cannot be read back from the persistent
    # cache without one; keep them out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=I32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def compiled_text(fn, *args, **static):
    """Compile ``fn`` for the described chip; return the executable's HLO."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return jitted.lower(*args, **static).compile().as_text()


# One block part of hex:256,256,256 in 8 (d1), of hex:128,128,128 in 8 (d2).
D1 = dict(n=2_097_152, w=6, g=131_072)
D2 = dict(n=262_144, w=6, g=65_536)


def _round_args(spec, n, w, g, h2=None):
    n_tab = n + g + 1
    args = (spec((n, w)), spec((n,)), spec((g,)), spec((n_tab,)),
            spec((n_tab,)), spec((n,), jnp.bool_))
    two_hop = spec((n, h2)) if h2 else None
    return args, two_hop


@pytest.mark.parametrize("problem", ["d1", "d2"])
def test_fused_round_compiles(spec, problem):
    dims = D1 if problem == "d1" else D2
    h2 = dims["w"] ** 2 if problem == "d2" else None
    args, two_hop = _round_args(spec, **dims, h2=h2)
    txt = compiled_text(ops.fused_round, *args, two_hop_cidx=two_hop,
                        problem=problem, interpret=False)
    assert "tpu_custom_call" in txt


# The diagonals of an x-major hex mesh's index (hex:256³ slabs for d1,
# hex:128³ for d2: strides 1, nz, ny·nz), and a residual of one ghost
# face per side.
HEX_OFFSETS = {
    "d1": (-65536, -256, -1, 1, 256, 65536),
    "d2": tuple(sorted({a + b for a in (-16384, -128, -1, 0, 1, 128, 16384)
                        for b in (-16384, -128, -1, 0, 1, 128, 16384)})),
}


@pytest.mark.parametrize("problem", ["d1", "d2"])
def test_fused_round_compiles_on_diagonals(spec, problem):
    """The diagonal reads at real shard widths: no gather but the
    residual's, and the kernels still there."""
    from repro.kernels.diagonals import Diagonals

    dims = D1 if problem == "d1" else D2
    h2 = dims["w"] ** 2 if problem == "d2" else None
    args, two_hop = _round_args(spec, **dims, h2=h2)
    face = 65_536 if problem == "d1" else 16_384
    diag = Diagonals(HEX_OFFSETS[problem], spec((2 * face,)),
                     spec((2 * face,)))
    assert len(diag.offsets) == (6 if problem == "d1" else 25)
    txt = compiled_text(ops.fused_round, *args, two_hop_cidx=two_hop,
                        diag=diag, problem=problem, interpret=False)
    assert "tpu_custom_call" in txt


def test_fused_round_compiles_pd2_bip_shard(spec):
    """pd2 on one of 8 parts of a ``bip:`` Jacobian graph (real shard)."""
    from repro.core.distributed import build_device_state
    from repro.graph.partition import partition_graph
    from repro.launch.color import make_graph

    pg = partition_graph(make_graph("bip:50000,50000,4"), 8,
                         second_layer=True)
    st = build_device_state(pg, "pd2")
    n, w, g = pg.n_local, pg.ell_width, pg.n_ghost
    args, two_hop = _round_args(spec, n, w, g,
                                h2=st["two_hop_cidx"].shape[-1])
    txt = compiled_text(ops.fused_round, *args, two_hop_cidx=two_hop,
                        problem="pd2", interpret=False)
    assert "tpu_custom_call" in txt


CHAINED = {
    "vb_bit_assign": lambda s: (
        ops.vb_bit_assign,
        (s((D1["n"], 6)), s((D1["n"],)), s((D1["n"],)), s((D1["n"],)),
         s((D1["n"] + D1["g"] + 1,))), {}),
    "conflict_detect": lambda s: (
        ops.conflict_detect,
        (s((D1["n"], 6)),) + (s((D1["n"],)),) * 3
        + (s((D1["n"],), jnp.bool_),) + (s((D1["n"] + D1["g"] + 1,)),) * 3,
        {"n_loc": D1["n"]}),
    "d2_forbidden": lambda s: (
        ops.d2_forbidden,
        (s((D2["n"], 6)),) + (s((D2["n"],)),) * 3
        + (s((D2["n"] + D2["g"] + 1,)), s((D2["n"] + D2["g"] + 1, 6))), {}),
    "pair_scatter": lambda s: (
        ops.pair_scatter, (s((D1["g"],)), s((4096,)), s((4096,))), {}),
}


@pytest.mark.parametrize("kernel", sorted(CHAINED))
def test_chained_kernel_compiles(spec, kernel):
    fn, args, static = CHAINED[kernel](spec)
    txt = compiled_text(fn, *args, interpret=False, **static)
    assert "tpu_custom_call" in txt


def test_simulate_plan_loop_compiles(spec):
    """The ``pallas_fused`` plan's loop program: 8 parts vmapped."""
    import numpy as np

    from repro.core.backend import PallasFusedBackend
    from repro.core.plan import build_plan
    from repro.graph.partition import partition_graph
    from repro.launch.color import make_graph

    pg = partition_graph(make_graph("hex:32,32,32"), 8)
    plan = build_plan(pg, problem="d1", engine="simulate",
                      backend=PallasFusedBackend(interpret=False),
                      exchange="sparse_delta")
    shapes = lambda x: spec(np.shape(x), x.dtype)  # noqa: E731
    st = jax.tree_util.tree_map(shapes, plan.state)
    inputs = [shapes(np.asarray(x)) for x in plan.request_inputs()]
    txt = compiled_text(jax.jit(plan.raw_fn, donate_argnums=(1,)), st,
                        *inputs)
    assert "tpu_custom_call" in txt


def test_simulate_plan_loop_compiles_on_diagonals(spec):
    """The ``hex128-d1`` benchmark configuration's loop program, one part
    on one chip: its neighbor blocks are all read along the mesh's six
    diagonals, so no gather is left."""
    import numpy as np

    from repro.core.backend import PallasFusedBackend
    from repro.core.plan import build_plan
    from repro.graph.partition import partition_graph
    from repro.launch.color import make_graph

    pg = partition_graph(make_graph("hex:128,128,128"), 1)
    plan = build_plan(pg, problem="d1", engine="simulate",
                      backend=PallasFusedBackend(interpret=False),
                      exchange="sparse_delta")
    assert (plan.stats.diagonals, plan.stats.diagonal_share) == (6, 1.0)
    shapes = lambda x: spec(np.shape(x), x.dtype)  # noqa: E731
    st = jax.tree_util.tree_map(shapes, plan.state)
    inputs = [shapes(np.asarray(x)) for x in plan.request_inputs()]
    txt = compiled_text(jax.jit(plan.raw_fn, donate_argnums=(1,)), st,
                        *inputs)
    assert "tpu_custom_call" in txt
    assert not re.search(r"= \S+ gather\(", txt)


def test_shard_map_plan_loop_compiles_on_four_chips(topo):
    """The four-chip cell's loop program (``shard_map`` over a 2x2 mesh,
    ``sparse_delta``'s ``ppermute`` phases, the per-part iteration count
    as a sharded output, neighbor reads along the diagonals), at a small
    mesh."""
    import copy

    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as PS

    from repro.core.backend import PallasFusedBackend
    from repro.core.exchange import get_exchange
    from repro.core.distributed import neighbor_diagonals
    from repro.core.plan import (PlanStats, _build_shard_map_fn,
                                 cached_device_state)
    from repro.graph.partition import partition_graph
    from repro.launch.color import make_graph

    pg = partition_graph(make_graph("hex:64,32,32"), 4)
    strategy = copy.copy(get_exchange("sparse_delta"))
    st_np = dict(cached_device_state(pg, "d1"))
    active0 = st_np.pop("active0")
    # The diagonal reads, with the far ghosts' residual sharded per part.
    st_np["nbr_diag"], _ = neighbor_diagonals(st_np, "d1")
    assert st_np["nbr_diag"].res_pos.shape[0] == 4
    st_np.update(strategy.prepare(pg, st_np))
    mesh = Mesh(np.array(topo.devices), ("p",), axis_types=(AxisType.Auto,))
    _, fn = _build_shard_map_fn(
        strategy, PallasFusedBackend(interpret=False), problem="d1",
        recolor_degrees=True, max_rounds=64, n_parts=4, mesh=mesh,
        st_keys=list(st_np), stats=PlanStats())
    part, rep = NamedSharding(mesh, PS("p")), NamedSharding(mesh, PS())

    def shape(x, sharding=part):
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    colors0 = np.zeros((4, pg.n_local), np.int32)
    ghost0 = np.zeros(pg.ghost_gid.shape, np.int32)
    compiled = fn.lower(jax.tree_util.tree_map(shape, st_np),
                        shape(colors0), shape(ghost0), shape(active0),
                        shape(np.int32(0), rep)).compile()
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt and "collective-permute" in txt
    assert compiled.output_shardings[-1].spec == PS("p")   # iters per part
