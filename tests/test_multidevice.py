"""Multi-device integration tests (8 host CPU devices via subprocess).

The shard_map engine and the mesh-sharded train path need >1 device;
XLA locks the device count at first init, so these run in a subprocess
with XLA_FLAGS set (smoke tests elsewhere keep seeing 1 device).
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    return res.stdout


def test_shard_map_matches_simulate_and_halo():
    out = run_py("""
import numpy as np
from repro.graph.generators import hex_mesh, rmat
from repro.graph.partition import partition_graph
from repro.core.distributed import color_distributed
from repro.core.validate import is_proper_d1, is_proper_d2

g = hex_mesh(24, 8, 8)
pg = partition_graph(g, 8, second_layer=True)
for problem in ("d1", "d1_2gl", "d2"):
    sim = color_distributed(pg, problem=problem, engine="simulate")
    smap = color_distributed(pg, problem=problem, engine="shard_map")
    assert sim.converged and smap.converged, problem
    assert (sim.colors == smap.colors).all(), problem
    assert sim.rounds == smap.rounds, problem
    assert sim.spec_iters == smap.spec_iters > 0, problem
halo = color_distributed(pg, problem="d1", engine="shard_map", exchange="halo")
ag = color_distributed(pg, problem="d1", engine="shard_map")
assert (halo.colors == ag.colors).all()
assert halo.comm_bytes_per_round < ag.comm_bytes_per_round
s = rmat(8, 6, seed=5)
pgs = partition_graph(s, 8, strategy="edge_balanced", second_layer=True)
a = color_distributed(pgs, problem="pd2", engine="simulate")
b = color_distributed(pgs, problem="pd2", engine="shard_map")
assert (a.colors == b.colors).all()
print("OK")
""")
    assert "OK" in out


def test_backend_exchange_matrix_shard_map():
    """Backends × exchanges through the shard_map engine: every combination
    must produce the identical coloring in the identical round count, and
    ``delta``'s measured per-round payload must drop strictly below
    ``all_gather``'s after round 1 (ISSUE-1 acceptance)."""
    out = run_py("""
import numpy as np
from repro.graph.generators import hex_mesh
from repro.graph.partition import partition_graph
from repro.core.distributed import color_distributed
from repro.core.validate import is_proper_d1, is_proper_d2

g = hex_mesh(24, 8, 8)
pg = partition_graph(g, 8, second_layer=True)   # block slabs -> halo-legal
ref = color_distributed(pg, problem="d1", engine="simulate")
for backend in ("reference", "pallas", "pallas_fused"):
    for exchange in ("all_gather", "halo", "delta", "sparse_delta"):
        res = color_distributed(pg, problem="d1", engine="shard_map",
                                backend=backend, exchange=exchange)
        assert res.converged, (backend, exchange)
        assert (res.colors == ref.colors).all(), (backend, exchange)
        assert res.rounds == ref.rounds, (backend, exchange)
        assert res.spec_iters == ref.spec_iters, (backend, exchange)
assert is_proper_d1(g, ref.colors)

# Measured accounting: delta < all_gather per round after round 1, and
# sparse_delta's pair payload (the bytes the ppermute rounds actually
# move) beats all_gather in total and matches the simulate engine exactly.
ag = color_distributed(pg, problem="d1", engine="shard_map")
de = color_distributed(pg, problem="d1", engine="shard_map", exchange="delta")
assert ag.rounds >= 1
assert len(de.comm_bytes_by_round) == de.rounds + 1
assert all(d < a for d, a in zip(de.comm_bytes_by_round[1:],
                                 ag.comm_bytes_by_round[1:]))
assert de.comm_bytes_total < ag.comm_bytes_total
sd = color_distributed(pg, problem="d1", engine="shard_map",
                       exchange="sparse_delta")
sd_sim = color_distributed(pg, problem="d1", engine="simulate",
                           exchange="sparse_delta")
assert (sd.colors == ref.colors).all() and sd.rounds == ref.rounds
assert sd.comm_bytes_total < ag.comm_bytes_total
assert list(sd.comm_bytes_by_round) == list(sd_sim.comm_bytes_by_round)

# Pallas backends round-trip d2/pd2 through shard_map + sparse a2a too
# (chained kernels AND the fused round).
for problem in ("d2", "pd2"):
    p_ref = color_distributed(pg, problem=problem, engine="simulate")
    for backend in ("pallas", "pallas_fused"):
        p_pal = color_distributed(pg, problem=problem, engine="shard_map",
                                  backend=backend, exchange="sparse_delta")
        assert (p_ref.colors == p_pal.colors).all(), (problem, backend)
        assert p_ref.rounds == p_pal.rounds, (problem, backend)
        if problem == "d2":
            assert is_proper_d2(g, p_pal.colors)
print("OK")
""")
    assert "OK" in out


def test_hier_exchange_shard_map_matches_simulate():
    """ISSUE-8 acceptance: the four-stage hier_delta device path (intra
    pairs, member→leader aggregation, one leader→leader hop per routed
    node edge, leader broadcast) on a real 4-device mesh is bit-identical
    to the simulate engine AND to all_gather — colors, rounds, totals,
    and the per-round [intra, inter] byte split — and the packed-wire
    byte ordering hier < sparse < all_gather holds on-device."""
    out = run_py("""
import numpy as np
from repro.graph.generators import hex_mesh
from repro.graph.partition import two_level_partition
from repro.core.distributed import color_distributed
from repro.core.exchange import SparseDeltaExchange
from repro.core.validate import is_proper_d1, is_proper_d2

g = hex_mesh(12, 6, 6)
pg = two_level_partition(g, 2, 2, second_layer=True)
for problem in ("d1", "d2", "pd2"):
    ag = color_distributed(pg, problem=problem, engine="shard_map")
    hd = color_distributed(pg, problem=problem, engine="shard_map",
                           exchange="hier_delta")
    sim = color_distributed(pg, problem=problem, engine="simulate",
                            exchange="hier_delta", cache=False)
    assert (hd.colors == ag.colors).all(), problem
    assert hd.rounds == ag.rounds, problem
    assert (hd.colors == sim.colors).all(), problem
    assert hd.comm_bytes_total == sim.comm_bytes_total, problem
    assert (hd.comm_bytes_by_level == sim.comm_bytes_by_level).all(), problem
    assert hd.comm_bytes_intra > 0 and hd.comm_bytes_inter > 0, problem
    if problem == "d1":
        assert is_proper_d1(g, hd.colors)
    elif problem == "d2":
        assert is_proper_d2(g, hd.colors)

sd = color_distributed(pg, problem="d1", engine="shard_map",
                       exchange="sparse_delta")
ag = color_distributed(pg, problem="d1", engine="shard_map")
hd = color_distributed(pg, problem="d1", engine="shard_map",
                       exchange="hier_delta")
assert hd.comm_bytes_total < sd.comm_bytes_total < ag.comm_bytes_total

# Transport is an explicit choice: the default sparse_delta program is the
# ppermute phase loop; ragged=True lowers to one ragged all-to-all (which
# XLA:CPU cannot run, so only its lowering is checked here — the chip
# smoke runs it).
from repro.core.plan import build_plan
import jax
def lowered(exchange):
    plan = build_plan(pg, problem="d1", engine="shard_map",
                      exchange=exchange)
    c0, g0, a0, seed = plan.request_inputs()
    return plan._fn.lower(plan.state, c0, g0, a0, seed).as_text()
loop_txt, rag_txt = lowered("sparse_delta"), lowered(
    SparseDeltaExchange(ragged=True))
assert "ragged_all_to_all" not in loop_txt and "collective_permute" in loop_txt
assert "ragged_all_to_all" in rag_txt
print("OK")
""", devices=4)
    assert "OK" in out


def test_ragged_transport_emulated_matches_phase_loop():
    """``SparseDeltaExchange(ragged=True)`` on a 4-device mesh, with
    ``lax.ragged_all_to_all`` replaced by an all_gather emulation of its
    documented semantics (XLA:CPU cannot run the real collective): the
    packed rows must carry every counted pair inside the trimmed
    ``send_sizes`` prefix, so colors, rounds and bytes equal the phase
    loop's."""
    out = run_py("""
import jax
import jax.numpy as jnp
from repro.graph.generators import hex_mesh
from repro.graph.partition import partition_graph
from repro.core.distributed import color_distributed
from repro.core.exchange import SparseDeltaExchange


def emulated(operand, output, input_offsets, send_sizes, output_offsets,
             recv_sizes, *, axis_name):
    # One slice per peer: source j's slice for this device starts at its
    # input_offsets[me] and lands at its output_offsets[me] (sender side).
    me = jax.lax.axis_index(axis_name)
    ops = jax.lax.all_gather(operand, axis_name)
    col = lambda x: jax.lax.all_gather(x, axis_name)[:, me]
    in_off, size, out_off = col(input_offsets), col(send_sizes), col(
        output_offsets)
    k = jnp.arange(output.shape[0])
    for j in range(ops.shape[0]):
        rel = k - out_off[j]
        hit = (rel >= 0) & (rel < size[j])
        src = jnp.clip(in_off[j] + rel, 0, ops.shape[1] - 1)
        output = jnp.where(hit, ops[j][src], output)
    return output


jax.lax.ragged_all_to_all = emulated
g = hex_mesh(12, 8, 8)
pg = partition_graph(g, 4, second_layer=True)
for problem in ("d1", "d2", "pd2"):
    loop = color_distributed(pg, problem=problem, engine="shard_map",
                             exchange=SparseDeltaExchange(), cache=False)
    rag = color_distributed(pg, problem=problem, engine="shard_map",
                            exchange=SparseDeltaExchange(ragged=True),
                            cache=False)
    assert loop.rounds >= 1, problem
    assert (rag.colors == loop.colors).all(), problem
    assert rag.rounds == loop.rounds, problem
    assert (list(rag.comm_bytes_by_round)
            == list(loop.comm_bytes_by_round)), problem
print("OK")
""", devices=4)
    assert "OK" in out


def test_plan_warm_path_shard_map():
    """Compile-once plans through the shard_map engine: warm runs are
    bit-identical to the simulate engine and to cold calls, retrace
    nothing, and the recoloring service's sequential warm path works."""
    out = run_py("""
import numpy as np
from repro.graph.generators import hex_mesh
from repro.graph.partition import partition_graph
from repro.core.distributed import color_distributed
from repro.core.plan import PlanCache, get_plan
from repro.core import plan as plan_mod
from repro.serve.coloring import ColoringService
from repro.core.validate import is_proper_d1

g = hex_mesh(24, 8, 8)
pg = partition_graph(g, 8, second_layer=True)
cache = PlanCache()
combos = (("d1", "all_gather"), ("d1", "sparse_delta"), ("d2", "delta"))
plans, firsts, sims = {}, {}, {}
for problem, exchange in combos:
    plan = get_plan(pg, problem=problem, exchange=exchange,
                    engine="shard_map", cache=cache)
    assert plan.key.engine == "shard_map"
    plans[problem, exchange] = plan
    firsts[problem, exchange] = plan.run()
    sims[problem, exchange] = color_distributed(
        pg, problem=problem, exchange=exchange, engine="simulate",
        cache=False)
assert cache.misses == 3 and len(cache) == 3

plan_mod.build_device_state = None       # any warm rebuild would now crash
for combo, plan in plans.items():
    traces = plan.stats.traces
    warm = plan.run()
    assert plan.stats.traces == traces, combo   # zero retraces
    assert (firsts[combo].colors == warm.colors).all()
    sim = sims[combo]
    assert (warm.colors == sim.colors).all(), combo
    assert warm.rounds == sim.rounds
    assert list(warm.comm_bytes_by_round) == list(sim.comm_bytes_by_round)

# The service's shard_map batch path runs through the mesh slot
# engine: one persistent shard_map program per bucket, harvest/refill
# scheduled from the host.
svc = ColoringService(pg, problem="d1", engine="shard_map", cache=cache)
assert svc.plan.raw_step is not None
outs = svc.run_batch([{}, {"color_mask": np.arange(g.n) % 2 == 0}, {}])
assert (outs[0].colors == outs[2].colors).all()
assert is_proper_d1(g, outs[0].colors)
assert svc.stats.requests == 3
assert svc.buckets == [4]
print("OK")
""")
    assert "OK" in out


def test_frontend_stream_shard_map_slot_engine():
    """The tentpole pin (ISSUE-7 acceptance): the cross-topology frontend
    on a 4-device mesh batches requests through the persistent shard_map
    slot program — finished slots are harvested and refilled mid-wave
    (``stats.refills > 0``) and every per-request result is bit-identical
    to its solo ``plan.run`` on the same engine *and* to the simulate
    engine (colors, rounds, and measured per-round comm bytes)."""
    out = run_py("""
import numpy as np
from repro.graph.generators import hex_mesh, rmat
from repro.graph.partition import partition_graph
from repro.core.plan import PlanCache, get_plan
from repro.serve import ColoringFrontend, ColoringRequest
from repro.core.validate import is_proper_d1

g1 = hex_mesh(12, 6, 6)
g2 = rmat(8, 6, seed=5)
pg1 = partition_graph(g1, 4, second_layer=True)
pg2 = partition_graph(g2, 4, strategy="edge_balanced", second_layer=True)
cache = PlanCache()
fe = ColoringFrontend(engine="shard_map", cache=cache, max_batch=2)
pairs = []
for i in range(6):
    for pg in (pg1, pg2):
        req = (ColoringRequest() if i % 3 != 2 else
               ColoringRequest(color_mask=np.arange(pg.n_global) % 2 == 0))
        pairs.append((pg, req))
results = fe.run_stream(pairs)
for group in fe._groups.values():
    assert group.plan.key.engine == "shard_map"
    assert group.plan.raw_step is not None          # mesh slot program
assert fe.stats.refills > 0                         # harvest/refill mid-wave
assert fe.stats.batches >= 2
assert fe.stats.requests == fe.stats.warm_requests == len(pairs)
oracle = PlanCache()
for (pg, req), res in zip(pairs, results):
    solo = get_plan(pg, engine="shard_map", cache=cache).run(
        **req.plan_inputs())
    sim = get_plan(pg, engine="simulate", cache=oracle).run(
        **req.plan_inputs())
    assert (res.colors == solo.colors).all()
    assert (res.colors == sim.colors).all()
    assert res.rounds == solo.rounds == sim.rounds
    assert res.spec_iters == solo.spec_iters == sim.spec_iters
    assert list(res.comm_bytes_by_round) == list(sim.comm_bytes_by_round)
assert is_proper_d1(g1, results[0].colors)
print("OK")
""", devices=4)
    assert "OK" in out


def test_frontend_stream_shard_map_with_reduction():
    """reduce_passes>0 on the shard_map engine: the batched reduction's
    supersteps ride the mesh slot engine (``run_many=group.execute``),
    and results stay bit-identical to solo simulate-engine reduction."""
    out = run_py("""
import numpy as np
from repro.graph.generators import hex_mesh, rmat
from repro.graph.partition import partition_graph
from repro.core.plan import PlanCache, get_plan
from repro.core.reduce import reduce_colors
from repro.serve import ColoringFrontend
from repro.core.validate import is_proper_d1

g1 = hex_mesh(24, 8, 8)
g2 = rmat(8, 6, seed=5)
pg1 = partition_graph(g1, 8, second_layer=True)
pg2 = partition_graph(g2, 8, strategy="edge_balanced", second_layer=True)
cache = PlanCache()
fe = ColoringFrontend(engine="shard_map", cache=cache, reduce_passes=1)
pairs = []
for _ in range(2):
    for pg in (pg1, pg2):
        pairs.append((pg, {}))
        pairs.append((pg, {"color_mask": np.arange(pg.n_global) % 2 == 0}))
results = fe.run_stream(pairs)
oracle = PlanCache()
for (pg, req), res in zip(pairs, results):
    plan = get_plan(pg, engine="simulate", cache=oracle)
    base = plan.run(**req)
    red = reduce_colors(plan, base, passes=1, cache=oracle,
                        color_mask=req.get("color_mask"))
    solo = red.merged_result(base)
    assert (res.colors == solo.colors).all()
    assert res.n_colors == solo.n_colors
    assert res.rounds == solo.rounds
assert fe.stats.requests == len(pairs)
assert fe.stats.warm_requests == len(pairs)
assert is_proper_d1(g1, results[0].colors)
print("OK")
""")
    assert "OK" in out


def test_reduce_colors_shard_map():
    """The color-reduction subsystem through the shard_map engine: never
    more colors, proper, conflict-free supersteps, and bit-identical to
    the simulate engine (both rebuild the same classes in the same
    order against the same frozen ghosts)."""
    out = run_py("""
import numpy as np
from repro.graph.generators import hex_mesh
from repro.graph.partition import partition_graph
from repro.core.plan import PlanCache, get_plan
from repro.core.reduce import reduce_colors
from repro.core.validate import is_proper_d1, is_proper_d2

g = hex_mesh(24, 8, 8)
pg = partition_graph(g, 8, second_layer=True)
cache = PlanCache()
for problem, check in (("d1", is_proper_d1), ("d2", is_proper_d2)):
    plan = get_plan(pg, problem=problem, engine="shard_map", cache=cache)
    assert plan.key.engine == "shard_map"
    res = plan.run()
    red = reduce_colors(plan, res, passes=2, cache=cache)
    assert red.n_colors <= res.n_colors, problem
    assert check(g, red.colors), problem
    assert all(r == 0 for r in red.rounds_by_pass), problem   # conflict-free
    sim_plan = get_plan(pg, problem=problem, engine="simulate", cache=cache)
    sim_red = reduce_colors(sim_plan, sim_plan.run(), passes=2, cache=cache)
    assert (red.colors == sim_red.colors).all(), problem
    assert red.colors_by_pass == sim_red.colors_by_pass, problem
print("OK")
""")
    assert "OK" in out


def test_sharded_train_two_axis_mesh():
    out = run_py("""
import jax
from repro.configs import get_smoke
from repro.launch.mesh import make_mesh
from repro.launch.train import train_loop

mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_smoke("tinyllama_1_1b")
params, hist = train_loop(cfg, steps=6, global_batch=4, seq_len=64, mesh=mesh)
assert hist[-1]["loss"] < hist[0]["loss"]
print("OK", hist[0]["loss"], "->", hist[-1]["loss"])
""")
    assert "OK" in out


def test_elastic_restore_onto_smaller_mesh():
    """Checkpoint on 8 devices, restore+continue on 4 (node-failure drill)."""
    out = run_py("""
import tempfile, jax
from repro.configs import get_smoke
from repro.launch.mesh import make_mesh
from repro.launch.train import train_loop

cfg = get_smoke("stablelm_1_6b")
d = tempfile.mkdtemp()
mesh8 = make_mesh((2, 4), ("data", "model"))
_, h1 = train_loop(cfg, steps=4, global_batch=4, seq_len=64, mesh=mesh8,
                   ckpt_dir=d, ckpt_every=2)
# "Lose" half the devices: restore on a 4-device mesh and keep training.
mesh4 = make_mesh((2, 2), ("data", "model"))
_, h2 = train_loop(cfg, steps=6, global_batch=4, seq_len=64, mesh=mesh4,
                   ckpt_dir=d, ckpt_every=100)
assert h2[0]["step"] == 4   # resumed, not restarted
print("OK")
""")
    assert "OK" in out


def test_mini_dryrun_multipod_axes():
    """3-axis (pod, data, model) mesh lowers + compiles a smoke config."""
    out = run_py("""
import jax
from repro.configs import get_smoke
from repro.launch.mesh import make_mesh
from repro.launch.specs import step_and_specs
from repro.models.sharding import use_policy
import repro.launch.specs as S

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
# monkeypatch a smoke config + small shape into the cell builder
import repro.configs as C
cfg = get_smoke("qwen3_moe_30b_a3b")
orig = C.SHAPES["train_4k"]
C.SHAPES["train_4k"] = type(orig)("train_4k", 64, 8, "train")
import repro.launch.specs as SP
SP.SHAPES = C.SHAPES
old_get = SP.get_config
SP.get_config = lambda a: cfg
fn, sds, shardings, policy = step_and_specs("qwen3_moe_30b_a3b", "train_4k", mesh)
with use_policy(policy):
    compiled = jax.jit(fn, in_shardings=shardings).lower(*sds).compile()
ca = compiled.cost_analysis()
if isinstance(ca, list):   # jax<=0.4.x returns [dict], newer returns dict
    ca = ca[0]
print("OK", ca.get("flops", 0) > 0)
""")
    assert "OK True" in out


def test_shard_map_moe_matches_gspmd():
    """§Perf cells A/C: the explicit-collective MoE must be numerically
    equivalent to the GSPMD path (dropless smoke config)."""
    out = run_py("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke
from repro.launch.mesh import make_mesh, dp_axes
from repro.models.sharding import make_activation_policy, use_policy, params_sharding_tree
from repro.models.transformer import forward, init_params

mesh = make_mesh((2, 4), ("data", "model"))
base = get_smoke("qwen3_moe_30b_a3b")
key = jax.random.PRNGKey(0)
toks = jax.random.randint(key, (4, 16), 0, base.vocab_size)
outs = {}
for impl in ("gspmd", "shard_map"):
    cfg = dataclasses.replace(base, moe_impl=impl)
    params = init_params(cfg, key)
    policy = make_activation_policy(mesh, cfg, dp=dp_axes(mesh))
    with use_policy(policy):
        logits, aux = jax.jit(lambda p, t: forward(p, cfg, t))(params, toks)
    outs[impl] = np.asarray(logits)
np.testing.assert_allclose(outs["gspmd"], outs["shard_map"], rtol=2e-4, atol=2e-4)
print("OK")
""")
    assert "OK" in out
