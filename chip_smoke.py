"""On-chip smoke test of the coloring runtime.

Drives the main path once through the entry points a user calls —
partition → ``ColoringPlan`` → loop program → exchange → validators, plus
the serving frontend — at deployment sizes, with the ``pallas_fused``
backend compiled by Mosaic, and checks every result against the
``reference`` backend and the validators:

    python chip_smoke.py              # one chip (the default phases)
    python chip_smoke.py --chips 4    # four chips: shard_map exchanges only

One chip: d1 timestep coloring of ``hex:256,256,256`` (16.8M vertices,
50.1M edges, 8 block parts, ``simulate`` engine, ``sparse_delta``) plus 4
warm masked timesteps through ``ColoringService``; d2 coloring of
``hex:128,128,128``; 8 served requests over ``hex:128,128,128`` and
``grid:2048,2048`` through ``ColoringFrontend(max_batch=4)``.  Four chips:
4 parts of ``hex:256,256,256`` on the ``shard_map`` engine with
``all_gather``, ``sparse_delta`` (phase loop and ragged all-to-all) and
``hier_delta`` (node size 2), each bit-identical to the ``simulate``
engine on device 0.

Every input is generated in-process: the graphs by
``repro.graph.generators``, the request masks from ``--seed``.  The
script runs in one process that holds the chip for its whole life,
exits non-zero without a result when JAX finds no TPU (there is no CPU
fallback) or any phase fails, and prints one JSON object as its last
line.  Times are host wall-clock milliseconds around calls that return
host arrays (so the device work has finished); ``peak_bytes_in_use`` is
the device allocator's peak.
The compile cache goes to ``$JAX_COMPILATION_CACHE_DIR`` or
``<repo>/.jax_cache`` (``repro.launch.cache``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

# Deployment sizes of the phases (``repro.launch.color.make_graph`` specs).
D1_GRAPH = "hex:256,256,256"
D2_GRAPH = "hex:128,128,128"
SERVE_GRAPHS = ("hex:128,128,128", "grid:2048,2048")
FOUR_CHIP_GRAPH = "hex:256,256,256"
PARTS = 8


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def report(phase, **fields):
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


class CompileCounter:
    """Counts jax trace/lower/compile events (any of them is a recompile)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.n += 1


def same_result(a, b, what):
    import numpy as np

    check((a.colors == b.colors).all(), f"{what}: colors differ")
    check(a.rounds == b.rounds, f"{what}: rounds {a.rounds} != {b.rounds}")
    check(a.total_conflicts == b.total_conflicts, f"{what}: conflict totals differ")
    check(np.array_equal(a.comm_bytes_by_round, b.comm_bytes_by_round),
          f"{what}: comm_bytes_by_round differ")


def timed_plan(phase, dev, pg, *, backend, exchange, engine="simulate",
               problem="d1", **kw):
    """Build a plan and run it once: trace + compile, then execute.

    One run only — a coloring at these sizes takes tens of seconds, and the
    whole script must stay well inside its time limit; warm runs are the
    d1 timesteps.  ``run_ms`` is the run minus its compile.
    """
    from repro.core.plan import get_plan

    plan = get_plan(pg, problem=problem, backend=backend, exchange=exchange,
                    engine=engine, **kw)
    t0 = time.perf_counter()
    res = plan.run()
    run_ms = (time.perf_counter() - t0) * 1e3
    report(phase, backend=backend, exchange=getattr(exchange, "name", exchange),
           engine=plan.key.engine, device_kind=dev.device_kind,
           build_ms=f"{plan.stats.build_ms:.1f}",
           compile_ms=f"{plan.stats.compile_ms:.1f}",
           run_ms=f"{run_ms - plan.stats.compile_ms:.1f}",
           rounds=res.rounds, spec_iters=res.spec_iters, colors=res.n_colors,
           diagonals=plan.stats.diagonals,
           diagonal_share=f"{plan.stats.diagonal_share:.4f}",
           comm_bytes_total=res.comm_bytes_total,
           peak_bytes_in_use=peak_bytes(dev))
    return plan, res


def built(phase, dev, spec, parts, *, second_layer=False):
    """Generate ``spec`` (``repro.graph.generators``) and partition it."""
    from repro.graph.partition import partition_graph
    from repro.launch.color import make_graph

    t0 = time.perf_counter()
    g = make_graph(spec)
    pg = partition_graph(g, parts, second_layer=second_layer)
    report(phase, graph=spec, n=g.n, edges=g.num_edges, parts=parts,
           n_local=pg.n_local, n_ghost=pg.n_ghost,
           graph_build_ms=f"{(time.perf_counter() - t0) * 1e3:.1f}",
           device_kind=dev.device_kind)
    return g, pg


def has_kernel(plan):
    return "tpu_custom_call" in plan.executable.as_text()


def phase_d1(dev, seed):
    import numpy as np

    from repro.core.validate import is_proper_d1
    from repro.serve.coloring import ColoringService

    g, pg = built("d1", dev, D1_GRAPH, PARTS)
    plan, res = timed_plan("d1", dev, pg, backend="pallas_fused",
                           exchange="sparse_delta")
    check(has_kernel(plan), "d1: no tpu_custom_call in the pallas_fused "
                              "loop program")
    _, ref = timed_plan("d1", dev, pg, backend="reference",
                        exchange="sparse_delta")
    same_result(res, ref, "d1 pallas_fused vs reference")
    check(res.converged and is_proper_d1(g, res.colors), "d1: not proper")
    report("d1", proper=True, bit_identical_to_reference=True,
           tpu_custom_call=True)

    # Timesteps: recolor a seeded 10% subset each step from the previous
    # coloring, through the service's warm path.
    svc = ColoringService(pg, backend="pallas_fused", exchange="sparse_delta",
                          engine="simulate")
    check(svc.plan is plan, "d1: the service did not reuse the cached plan")
    counter = CompileCounter()
    traces, compiles = plan.stats.traces, plan.stats.compiles
    rng = np.random.default_rng(seed)
    prev = res.colors
    for step in range(4):
        mask = rng.random(g.n) < 0.1
        t0 = time.perf_counter()
        out = svc.submit(color_mask=mask, colors0=np.where(mask, 0, prev))
        ms = (time.perf_counter() - t0) * 1e3
        check(is_proper_d1(g, out.colors), f"d1 timestep {step}: not proper")
        check((out.colors[~mask] == prev[~mask]).all(),
              f"d1 timestep {step}: a frozen vertex changed color")
        prev = out.colors
        report("d1-timestep", step=step, recolored=int(mask.sum()),
               rounds=out.rounds, run_ms=f"{ms:.1f}")
    check(counter.n == 0 and plan.stats.traces == traces
          and plan.stats.compiles == compiles and svc.stats.cold_runs == 0,
          f"d1 timesteps recompiled ({counter.n} compile events)")
    report("d1-timestep", warm_steps=4, recompiles=0,
           peak_bytes_in_use=peak_bytes(dev))


def phase_d2(dev, seed):
    from repro.core.validate import is_proper_d2

    g, pg = built("d2", dev, D2_GRAPH, PARTS, second_layer=True)
    plan, res = timed_plan("d2", dev, pg, problem="d2",
                           backend="pallas_fused", exchange="sparse_delta")
    check(has_kernel(plan), "d2: no tpu_custom_call in the loop program")
    _, ref = timed_plan("d2", dev, pg, problem="d2", backend="reference",
                        exchange="sparse_delta")
    same_result(res, ref, "d2 pallas_fused vs reference")
    check(res.converged and is_proper_d2(g, res.colors), "d2: not proper")
    report("d2", proper=True, bit_identical_to_reference=True)


def phase_serve(dev, seed):
    import numpy as np

    from repro.core.plan import get_plan
    from repro.core.validate import is_proper_d1
    from repro.serve.coloring import ColoringFrontend, ColoringRequest

    graphs = [built("serve", dev, s, PARTS) for s in SERVE_GRAPHS]
    cfg = dict(backend="pallas_fused", exchange="sparse_delta",
               engine="simulate")
    fe = ColoringFrontend(max_batch=4, **cfg)
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(8):
        g, pg = graphs[i % len(graphs)]
        reqs.append((g, pg, ColoringRequest(
            color_mask=rng.random(g.n) < rng.uniform(0.3, 0.9))))
    t0 = time.perf_counter()
    results = fe.run_stream([(pg, r) for _, pg, r in reqs])
    stream_ms = (time.perf_counter() - t0) * 1e3
    for i, ((g, pg, req), got) in enumerate(zip(reqs, results)):
        solo = get_plan(pg, **cfg).run(color_mask=req.color_mask)
        same_result(got, solo, f"served request {i} vs solo plan.run")
        mask = req.color_mask
        check(is_proper_d1(g, got.colors, require_complete=False)
              and (got.colors[mask] > 0).all()
              and (got.colors[~mask] == 0).all(),
              f"served request {i}: not a proper coloring of its mask")
    st = fe.stats
    report("serve", requests=len(reqs), stream_ms=f"{stream_ms:.1f}",
           compile_ms=f"{st.cold_ms:.1f}", cold_programs=st.cold_runs,
           batches=st.batches, refills=st.refills,
           bit_identical_to_solo=True, proper=True,
           peak_bytes_in_use=peak_bytes(dev))


def phase_four_chips(dev, seed):
    """shard_map over 4 chips vs the simulate engine on device 0."""
    from repro.core.exchange import HierDeltaExchange, SparseDeltaExchange
    from repro.core.validate import is_proper_d1

    g, pg = built("4chip", dev, FOUR_CHIP_GRAPH, 4)
    sims = {}
    for name in ("all_gather", "sparse_delta", "hier_delta"):
        _, sims[name] = timed_plan("4chip-simulate", dev, pg,
                                   backend="pallas_fused", exchange=name)
    legs = (("all_gather", "all_gather"),
            ("sparse_delta", "sparse_delta"),
            ("hier_delta", HierDeltaExchange(node_size=2)),
            ("sparse_delta-ragged", SparseDeltaExchange(ragged=True)))
    for label, exchange in legs:
        plan, res = timed_plan(f"4chip-{label}", dev, pg,
                               backend="pallas_fused", exchange=exchange,
                               engine="shard_map")
        mesh_devs = {d.id for d in plan.mesh.devices.flat}
        check(len(mesh_devs) == 4 and plan.mesh.devices.size == 4,
              f"{label}: mesh spans {sorted(mesh_devs)}")
        if label.endswith("ragged"):
            check("ragged-all-to-all" in plan.executable.as_text(),
                  "ragged leg has no ragged-all-to-all in its program")
        same_result(res, sims[label.split("-")[0]],
                    f"{label} shard_map vs simulate")
        check(is_proper_d1(g, res.colors), f"{label}: not proper")
        report(f"4chip-{label}", mesh_devices=sorted(mesh_devs),
               bit_identical_to_simulate=True, proper=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip shard_map path")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print("chip_smoke: the repro package (src/repro) is not next to "
              "this script; run it from a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU found ({e})", file=sys.stderr)
        return 3
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found: JAX platform is "
              f"{devs[0].platform!r}; this smoke runs only on the chip",
              file=sys.stderr)
        return 3
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
              f"device(s)", file=sys.stderr)
        return 3
    from repro.kernels import default_interpret
    from repro.launch.cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    dev = devs[0]
    report("device", platform=dev.platform, kind=dev.device_kind,
           count=len(devs), jax=jax.__version__, compile_cache=cache_dir)
    t0 = time.perf_counter()
    try:
        check(default_interpret() is False, "kernels would run interpreted")
        if args.chips == 4:
            phase_four_chips(dev, args.seed)
        else:
            phase_d1(dev, args.seed)
            phase_d2(dev, args.seed)
            phase_serve(dev, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    report("done", total_ms=f"{(time.perf_counter() - t0) * 1e3:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
