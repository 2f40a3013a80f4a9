"""Distributed-coloring driver (the paper's workload as a CLI).

  PYTHONPATH=src python -m repro.launch.color --graph hex:24,24,24 \
      --parts 8 --problem d1 [--no-recolor-degrees] [--backend reference] \
      [--exchange halo|delta|sparse_delta] [--baseline] [--repeat 16]

Graph specs: hex:NX,NY,NZ | grid:NX,NY | rmat:SCALE,EF | rgg:N,R |
myc:K | er:N,DEG | bip:ROWS,COLS,NNZ

--backend selects the local-compute backend (``pallas_fused``, the
default — one jitted round on dense Mosaic kernels, interpret mode on the
CPU backend —, the chained ``pallas`` kernels, or the ``reference`` jnp
path); --exchange the ghost-exchange strategy, where ``delta``
ships only boundary colors that changed since the previous round and
``sparse_delta`` routes them as count-prefixed (slot, color) pairs over
edge-colored ppermute phases — for both, the reported comm/round is the
measured payload.

--repeat N is the timestep mode (the paper's motivating workload): the
same topology is recolored N times through the compile-once plan cache
(``repro.serve.ColoringService``); the cold first request (host state
build + trace + compile) and the warm per-timestep latency are reported
separately.

--stream "spec|spec|..." is the mixed-topology replay mode: --requests N
requests are enqueued round-robin over the listed graph specs and served
by the continuous-batching ``ColoringFrontend`` (plans routed per
topology through the plan cache, finished vmap slots refilled from the
queue).  The stream is replayed twice — the first pass pays every
topology's plan build + compile, the second runs entirely warm — and
sustained requests/sec are reported for both.

--reduce-passes P runs up to P iterative color-reduction passes
(``repro.core.reduce``) over the finished coloring, rebuilding its color
classes in --reduce-order; the colors-vs-passes trajectory and the
measured per-pass comm payload are printed, and the final (reduced)
coloring is validated.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.core.backend import list_backends
from repro.core.baseline import color_baseline
from repro.core.distributed import color_distributed
from repro.core.exchange import list_exchanges
from repro.core.plan import get_plan
from repro.core.reduce import list_orders
from repro.core.validate import is_proper_d1, is_proper_d2, is_proper_pd2
from repro.graph import generators as gen
from repro.graph.partition import partition_graph, two_level_partition
from repro.launch.cache import enable_compilation_cache
from repro.launch.mesh import factor_parts


def make_graph(spec: str):
    kind, _, rest = spec.partition(":")
    args = [float(x) if "." in x else int(x) for x in rest.split(",")] if rest else []
    return {
        "hex": lambda: gen.hex_mesh(*args),
        "grid": lambda: gen.grid_2d(*args),
        "rmat": lambda: gen.rmat(*args),
        "rgg": lambda: gen.random_geometric(args[0], args[1]),
        "myc": lambda: gen.mycielskian(*args),
        "er": lambda: gen.erdos_renyi(args[0], args[1]),
        "bip": lambda: gen.bipartite_random(*args),
    }[kind]()


VALIDATORS = {
    "d1": is_proper_d1, "d1_2gl": is_proper_d1,
    "d2": is_proper_d2, "pd2": is_proper_pd2,
}


def make_partition(g, args):
    """Flat or two-level partition per ``--node-size`` (0 = flat)."""
    needs_l2 = args.problem != "d1"
    if args.node_size:
        n_nodes, node_size = factor_parts(args.parts, args.node_size)
        return two_level_partition(g, n_nodes, node_size,
                                   strategy=args.strategy,
                                   second_layer=needs_l2)
    return partition_graph(g, args.parts, strategy=args.strategy,
                           second_layer=needs_l2)


def run_stream(args) -> None:
    """Mixed-topology replay through the continuous-batching frontend."""
    from repro.serve import ColoringFrontend, ColoringRequest

    specs = [s for s in args.stream.split("|") if s]
    graphs = [make_graph(s) for s in specs]
    pgs = []
    for g, spec in zip(graphs, specs):
        pg = make_partition(g, args)
        pgs.append(pg)
        print(f"[color] topology {spec}: n={g.n} m={g.num_edges} "
              f"sig={pg.signature[:12]}")
    fe = ColoringFrontend(
        problem=args.problem, recolor_degrees=not args.no_recolor_degrees,
        backend=args.backend, exchange=args.exchange, engine=args.engine,
        reduce_passes=args.reduce_passes, reduce_order=args.reduce_order)
    pairs = [(pgs[i % len(pgs)], ColoringRequest())
             for i in range(args.requests)]

    t0 = time.time()
    cold_results = fe.run_stream(pairs)
    cold_s = time.time() - t0
    t0 = time.time()
    results = fe.run_stream(pairs)              # warm replay
    warm_s = time.time() - t0
    first_for_pg = {}
    for (pg, _), cold, warm in zip(pairs, cold_results, results):
        g = graphs[pgs.index(pg)]
        first_for_pg.setdefault(id(pg), warm)
        if not VALIDATORS[args.problem](g, warm.colors):
            raise SystemExit(f"improper coloring for {g.name}")
        if (cold.colors != warm.colors).any():
            raise SystemExit(f"warm replay diverged for {g.name}")
    s = fe.stats
    print(f"[color] stream topologies={len(pgs)} requests={args.requests} "
          f"req/s cold={args.requests / cold_s:.1f} "
          f"warm={args.requests / warm_s:.1f} "
          f"(compile {s.cold_ms:.0f}ms over {s.cold_runs} programs; "
          f"warm {s.warm_ms_mean:.2f}ms/request; refills={s.refills})")
    # Only topologies the stream actually reached (requests may be fewer).
    for spec, pg in zip(specs[:args.requests], pgs):
        res = first_for_pg[id(pg)]
        print(f"[color]   {spec}: colors={res.n_colors} rounds={res.rounds} "
              f"comm_total={res.comm_bytes_total}B")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph")
    ap.add_argument("--stream", metavar="SPEC|SPEC|...",
                    help="mixed-topology replay: serve --requests N "
                         "round-robin over these graph specs through the "
                         "continuous-batching frontend")
    ap.add_argument("--requests", type=int, default=16,
                    help="stream mode: total requests to replay")
    ap.add_argument("--parts", type=int, default=8)
    ap.add_argument("--problem", default="d1",
                    choices=["d1", "d1_2gl", "d2", "pd2"])
    ap.add_argument("--strategy", default="block",
                    choices=["block", "edge_balanced", "random"])
    ap.add_argument("--backend", default="pallas_fused",
                    choices=list_backends())
    ap.add_argument("--exchange", default="all_gather",
                    choices=list_exchanges())
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "shard_map", "simulate"])
    ap.add_argument("--node-size", type=int, default=0, metavar="L",
                    help="two-level partition: L parts per node "
                         "(0 = flat; pairs with --exchange hier_delta)")
    ap.add_argument("--no-recolor-degrees", action="store_true")
    ap.add_argument("--baseline", action="store_true",
                    help="Bozdağ/Zoltan-style batched boundary coloring")
    ap.add_argument("--repeat", type=int, default=1, metavar="N",
                    help="timestep mode: recolor the topology N times "
                         "through the plan cache, report cold vs warm ms")
    ap.add_argument("--reduce-passes", type=int, default=0, metavar="P",
                    help="post-color quality: up to P iterative color-"
                         "reduction passes (repro.core.reduce)")
    ap.add_argument("--reduce-order", default="reverse",
                    choices=list_orders(),
                    help="class-rebuild order used by --reduce-passes")
    args = ap.parse_args()

    # Persistent XLA compilation cache ($JAX_COMPILATION_CACHE_DIR, else
    # <repo>/.jax_cache): relaunching the same topology / config pays the
    # host-state build only.
    enable_compilation_cache()

    if args.stream:
        run_stream(args)
        return
    if not args.graph:
        ap.error("one of --graph or --stream is required")
    g = make_graph(args.graph)
    print(f"[color] graph {g.name}: n={g.n} m={g.num_edges} "
          f"maxdeg={g.max_degree}")
    pg = make_partition(g, args)
    t0 = time.time()
    plan = None                     # the plan that colored, for its stats
    if args.baseline:
        if args.backend != "reference" or args.exchange != "all_gather":
            print("[color] note: --baseline uses the reference backend and "
                  "all_gather exchange; --backend/--exchange are ignored")
        res = color_baseline(pg, problem=args.problem,
                             recolor_degrees=not args.no_recolor_degrees)
    elif args.repeat > 1:
        from repro.serve.coloring import ColoringService

        svc = ColoringService(
            pg, problem=args.problem,
            recolor_degrees=not args.no_recolor_degrees,
            backend=args.backend, exchange=args.exchange, engine=args.engine,
            reduce_passes=args.reduce_passes, reduce_order=args.reduce_order)
        for _ in range(args.repeat):
            res = svc.submit()
        plan = svc.plan
        print(f"[color] repeat={args.repeat} engine={svc.engine} "
              f"compile_ms={svc.stats.cold_ms:.1f} "
              f"({svc.stats.cold_runs} programs, paid once) "
              f"warm_ms={svc.stats.warm_ms_mean:.2f} "
              f"(mean execution of {svc.stats.warm_requests} timesteps)")
    else:
        kw = dict(problem=args.problem,
                  recolor_degrees=not args.no_recolor_degrees,
                  backend=args.backend, exchange=args.exchange,
                  engine=args.engine)
        res = color_distributed(pg, **kw)
        plan = get_plan(pg, **kw)   # the cached plan that just ran
    if args.reduce_passes > 0 and (args.baseline or args.repeat <= 1):
        from repro.core.quality import trajectory
        from repro.core.reduce import reduce_colors

        red = reduce_colors(
            pg, res, passes=args.reduce_passes, order=args.reduce_order,
            problem=args.problem,
            recolor_degrees=not args.no_recolor_degrees,
            backend="reference" if args.baseline else args.backend,
            exchange="all_gather" if args.baseline else args.exchange,
            engine=args.engine)
        print(f"[color] reduce order={args.reduce_order} "
              f"passes={red.passes_run}/{args.reduce_passes} "
              f"colors {red.initial_n_colors} -> {red.n_colors} "
              f"({trajectory(red.colors_by_pass, red.comm_bytes_by_pass)})")
        res = red.merged_result(res)
    dt = time.time() - t0
    ok = VALIDATORS[args.problem](g, res.colors)
    print(f"[color] {res.problem} parts={res.n_parts} "
          f"backend={res.backend} exchange={res.exchange} "
          f"colors={res.n_colors} rounds={res.rounds} "
          f"spec_iters={res.spec_iters} "
          + (f"diagonals={plan.stats.diagonals} "
             f"diagonal_share={plan.stats.diagonal_share:.4f} "
             if plan is not None else "") +
          f"conflicts={res.total_conflicts} proper={ok} "
          f"converged={res.converged} "
          f"comm/round={res.comm_bytes_per_round}B "
          f"comm_total={res.comm_bytes_total}B time={dt:.2f}s "
          f"(devices={len(jax.devices())})")
    if res.comm_bytes_by_round is not None:
        print(f"[color] comm_bytes_by_round="
              f"{[int(b) for b in res.comm_bytes_by_round]}")
    if res.comm_bytes_by_level is not None and res.comm_bytes_intra:
        print(f"[color] comm_bytes intra-node={res.comm_bytes_intra}B "
              f"inter-node={res.comm_bytes_inter}B")
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
