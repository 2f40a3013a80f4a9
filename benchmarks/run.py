"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Select subsets with
``python -m benchmarks.run [fig2 fig3 fig5 fig6 fig7 fig11 kernels a2a
recolor quality serve_stream serve_stream_mesh weak_exchange
exchange_smoke weak_exchange_smoke kernels_smoke recolor_smoke
quality_smoke serve_stream_smoke serve_stream_mesh_smoke]``.
``--json PATH`` additionally writes the rows as a JSON list of
``{name, us_per_call, derived}`` records — CI's bench-smoke job runs
``exchange_smoke`` (the fig3 exchange sweep at toy sizes) and uploads
that file as the per-PR comm-bytes artifact; the serve-smoke job runs
``recolor_smoke`` (the timestep-recoloring bench at toy sizes) and
uploads the cold-vs-warm latency artifact; the quality-smoke job runs
``quality_smoke`` (the color-reduction bench at toy sizes) and uploads
the colors-vs-passes artifact; the serve-stream-smoke job runs
``serve_stream_smoke`` (mixed-topology streams through the
continuous-batching frontend) and uploads the requests/sec artifact;
the multidevice job's serve-stream leg runs ``serve_stream_mesh_smoke``
(the same streams batched through the persistent shard_map slot program
on a forced 4-device mesh) and uploads the sustained-req/s artifact;
the kernel-parity job runs ``kernels_smoke`` (the kernel microbench at
toy sizes, including the CPU-compiler HLO bytes of the chained and fused
round programs) and uploads that artifact.
"""
from __future__ import annotations

import json
import sys
import time

from benchmarks import (
    bench_2gl_rounds,
    bench_d1_quality,
    bench_d1_scaling,
    bench_d2,
    bench_kernels,
    bench_moe_a2a,
    bench_pd2,
    bench_recolor_timesteps,
    bench_reduce,
    bench_serve_stream,
    bench_weak_scaling,
)

SUITES = {
    "fig2": lambda: bench_d1_quality.run(),
    "fig3": lambda: bench_d1_scaling.run(),
    "fig5": lambda: bench_weak_scaling.run(d2=False),
    "fig6": lambda: bench_2gl_rounds.run(),
    "fig7": lambda: bench_d2.run(),
    "fig10": lambda: bench_weak_scaling.run(d2=True),
    "fig11": lambda: bench_pd2.run(),
    "kernels": lambda: bench_kernels.run(),
    "a2a": lambda: bench_moe_a2a.run(),
    "recolor": lambda: bench_recolor_timesteps.run(),
    "quality": lambda: bench_reduce.run(),
    "serve_stream": lambda: bench_serve_stream.run(),
    "serve_stream_mesh": lambda: bench_serve_stream.run_mesh(),
    "weak_exchange": lambda: bench_weak_scaling.run_exchange_sweep(),
    "exchange_smoke": lambda: bench_d1_scaling.run_exchange(toy=True),
    "weak_exchange_smoke": lambda: bench_weak_scaling.run_exchange_sweep(
        toy=True),
    "kernels_smoke": lambda: bench_kernels.run(toy=True),
    "recolor_smoke": lambda: bench_recolor_timesteps.run(toy=True),
    "quality_smoke": lambda: bench_reduce.run(toy=True),
    "serve_stream_smoke": lambda: bench_serve_stream.run(toy=True),
    "serve_stream_mesh_smoke": lambda: bench_serve_stream.run_mesh(toy=True),
}


def _to_record(csv_row: str) -> dict:
    name, us, derived = csv_row.split(",", 2)
    return {"name": name, "us_per_call": float(us), "derived": derived}


def main() -> None:
    argv = sys.argv[1:]
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv):
            raise SystemExit("usage: benchmarks.run [suites...] --json PATH")
        json_path = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    which = argv or [k for k in SUITES if not k.endswith("_smoke")]
    records = []
    print("name,us_per_call,derived")
    for key in which:
        t0 = time.time()
        for r in SUITES[key]():
            print(r, flush=True)
            records.append(_to_record(r))
        print(f"# suite {key} done in {time.time()-t0:.0f}s", flush=True)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(records, f, indent=1)
        print(f"# wrote {len(records)} rows to {json_path}", flush=True)


if __name__ == "__main__":
    main()
