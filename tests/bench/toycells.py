"""Toy-size instances of the benchmark's cells, for the CPU tests."""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import manifest  # noqa: E402

# Per configuration, a mesh a test run can hold (same problem, parts,
# engine, backend and exchange as the cell).
TOY_EXTENTS = {"hex128-d1": [8, 8, 8], "hex64-d2": [6, 6, 6],
               "hex-d1-4chip": [16, 8, 4]}

# Toy cells that BENCHMARK.json does not list: (configuration, traffic,
# chips, graph).  ``kron10-d1-full`` is ``hex128-d1`` with a Graph500
# Kronecker graph of scale 10 in place of its mesh: the skewed degrees and
# scattered ids of the gather path, on the program's CPU path.
KRON10 = {"kind": "kron", "scale": 10, "edge_factor": 16,
          "initiator": [0.57, 0.19, 0.19], "seed": 2**33 + 9}
UNLISTED = {"kron10-d1-full": ("hex128-d1", "full", 1, KRON10)}


def toy_cell(workload: str) -> manifest.Cell:
    graph = None
    if workload in UNLISTED:
        *names, graph = UNLISTED[workload]
        cell = manifest.cell(workload, *names, manifest.load_manifest())
    else:
        cell = manifest.resolve(workload)
    cfg = json.loads(json.dumps(cell.config))
    if graph is None:
        cfg["graph"]["extents"] = TOY_EXTENTS[cfg["name"]]
    else:
        cfg["graph"] = dict(graph)
    return dataclasses.replace(cell, config=cfg)


def run_toy(workload: str, *, seed: int = 2**33 + 7, seconds: float = 0.3,
            fault: str | None = None, trace: bool = False) -> dict:
    """One CPU run of the toy cell through the harness: its result line."""
    from bench import run

    cell = toy_cell(workload)
    return run.result_line(cell, run.run_cell(
        cell, seed=seed, seconds=seconds, trace=trace, fault=fault,
        require_chip=False))


def run_toy_subprocess(workload: str, *, devices: int, **kw) -> dict:
    """``run_toy`` in a fresh process with ``devices`` virtual CPU devices
    (the four-chip cell's ``shard_map`` engine needs a mesh)."""
    code = ("import json, sys, jax; "
            "jax.config.update('jax_enable_compilation_cache', False); "
            "sys.path.insert(0, %r); import toycells; "
            "print(json.dumps(toycells.run_toy(%r, **%r)))"
            % (str(Path(__file__).parent), workload, kw))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])
