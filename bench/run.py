#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip, and print one line.

    python bench/run.py --workload hex128-d1-full --seed 7 --seconds 30 \\
        --trace 0

The cell (``BENCHMARK.json``) names a configuration and a traffic mix.
One process: set-up (graph, partition, plan, compile or cache load, a
warm-up request of the cell's own kind), then a closed-loop window of
``--seconds`` through ``ColoringService.submit`` — the next request goes
when the previous one has returned, and the one in flight at the end
finishes and counts — then the reference's checks of a sample of the
window's answers, ``CHECKED`` of them drawn from the seed (all of them in
a window that returns fewer).  The window keeps only the sample: every
other answer is dropped when the next request is built, so the host
reuses its memory instead of touching fresh pages each request.
``--trace 1`` records the window with the JAX profiler and reports the
per-layer metrics; ``--trace 0`` the end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number with its limit,
also printed as the last lines of standard error).  Without a TPU, or
with fewer chips than the cell asks for, it exits 3 and prints no result.
JAX's compile cache is ``$JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import faults, graphs, manifest, roofline  # noqa: E402
from bench import trace as tracing  # noqa: E402
from bench.reference import Reference, passes  # noqa: E402
from bench.requests import Request, Requests  # noqa: E402


# Answers of a window that the reference checks: a uniform sample, drawn
# from the seed, of fixed size (a reservoir), so neither the memory the
# window holds nor the time the checks take grows with its requests.
CHECKED = 32


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class NoProgram(RuntimeError):
    """The checkout holds no program (``src/repro``) to measure."""


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take their number here."""

    chips: int
    peaks: dict | None
    setup_s: float
    graph_build_s: float
    plan_build_s: float
    compile_s: float
    window_s: float
    requests: list              # one dict per request of the window
    memory_peak_bytes: int | None
    device: dict                # platform, kind and count as JAX reports
    checks: dict                # compared number -> {"value", "limit"}
    checked: int                # requests whose answer was checked
    failed: int                 # checked answers that failed a check
    compiles: int               # compile events inside the window
    trace: tracing.Summary | None = None


def use_program():
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise NoProgram(f"no program to measure: {src / 'repro'} is missing")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def devices(chips: int, require_chip: bool):
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX finds no device: {e}") from e
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees {len(devs)}")
    return devs


class CompileEvents:
    """Counts JAX trace, lower and compile events while ``on``."""

    def __init__(self):
        import jax

        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **_):
        if self.on and event.startswith("/jax/core/compile/"):
            self.n += 1


class Sample:
    """A uniform sample of ``size`` of the items offered, the choice drawn
    from ``seed`` (Algorithm R): the i-th item takes a place with chance
    size / (i + 1), in place of a uniform one."""

    def __init__(self, size: int, seed: int):
        self.size, self.seen, self.kept = size, 0, {}
        self.rng = np.random.default_rng([seed, 1])

    def offer(self, i: int, item):
        slot = self.seen if self.seen < self.size else int(
            self.rng.integers(0, self.seen + 1))
        self.seen += 1
        if slot < self.size:
            self.kept[slot] = (i, item)

    def items(self) -> list:
        """The kept ``(i, item)`` pairs, in the order offered."""
        return sorted(self.kept.values(), key=lambda x: x[0])


class Collections:
    """Times Python's garbage collections while ``on``; in a trace each is
    a ``gc`` host span, so an idle gap it causes is named after it."""

    def __init__(self):
        self.on, self.ns, self._open = False, 0, None
        gc.callbacks.append(self._seen)

    def _seen(self, phase, info):
        from jax.profiler import TraceAnnotation

        if phase == "start" and self.on:
            self._open = (time.perf_counter_ns(), TraceAnnotation("gc"))
            self._open[1].__enter__()
        elif phase == "stop" and self._open is not None:
            t, span = self._open
            span.__exit__(None, None, None)
            self.ns += time.perf_counter_ns() - t
            self._open = None


@contextlib.contextmanager
def profiled(enabled: bool):
    """Record the JAX profiler trace of the block; yields a dict that holds
    the events once the block has ended."""
    out = {}
    if not enabled:
        yield out
        return
    import jax

    logdir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        try:
            out["events"] = tracing.load(logdir)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)


def peak_bytes(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: manifest.Cell, *, seed: int, seconds: float, trace: bool,
             fault: str | None = None, require_chip: bool = True):
    """One run of ``cell``; see the module docstring."""
    import jax
    from jax.profiler import TraceAnnotation

    from bench.system import System

    cfg = cell.config
    devs = devices(cell.chips, require_chip)
    peaks = manifest.peaks(devs[0].device_kind) if require_chip else None
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}

    g = graphs.make(cfg["graph"])
    ref = Reference(cfg["problem"], g)
    with faults.planted_in_program(fault, cfg["exchange"]):
        system = System(cfg, g)
        call = faults.wrap(fault, system, ref)
        gen = Requests(cell.mix, g, seed)
        with TraceAnnotation("warmup"):
            prev = call(Request(None, None)).colors if gen.warm_start else None
            prev = call(gen.next(prev)).colors
        compiles, collections = CompileEvents(), Collections()
        window, sample = [], Sample(CHECKED, seed)
        with profiled(trace) as recorded:
            compiles.on = collections.on = True
            t0 = time.perf_counter()
            with TraceAnnotation("window"):
                while True:
                    with TraceAnnotation("traffic"):
                        req = gen.next(prev)
                    host = (time.perf_counter(), time.process_time(),
                            collections.ns)
                    with TraceAnnotation("request"):
                        ans = call(req)
                    window.append(dict(
                        seconds=time.perf_counter() - host[0],
                        cpu_s=time.process_time() - host[1],
                        gc_s=(collections.ns - host[2]) / 1e9,
                        rounds=ans.rounds, comm_bytes=ans.comm_bytes,
                        mask=req.mask))
                    sample.offer(len(window) - 1, (req, ans))
                    prev = ans.colors
                    del req, ans
                    if time.perf_counter() - t0 >= seconds:
                        break
            t1 = time.perf_counter()
            compiles.on = collections.on = False
        gc.callbacks.remove(collections._seen)
        memory = peak_bytes(devs[:cell.chips])
        setup = dict(graph_build_s=system.graph_build_s,
                     plan_build_s=system.plan_build_s,
                     compile_s=system.compile_s)
        del system, call
    jax.clear_caches()

    # The reference, once the window has closed and the peak is read.
    limits = ref.limits()
    hood = ref.hood_sizes()
    worst = {k: 0 for k in limits}
    failed = 0
    for r in window:
        r["least_bytes"] = roofline.least_bytes(hood, r.pop("mask"))
    for i, (req, ans) in sample.items():
        nums = ref.check(ans.colors, req.mask, req.colors0)
        failed += not passes(nums, limits)
        worst = {k: max(worst[k], nums[k]) for k in limits}
        window[i]["colors"] = int(
            np.count_nonzero(np.bincount(ans.colors)[1:]))
    return Run(
        chips=cell.chips, peaks=peaks, setup_s=t0 - T_START,
        window_s=t1 - t0, requests=window, memory_peak_bytes=memory,
        device=device, checked=len(sample.kept), failed=failed,
        compiles=compiles.n,
        checks={k: {"value": worst[k], "limit": limits[k]} for k in limits},
        trace=tracing.summarize(recorded["events"]) if trace else None,
        **setup)


def result_line(cell: manifest.Cell, run: Run) -> dict:
    """The run's result line; per-layer metrics where it was traced."""
    metrics = {}
    for m in (cell.end_to_end if run.trace is None else cell.per_layer):
        value = m.reader.read(run)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    device = dict(run.device, memory_peak_bytes=run.memory_peak_bytes)
    out = {"correct": run.failed == 0 and run.checked > 0,
           "attempted": len(run.requests), "failed": run.failed,
           "metrics": metrics, "device": device}
    if run.trace is not None:
        t = run.trace
        if t.chips:
            device["busy_s"] = tracing.mean(
                c.busy_ns for c in t.chips.values()) / 1e9
        device["window_s"] = t.window_ns / 1e9
        out["breakdown"] = {"device_ops": [list(x) for x in t.device_ops],
                            "idle_gaps": [list(x) for x in t.idle_gaps]}
    took = sorted(r["seconds"] * 1e3 for r in run.requests)
    slowest = max(run.requests, key=lambda r: r["seconds"])
    out["window"] = {
        "requests": len(run.requests), "seconds": run.window_s,
        "checked": run.checked, "compiles": run.compiles,
        "request_ms": [took[0], took[len(took) // 2], took[-1]],
        "gc_ms": sum(r["gc_s"] for r in run.requests) * 1e3,
        "slowest": {"ms": slowest["seconds"] * 1e3,
                    "cpu_ms": slowest["cpu_s"] * 1e3,
                    "gc_ms": slowest["gc_s"] * 1e3}}
    out["checks"] = run.checks
    return out


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", choices=faults.FAULTS, default=None,
                    help="plant a fault under the timed path (checks only)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        cell = manifest.resolve(args.workload)
        use_program()
    except (NoProgram, KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        run = run_cell(cell, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), fault=args.fault)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    if run.compiles:
        print(f"bench: {run.compiles} compile events inside the window",
              file=sys.stderr)
    for name, c in run.checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result_line(cell, run)), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    sys.exit(main())
