"""Plan/executor layer: cache keying, LRU eviction, warm-path contract.

The compile-once contract (ISSUE-3): a plan's static half — host state
tables, exchange prepare, traced program — is built once per
``(topology, problem, recolor_degrees, backend, exchange, engine,
max_rounds)``; ``plan.run()`` performs zero host-side state rebuilds and
zero retraces, and is bit-identical to a cold ``color_distributed``.
"""
import glob
import re

import numpy as np
import pytest

from repro.core import plan as plan_mod
from repro.core.distributed import color_distributed
from repro.core.plan import PlanCache, PlanKey, build_plan, get_plan
from repro.core.validate import is_proper_d1, is_proper_d2
from repro.graph.generators import grid_2d, hex_mesh
from repro.graph.partition import partition_graph
from repro.serve.coloring import ColoringService

GRAPH = hex_mesh(6, 4, 4)
PG = partition_graph(GRAPH, 3, strategy="block", second_layer=True)


# ---------------------------------------------------------------------------
# Topology signature.
# ---------------------------------------------------------------------------

def test_signature_content_addressed():
    """Same structural tables -> same signature, regardless of instance."""
    pg_a = partition_graph(GRAPH, 3, strategy="block", second_layer=True)
    pg_b = partition_graph(GRAPH, 3, strategy="block", second_layer=True)
    assert pg_a is not pg_b
    assert pg_a.signature == pg_b.signature
    assert pg_a.signature == pg_a.signature          # memoized, stable


def test_signature_distinguishes_topologies():
    sigs = {
        PG.signature,
        partition_graph(GRAPH, 4, strategy="block", second_layer=True).signature,
        partition_graph(GRAPH, 3, strategy="block").signature,   # no 2nd layer
        partition_graph(GRAPH, 3, strategy="random", seed=1,
                        second_layer=True).signature,
        partition_graph(grid_2d(10, 10), 3, strategy="block",
                        second_layer=True).signature,
    }
    assert len(sigs) == 5


# ---------------------------------------------------------------------------
# Cache keying: every key component misses once, then hits.
# ---------------------------------------------------------------------------

def test_cache_hit_miss_on_every_key_component():
    cache = PlanCache(maxsize=32)
    base = dict(problem="d1", recolor_degrees=True, backend="reference",
                exchange="all_gather", engine="simulate", max_rounds=64)
    variants = [
        base,
        {**base, "problem": "d2"},
        {**base, "recolor_degrees": False},
        {**base, "backend": "pallas"},
        {**base, "exchange": "delta"},
        {**base, "max_rounds": 32},
    ]
    for i, kw in enumerate(variants):
        plan = get_plan(PG, cache=cache, **kw)
        assert cache.misses == i + 1, kw
        again = get_plan(PG, cache=cache, **kw)
        assert again is plan, kw                      # hit returns same plan
    assert cache.hits == len(variants)

    # Different topology -> miss; identical-content topology -> hit.
    other = partition_graph(GRAPH, 4, strategy="block", second_layer=True)
    get_plan(other, cache=cache, **base)
    assert cache.misses == len(variants) + 1
    clone = partition_graph(GRAPH, 3, strategy="block", second_layer=True)
    assert get_plan(clone, cache=cache, **base) is get_plan(
        PG, cache=cache, **base)


def test_cache_bypass_for_uncacheable_inputs():
    from repro.core.exchange import SparseDeltaExchange

    cache = PlanCache()
    a = get_plan(PG, exchange=SparseDeltaExchange(), engine="simulate",
                 cache=cache)
    b = get_plan(PG, exchange=SparseDeltaExchange(), engine="simulate",
                 cache=cache)
    assert a is not b                                 # instances bypass cache
    assert len(cache) == 0
    c = get_plan(PG, engine="simulate", cache=False)  # explicit cold build
    d = get_plan(PG, engine="simulate", cache=False)
    assert c is not d


def test_cache_false_is_fully_cold():
    """cache=False must not read or populate the shared host state cache:
    the cold benchmark baseline really pays the host state build."""
    plan_mod._STATE_CACHE.clear()
    color_distributed(PG, problem="d1", engine="simulate", cache=False)
    assert len(plan_mod._STATE_CACHE) == 0
    color_distributed(PG, problem="d1", engine="simulate",
                      cache=PlanCache())
    assert len(plan_mod._STATE_CACHE) == 1            # cached path populates


def test_cache_true_means_default_cache():
    from repro.core.plan import default_plan_cache

    a = get_plan(PG, engine="simulate", cache=True)
    b = get_plan(PG, engine="simulate", cache=None)
    assert a is b
    assert a.key in default_plan_cache()


def test_cached_plan_stored_under_its_own_key():
    """The cache key and plan.key come from one constructor — a plan is
    always findable in its cache under the key it carries."""
    cache = PlanCache()
    plan = get_plan(PG, problem="d2", exchange="delta", engine="simulate",
                    cache=cache)
    assert plan.key in cache
    assert cache.keys() == [plan.key]


def test_cache_lru_eviction_order():
    cache = PlanCache(maxsize=2)
    ka = get_plan(PG, problem="d1", engine="simulate", cache=cache).key
    kb = get_plan(PG, problem="d2", engine="simulate", cache=cache).key
    get_plan(PG, problem="d1", engine="simulate", cache=cache)   # touch A
    kc = get_plan(PG, problem="d1_2gl", engine="simulate", cache=cache).key
    assert len(cache) == 2
    assert kb not in cache                            # LRU evicted
    assert ka in cache and kc in cache
    assert cache.keys() == [ka, kc]                   # LRU -> MRU order


def test_cache_byte_bounded_eviction():
    """A sweep over many topologies must evict by pinned device-state
    bytes, not only by entry count (ROADMAP follow-up: cached plans pin
    their state tables, so 16 huge topologies could otherwise all stay
    resident)."""
    probe = build_plan(PG, engine="simulate")
    assert probe.nbytes > 0
    budget = int(probe.nbytes * 2.5)          # fits ~2 same-sized plans
    cache = PlanCache(maxsize=32, max_bytes=budget)
    topologies = [
        partition_graph(hex_mesh(6, 4, k), 3, strategy="block",
                        second_layer=True)
        for k in (3, 4, 5, 6)
    ]
    keys = [get_plan(t, engine="simulate", cache=cache).key
            for t in topologies]
    assert cache.misses == len(topologies)
    assert len(cache) < len(topologies)       # byte limit forced eviction
    assert cache.total_bytes <= budget
    assert keys[-1] in cache                  # most recent always survives
    assert keys[0] not in cache               # LRU evicted first
    # A single over-budget plan is kept: the cache never self-empties.
    tiny = PlanCache(maxsize=8, max_bytes=1)
    k = get_plan(PG, engine="simulate", cache=tiny).key
    assert len(tiny) == 1 and k in tiny


def test_plan_key_records_resolved_engine():
    plan = build_plan(PG, engine="auto")
    assert plan.key.engine in ("simulate", "shard_map")
    assert plan.key == PlanKey(
        topology=PG.signature, problem="d1", recolor_degrees=True,
        backend="reference", exchange="all_gather",
        engine=plan.key.engine, max_rounds=64)


# ---------------------------------------------------------------------------
# plan.run() parity vs the cold path, all problems x backends x exchanges.
# ---------------------------------------------------------------------------

_CACHE = PlanCache(maxsize=64)


@pytest.mark.parametrize("problem", ["d1", "d1_2gl", "d2", "pd2"])
@pytest.mark.parametrize("backend,exchange", [
    ("reference", "all_gather"),
    ("reference", "halo"),
    ("reference", "delta"),
    ("reference", "sparse_delta"),
    ("reference", "hier_delta"),
    ("pallas", "all_gather"),
    ("pallas", "sparse_delta"),
    ("pallas", "hier_delta"),
    ("pallas_fused", "all_gather"),
    ("pallas_fused", "sparse_delta"),
])
def test_plan_run_matches_cold_color_distributed(problem, backend, exchange):
    if exchange == "halo" and not PG.halo_neighbors_ok():
        pytest.skip("partition not slab-legal")
    plan = get_plan(PG, problem=problem, backend=backend, exchange=exchange,
                    engine="simulate", cache=_CACHE)
    warm = plan.run()
    cold = color_distributed(PG, problem=problem, backend=backend,
                             exchange=exchange, engine="simulate",
                             cache=False)
    assert (warm.colors == cold.colors).all()
    assert warm.rounds == cold.rounds
    assert warm.n_colors == cold.n_colors
    assert warm.total_conflicts == cold.total_conflicts
    assert list(warm.comm_bytes_by_round) == list(cold.comm_bytes_by_round)
    check = is_proper_d2 if problem == "d2" else is_proper_d1
    if problem != "pd2":
        assert check(GRAPH, warm.colors)


# ---------------------------------------------------------------------------
# Warm-path contract: zero host rebuilds, zero retraces.
# ---------------------------------------------------------------------------

def test_warm_run_no_host_rebuild_no_retrace(monkeypatch):
    plan = build_plan(PG, problem="d2", exchange="sparse_delta",
                      engine="simulate")
    first = plan.run()
    traces_after_first = plan.stats.traces
    assert traces_after_first >= 1

    def _forbidden(*a, **kw):
        raise AssertionError("warm plan.run() rebuilt host state")

    monkeypatch.setattr(plan_mod, "build_device_state", _forbidden)
    monkeypatch.setattr(plan._strategy, "prepare", _forbidden)
    mask = np.arange(GRAPH.n) % 3 != 0
    second = plan.run()
    masked = plan.run(color_mask=mask)                # dynamic input only
    seeded = plan.run(seed=7)
    assert plan.stats.traces == traces_after_first    # zero retraces
    assert plan.stats.runs == 4
    assert (second.colors == first.colors).all()
    assert (seeded.colors == first.colors).all()      # deterministic runtime
    assert set(np.nonzero(masked.colors)[0]) <= set(np.nonzero(mask)[0])


def test_warm_run_no_retrace_hier_delta(monkeypatch):
    """The hierarchical exchange honours the compile-once contract: its
    prepare() tables (route plans, aggregated-need masks, wire dtypes)
    are built once, and warm ``plan.run()`` never retraces."""
    plan = build_plan(PG, problem="d2", exchange="hier_delta",
                      engine="simulate")
    first = plan.run()
    traces_after_first = plan.stats.traces

    def _forbidden(*a, **kw):
        raise AssertionError("warm hier_delta plan.run() rebuilt host state")

    monkeypatch.setattr(plan_mod, "build_device_state", _forbidden)
    monkeypatch.setattr(plan._strategy, "prepare", _forbidden)
    second = plan.run()
    assert plan.stats.traces == traces_after_first    # zero retraces
    assert (second.colors == first.colors).all()
    assert second.comm_bytes_by_level is not None
    assert (second.comm_bytes_by_level == first.comm_bytes_by_level).all()


def test_warm_run_no_retrace_pallas_fused(monkeypatch):
    """The fused-round backend honours the same compile-once contract:
    warm ``plan.run()`` never rebuilds host state or retraces."""
    plan = build_plan(PG, problem="d2", backend="pallas_fused",
                      engine="simulate")
    first = plan.run()
    traces_after_first = plan.stats.traces

    def _forbidden(*a, **kw):
        raise AssertionError("warm pallas_fused plan.run() rebuilt host state")

    monkeypatch.setattr(plan_mod, "build_device_state", _forbidden)
    monkeypatch.setattr(plan._strategy, "prepare", _forbidden)
    second = plan.run()
    assert plan.stats.traces == traces_after_first    # zero retraces
    assert (second.colors == first.colors).all()


def test_warm_run_no_implicit_host_transfers():
    """Static shard tables are device-resident (donated/closure constants):
    a warm run performs only the *explicit* per-request device_puts, so it
    survives ``transfer_guard_host_to_device("disallow")`` (which rejects
    implicit host->device transfers)."""
    import jax

    plan = build_plan(PG, problem="d1", exchange="sparse_delta",
                      engine="simulate")
    first = plan.run()                                # pays trace + transfers
    with jax.transfer_guard_host_to_device("disallow"):
        warm = plan.run()
    assert (warm.colors == first.colors).all()


def test_color_mask_and_colors0_through_plan():
    mask = np.arange(GRAPH.n) < GRAPH.n // 2
    plan = get_plan(PG, engine="simulate", cache=_CACHE)
    via_plan = plan.run(color_mask=mask)
    direct = color_distributed(PG, color_mask=mask, engine="simulate",
                               cache=False)
    assert (via_plan.colors == direct.colors).all()
    # colors0 seeds the frozen half; active half must still color properly.
    base = plan.run().colors
    warm_start = plan.run(color_mask=mask, colors0=base)
    assert (warm_start.colors[~mask] == base[~mask]).all()


# ---------------------------------------------------------------------------
# Host device-state cache (shared with baseline / Jones-Plassmann).
# ---------------------------------------------------------------------------

def test_cached_device_state_shared():
    pg_a = partition_graph(GRAPH, 3, strategy="block", second_layer=True)
    pg_b = partition_graph(GRAPH, 3, strategy="block", second_layer=True)
    st_a = plan_mod.cached_device_state(pg_a, "d2")
    st_b = plan_mod.cached_device_state(pg_b, "d2")
    assert st_a is st_b                               # content-addressed
    assert plan_mod.cached_device_state(pg_a, "d1") is not st_a


# ---------------------------------------------------------------------------
# Batched recoloring service.
# ---------------------------------------------------------------------------

def test_service_batch_bit_identical_to_solo():
    """Batch sizes 3 and 5 pad up to power-of-two buckets (4, 8) with
    inactive requests; every real element matches its solo run."""
    svc = ColoringService(PG, problem="d1", exchange="delta",
                          engine="simulate", cache=PlanCache())
    n = GRAPH.n
    masks = [None, np.arange(n) < n // 2, np.arange(n) % 2 == 0,
             np.arange(n) % 3 != 0, np.arange(n) >= n // 3]
    for size in (3, 5):
        batch = svc.run_batch([{"color_mask": m} for m in masks[:size]])
        assert len(batch) == size
        for m, b in zip(masks, batch):
            solo = svc.plan.run(color_mask=m)
            assert (b.colors == solo.colors).all()
            assert b.rounds == solo.rounds
            assert b.spec_iters == solo.spec_iters
            assert b.total_conflicts == solo.total_conflicts
            assert list(b.comm_bytes_by_round) == list(solo.comm_bytes_by_round)
    assert svc.buckets == [4, 8]                      # bucketed, not per-size


def test_service_stats_cold_vs_warm():
    """Accounting splits trace/compile from execution: cold_ms holds only
    program builds, and every request's execution — including the ones
    riding a bucket's first batch — is attributed to the warm path."""
    svc = ColoringService(PG, engine="simulate", cache=PlanCache())
    svc.submit()
    assert svc.stats.cold_runs == 1                   # the plan program
    assert svc.stats.cold_ms > 0
    assert svc.stats.warm_requests == 1               # execution is warm
    for _ in range(3):
        svc.submit()
    assert svc.stats.requests == 4
    assert svc.stats.cold_runs == 1
    assert svc.stats.warm_requests == 4
    assert svc.stats.warm_ms_mean > 0
    # Per-request execution is far below the compile cost it amortizes.
    assert svc.stats.warm_ms_mean < svc.stats.cold_ms
    # A first-use batch bucket compiles its step+refill programs (cold
    # events), but the N requests it carried still book as warm — the
    # mean no longer overstates steady-state latency early in a stream.
    cold_before, warm_before = svc.stats.cold_runs, svc.stats.warm_requests
    cold_ms_before = svc.stats.cold_ms
    svc.run_batch([{}, {}])
    assert svc.stats.cold_runs == cold_before + 2     # step + refill
    assert svc.stats.cold_ms > cold_ms_before
    assert svc.stats.warm_requests == warm_before + 2
    cold_after, cold_ms_after = svc.stats.cold_runs, svc.stats.cold_ms
    svc.run_batch([{}, {}])
    assert svc.stats.cold_runs == cold_after          # bucket reused
    assert svc.stats.cold_ms == cold_ms_after
    assert svc.stats.warm_requests == warm_before + 4


def test_service_empty_and_single_batches():
    svc = ColoringService(PG, engine="simulate", cache=PlanCache())
    assert svc.run_batch([]) == []
    [res] = svc.run_batch([{}])
    assert is_proper_d1(GRAPH, res.colors)


def test_service_rejects_unknown_request_keys():
    svc = ColoringService(PG, engine="simulate", cache=PlanCache())
    with pytest.raises(TypeError, match="unknown request keys"):
        svc.run_batch([{"mask": None}, {}])           # typo for color_mask
    with pytest.raises(TypeError, match="unknown request keys"):
        svc.run_batch([{"color_mask": None, "seeds": 1}])


# ---------------------------------------------------------------------------
# Tracing: host spans, named device scopes, the speculation counter.
# ---------------------------------------------------------------------------

def test_plan_run_emits_its_host_spans_in_order(tmp_path):
    """Each run passes the ``plan.*`` spans in order; ``plan.compile``
    only on the run that compiles."""
    import jax
    from jax.profiler import ProfileData

    plan = build_plan(PG, problem="d1", exchange="sparse_delta",
                      engine="simulate")
    jax.profiler.start_trace(str(tmp_path))
    try:
        plan.run()
        plan.run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = sorted((e.start_ns, e.name)
                   for plane in ProfileData.from_file(path).planes
                   for line in plane.lines for e in line.events
                   if e.name in plan_mod.PLAN_SPANS)
    cold = list(plan_mod.PLAN_SPANS)
    warm = [s for s in cold if s != "plan.compile"]
    assert [name for _, name in spans] == cold + warm


# The scopes the loop program names its device work by (``_make_loop``,
# ``kernels.fused_round``).
SCOPES = ("spec.invariant", "spec.gather_assign", "spec.assign",
          "spec.gather_resolve", "spec.resolve", "round", "round.detect",
          "exchange")


@pytest.mark.parametrize("problem", ["d1", "d2"])
def test_named_scopes_land_on_compiled_instructions(problem):
    plan = build_plan(PG, problem=problem, backend="pallas_fused",
                      exchange="sparse_delta", engine="simulate")
    plan.run()
    op_names = re.findall(r'op_name="([^"]*)"', plan.executable.as_text())
    scopes = {part for name in op_names for part in name.split("/")}
    assert set(SCOPES) <= scopes


@pytest.mark.parametrize("problem", ["d1", "d2", "pd2"])
def test_spec_iters_counted_alike_by_both_backends(problem):
    """The reference backend counts its own loop; its math is the fused
    kernels', so the counts agree.  A request with an empty mask runs no
    iteration."""
    results = {}
    for backend in ("reference", "pallas_fused"):
        plan = build_plan(PG, problem=problem, backend=backend,
                          exchange="sparse_delta", engine="simulate")
        full = plan.run()
        empty = plan.run(color_mask=np.zeros(GRAPH.n, bool),
                         colors0=full.colors)
        assert empty.spec_iters == 0 and (empty.colors == full.colors).all()
        results[backend] = full
    ref, fused = results["reference"], results["pallas_fused"]
    assert (ref.colors == fused.colors).all()
    assert ref.spec_iters == fused.spec_iters > 0
