"""The program's own names in the trace reduction, on hand-made events
and on a trace recorded on a TPU v5e chip
(``data/trace_hex128_d1_full_spans.json.gz``: a short traced window of
``hex128-d1-full``, two requests).

The events are kept as ``bench/trace.py`` loads them, plus the ``plan.*``
host spans of ``ColoringPlan.run`` (``repro.core.plan.PLAN_SPANS``), which
its loader does not keep yet; ``scope_of`` maps each device operation (HLO
instruction) to the innermost named scope of the loop program it ran
under (``""`` for none).
"""
import gzip
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import trace  # noqa: E402
from repro.core.plan import PLAN_SPANS  # noqa: E402

DATA = Path(__file__).parent / "data" / "trace_hex128_d1_full_spans.json.gz"
GATHERS = ("spec.invariant", "spec.gather_assign", "spec.gather_resolve")


def recorded():
    with gzip.open(DATA, "rt") as f:
        data = json.load(f)
    events = [(tuple(w), n, s, e) for w, n, s, e in data["events"]]
    return events, data["scope_of"]


def test_every_request_passes_the_plan_spans_in_order():
    events, _ = recorded()
    spans = sorted((s, n) for (k, _), n, s, e in events
                   if k == "host" and n in PLAN_SPANS)
    warm = [n for n in PLAN_SPANS if n != "plan.compile"]
    names = [n for _, n in spans]
    assert names and names == warm * (len(names) // len(warm))


def test_a_gap_inside_plan_fetch_is_named_by_it():
    """Hand-made events: the device idles while the host copies the colors
    back; the innermost span over the gap is ``plan.fetch``."""
    host = lambda name, s, e: (("host", "python"), name, s, e)  # noqa: E731
    op = "%fusion.1 = s32[64]{0} fusion(s32[8]{0} %p.1), kind=kLoop"
    events = [host("window", 0, 100), host("request", 0, 100),
              host("plan.dispatch", 0, 2), host("plan.wait", 2, 60),
              host("plan.fetch", 60, 100), (("device", 0), op, 1, 58)]
    assert trace.summarize(events).idle_gaps[0] == ("plan.fetch", 42e-9)


def test_idle_gaps_are_named_by_plan_spans():
    events, _ = recorded()
    s = trace.summarize(events)
    assert s.idle_gaps[0][0] == "plan.fetch"
    assert all(name.startswith("plan.") for name, sec in s.idle_gaps
               if sec > 5e-3)


def test_gather_scopes_hold_most_of_the_busy_time():
    events, scope_of = recorded()
    s = trace.summarize(events)
    window = next((a, b) for (k, _), n, a, b in events if n == "window")
    by_scope = {}
    for (k, _), text, a, b in events:
        op = trace.op_of(text)
        a, b = max(a, window[0]), min(b, window[1])
        if k == "device" and b > a and not op.container:
            scope = scope_of[op.name]
            by_scope[scope] = by_scope.get(scope, 0) + b - a
    busy = s.chips[0].busy_ns
    assert sum(by_scope.get(g, 0) for g in GATHERS) > 0.9 * busy
