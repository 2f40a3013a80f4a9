"""The trace reduction gives known numbers: on hand-made events, and on a
small trace recorded on a TPU v5e chip (``data/``)."""
import gzip
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import trace  # noqa: E402

DATA = Path(__file__).parent / "data"


def dev(chip, name, s, e):
    return (("device", chip), name, s, e)


def host(name, s, e):
    return (("host", "python"), name, s, e)


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


FUSION = "%fusion.1 = s32[64]{0:T(1024)} fusion(s32[8]{0} %p.1), kind=kCustom"
KERNEL = ("%body.3 = s32[1,64]{1,0:T(1,128)} custom-call(s32[6,64]{1,0} %b.2),"
          " custom_call_target=\"tpu_custom_call\"")
PERMUTE = ("%collective-permute-done.2 = s32[8]{0} collective-permute-done("
           "(s32[8]{0}, s32[8]{0}) %collective-permute-start.2)")
LOOP = ("%while.7 = (s32[64]{0}, s32[]) while((s32[64]{0}, s32[]) %t.1), "
        "condition=%cond, body=%body")
GATHER = "%gather.9 = s32[64]{0} gather(s32[8]{0} %a, s32[64,1]{1,0} %i)"


def test_op_of_reads_name_opcode_and_kind():
    assert trace.op_of(FUSION).opcode == "fusion"
    assert trace.op_of(FUSION).name == "fusion.1"
    assert trace.op_of(KERNEL).kernel and not trace.op_of(FUSION).kernel
    assert trace.op_of(PERMUTE).collective
    assert trace.op_of(LOOP).container and trace.op_of(LOOP).opcode == "while"
    # an operand named like a custom call does not make a kernel
    assert not trace.op_of(
        "%fusion.9 = s32[4]{0} fusion(s32[4]{0} %custom-call.51)").kernel


def test_busy_kernel_collective_and_idle_on_hand_made_events():
    events = [
        host("window", 0, 100), host("request", 0, 60),
        host("traffic", 60, 100),
        dev(0, LOOP, 0, 60),                    # a container: left out
        dev(0, FUSION, -10, 20),                # clipped to the window
        dev(0, KERNEL, 15, 30),                 # a kernel, overlapping
        dev(0, PERMUTE, 40, 50),
        dev(0, GATHER, 200, 300),               # outside the window
        dev(1, FUSION, 0, 90),
    ]
    s = trace.summarize(events)
    assert s.window_ns == 100
    c0, c1 = s.chips[0], s.chips[1]
    assert c0.busy_ns == 40 and c0.kernel_ns == 15 and c0.collective_ns == 10
    assert c1.busy_ns == 90 and c1.kernel_ns == 0
    # chip 0 is the most idle: gaps 50-100 (mostly traffic: 50-60
    # request, 60-100 traffic) and 30-40 (request).
    assert s.idle_gaps == [("traffic", 50e-9), ("request", 10e-9)]
    label, seconds = s.device_ops[0]
    assert label.startswith("fusion.1 fusion") and seconds == 110 / 2 / 1e9


def test_an_idle_gap_is_named_by_the_innermost_span_covering_it():
    events = [host("window", 0, 100), host("request", 0, 100),
              host("gc", 40, 90), dev(0, FUSION, 0, 40),
              dev(0, FUSION, 95, 100)]
    s = trace.summarize(events)
    # 40-95: gc covers 50 of 55, request all of it; gc is the shorter
    assert s.idle_gaps == [("gc", 55e-9)]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.summarize([dev(0, "fusion", 0, 1)])


def recorded(name):
    with gzip.open(DATA / name, "rt") as f:
        return [(tuple(w), n, s, e) for w, n, s, e in json.load(f)]


def sweep_busy(events, window):
    """Busy time by a sweep over start/end marks (a second way to the
    union), leaving out control-flow containers."""
    marks = []
    for (kind, _), text, s, e in events:
        if kind == "device" and " while(" not in text:
            s, e = max(s, window[0]), min(e, window[1])
            if e > s:
                marks += [(s, 1), (e, -1)]
    busy, depth, since = 0.0, 0, None
    for t, d in sorted(marks):
        if depth == 0 and d > 0:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    return busy


def test_recorded_one_chip_trace():
    """One request of hex128-d1-full, traced on a TPU v5 lite."""
    events = recorded("trace_hex128_d1_full.json.gz")
    s = trace.summarize(events)
    window = next((s_, e) for (k, _), n, s_, e in events if n == "window")
    chip = s.chips[0]
    assert list(s.chips) == [0]
    assert s.window_ns == 1_782_712_091
    assert chip.busy_ns == sweep_busy(events, window) == 1_745_762_862
    kernels = sum(e - s_ for (k, _), n, s_, e in events
                  if k == "device" and " custom-call(" in n
                  and "tpu_custom_call" in n)
    assert chip.kernel_ns == kernels == 6_393_638
    assert chip.collective_ns == 0
    assert 1 - chip.busy_ns / s.window_ns == pytest.approx(0.020727, abs=1e-6)
    # the gathers of the (6, 2,097,152) neighbor blocks lead
    assert s.device_ops[0][0] == "fusion.55 fusion s32[12582912]"
    assert s.idle_gaps[0][0] == "request"
