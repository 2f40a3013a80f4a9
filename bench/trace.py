"""From a profiler trace to the device numbers of a window.

:func:`load` reads the ``.xplane.pb`` the JAX profiler wrote into plain
events; :func:`summarize` reduces them, and it alone, to the numbers the
metric readers use.  Events are ``(where, name, start_ns, end_ns)`` with
``where`` = ``("device", chip)`` for an operation on a chip or
``("host", line)`` for a host span.

* A chip's operations are the events of its ``XLA Ops`` line.  Its busy
  time is the union of their intervals inside the window, which is the
  benchmark's own ``window`` host span.
* The TPU names each operation by its HLO instruction
  (``%fusion.55 = s32[12582912]{...} fusion(...), ...``); :func:`op_of`
  reads its name and opcode.  Control-flow containers (``while``,
  ``conditional``, ``call``) span the operations of their bodies and are
  left out.  An operation is a Mosaic kernel when it is a custom call of
  the ``tpu_custom_call`` target, and a collective when its opcode is an
  XLA collective (:data:`COLLECTIVE`); the rest is XLA's own work
  (gathers, scatters, fusions, copies).
* Idle gaps are the stretches of the window in which a chip runs no
  operation; each is named by the shortest host span that covers half of
  it or more (a ``gc`` inside a ``request``), else by the one that covers
  most of it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# Host spans the harness records (bench/run.py) — the window and what the
# host does inside it, Python's garbage collections among it.
HOST_SPANS = ("window", "request", "traffic", "gc")
CONTAINERS = ("while", "conditional", "call")
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|collective-permute|all-to-all|"
    r"ragged-all-to-all|reduce-scatter|collective-broadcast|send|recv)"
    r"(-start|-done)?$")
_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str           # e.g. "fusion.55"
    opcode: str         # e.g. "fusion"; "" where the text is no HLO
    label: str          # name, opcode and result type, for the breakdown
    kernel: bool
    collective: bool
    container: bool


def op_of(text: str) -> Op:
    """Read an ``XLA Ops`` event name (an HLO instruction's text)."""
    head, eq, rest = text.partition(" = ")
    if not eq:
        return Op(text, "", text[:120], False, False, False)
    m = _OPCODE.search(rest)
    opcode = m.group(1) if m else ""
    result = _LAYOUT.sub("", rest[:m.start()] if m else rest)
    name = head.lstrip("%")
    return Op(
        name=name, opcode=opcode,
        label=f"{name} {opcode} {result}"[:120],
        kernel=opcode == "custom-call" and "tpu_custom_call" in rest,
        collective=bool(COLLECTIVE.match(opcode)),
        container=opcode in CONTAINERS)


@dataclasses.dataclass
class Chip:
    busy_ns: float = 0.0
    kernel_ns: float = 0.0
    collective_ns: float = 0.0
    ops: dict = dataclasses.field(default_factory=dict)   # name -> ns
    gaps: list = dataclasses.field(default_factory=list)  # (s, e)


@dataclasses.dataclass
class Summary:
    window_ns: float
    chips: dict                 # chip id -> Chip
    idle_gaps: list             # [(host span name, seconds)], longest first
    device_ops: list            # [(op name, seconds)], most time first


def load(logdir: str) -> list[tuple]:
    """Events of the newest ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    events = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    events += [(("device", int(m.group(1))), e.name,
                                e.start_ns, e.end_ns) for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events += [(("host", line.name), e.name, e.start_ns,
                            e.end_ns) for e in line.events
                           if e.name in HOST_SPANS]
    return events


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _overlap(a, b):
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def summarize(events, top: int = 10) -> Summary:
    """Reduce ``events`` to per-chip busy, kernel, collective and idle time
    inside the (last) ``window`` host span."""
    windows = [(s, e) for (kind, _), name, s, e in events
               if kind == "host" and name == "window"]
    if not windows:
        raise ValueError("the trace holds no 'window' host span")
    w = windows[-1]
    spans = [(name, (s, e)) for (kind, _), name, s, e in events
             if kind == "host" and name != "window"
             and _overlap((s, e), w) > 0]
    chips: dict[int, Chip] = {}
    ops: dict[str, Op] = {}
    ran: dict[int, list] = {}
    for (kind, where), text, s, e in events:
        if kind != "device":
            continue
        chip = chips.setdefault(where, Chip())
        if text not in ops:
            ops[text] = op_of(text)
        op = ops[text]
        s, e = max(s, w[0]), min(e, w[1])
        if e <= s or op.container:
            continue
        chip.ops[op.label] = chip.ops.get(op.label, 0.0) + (e - s)
        chip.kernel_ns += (e - s) * op.kernel
        chip.collective_ns += (e - s) * op.collective
        ran.setdefault(where, []).append((s, e))
    for where, chip in chips.items():
        busy = union(ran.get(where, []))
        chip.busy_ns = sum(e - s for s, e in busy)
        edges = [w[0]] + [x for iv in busy for x in iv] + [w[1]]
        chip.gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]

    idle = []
    if chips:
        worst = min(chips.values(), key=lambda c: c.busy_ns)
        for gap in sorted(worst.gaps, key=lambda g: g[0] - g[1])[:top]:
            cover = {}
            for name, iv in spans:
                cover[name] = cover.get(name, 0.0) + _overlap(gap, iv)
            inner = [name for name, iv in sorted(
                spans, key=lambda x: x[1][1] - x[1][0])
                if 2 * _overlap(gap, iv) >= gap[1] - gap[0]]
            best = inner[0] if inner else max(cover, key=cover.get,
                                               default=None)
            label = best if best and cover[best] > 0 else "host:other"
            idle.append((label, (gap[1] - gap[0]) / 1e9))
    totals: dict[str, float] = {}
    for chip in chips.values():
        for name, t in chip.ops.items():
            totals[name] = totals.get(name, 0.0) + t / 1e9 / len(chips)
    device_ops = sorted(totals.items(), key=lambda x: -x[1])[:top]
    return Summary(window_ns=w[1] - w[0], chips=chips, idle_gaps=idle,
                   device_ops=device_ops)


def mean(values) -> float:
    return float(np.mean(list(values)))
