"""Faults planted under the timed path, to show that ``correct`` catches
them (``tests/bench`` and the control runs on the chip; the benchmark's
own runs plant none).

* ``control``     — the reference's own coloring with its conflict
  resolution left out, in the program's place (``Reference.control``);
* ``unchanged``   — the step returns the state it was given (``colors0``);
* ``half``        — every second active cell is left out of the request;
* ``altered``     — one answer is altered where it is produced: an active
  cell takes its first neighbor's color;
* ``no_exchange`` — the exchange between parts delivers nothing: every
  ghost color a part reads stays 0.
"""
from __future__ import annotations

import contextlib

import numpy as np

from bench.requests import Request
from bench.system import Answer

FAULTS = ("control", "unchanged", "half", "altered", "no_exchange")


@contextlib.contextmanager
def planted_in_program(fault: str | None, exchange: str):
    """Patch the program for faults that live inside it (while built)."""
    if fault != "no_exchange":
        yield
        return
    import jax.numpy as jnp

    from repro.core.exchange import get_exchange
    from repro.core.plan import default_plan_cache

    default_plan_cache().clear()        # no plan compiled without the fault
    cls = type(get_exchange(exchange))
    device, stacked = cls.device, cls.stacked

    def mute(fn):
        def wrapped(self, *a, **kw):
            ghost, nbytes, state = fn(self, *a, **kw)
            return jnp.zeros_like(ghost), nbytes, state
        return wrapped

    cls.device, cls.stacked = mute(device), mute(stacked)
    try:
        yield
    finally:
        cls.device, cls.stacked = device, stacked


def wrap(fault: str | None, call, ref):
    """``call(request) -> Answer`` with ``fault`` planted around it."""
    if fault in (None, "no_exchange"):
        return call
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r} (have {FAULTS})")
    n = ref.g.n

    def active(req):
        return np.ones(n, bool) if req.mask is None else req.mask

    def faulty(req):
        if fault == "control":
            return Answer(ref.control(req.mask, req.colors0))
        if fault == "unchanged":
            c0 = (np.zeros(n, np.int32) if req.colors0 is None
                  else req.colors0)
            return Answer(c0.copy())
        if fault == "half":
            mask = active(req).copy()
            mask[np.flatnonzero(mask)[1::2]] = False
            return call(Request(mask, req.colors0))
        ans = call(req)                                    # "altered"
        csr = ref.csr
        v = int(np.flatnonzero(active(req) & (csr.sizes() > 0))[0])
        ans.colors = ans.colors.copy()
        ans.colors[v] = ans.colors[csr.indices[csr.indptr[v]]]
        return ans

    return faulty
