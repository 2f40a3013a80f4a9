"""Pytest bootstrap: prefer real deps, fall back to hermetic stand-ins.

The dev container is hermetic (no pip), so when ``hypothesis`` is absent
the property tests run against ``repro._compat.hypothesis_fallback`` — a
deterministic sampler with the same decorator surface.  CI installs the
real package and this shim is a no-op there.

The persistent compilation cache stays off for the test suite: the CLI
and the serving frontend enable it on first use (``launch/cache.py``),
and tests should neither write executables into the checkout nor restore
CPU executables compiled by another worker.
"""
import importlib.util
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "src"))

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)

if importlib.util.find_spec("hypothesis") is None:
    from repro._compat import hypothesis_fallback

    sys.modules["hypothesis"] = hypothesis_fallback
    sys.modules["hypothesis.strategies"] = hypothesis_fallback.strategies
