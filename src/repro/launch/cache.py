"""Persistent jax compilation cache wiring.

The cold path of a coloring plan is dominated by the XLA compile of its
loop program (and, on TPU, the Mosaic compile of its kernels); jax can
persist compiled executables to disk so a relaunch on the same
topology/config key pays the host-state build only.  This module is the
one place that knob is set — the CLI (``launch/color.py``), the serving
frontend (``serve/coloring.py``) and ``chip_smoke.py`` call
:func:`enable_compilation_cache` before their first compile.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set — jax already reads it; the directory
  is left exactly as given and no other path is set in code.
* unset — one fixed directory inside the checkout, :data:`DEFAULT_DIR`
  (``<repo>/.jax_cache``, git-ignored), so a relaunch from the same
  checkout finds what the last one compiled; it never depends on a temp
  dir, pid or time.

``jax_enable_compilation_cache`` is left alone, so a caller that turned
the cache off (the test suite's ``conftest.py``) keeps it off.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compilation_cache", "DEFAULT_DIR"]

DEFAULT_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")
_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compilation_cache() -> str:
    """Point jax's persistent compilation cache at its directory.

    Returns the directory in use: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else :data:`DEFAULT_DIR`.  Idempotent.
    """
    import jax

    path = os.environ.get(_ENV) or DEFAULT_DIR
    if jax.config.jax_compilation_cache_dir != path:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        # jax initializes its cache at most once, on the first compile; a
        # compile that ran before this call latched the old setting.
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()
    # Persist every executable, however fast it compiled: the plans this
    # repo builds are many small programs, and the default min-compile-time
    # threshold would skip most of them.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
