"""Where JAX's persistent compilation cache lives.

Each served model warms one executable per power-of-two bucket plus its
staged entry pads, so a fresh process compiles dozens of small programs.
JAX's persistent cache keeps them across processes. Entry points (the
examples, ``benchmarks.run``, ``chip_smoke.py``) call :func:`enable` once,
before their first compile; the library never turns the cache on by
itself, and the tests leave it off.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads that directory, and
  nothing here points it anywhere else.
* Not set: the cache goes to ``.jax_cache/`` at the checkout root. The path
  is fixed because it is part of what JAX looks entries up by.

Either way every compile is kept, however short: the bucket executables
of the paper models each compile in well under JAX's default one-second
threshold.
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


@contextlib.contextmanager
def disabled():
    """Compile without the persistent cache inside the block (a cold-boot
    measurement must really compile), then restore the previous state."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()  # JAX decides once per process whether the cache is used
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()
