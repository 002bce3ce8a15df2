"""One home for JAX's persistent compilation cache.

Every entry point (``chip_smoke.py``, ``launch/serve_bcnn.py``,
``launch/serve.py``, ``launch/train_bcnn.py``, and the benchmark's
``bench/harness.py`` behind ``bench/run.py``) calls ``enable()`` before its
first compile, so a second cold run in the same checkout reads its
executables back instead of compiling them again.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets no directory.
* unset, on a TPU: the cache lives in ``CACHE_DIR_NAME`` at the root of the
  checkout. The path is fixed — never built from a temp name, a pid or the
  time — because a cache whose directory moves is never hit.
* unset, on any other backend: nothing changes. A CPU run (the tests, with
  interpret-mode Pallas) compiles in seconds, and a cached CPU executable
  is tied to the CPU features of the host that compiled it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR_NAME = ".jax_compile_cache"
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_dir() -> Path:
    """``<checkout>/.jax_compile_cache`` (this file is
    ``<checkout>/src/repro/launch/compile_cache.py``)."""
    return Path(__file__).resolve().parents[3] / CACHE_DIR_NAME


def enable() -> str | None:
    """Turn the persistent cache on; returns the directory in use (None
    when there is none). Every compile is cached, however short: a cold
    start pays for many small kernel programs, not one large one."""
    path = os.environ.get(ENV_VAR)
    if not path:
        if jax.default_backend() != "tpu":
            return None
        path = str(default_dir())
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
