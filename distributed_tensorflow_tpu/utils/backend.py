"""Process-level JAX backend setup, shared by every entry point that may
initialise a backend (``train.py``, the serve/export/autotune/determinism
tools, ``examples/serve.py``, ``perfbench/worker.py`` and the children of
``chip_smoke.py``).

Two decisions live here so no entry point carries its own copy:

- ``--platform``: an explicit platform override goes through
  ``jax.config`` before the first backend use.
- The persistent compilation cache.  Its directory is part of the cache
  key, so it must be the same path in every process and every run: where
  ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing is
  set in code; otherwise the cache lives at ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os

import jax

#: The in-checkout cache location (``.gitignore`` lists it).  A fixed path,
#: never a tempdir, pid or timestamp: a cache directory that moves never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_backend(platform: str | None = None) -> str | None:
    """Apply ``--platform`` and place the compile cache; call before the
    first backend use.  Returns the cache directory set in code, or None
    when ``JAX_COMPILATION_CACHE_DIR`` places it from outside."""
    if platform:
        jax.config.update("jax_platforms", platform)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR
