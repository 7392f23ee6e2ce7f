"""One place that points JAX's persistent compilation cache at a fixed path.

JAX keys cache entries partly by the cache directory, so a directory that
moves between runs never hits.  ``enable_compile_cache`` therefore picks:

  * nothing, when ``JAX_COMPILATION_CACHE_DIR`` is set — JAX reads that
    variable itself and the program sets no other directory;
  * ``<checkout>/.jax_cache`` otherwise — one fixed in-checkout path (never
    a temp name, pid or time), listed in ``.gitignore``.

Entry points that compile at deployment size (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.run``) call it once at start-up.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


__all__ = ["DEFAULT_DIR", "enable_compile_cache"]
