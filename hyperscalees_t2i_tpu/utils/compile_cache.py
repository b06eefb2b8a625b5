"""Where the persistent XLA compile cache lives — one rule, one function.

The cache directory is part of every entry's key, so a directory that moves
never hits. The rule every entry point (``train.cli.main``,
``ServeEngine.__init__``, ``bench.py``, ``chip_smoke.py``,
``__graft_entry__.py``) applies through :func:`place_compile_cache`:

- ``JAX_COMPILATION_CACHE_DIR`` set → the cache is there; nothing in this
  repository points it anywhere else. The environment variable is the
  interface: a runner that wants the cache somewhere (under an output
  directory that survives a throw-away machine, say) sets it for the
  command it launches.
- unset → ``<checkout>/.jax_cache``, derived from this file's own location,
  the same path on every run of the same checkout.

Afterwards the environment and ``jax.config`` agree, so children inherit the
directory and ``obs.metrics.compile_cache_entries`` reads the one in use.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def place_compile_cache() -> str:
    """Apply the rule above; returns the directory in use. Call once per
    entry point, before the first compile."""
    cache_dir = os.environ.get(ENV)
    if not cache_dir:
        cache_dir = str(CHECKOUT_CACHE)
        os.environ[ENV] = cache_dir
    os.makedirs(cache_dir, exist_ok=True)
    import jax

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
