"""Where JAX's persistent compilation cache lives for this checkout.

One rule for every entry point (``serve_cv``, ``chip_smoke.py``), applied
before the first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; that directory
  is the cache and nothing here names another.
* unset: ``<checkout>/.jax_cache`` — a fixed path (listed in
  ``.gitignore``), because the path is part of what a later process must
  find again; a temporary, per-process or dated directory never hits.

Every program is cached (both size and compile-time thresholds are
zero), so a restarted server finds even the small ones. Errors raise:
a cache that silently fails to attach makes every boot cold without a
word.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "setup_compilation_cache"]

#: The default cache directory: ``.jax_cache`` at the checkout root.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compilation_cache() -> str:
    """Attach the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
