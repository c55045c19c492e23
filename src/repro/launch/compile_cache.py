"""Where the entry points keep JAX's persistent compilation cache.

Every ``main()`` (``repro.launch.serve``, ``chip_smoke.py``, the
benchmark mains, ``tools/compile_gate.py``) calls
:func:`enable_compile_cache` first thing, never at import, so importing
the package never changes a process's JAX configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path inside the checkout (git-ignored): the cache is keyed by
# what is compiled, and a directory that moved between runs never hits.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
