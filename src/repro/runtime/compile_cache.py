"""Persistent XLA compilation cache for the entry points.

Every process that reaches an accelerator pays its compiles again unless
JAX's persistent cache is on.  :func:`enable_compile_cache` turns it on
and is called only from ``__main__`` paths (``chip_smoke.py``,
``repro.api.cli``, ``benchmarks.run``, ``benchmarks.serve_bench``) —
never at import, so library users and the tests keep JAX's defaults.

The rule: if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing else is set here; otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (git-ignored).  The path is part of the cache
key, so it must not move between runs.
"""
from __future__ import annotations

import os

import jax

#: the repository checkout this package is imported from
#: (``<checkout>/src/repro/runtime/compile_cache.py``)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
