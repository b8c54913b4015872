"""Where JAX's persistent compilation cache lives.

One rule, for every entry point that wants warm compiles across
processes (``chip_smoke.py``, ``bench.py``, ``scripts/heat_test.py``,
``tests/conftest.py``): where ``JAX_COMPILATION_CACHE_DIR`` is set the
deployment has placed the cache and JAX reads the variable itself —
nothing is set in code; where it is not, the cache goes to one fixed,
git-ignored directory inside the checkout.  The directory is part of the
cache key, so it never comes from ``tempfile``, a pid or the clock: a
directory that moves never hits.
"""

from __future__ import annotations

import os

import jax

__all__ = ["use_compile_cache"]

#: the fallback location: ``<checkout>/.jax_cache`` (listed in .gitignore)
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns the directory in
    effect.  Call before the first compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE)
    return _CHECKOUT_CACHE
