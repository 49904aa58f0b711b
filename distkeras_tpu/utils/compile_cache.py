"""Persistent XLA compilation cache for the entry scripts.

A machine that starts cold pays every compile again (tens of seconds for the
larger epoch programs), so the scripts that run on a chip — ``chip_smoke.py``,
``benchmark/run.py``, ``examples/*.py`` — call :func:`enable_compile_cache`
before their first jit.  It is deliberately NOT called at package import or
from the test suite: a library import must not start writing to disk, and the
tests must compile what they test.

Where the cache lives is the entry script's to say, not this library's:
where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this
function sets nothing; otherwise the script names its own checkout and the
cache is ``<checkout>/.jax_cache`` (git-ignored) — a fixed path, so the next
run finds it.
"""

from __future__ import annotations

import os

__all__ = ["enable_compile_cache"]


def enable_compile_cache(checkout: str) -> str:
    """Turn JAX's persistent compilation cache on; return the directory.
    ``checkout`` is the root of the calling script's checkout."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
