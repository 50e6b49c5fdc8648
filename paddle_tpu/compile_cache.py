"""Where XLA's persistent compilation cache lives.

One function, called by every entry point that compiles at real size
(`chip_smoke.py`, `benchmarks/run.py`) before its first compile.  A
later run finds the cache only where the
earlier one left it, so it is either where the environment says or at
one fixed place in the checkout — never a temp name, a pid or a time.
(The checkout's own path is part of every entry's key: a moved
checkout starts cold even with the cache directory kept — PERF.md.)
Tests do not turn it on.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory.  With `JAX_COMPILATION_CACHE_DIR` set, jax reads it
    itself and nothing is set in code; otherwise the cache goes to
    `<checkout>/.jax_cache` (git-ignored)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
