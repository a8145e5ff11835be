"""JAX's persistent compilation cache, at a place that can be chosen from
outside.

A chip call starts with no compiled code; the LM step alone compiles for
the better part of a minute. The cache's directory is part of its key,
so it has to be the same path on every run: ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it (JAX reads that variable itself, and this
code then sets no other directory), else ``<checkout>/.jax_cache``.

A run that asked for the CPU (``JAX_PLATFORMS=cpu``: the tests and the
rehearsals) gets no cache from here: XLA:CPU logs a screen of
machine-feature warnings for every entry it reads back, and CPU entries
in the checkout would only ride along to the chip, which cannot use them.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str | None:
    """Turn the persistent compile cache on; returns its directory (None
    when left off). Call once at the top of an entry point, before the
    first compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return None
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # Cache every program, not only those that compile for over a second
    # (the default): a cold process also pays for hundreds of small ones.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
