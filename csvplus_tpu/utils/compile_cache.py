"""Placement of JAX's persistent compilation cache.

Compiling is a large part of a cold run on the chip (the device sort
alone compiles for tens of seconds per row count), so entry-point
scripts call :func:`place_compile_cache` under ``__main__`` — never at
``import csvplus_tpu`` — before their first jitted call.

The directory is part of the cache key's lookup, so it must not move
between runs: where the caller placed the cache from outside
(``JAX_COMPILATION_CACHE_DIR``, which jax reads into its config at
import) nothing is touched; otherwise it goes to one fixed, git-ignored
path inside the checkout.  No temp name, pid or time in the path.

A CPU backend gets no cache of its own: its compiles are cheap, and
XLA:CPU's loader logs a machine-feature mismatch for every program it
reads back.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

#: Where the cache lives when nothing outside placed it.
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def place_compile_cache() -> "str | None":
    """Make sure a persistent compilation cache is configured; returns
    its directory (None on a CPU backend nobody placed one for).  Reads
    jax's config (not ``os.environ``), so an externally placed cache is
    left exactly as jax already honours it."""
    import jax

    placed = jax.config.jax_compilation_cache_dir
    if placed:
        return placed
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # the many sub-second programs between the big kernels add up on a
    # cold chip run; cache them all, not only compiles over jax's 1 s
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return DEFAULT_CACHE_DIR
