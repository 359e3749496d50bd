"""Where JAX's persistent compilation cache lives — the ONE setter.

Every process that compiles (worker CLI, benchmarks/chip/,
chip_smoke.py, the tests) calls :func:`enable_compile_cache` before its
first compile.  The directory is part of the cache key, so it must not
move between runs: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX's own
handling of it stands and nothing is set here; otherwise the cache is a
fixed, git-ignored directory inside the checkout.
"""

from __future__ import annotations

import contextlib
import os
from pathlib import Path

#: <checkout>/.jax_cache (listed in .gitignore and .chiprunignore).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"
#: The JAX option this module — and nothing else in the repo — sets.
CACHE_OPTION = "jax_compilation_cache_dir"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use.
    Imports jax but initializes no backend."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update(CACHE_OPTION, str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


@contextlib.contextmanager
def compile_cache_bypassed():
    """Inside the block nothing is compiled into, or loaded from, the
    persistent cache (process-wide: for a start-up step, not a hot path).

    For the one kind of program that does not survive it: an executable
    whose RESULT has a layout that is not the default.  Loaded back from
    the cache it still writes that layout but hands out a buffer labelled
    row-major, which every later program then misreads — seen on the chip
    and on the CPU backend alike with ``jax.device_put(x, Format(...))``
    (jax 0.9.0; PERF.md §6, PR 41).  Programs compiled for an ARGUMENT's
    layout load back sound."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
