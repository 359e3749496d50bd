"""Where JAX's persistent compilation cache lives — the ONE setter.

Every process that compiles (worker CLI, benchmarks/chip/,
chip_smoke.py, the tests) calls :func:`enable_compile_cache` before its
first compile.  The directory is part of the cache key, so it must not
move between runs: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX's own
handling of it stands and nothing is set here; otherwise the cache is a
fixed, git-ignored directory inside the checkout.
"""

from __future__ import annotations

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
