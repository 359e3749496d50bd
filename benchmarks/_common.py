"""Shared benchmark bootstrap: repo-root import path + the compile cache.

Imported for its side effects at the top of every benchmark script.
Initializes no JAX backend: each script is one process, and the scripts
that start children keep the parent off the device.
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # repo root

from crowdllama_tpu.utils.jaxcache import enable_compile_cache  # noqa: E402

enable_compile_cache()


def device_info() -> dict:
    """The device this process measures on, as JAX reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "count": len(devs)}


def emit(result: dict) -> None:
    """Print a script's final JSON line; every result names its device."""
    result.setdefault("device", device_info())
    print(json.dumps(result))
