"""The worker, started by the benchmark: the program's CLI ``main`` called
unchanged, after a check of the devices.  (A traced run starts and stops
the profiler over the worker's own ``POST /debug/profile/start|stop``.)

Before the CLI starts, the process asks JAX what it runs on, writes that to
``BENCH_DEVICE_FILE`` for the parent, and exits at once with code 3 when the
cell needs TPU chips that are not there (the program itself would spend
minutes initializing a 7B model on the CPU first).
"""

from __future__ import annotations

import json
import os
import sys


def _require_devices(need: int, out: str) -> None:
    """Fail at once — before minutes of weight init — when JAX finds no TPU
    or fewer chips than the cell asks for; say what was found otherwise."""
    import jax

    dev = jax.devices()
    found = {"platform": dev[0].platform, "kind": dev[0].device_kind,
             "count": len(dev)}
    with open(out, "w") as f:
        json.dump(found, f)
    if need and (found["platform"] != "tpu" or found["count"] < need):
        print(f"BENCH: the cell needs {need} TPU chip(s), JAX found {found}",
              flush=True)
        sys.exit(3)


def main() -> int:
    if os.environ.get("BENCH_DEVICE_FILE"):
        _require_devices(int(os.environ.get("BENCH_REQUIRE_TPU_CHIPS", "0")),
                         os.environ["BENCH_DEVICE_FILE"])
    from crowdllama_tpu.cli.main import main as cli_main

    return cli_main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
