"""The worker, started by the benchmark: the program's CLI ``main`` called
unchanged, plus — in a traced run — a device trace bracketed by signals.

Only the process that holds the chip can trace it, and the program has no
worker-side profile trigger (PERF.md, Open questions), so the benchmark's
launcher carries one: with ``BENCH_PROFILE_DIR`` set, SIGUSR1 starts
``jax.profiler`` tracing into that directory and SIGUSR2 stops it.  The
signals only set events; a helper thread makes the profiler calls, so the
event loop of the node is never blocked inside a signal handler.  When the
trace is written the thread drops a ``done.json`` with the host's clock
at start and stop.

Before the CLI starts, the process asks JAX what it runs on, writes that to
``BENCH_DEVICE_FILE`` for the parent, and exits at once with code 3 when the
cell needs TPU chips that are not there (the program itself would spend
minutes initializing a 7B model on the CPU first).
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time


def _install_tracer(profile_dir: str) -> None:
    start, stop = threading.Event(), threading.Event()

    def tracer() -> None:
        start.wait()
        import jax

        # no Python tracer: it slows the host it is meant to observe and
        # makes the trace hundreds of MB; the runtime's own host events stay
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        t0 = time.monotonic()
        jax.profiler.start_trace(profile_dir, profiler_options=opts)
        t1 = time.monotonic()
        stop.wait()
        t2 = time.monotonic()
        jax.profiler.stop_trace()
        t3 = time.monotonic()
        with open(os.path.join(profile_dir, "done.json"), "w") as f:
            json.dump({"start_call": t0, "started": t1, "stop_call": t2,
                       "stopped": t3, "unix_at_started": time.time() - (
                           time.monotonic() - t1)}, f)

    os.makedirs(profile_dir, exist_ok=True)
    threading.Thread(target=tracer, name="bench-tracer", daemon=True).start()
    signal.signal(signal.SIGUSR1, lambda *_: start.set())
    signal.signal(signal.SIGUSR2, lambda *_: stop.set())


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
    profile_dir = os.environ.get("BENCH_PROFILE_DIR", "")
    if profile_dir:
        _install_tracer(profile_dir)
    from crowdllama_tpu.cli.main import main as cli_main

    return cli_main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
