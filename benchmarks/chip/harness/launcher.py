"""Start the system under test: DHT node, worker and gateway as three child
processes through the program's own CLI ``main`` (copied from
``chip_smoke.py``, PR 21).  The parent never imports JAX — a chip belongs to
one process at a time, and that process is the worker.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

from . import synth_tokenizer

CHIP_DIR = Path(__file__).resolve().parents[1]          # benchmarks/chip
ROOT = CHIP_DIR.parents[1]                              # the checkout
RUN_DIR = CHIP_DIR / "_run"                             # git-ignored


class BenchFailure(Exception):
    """The run cannot give a result; the command exits non-zero.  ``child``
    names the process that died or answered wrongly and ``log`` its log
    file, where there is one: run.py keeps the log's last lines in the
    run's ``failure.json``."""

    def __init__(self, message: str, *, child: str | None = None,
                 log: Path | None = None) -> None:
        super().__init__(message)
        self.child, self.log = child, log


def cache_dir() -> str:
    """JAX's persistent compile cache: where the machine placed it, else a
    fixed directory inside the checkout (the path is part of the cache key)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")


def child_env(extra: dict[str, str] | None = None) -> dict[str, str]:
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": str(ROOT) + os.pathsep + str(CHIP_DIR),
        "PYTHONUNBUFFERED": "1",
        # compressed discovery/advertise intervals: three nodes on one
        # machine find each other in a second or two instead of tens
        "CROWDLLAMA_TPU_TEST_MODE": "1",
        "JAX_COMPILATION_CACHE_DIR": cache_dir(),
        # every program is cached, also the many small weight-init ones
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
        "TPU_LOG_DIR": "disabled",
    })
    env.pop("BENCH_RUN", None)
    env.update(extra or {})
    return env


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: Path, n: int = 40) -> str:
    try:
        return "\n".join(path.read_text(errors="replace").splitlines()[-n:])
    except OSError as e:
        return f"<{path}: {e}>"


def http_request(method: str, port: int, path: str,
                 timeout: float = 10.0) -> str:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path)
        resp = conn.getresponse()
        body = resp.read().decode()
        if resp.status != 200:
            raise BenchFailure(
                f"{method} :{port}{path} -> {resp.status}: {body}")
        return body
    finally:
        conn.close()


def http_get(port: int, path: str, timeout: float = 10.0) -> str:
    return http_request("GET", port, path, timeout)


def write_model_dir(config: dict) -> Path:
    """The configuration as a directory the worker serves from: the model's
    public ``config.json`` (the configuration file's own top-level keys) and
    the synthetic tokenizer.  No safetensors, so the worker's seeded random
    int8 init makes the weights."""
    model_dir = RUN_DIR / "models" / config["bench"]["name"]
    model_dir.mkdir(parents=True, exist_ok=True)
    hf = {k: v for k, v in config.items() if k != "bench"}
    (model_dir / "config.json").write_text(json.dumps(hf, indent=1))
    if not (model_dir / "tokenizer.json").exists():
        synth_tokenizer.write_tokenizer(model_dir, hf["vocab_size"])
    return model_dir


class Nodes:
    """The three node processes; always terminated and waited for."""

    def __init__(self, out_dir: Path) -> None:
        self.out = out_dir
        self.procs: list[tuple[str, subprocess.Popen, Path]] = []
        self.ports: dict[str, int] = {}
        self.died: list[tuple[str, int, Path]] = []   # see stop()
        self.t_start = time.monotonic()

    def _start(self, name: str, argv: list[str],
               env: dict[str, str]) -> subprocess.Popen:
        log = self.out / f"{name}.log"
        with log.open("w") as f:
            proc = subprocess.Popen(
                [sys.executable, *argv], cwd=ROOT, env=env,
                stdout=f, stderr=subprocess.STDOUT)
        self.procs.append((name, proc, log))
        return proc

    def start(self, config: dict, model_dir: Path, *,
              extra_worker_flags: list[str], require_chips: int) -> None:
        """The same flags and environment whatever the run traces: the
        worker's profiler is started and stopped over its own HTTP control
        (``--profile-dir``), and the span rings' default holds a window."""
        b = config["bench"]
        self.ports = {k: free_port() for k in (
            "dht", "worker", "metrics", "gateway_p2p", "gateway")}
        boot = f"127.0.0.1:{self.ports['dht']}"
        keys = RUN_DIR / "keys"
        keys.mkdir(parents=True, exist_ok=True)
        self.t_start = time.monotonic()
        self._start("dht", [
            "-m", "crowdllama_tpu.cli.dht", "start",
            "--port", str(self.ports["dht"]), "--host", "127.0.0.1",
            "--key-path", str(keys / "dht.key")], child_env())
        flags = [*b["worker_flags"], *extra_worker_flags]
        wenv = dict(b.get("worker_env") or {})
        # Through the benchmark's worker_main, which checks the devices and
        # then calls the CLI's main unchanged.
        wenv["BENCH_DEVICE_FILE"] = str(self.out / "device.json")
        wenv["BENCH_REQUIRE_TPU_CHIPS"] = str(require_chips)
        self._start("worker", [
            str(CHIP_DIR / "harness" / "worker_main.py"),
            "start", "--worker-mode", "--model", b["name"],
            "--model-path", str(model_dir), *flags,
            "--profile-dir", str(self.out / "profile"),
            "--bootstrap-peers", boot,
            "--listen-port", str(self.ports["worker"]),
            "--worker-metrics-port", str(self.ports["metrics"]),
            "--key-path", str(keys / "worker.key")], child_env(wenv))
        self._start("gateway", [
            "-m", "crowdllama_tpu.cli.main", "start",
            "--bootstrap-peers", boot,
            "--listen-port", str(self.ports["gateway_p2p"]),
            "--gateway-port", str(self.ports["gateway"]),
            "--key-path", str(keys / "gateway.key")],
            child_env({"JAX_PLATFORMS": "cpu"}))

    def assert_alive(self) -> None:
        for name, proc, log in self.procs:
            if proc.poll() is not None:
                raise BenchFailure(
                    f"{name} exited with code {proc.returncode}",
                    child=name, log=log)

    def wait_ready(self, timeout: float) -> dict:
        """Until the gateway's /api/health shows the worker."""
        while True:
            self.assert_alive()
            if time.monotonic() - self.t_start > timeout:
                raise BenchFailure(
                    f"no worker behind the gateway after {timeout:.0f}s",
                    child="worker", log=self.out / "worker.log")
            try:
                health = json.loads(
                    http_get(self.ports["gateway"], "/api/health"))
                if health.get("worker_count", 0) >= 1:
                    return health
            except (OSError, ValueError, BenchFailure):
                pass
            time.sleep(0.5)

    def stop(self) -> None:
        """Terminate and wait for every child; ``died`` keeps (name, exit
        code, log) of those that had ended by themselves before."""
        self.died += [(name, proc.returncode, log)
                      for name, proc, log in self.procs
                      if proc.poll() is not None]
        for _, proc, _ in reversed(self.procs):
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + 45
        for _, proc, _ in self.procs:
            try:
                proc.wait(max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        self.procs = []
