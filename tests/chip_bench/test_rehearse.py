"""The whole flow at tiny size on the CPU: three node processes through the
CLI's main, the load generator, the scrapes, the reference check in its own
process — so that a later PR cannot break the harness unseen."""

import json
import os
import subprocess
import sys

from conftest import CHIP_DIR


def test_run_py_rehearse_prints_a_cpu_result_and_no_device_metric():
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS",)}      # one CPU device, as a worker has
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, str(CHIP_DIR / "run.py"), "--rehearse",
         "--workload", "mistral7b.chat_open", "--seed", "2147483659",
         "--seconds", "5", "--trace", "1"],
        capture_output=True, text=True, timeout=400, env=env)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 8
    bench = json.loads((CHIP_DIR.parents[1] / "BENCHMARK.json").read_text())
    device_metrics = {m["name"] for m in bench["per_layer"]
                      if m["source"] == "device_trace"}
    assert not device_metrics & set(line["metrics"])
    assert "busy_s" not in line["device"] and "breakdown" not in line
    # spans and counters are read also on the CPU
    assert {"gateway.self_ms_p50", "sched.queue_wait_ms_p80",
            "engine.compiles_in_window"} <= set(line["metrics"])
    assert "frames/token 1.0000" in p.stdout
