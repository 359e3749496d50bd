"""The whole flow at tiny size on the CPU: three node processes through the
CLI's main, the load generator, the scrapes, the reference check in its own
process — so that a later PR cannot break the harness unseen."""

import json
import subprocess
import sys

from conftest import CHIP_DIR, cpu_env


import pytest


def rehearse(cell: str, trace: int, chip_dir=CHIP_DIR) -> tuple[dict, str]:
    p = subprocess.run(
        [sys.executable, str(chip_dir / "run.py"), "--rehearse",
         "--workload", cell, "--seed", "2147483659",
         "--seconds", "5", "--trace", str(trace)],
        capture_output=True, text=True, timeout=400, env=cpu_env())
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = p.stdout.strip().splitlines()[-1]
    assert sum(ln.startswith("{") for ln in p.stdout.splitlines()) == 1
    return json.loads(last), p.stdout


@pytest.mark.parametrize("trace", [1, 2])
def test_run_py_rehearse_prints_a_cpu_result_and_no_device_metric(trace):
    line, out = rehearse("mistral7b.chat_open", trace)
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 8
    bench = json.loads((CHIP_DIR.parents[1] / "BENCHMARK.json").read_text())
    device_metrics = {m["name"] for m in bench["per_layer"]
                      if m["source"] == "device_trace"}
    assert not device_metrics & set(line["metrics"])
    assert "busy_s" not in line["device"] and "breakdown" not in line
    # spans and counters are read also on the CPU, through the worker's own
    # series (no log line, no sampler): the old readers and the new
    assert {"gateway.self_ms_p50", "sched.queue_wait_ms_p80",
            "engine.compiles_in_window", "sched.prefix_hit_share",
            "sched.dispatch_wait_ms_p50", "step.prefill_exec_ms_p50",
            "step.decode_wall_ms", "sched.slot_fill_share.served",
            "sched.prefix_reuse_share", "setup.weights_s",
            "setup.warmup_s"} <= set(line["metrics"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["sched.dispatch_wait_ms_p50"] + m[
        "step.prefill_exec_ms_p50"] <= 2 * m["step.prefill_ms_p50"]
    # --trace 2: one last line with both groups of metrics, the end-to-end
    # ones from the window; --trace 1: the per-layer ones alone
    e2e = {"ttft_p80_ms", "itl_p95_ms", "setup_s"}
    assert (e2e <= set(m)) if trace == 2 else not (e2e & set(m))
    assert "frames/token 1.0000" in out
    assert ("profiler on for" in out) == (trace == 2)
