"""The per-layer metrics PR 40 added (``benchmarks/chip/TRACING.moe_banks.md``):
the banks an expert layer's decode steps route rows to and the banks its
grouped matmuls fetch, the share of short flights, the share of first tokens
the host read, and the two stamps of a worker's start — each a data file for
a reader the benchmark already had.  Their data files name what the program
records; a program that lacks a family gives nothing (the parent commit, on
which the driver runs these files too); and the two expert cells rehearsed
end to end on the CPU print them."""

import json
import subprocess
import sys

import pytest
from conftest import CHIP_DIR, cpu_env

from harness import reducers

BENCH = json.loads((CHIP_DIR.parents[1] / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
EXPERT_CELLS = ["nemotron3super-p1.decode_sat", "kimilinear-p1.reason_sat"]

# metric -> (the family it reads first, its entry in BENCHMARK.json)
PR40_METRICS = {
    "moe.banks_routed_share": ("crowdllama_moe_banks_total", {
        "unit": "%", "better": "lower", "layer": "engine step",
        "moves": "out_tokens_per_s", "workloads": EXPERT_CELLS}),
    "moe.banks_fetched_share": ("crowdllama_moe_banks_fetched_total", {
        "unit": "%", "better": "lower", "layer": "engine step",
        "moves": "out_tokens_per_s", "workloads": EXPERT_CELLS}),
    "sched.short_flight_share": ("crowdllama_engine_flights_total", {
        "unit": "%", "better": "higher", "layer": "scheduler",
        "moves": "itl_p95_ms", "workloads": CELLS[:5]}),
    "sched.host_first_token_share": ("crowdllama_admissions_total", {
        "unit": "%", "better": "lower", "layer": "scheduler",
        "moves": "itl_p95_ms", "workloads": ["mistral7b.chat_open"]}),
    "setup.ready_s": ("crowdllama_startup_seconds", {
        "unit": "s", "better": "lower", "layer": "engine start",
        "moves": "setup_s"}),
    "setup.process_s": ("crowdllama_startup_seconds", {
        "unit": "s", "better": "lower", "layer": "engine start",
        "moves": "setup_s"}),
}


@pytest.mark.parametrize("metric", sorted(PR40_METRICS))
def test_the_metric_is_listed_and_reads_what_the_program_records(metric):
    from crowdllama_tpu.obs.metrics import ENGINE_TELEMETRY

    family, entry = PR40_METRICS[metric][0], dict(PR40_METRICS[metric][1])
    listed = dict(next(m for m in BENCH["per_layer"] if m["name"] == metric))
    # (a later cell may be appended to a metric's list)
    assert set(listed.pop("workloads", [])) >= set(entry.pop("workloads", []))
    assert listed == {"name": metric, "source": "program_counter", **entry}
    spec = reducers.spec("layer_metrics", metric)
    assert spec["node"] == "worker"
    assert spec["reducer"] == ("gauge_value" if metric.startswith("setup.")
                               else "counter_ratio")
    # every series the file names is one the program's /metrics serves
    served = "\n".join(ENGINE_TELEMETRY.expose())
    terms = spec["num"] + spec["den"] if "num" in spec else [spec]
    assert terms[0]["family"] == family
    for t in terms:
        assert reducers.samples(served, t["family"], t.get("labels")), t


PARENT = """# TYPE crowdllama_moe_assignments_total counter
crowdllama_moe_assignments_total{held="yes"} 10
crowdllama_moe_assignments_total{held="no"} 30
# TYPE crowdllama_startup_seconds gauge
crowdllama_startup_seconds{phase="weights"} 3.100
crowdllama_startup_seconds{phase="warmup"} 9.600
crowdllama_startup_seconds{phase="ready"} 34.000
# TYPE crowdllama_admissions_total counter
crowdllama_admissions_total{first_token="device"} 7
crowdllama_admissions_total{first_token="host"} 1
# TYPE crowdllama_engine_flights_total counter
crowdllama_engine_flights_total{length="short"} 4
crowdllama_engine_flights_total{length="full"} 16
"""
BANKS = """# TYPE crowdllama_moe_banks_total counter
crowdllama_moe_banks_total{dispatch="plain",state="routed"} %d
crowdllama_moe_banks_total{dispatch="plain",state="unrouted"} %d
crowdllama_moe_banks_total{dispatch="ragged",state="routed"} %d
crowdllama_moe_banks_total{dispatch="ragged",state="unrouted"} 0
# TYPE crowdllama_moe_banks_fetched_total counter
crowdllama_moe_banks_fetched_total{dispatch="plain"} %d
crowdllama_moe_banks_fetched_total{dispatch="ragged"} %d
"""


def run_of(start: str, end: str) -> reducers.RunData:
    return reducers.RunData(records=[], seconds=5.0, config={},
                            scrapes={"worker": {"start": start, "end": end}})


def test_a_program_without_the_families_gives_nothing_and_does_not_raise():
    """The parent commit serves neither bank family nor the ``process``
    phase: their metrics are left out of its line; the three that read what
    earlier PRs recorded are read there as here."""
    end = (PARENT.replace('"device"} 7', '"device"} 60')
           .replace('"host"} 1', '"host"} 9')
           .replace('"short"} 4', '"short"} 104')
           .replace('"full"} 16', '"full"} 416'))
    run = run_of(PARENT, end)
    got = {m: reducers.compute("layer_metrics", m, run) for m in PR40_METRICS}
    assert got["moe.banks_routed_share"] is None
    assert got["moe.banks_fetched_share"] is None
    assert got["setup.process_s"] is None
    assert got["setup.ready_s"] == 34.0
    assert got["sched.short_flight_share"] == pytest.approx(100 * 100 / 500)
    assert got["sched.host_first_token_share"] == pytest.approx(100 * 8 / 61)


def test_the_shares_are_of_the_plain_flights_banks_alone():
    """128 held banks x 10 layers x 100 decode steps in the window, 60 of
    128 routed to, every one fetched; the ragged flights' prefill chunks,
    which route to every bank, dilute neither share."""
    start = PARENT + BANKS % (600, 680, 1280, 1280, 1280)
    end = PARENT + BANKS % (600 + 60_000, 680 + 68_000, 1280 + 12_800,
                            1280 + 128_000, 1280 + 12_800)
    end = end.replace('phase="ready"} 34.000', 'phase="ready"} 34.000\n'
                      'crowdllama_startup_seconds{phase="process"} 55.250')
    run = run_of(start, end)
    assert reducers.compute("layer_metrics", "moe.banks_routed_share", run
                            ) == pytest.approx(100 * 60 / 128)
    assert reducers.compute("layer_metrics", "moe.banks_fetched_share", run
                            ) == pytest.approx(100.0)
    assert reducers.compute("layer_metrics", "setup.process_s", run) == 55.25
    # a window with no plain step: nothing, not a division by zero
    assert reducers.compute("layer_metrics", "moe.banks_fetched_share",
                            run_of(start, start)) is None


@pytest.mark.parametrize("cell,seed", [
    (EXPERT_CELLS[0], "2147483659"),
    # test_kimi_linear_bench.py says why this cell rehearses on this seed
    (EXPERT_CELLS[1], "3000000001")])
def test_the_expert_cells_rehearse_with_their_banks_counted(cell, seed):
    p = subprocess.run(
        [sys.executable, str(CHIP_DIR / "run.py"), "--rehearse",
         "--workload", cell, "--seed", seed, "--seconds", "5",
         "--trace", "2"],
        capture_output=True, text=True, timeout=400, env=cpu_env())
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(PR40_METRICS) - {"sched.host_first_token_share"} <= set(m)
    # on the CPU lax.ragged_dot multiplies: it reads every held bank
    assert m["moe.banks_fetched_share"] == 100.0
    # the tiny models hold 8 of 16 experts and route 4 a token
    assert 0 < m["moe.banks_routed_share"] <= 100.0
    assert 35 < m["moe.held_assignment_share"] < 65
    # a closed loop flies a short flight at every admission, full ones between
    assert 0 < m["sched.short_flight_share"] < 100.0
    # the process started before its first import
    assert 0 < m["setup.ready_s"] < m["setup.process_s"] <= m["setup_s"]
    assert m["setup.weights_s"] + m["setup.warmup_s"] < m["setup.ready_s"]
