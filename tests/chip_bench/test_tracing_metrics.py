"""The per-layer metrics PR 23 added: their data files name what the
program records, and ``counter_ratio``'s arithmetic."""

import json

import pytest
from conftest import CHIP_DIR

from harness import reducers

BENCH = json.loads((CHIP_DIR.parents[1] / "BENCHMARK.json").read_text())

PR23_METRICS = {   # metric -> the program's series or span it reads
    "sched.dispatch_wait_ms_p50": "dispatch_wait",
    "step.prefill_exec_ms_p50": "prefill_exec",
    "step.decode_wall_ms": "crowdllama_engine_flight_seconds_total",
    "sched.slot_fill_share": "crowdllama_useful_tokens_total",
    "sched.slot_fill_share.served": "crowdllama_useful_tokens_total",
    "sched.prefix_reuse_share": "crowdllama_prefix_tokens_reused_total",
    "setup.weights_s": "crowdllama_startup_seconds",
    "setup.warmup_s": "crowdllama_startup_seconds",
}


@pytest.mark.parametrize("metric", sorted(PR23_METRICS))
def test_the_tracing_metrics_read_what_the_program_records(metric):
    """Each names a span the worker records or a family its /metrics
    serves (obs/trace.py, obs/metrics.py), and is in BENCHMARK.json."""
    from crowdllama_tpu.obs import metrics as obs_metrics
    from crowdllama_tpu.obs import trace as obs_trace

    assert metric in {m["name"] for m in BENCH["per_layer"]}
    text = json.dumps(reducers.spec("layer_metrics", metric))
    assert PR23_METRICS[metric] in text
    known = ([v for v in vars(obs_trace).values() if isinstance(v, str)]
             if "span" in text
             else "\n".join(obs_metrics.ENGINE_TELEMETRY.expose()))
    assert PR23_METRICS[metric] in known


def test_counter_ratio_is_growth_over_growth_between_the_windows_scrapes():
    from harness import reducers
    from harness.reducers import counter_ratio

    start = """# TYPE f_seconds_total counter
f_seconds_total{dispatch="plain"} 1.5
f_seconds_total{dispatch="megastep"} 0
f_seconds_total{dispatch="ragged"} 9
f_steps_total{dispatch="plain"} 100
f_steps_total{dispatch="megastep"} 0
f_steps_total{dispatch="ragged"} 10
useful_total 50
waste_total 50
"""
    end = (start.replace('"plain"} 1.5', '"plain"} 4.5')
           .replace('"megastep"} 0\nf_seconds_total{dispatch="ragged"}',
                    '"megastep"} 1\nf_seconds_total{dispatch="ragged"}')
           .replace('"plain"} 100', '"plain"} 180')
           .replace('"megastep"} 0\nf_steps_total{dispatch="ragged"}',
                    '"megastep"} 20\nf_steps_total{dispatch="ragged"}')
           .replace("useful_total 50", "useful_total 350")
           .replace("waste_total 50", "waste_total 150"))
    run = reducers.RunData(records=[], seconds=10.0, config={})
    run.scrapes = {"worker": {"start": start, "end": end}}

    def terms(family):
        return [{"family": family, "labels": {"dispatch": c}}
                for c in ("plain", "megastep")]

    # (3.0 + 1.0) s over (80 + 20) steps; the ragged class is not asked for
    assert counter_ratio.reduce(
        {"node": "worker", "num": terms("f_seconds_total"),
         "den": terms("f_steps_total"), "scale": 1000.0}, run
    ) == pytest.approx(40.0)
    share = {"node": "worker", "num": [{"family": "useful_total"}],
             "den": [{"family": "useful_total"}, {"family": "waste_total"}],
             "scale": 100.0}
    assert counter_ratio.reduce(share, run) == pytest.approx(75.0)
    # a program without the family, a denominator that did not grow, a
    # node that was not scraped: nothing, and no exception
    assert counter_ratio.reduce(
        {**share, "num": [{"family": "absent_total"}]}, run) is None
    run.scrapes["worker"]["end"] = start
    assert counter_ratio.reduce(share, run) is None
    assert counter_ratio.reduce({**share, "node": "gateway"}, run) is None
