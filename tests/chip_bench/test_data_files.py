"""Every data file loads, and BENCHMARK.json hangs together."""

import json
import re

import pytest
from conftest import CHIP_DIR

from harness import costs, generators, reducers

ROOT = CHIP_DIR.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def reported(group, cell):
    return {m["name"] for m in BENCH[group]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("kind", ["configs", "traffic", "e2e_metrics",
                                  "layer_metrics"])
def test_every_data_file_loads(kind):
    files = sorted((CHIP_DIR / kind).glob("*"))
    assert files
    for f in files:
        assert f.suffix == ".json" and NAME.match(f.stem), f
        assert isinstance(json.loads(f.read_text()), dict)


@pytest.mark.parametrize("mix", sorted(
    p.stem for p in (CHIP_DIR / "traffic").glob("*.json")))
def test_every_traffic_file_names_a_generator_and_rehearses(mix):
    t = json.loads((CHIP_DIR / "traffic" / f"{mix}.json").read_text())
    assert hasattr(generators.load(t["generator"]), "plan")
    ctx = {"seed": 1, "seconds": 4.0, "slots": 4, "context": 256,
           "vocab_size": 512}
    plan = generators.build_plan({**t, **t["rehearsal"]}, ctx)
    assert plan.actors and plan.ramp_s > 0


@pytest.mark.parametrize("kind,group", [("e2e_metrics", "end_to_end"),
                                        ("layer_metrics", "per_layer")])
def test_every_metric_has_a_reader(kind, group):
    import importlib

    for m in BENCH[group]:
        if m["name"] == "setup_s":
            continue
        s = reducers.spec(kind, m["name"])
        mod = importlib.import_module(f"harness.reducers.{s['reducer']}")
        assert callable(mod.reduce)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files_and_reports_enough(cell):
    w = next(x for x in BENCH["workloads"] if x["name"] == cell)
    cfg = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    conf = json.loads((ROOT / cfg["file"]).read_text())
    assert (CHIP_DIR / "traffic" / f"{w['traffic']}.json").exists()
    assert conf["bench"]["chips"] == w["chips"] in (1, 4)
    assert conf["bench"]["source"] == cfg["source"]
    assert conf["bench"]["reduced"] == cfg["reduced"]
    e2e = reported("end_to_end", cell)
    assert "setup_s" in e2e and len(e2e) >= 2
    assert reported("per_layer", cell)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_moves_names_an_end_to_end_metric_of_every_cell_it_is_in(metric):
    m = next(x for x in BENCH["per_layer"] if x["name"] == metric)
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        assert m["moves"] in reported("end_to_end", cell), (metric, cell)


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer",
                          "trace_in_run"}
    assert BENCH["trace_in_run"] is True
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [m["name"] for g in ("end_to_end", "per_layer") for m in BENCH[g]]
    assert len(names) == len(set(names)) and all(map(NAME.match, names))
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert layers <= {"client", "gateway", "p2p plane", "scheduler",
                      "engine start", "engine step", "kernels", "device"}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert "bound" not in m and set(m) <= {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(pairs) // 4)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert not any(k.endswith(("_dim", "_rank")) or "size" in k
                       for k in c["reduced"])


def test_peaks_unknown_device_is_an_error():
    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert costs.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("cpu", "TPU v9", "_source"):
        with pytest.raises(KeyError):
            costs.peaks(kind)


def test_costs_from_the_published_sizes():
    m = json.loads((CHIP_DIR / "configs" / "mistral-7b-int8.json").read_text())
    x = json.loads((CHIP_DIR / "configs" / "mixtral-8x7b-d4-int8.json"
                    ).read_text())
    # Mistral-7B: 218.1M matmul weights a layer x 32 + a 131M head
    assert costs.attn_weight_bytes(m) == 32 * 41_943_040
    assert costs.ffn_weight_bytes(m, 8) == 32 * 3 * 4096 * 14336
    assert costs.head_bytes(m) == 4096 * 32000
    assert m["bench"]["kv_bytes_per_token"] == 2 * 32 * 8 * 128 * 2
    assert x["bench"]["kv_bytes_per_token"] == 2 * 4 * 8 * 128 * 2
    # 16 tokens x top-2 touch nearly all 8 experts; one token exactly two
    assert costs.experts_touched(x, 1) == pytest.approx(2.0)
    assert 7.8 < costs.experts_touched(x, 16) < 8.0
    full = 4 * 8 * 3 * 4096 * 14336
    assert costs.ffn_weight_bytes(x, 1e9) == pytest.approx(full)
    assert costs.decode_step_bytes(m, 8, 8 * 256) == pytest.approx(
        32 * 218_103_808 + 4096 * 32000 + 8 * 256 * 131072)
