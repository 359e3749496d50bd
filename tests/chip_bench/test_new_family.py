"""A new model family is added to the benchmark as NEW files only (PR 26).

The benchmark is copied to a temporary directory and a toy family added
there — reference module, limits file, cost module, a tiny configuration
that names itself as its rehearsal, one cell — without an edit to a file
that exists (BENCHMARK.json gains entries, as it must).  The loader, the
limits lookup, the cost dispatch and ``trace_hbm_share`` find the pieces,
``run.py --rehearse`` runs the family end to end, and each missing piece is
an error that names its file — never a silent dense reading."""

import json
import subprocess
import sys

import pytest
from conftest import CHIP_DIR, copy_of_the_benchmark, cpu_env

from harness import costs, reference

TOY_CONFIG = {
    "architectures": ["MixtralForCausalLM"], "hidden_size": 64,
    "intermediate_size": 128, "max_position_embeddings": 256,
    "model_type": "mixtral", "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 2,
    "num_local_experts": 4, "num_experts_per_tok": 2,
    "rms_norm_eps": 1e-06, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "vocab_size": 512,
    "bench": {
        "name": "toy-tiny", "source": "a test's toy family", "reduced": [],
        "assumed": [], "deployment": "none", "reference": "toy",
        "costs": "costs_toy", "rehearsal": "toy-tiny", "quantize": "int8",
        "slots": 4, "context": 256, "decode_chunk": 8,
        "kv_bytes_per_token": 256,
        "worker_flags": ["--quantize", "int8", "--kv-page-size", "32"],
        "worker_env": {"CROWDLLAMA_TPU_MAX_BATCH_SLOTS": "4",
                       "CROWDLLAMA_TPU_MAX_CONTEXT_LENGTH": "256",
                       "JAX_PLATFORMS": "cpu",
                       "CROWDLLAMA_PALLAS_INTERPRET": "1"},
        "sampling": {"temperature": 0.7, "top_p": 0.95}, "chips": 1,
        "ready_timeout_s": 300}}
TOY_FILES = {
    "configs/toy-tiny.json": json.dumps(TOY_CONFIG),
    "harness/reference/toy.py":
        '"""The toy family\'s plain reference."""\n'
        "from .moe import forward  # noqa: F401\n",
    "harness/reference/toy.tolerance.json": json.dumps(
        {"max_deficit": 0.5, "mean_deficit": 0.01, "set_from": "a test"}),
    "harness/costs_toy.py":
        '"""The toy family\'s bytes: 1000 a live token, 1 a KV token."""\n'
        "from .costs import kv_read_bytes  # noqa: F401\n\n\n"
        "def decode_step_bytes(c, tokens, kv_tokens):\n"
        "    return 1000.0 * tokens + kv_tokens\n",
}
PROBE = """
import json, sys
sys.argv = ["run.py"]
import run
from harness import costs, reducers, reference
from harness.reducers import trace_hbm_share
bench, cell, config, traffic = run.load_cell("toy.decode_sat", %(rehearse)r)
r = reducers.RunData(records=[], seconds=1.0, config=config)
r.device_kind = "TPU v5 lite"
r.gauge_samples = ["crowdllama_engine_batch_occupancy 0.5\\n"]
r.profile = {"devices": 1, "programs": {"jit__decode_paged_impl(1)": [0.004]},
             "ops": {"%%paged_decode_attention.8 = x": {
                 "count": 4, "self_s": 1e-5, "total_s": 1e-5,
                 "in_program": {"jit__decode_paged_impl(1)": [4, 1e-5]}}}}
spec = reducers.spec("layer_metrics", "step.decode_hbm_share")
print(json.dumps({
    "config": config["bench"]["name"], "reference": config["bench"]["reference"],
    "limits": reference.limits(config["bench"]["reference"]),
    "costs": costs.module_for(config).__name__,
    "kv_fn": costs.function(config, "costs.kv_read_bytes").__module__,
    "ffn_fn": costs.function(config, "costs.ffn_weight_bytes").__module__,
    "hbm_share": trace_hbm_share.reduce(spec, r)}))
"""


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """(root of the copy, its benchmarks/chip) with the toy family added."""
    root = tmp_path_factory.mktemp("family")
    chip, bench = copy_of_the_benchmark(root)
    before = {p.relative_to(chip): p.read_bytes()
              for p in chip.rglob("*") if p.is_file()}
    for rel, text in TOY_FILES.items():
        assert not (chip / rel).exists(), rel          # NEW files only
        (chip / rel).write_text(text)
    bench["configs"].append({
        "name": "toy-tiny", "source": "a test's toy family",
        "file": "benchmarks/chip/configs/toy-tiny.json", "reduced": [],
        "why": "a family added as files"})
    bench["workloads"].append({
        "name": "toy.decode_sat", "config": "toy-tiny",
        "traffic": "decode_sat", "chips": 1, "why": "the toy family's cell"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "mistral7b.decode_sat" in m.get("workloads", []):
            m["workloads"].append("toy.decode_sat")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    yield root, chip
    after = {p.relative_to(chip): p.read_bytes() for p in chip.rglob("*")
             if p.is_file() and "_run" not in p.parts
             and "__pycache__" not in p.parts}
    assert {k: after[k] for k in before} == before     # nothing was edited


def python(chip, code):
    return subprocess.run([sys.executable, "-c", code], cwd=chip,
                          env=cpu_env(), capture_output=True, text=True,
                          timeout=120)


@pytest.mark.parametrize("rehearse", [False, True])
def test_the_pieces_of_a_family_added_as_files_are_found(copy, rehearse):
    _, chip = copy
    p = python(chip, PROBE % {"rehearse": rehearse})
    assert p.returncode == 0, p.stderr[-2000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["config"] == "toy-tiny" and got["reference"] == "toy"
    assert got["limits"]["max_deficit"] == 0.5
    assert got["limits"]["mean_deficit"] == 0.01
    assert got["costs"] == "harness.costs_toy"
    # a roofline metric's bytes function: the family's own where it has
    # one of that name, else the module the metric names
    assert got["kv_fn"] == "harness.costs" and got["ffn_fn"] == "harness.costs"
    # 4 calls / 2 layers = 2 steps of 2 ms; half of 4 slots = 2 tokens,
    # no KV: 2000 bytes a step by the toy's formula
    assert got["hbm_share"] == pytest.approx(100 * 2000.0 / (0.002 * 819e9))
    assert "info: pieces: reference toy" in p.stdout
    assert "costs costs_toy" in p.stdout


MISSING = {
    "harness/costs_toy.py": "harness/costs_toy.py",
    "harness/reference/toy.tolerance.json": "reference/toy.tolerance.json",
    "harness/reference/toy.py": "harness/reference/toy.py",
    "configs/toy-tiny.json": "configs/toy-tiny.json",
}


@pytest.mark.parametrize("gone", sorted(MISSING))
def test_each_missing_piece_is_an_error_that_names_its_file(copy, gone):
    root, chip = copy
    kept = (chip / gone).read_text()
    if gone.startswith("configs/"):
        # the rehearsal configuration it names is the file that is missing
        cfg = json.loads(kept)
        cfg["bench"]["rehearsal"] = "toy-tinier"
        (chip / gone).write_text(json.dumps(cfg))
        want = "configs/toy-tinier.json"
    else:
        (chip / gone).unlink()
        want = MISSING[gone]
    try:
        p = subprocess.run(
            [sys.executable, str(chip / "run.py"), "--rehearse", "--workload",
             "toy.decode_sat", "--seconds", "3"], capture_output=True,
            text=True, timeout=120, env=cpu_env())
    finally:
        (chip / gone).write_text(kept)
    assert p.returncode == 1
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
    line = p.stderr.strip().splitlines()[-1]
    assert line.startswith("benchmark failed:") and want in line, line


def test_run_py_rehearses_the_new_family_end_to_end(copy):
    """Its tiny model through the three processes, its reference in the
    check, its limits and its cost module — on the CPU."""
    root, chip = copy
    p = subprocess.run(
        [sys.executable, str(chip / "run.py"), "--rehearse", "--workload",
         "toy.decode_sat", "--seed", "2147483777", "--seconds", "4",
         "--trace", "2"], capture_output=True, text=True, timeout=400,
        env=cpu_env())
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert "supported_models\": [\"toy-tiny\"]" in p.stdout
    assert "info: pieces: reference toy (limits {'max_deficit': 0.5, " \
           "'mean_deficit': 0.01}), costs costs_toy" in p.stdout
    assert '"limits": {"max_deficit": 0.5, "mean_deficit": 0.01}' in p.stdout
    assert {"itl_p95_ms", "out_tokens_per_s", "setup_s",
            "sched.batch_occupancy", "step.decode_wall_ms"} <= set(
                line["metrics"])


def test_tolerance_json_still_holds_the_first_two_references_limits():
    assert reference.limits("dense") == json.loads(
        (CHIP_DIR / "harness/reference/tolerance.json").read_text())["dense"]
    assert (reference.limits("dense")["max_deficit"],
            reference.limits("dense")["mean_deficit"]) == (0.2, 0.005)
    assert (reference.limits("moe")["max_deficit"],
            reference.limits("moe")["mean_deficit"]) == (2.0, 0.02)
    for name in ("olmoe", "unit", "set_from"):
        with pytest.raises(FileNotFoundError,
                           match=f"reference/{name}.tolerance.json"):
            reference.limits(name)


# the catalog's config.json keys of the next families (ROADMAP Queue 2)
OLMOE = {"hidden_size": 2048, "intermediate_size": 1024, "num_experts": 64,
         "num_experts_per_tok": 8, "num_attention_heads": 16,
         "num_key_value_heads": 16, "num_hidden_layers": 16,
         "vocab_size": 50304, "tie_word_embeddings": False,
         "norm_topk_prob": False, "rope_scaling": None}
TRINITY = {**OLMOE, "num_experts": 128, "moe_intermediate_size": 1024,
           "num_shared_experts": 1, "num_dense_layers": 2,
           "layer_types": ["sliding_attention", "full_attention"]}
GRANITE = {"hidden_size": 2048, "intermediate_size": 8192,
           "num_local_experts": 0, "num_experts_per_tok": 0,
           "shared_intermediate_size": 8192, "mamba_n_heads": 64,
           "layer_types": ["mamba", "attention"], "num_attention_heads": 32,
           "num_hidden_layers": 40, "vocab_size": 100352}


@pytest.mark.parametrize("config,names", [
    (OLMOE, ["num_experts"]),
    (TRINITY, ["moe_intermediate_size", "num_dense_layers", "num_experts",
               "num_shared_experts"]),
    (GRANITE, ["mamba_n_heads", "shared_intermediate_size",
               "layer_types ['attention', 'mamba']"]),
    ({**OLMOE, "num_experts": 0, "n_routed_experts": 8}, ["n_routed_experts"]),
], ids=["olmoe", "trinity-mini", "granite-4.0-h", "n_routed_experts"])
def test_costs_refuses_a_configuration_it_would_count_as_dense(config, names):
    c = {**config, "bench": {"name": "x", "kv_bytes_per_token": 1}}
    for fn in (costs.decode_step_bytes, costs.ffn_weight_bytes):
        with pytest.raises(costs.CostsMisread) as e:
            fn(c, 8, 1024)
        assert all(n in str(e.value) for n in names), str(e.value)
        assert "bench.costs" in str(e.value)
    # what it would have counted: OLMoE as a dense 1024-wide MLP
    dense = {k: v for k, v in c.items() if "expert" not in k
             and k not in ("moe_intermediate_size", "num_dense_layers",
                           "mamba_n_heads", "shared_intermediate_size",
                           "layer_types")}
    assert costs.ffn_weight_bytes(dense, 8) == (
        c["num_hidden_layers"] * 3 * c["hidden_size"] * c["intermediate_size"])


@pytest.mark.parametrize("name", ["mistral-7b-int8", "mixtral-8x7b-d4-int8",
                                  "rehearsal-tiny-mistral"])
def test_costs_still_reads_the_configurations_it_was_written_for(name):
    c = json.loads((CHIP_DIR / "configs" / f"{name}.json").read_text())
    costs.refuse_misread(c)
    assert costs.module_for(c) is costs
    assert costs.decode_step_bytes(c, 8, 2048) > 0
    with pytest.raises(FileNotFoundError, match="harness/costs_nowhere.py"):
        costs.module_for({"bench": {"name": name, "costs": "costs_nowhere"}})
