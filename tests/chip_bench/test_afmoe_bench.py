"""The ``afmoe`` family in the benchmark (PR 42), taken as added files: its
reference against the program at the rehearsal size, its cost module against
bytes counted by hand (ISSUE 42's table), its kernel metrics on a hand-made
reduction of the cell's shape (4-step and 1-step flights: four window and
one full attention call a step, under two names), and the cell
``trinity-p1.long_sat`` rehearsed end to end on the CPU at a context of
several windows.

The cell's metric list is asserted with ``<=``: a later PR may add a metric
to the cell without editing this file."""

import json
import subprocess
import sys

import pytest
from conftest import CHIP_DIR, cpu_env

from harness import costs, costs_afmoe, reducers
from harness.reducers import trace_hybrid, trace_kernel_roofline, trace_step_ms

CELL = "trinity-p1.long_sat"
CONFIG = json.loads((CHIP_DIR / "configs"
                     / "trinity-large-p1-ep8-int8.json").read_text())
TINY = json.loads((CHIP_DIR / "configs"
                   / "rehearsal-tiny-afmoe.json").read_text())
DECODE4, DECODE1 = "jit__decode_paged_impl(81)", "jit__decode_paged_impl(12)"
RAGGED = "jit__ragged_step_impl(1915714125240641424)"
FULL = ("%paged_decode_attention.11 = bf16[32,8,6,128]{3,2,1,0} custom-call("
        "s32[32,56]{1,0} %copy-done.3, s32[32]{0}")
WINDOW = ("%paged_decode_attention_window.45 = bf16[32,8,6,128]{3,2,1,0} "
          "custom-call(s32[32,33]{1,0} %fusion.1240, s32[32]{0}")
RAGGED_W = ("%ragged_paged_attention_window.50 = bf16[16,8,32,6,128]{4,3,2,1,0}"
            " custom-call(s32[16,34]{1,0}")
MOE = "%moe_grouped_matmul.65 = bf16[128,3072]{1,0:T(8,128)(2,1)} custom-call("
N4, N1, NRAG = 20, 6, 3             # flights of 4 steps, of 1 step, ragged
D4, D1, DRAG = 0.044, 0.0109, 0.080  # seconds each
STEPS = N4 * 4 + N1
T_FULL, T_WINDOW, T_MOE = 1.27e-3, 0.8e-3, 0.4e-3


def spec(name):
    return json.loads((CHIP_DIR / "layer_metrics" / f"{name}.json").read_text())


def reduction() -> dict:
    """What trace_reduce gives for ~1 s of the cell as the step program is
    built (deviceless compile, tests/test_tpu_compile.py): a decode step
    calls the window layers' kernel FOUR times and the full layer's once,
    and the grouped matmul twelve times; a ragged step calls the same two
    decode kernels for its decode rows, inside ANOTHER program."""
    def op(per_step_calls, seconds_a_call, ragged_calls=0):
        n4, n1 = N4 * 4 * per_step_calls, N1 * per_step_calls
        nr = NRAG * 4 * ragged_calls
        total = (n4 + n1 + nr) * seconds_a_call
        return {"count": n4 + n1 + nr, "self_s": total, "total_s": total,
                "in_program": {
                    DECODE4: [n4, n4 * seconds_a_call],
                    DECODE1: [n1, n1 * seconds_a_call],
                    **({RAGGED: [nr, nr * seconds_a_call]}
                       if ragged_calls else {})}}

    return {"devices": 1, "busy_s": 0.95, "window_s": 1.0,
            "programs": {DECODE4: [D4] * N4, DECODE1: [D1] * N1,
                         RAGGED: [DRAG] * NRAG},
            "ops": {FULL: op(1, T_FULL, 1), WINDOW: op(4, T_WINDOW, 4),
                    MOE: op(12, T_MOE, 12),
                    RAGGED_W: {"count": 4 * NRAG * 4, "self_s": 0.1,
                               "total_s": 0.1,
                               "in_program": {RAGGED: [4 * NRAG * 4, 0.1]}}}}


def run_of(occupancy=1.0, context=6656):
    from harness.loadgen import Record
    run = reducers.RunData(records=[], seconds=1.0, config=CONFIG)
    run.profile, run.device_kind = reduction(), "TPU v5 lite"
    run.gauge_samples = [f"crowdllama_engine_batch_occupancy {occupancy}\n"]
    # one stream's tokens in the window, at the cell's mean context
    run.records = [Record(actor=0, turn=0, tag="", prompt_len=context,
                          max_tokens=1, greedy=False, check=False, due=None,
                          frame_t=[0.5], frame_tokens=[1])]
    return run


def test_the_bytes_of_a_step_counted_by_hand():
    """The table under ISSUE 42's Tentpole 4, in this repo's bytes."""
    c = CONFIG
    assert costs_afmoe.attention_layers(c) == 5
    attn = 3 * 3072 * 6144 + 2 * 3072 * 1024
    assert costs_afmoe.attn_weight_bytes(c) == 5 * attn
    assert attn == pytest.approx(62.9e6, rel=1e-3)   # the gate counted
    expert = 3 * 3072 * 3072
    assert expert == pytest.approx(28.3e6, rel=1e-3)
    layer = attn + 32 * expert + expert + 2 * 3072 * 256
    assert layer == pytest.approx(998.8e6, rel=1e-4)
    dense = attn + 3 * 3072 * 12288
    assert dense == pytest.approx(176.2e6, rel=1e-3)
    assert costs_afmoe.ffn_dense_bytes(c) == (
        3 * 3072 * 12288 + 4 * (expert + 2 * 3072 * 256))
    whole = 4 * layer + dense + 2 * 25024 * 3072 + costs_afmoe.head_bytes(c)
    assert whole == pytest.approx(4.40e9, rel=1e-3)
    # 32 tokens, top-4 of 256, 32 held: 12.7 of 32 banks touched
    assert costs_afmoe.experts_touched(c, 32) == pytest.approx(
        32 * (1 - (63 / 64) ** 32)) == pytest.approx(12.67, abs=0.01)
    assert costs_afmoe.ffn_weight_bytes(c, 32) == pytest.approx(
        4 * expert * 12.67, rel=1e-3)
    # KV: 4096 B a token a layer; a window layer's read is capped
    assert costs_afmoe.kv_token_bytes(c) == 2 * 8 * 128 * 2 == 4096
    assert c["bench"]["kv_bytes_per_token"] == 5 * 4096
    at = {ctx: costs_afmoe.window_read_bytes(c, 32, 32 * ctx)
          for ctx in (2048, 4096, 5120, 6144, 7168, 262144)}
    assert at[2048] == 32 * 2048 * 4096 * 4
    assert (at[5120] == at[6144] == at[7168] == at[262144]
            == 32 * (4096 + 128) * 4096 * 4)
    assert costs_afmoe.full_read_bytes(c, 32, 32 * 7168) == 32 * 7168 * 4096
    assert costs_afmoe.kv_read_bytes(c, 32, 32 * 7168) == (
        at[7168] + 32 * 7168 * 4096)
    # the pools the program keeps: 3.42 GB where five full layers hold 4.70
    # at ISSUE 42's first context, 3.15 against 3.36 at the served 5120
    ring = 4096 + 512 + 128
    assert 32 * (7168 + 4 * ring) * 4096 == pytest.approx(3.42e9, rel=2e-3)
    assert 32 * 7168 * 4096 * 5 == pytest.approx(4.70e9, rel=2e-3)
    assert 32 * (5120 + 4 * ring) * 4096 == pytest.approx(3.15e9, rel=2e-3)
    assert 32 * 5120 * 4096 * 5 == pytest.approx(3.36e9, rel=2e-3)
    need = costs_afmoe.decode_step_bytes(c, 32, 32 * 6656)
    assert need == pytest.approx(
        5 * attn + 4 * expert * 12.67 + costs_afmoe.ffn_dense_bytes(c)
        + 25024 * 3072 + at[7168] + 32 * 6656 * 4096, rel=1e-3)


def test_a_step_is_five_attention_calls_under_two_names():
    run = run_of()
    step_s = (N4 * D4 + N1 * D1) / STEPS
    bw = costs.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    # both names count a step: the plain pair divides by all five layers
    assert trace_step_ms.reduce(spec("step.decode_device_ms"), run
                                ) == pytest.approx(1e3 * step_s)
    assert trace_hybrid.reduce(spec("step.decode_device_ms.hybrid"), run
                               ) == pytest.approx(1e3 * step_s)
    kv = 32 * 6656
    window = costs_afmoe.window_read_bytes(CONFIG, 32, kv)
    assert trace_kernel_roofline.reduce(
        spec("kernel.window_attn_roofline"), run) == pytest.approx(
        100 * window / bw / (4 * T_WINDOW), rel=1e-3)
    full = costs_afmoe.full_read_bytes(CONFIG, 32, kv)
    # the full layer's pattern leaves the window kernel's calls out, and the
    # decode rows' calls inside the ragged program are no decode step's
    assert trace_kernel_roofline.reduce(
        spec("kernel.full_attn_roofline"), run) == pytest.approx(
        100 * full / bw / T_FULL, rel=1e-3)
    ffn = costs_afmoe.ffn_weight_bytes(CONFIG, 32)
    assert trace_hybrid.reduce(spec("kernel.moe_held_ffn_roofline"), run
                               ) == pytest.approx(
        100 * ffn / bw / (12 * T_MOE), rel=1e-3)
    # a window layer's share does not move with the context past the window
    assert trace_kernel_roofline.reduce(
        spec("kernel.window_attn_roofline"), run_of(context=7000)
    ) == pytest.approx(100 * window / bw / (4 * T_WINDOW), rel=1e-3)


def test_the_banks_ops_take_their_shape_from_the_configuration():
    import re

    rx = re.compile(costs_afmoe.held_ffn_ops(CONFIG))
    assert rx.search(MOE)
    assert rx.search("%slice-done.9 = s8[8,3072,3072]{2,1,0} async-done(")
    assert not rx.search("%slice-done.3 = s8[3072,3072]{1,0} async-done(")
    assert not rx.search(WINDOW)


def test_the_window_gauge_is_read_by_its_kind():
    run = reducers.RunData(records=[], seconds=1.0, config=CONFIG)
    run.gauge_samples = [
        'crowdllama_engine_kv_live_bytes{kind="full"} 900\n'
        f'crowdllama_engine_kv_live_bytes{{kind="window"}} {n * 2 ** 20}\n'
        for n in (2340, 2360)]
    assert reducers.compute("layer_metrics", "cache.window_live_mib", run
                            ) == pytest.approx(2350.0)
    run.gauge_samples = ["crowdllama_engine_batch_occupancy 1\n"]
    # a program without the gauge (the parent): nothing, and no error
    assert reducers.compute("layer_metrics", "cache.window_live_mib",
                            run) is None


def test_the_cell_and_its_metrics_are_listed_as_the_issue_says():
    bench = json.loads((CHIP_DIR.parents[1] / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-large-p1-ep8-int8", "long_sat", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in bench[g] if "workloads" not in m
              or CELL in m["workloads"]}
    # ``<=``: a later PR may add to the cell what it reports
    assert {
        "itl_p95_ms", "out_tokens_per_s", "setup_s", "sched.batch_occupancy",
        "sched.slot_fill_share", "sched.short_flight_share",
        "device.idle_share.sat", "device.peak_mem_gib",
        "engine.compiles_in_window", "step.decode_device_ms",
        "step.decode_hbm_share", "moe.held_assignment_share",
        "moe.banks_routed_share", "moe.banks_fetched_share",
        "kernel.moe_held_ffn_roofline", "kernel.window_attn_roofline",
        "kernel.full_attn_roofline", "cache.window_live_mib"} <= listed
    assert not {"ttft_p80_ms", "kernel.paged_attn_roofline"} & listed
    entry = next(c for c in bench["configs"]
                 if c["name"] == "trinity-large-p1-ep8-int8")
    assert entry["reduced"] == CONFIG["bench"]["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"]
    assert entry["source"] == CONFIG["bench"]["source"]
    # the configuration: the catalog's numbers but for the cuts
    top = {k: v for k, v in CONFIG.items() if k != "bench"}
    assert (top["hidden_size"], top["num_attention_heads"],
            top["num_key_value_heads"], top["head_dim"],
            top["intermediate_size"], top["moe_intermediate_size"],
            top["num_experts_per_tok"], top["route_scale"],
            top["sliding_window"], top["global_attn_every_n_layers"]
            ) == (3072, 48, 8, 128, 12288, 3072, 4, 2.448, 4096, 4)
    assert (top["num_hidden_layers"], top["num_dense_layers"],
            top["num_experts"], top["num_experts_published"],
            top["expert_parallel_size"], top["vocab_size"],
            top["vocab_size_published"]) == (5, 1, 32, 256, 8, 25024, 200192)
    assert top["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    traffic = json.loads((CHIP_DIR / "traffic" / "long_sat.json").read_text())
    assert (traffic["generator"], traffic["clients_per_slot"],
            traffic["ramp_s"], traffic["checked"]) == (
        "closed_context", 2, 20, 4)
    b = CONFIG["bench"]
    # 4 and 1 parts of 5 of the served context: ISSUE 42's named fallback,
    # 4096 in and 1024 out at context 5120, at decode_chunk 2
    from harness import generators
    from harness.generators import closed_fixed

    ctx = {"seed": 5, "seconds": 51.0, "slots": b["slots"],
           "context": b["context"], "vocab_size": CONFIG["vocab_size"]}
    plan = generators.build_plan(traffic, ctx)
    assert len(plan.actors) == 64 and plan.ramp_s == 20
    turns = [a.next_turn(None) for a in plan.actors[32:]]
    assert {(len(t.prompt_ids), t.max_tokens) for t in turns} == {(4096, 1024)}
    # the very plan closed_fixed makes of those sizes
    same = closed_fixed.plan({**traffic, "prompt_tokens": 4096,
                              "output_tokens": 1024}, ctx)
    assert [t.prompt_ids for t in turns] == [
        a.next_turn(None).prompt_ids for a in same.actors[32:]]
    assert (b["slots"], b["context"], b["decode_chunk"], b["reference"],
            b["costs"], b["rehearsal"]) == (
        32, 5120, 2, "afmoe", "costs_afmoe", "rehearsal-tiny-afmoe")
    assert b["worker_env"]["CROWDLLAMA_TPU_DECODE_CHUNK"] == "2"
    assert b["worker_env"]["CROWDLLAMA_TPU_MAX_CONTEXT_LENGTH"] == "5120"


def test_the_program_reads_both_configurations_as_the_reference_does(tmp_path):
    """Both files, written as the launcher writes a model directory, through
    the worker's own reader: the pattern the reference derives is the
    program's, and the cost module counts the program's parameters."""
    from harness.reference import afmoe as R

    from crowdllama_tpu.engine.weights import resolve_model_config

    for doc in (CONFIG, TINY):
        hf = {k: v for k, v in doc.items() if k != "bench"}
        (tmp_path / "config.json").write_text(json.dumps(hf))
        cfg = resolve_model_config(doc["bench"]["name"], str(tmp_path))
        assert cfg.family == "afmoe"
        assert cfg.layer_pattern == R.hyper(hf)["pattern"]
        assert (cfg.num_experts, cfg.experts_held) == (
            hf["num_experts_published"], hf["num_experts"])
        assert cfg.sliding_window == hf["sliding_window"]
        assert costs_afmoe.kv_token_bytes(doc) * cfg.num_layers == (
            doc["bench"]["kv_bytes_per_token"])
    hf = {k: v for k, v in CONFIG.items() if k != "bench"}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg = resolve_model_config("x", str(tmp_path))
    assert cfg.layer_pattern == "WDWSWSWSFS"
    counted = (costs_afmoe.attn_weight_bytes(CONFIG)
               + 4 * 32 * 3 * 3072 * 3072
               + costs_afmoe.ffn_dense_bytes(CONFIG) - 4 * 3072 * 256
               + 2 * 3072 * 25024)
    assert counted == pytest.approx(cfg.param_count(), rel=2e-3)


def test_the_reference_is_the_program_at_the_rehearsal_size(tmp_path):
    """Teacher-forced logits of the rehearsal model, float32 weights: the
    program's prefill against the reference's full forward pass, at a
    context of several of its windows."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness.reference import afmoe as R

    from crowdllama_tpu.engine.weights import resolve_model_config
    from crowdllama_tpu.models import hybrid as H
    from crowdllama_tpu.models import transformer as T

    hf = {k: v for k, v in TINY.items() if k != "bench"}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg = resolve_model_config(TINY["bench"]["name"], str(tmp_path))
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    n = 4 * hf["sliding_window"] + 7
    ids = [int(t) for t in np.random.default_rng(0).integers(1, 512, n)]
    toks = np.zeros((1, 256), np.int32)
    toks[0, :n] = ids
    got = H.prefill(params, cfg, jnp.asarray(toks),
                    jnp.minimum(jnp.arange(256), n - 1)[None],
                    (jnp.arange(256) < n)[None])[0][0, :n]
    with jax.default_matmul_precision("highest"):
        ref = R.forward(params, hf, ids, list(range(n)))
        blind = R.forward(params, hf, ids, list(range(n)), ("no_window",))
    err = jnp.max(jnp.abs(got - ref), -1) / jnp.std(ref, -1)
    assert float(jnp.max(err)) < 1e-3, float(jnp.max(err))
    off = jnp.max(jnp.abs(got - blind), -1) / jnp.std(blind, -1)
    assert float(jnp.max(off)) > 0.1


def test_the_cell_rehearses_end_to_end():
    """The whole flow at tiny size on the CPU: prompts of three windows and
    replies of two more, admitted in chunks through the ragged step (the
    rehearsal's step token budget), both decode kernels and both ragged
    kernels in interpret mode, nothing compiled in the window."""
    p = subprocess.run(
        [sys.executable, str(CHIP_DIR / "run.py"), "--rehearse",
         "--workload", CELL, "--seed", "3000000001", "--seconds", "5",
         "--trace", "2"],
        capture_output=True, text=True, timeout=500, env=cpu_env())
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    out = p.stdout
    line = json.loads(out.strip().splitlines()[-1])
    # The flow is what is rehearsed: the check ran on what the timed path
    # emitted and read its two figures.  Its verdict is the chip's: the
    # limits are set from runs at the published widths, and 256 tokens of a
    # 64-wide model read a mean of 0.006-0.010 around them (a token whose
    # 4th and 5th expert tie within a bf16 rounding is routed apart).
    assert line["failed"] == 0 and isinstance(line["correct"], bool)
    check = json.loads(out.split("info: reference check: ", 1)[1]
                       .splitlines()[0])
    assert check["tokens"] == 4 * 64 and not check["problems"]
    assert check["mean_deficit"] < 0.05 and check["max_deficit"] < 3.0
    assert check["argmax_agree_share"] > 0.8
    assert line["device"]["platform"] == "cpu"
    assert "reference afmoe" in out and "costs costs_afmoe" in out
    assert out.count('path="pallas_interpret"') == 5
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert {"itl_p95_ms", "out_tokens_per_s", "setup_s",
            "step.decode_wall_ms", "sched.slot_fill_share",
            "moe.held_assignment_share", "moe.banks_fetched_share",
            "cache.window_live_mib"} <= set(m)
    assert m["engine.compiles_in_window"] == 0
    # the tiny model holds 8 of 16 experts
    assert 35 < m["moe.held_assignment_share"] < 65
    # four slots' rings of three 32-token pages, four window layers of 2 x
    # 2 kv heads x 16: never more than the rings, whatever the context
    assert 0 < m["cache.window_live_mib"] <= 4 * 3 * 4 * 2 * 2 * 32 * 16 * 2 / 2 ** 20
    # no device metric from a CPU
    assert not {"step.decode_device_ms", "kernel.window_attn_roofline",
                "kernel.full_attn_roofline", "kernel.moe_held_ffn_roofline"
                } & set(m)
