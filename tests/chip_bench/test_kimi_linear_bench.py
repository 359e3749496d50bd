"""The ``kimi_linear`` family in the benchmark (PR 37): its reference against
the program at the rehearsal size, its cost module against bytes counted by
hand, its three kernel metrics on a hand-made reduction of the cell's shape
(2-step and 1-step flights, prefills between them: seven latent attention
calls a step), and the cell ``kimilinear-p1.reason_sat`` rehearsed end to
end on the CPU.

The cell is listed in BENCHMARK.json (PR 37): its entries are read from
there, and the cell is rehearsed in this checkout as the accepted cells are
(test_rehearse.py)."""

import json
import re
import subprocess
import sys

import pytest
from conftest import CHIP_DIR, cpu_env

from harness import costs, costs_kimi_linear, reducers
from harness.reducers import trace_hybrid

CELL = "kimilinear-p1.reason_sat"
CONFIG = json.loads((CHIP_DIR / "configs"
                     / "kimi-linear-48b-p1-ep8-int8.json").read_text())
TINY = json.loads((CHIP_DIR / "configs"
                   / "rehearsal-tiny-kimi-linear.json").read_text())
DECODE2, DECODE1 = "jit__decode_paged_impl(81)", "jit__decode_paged_impl(12)"
PREFILL = "jit__prefill_impl(1915714125240641424)"
ATTN = "%paged_decode_attention_mla.11 = bf16[32,32,512]{2,1,0} custom-call("
FLASH = "%flash_prefill_attention.6 = bf16[1,256,1,32,576]{4,3,2,1,0} custom-call("
MOE = "%moe_grouped_matmul.4 = bf16[256,1024]{1,0:T(8,128)(2,1)} custom-call("
# XLA fetches part of a bank ahead of the kernel's call (my chip run, PR 37)
SLICE = ("%slice-done.90 = s8[8,1024,2304]{2,1,0:T(8,128)(4,1)S(1)} async-done(((s8["
         "32,1024,2304]{2,1,0:T(8,128)(4,1)}), s8[8,1024,2304]{2,1,0:T(8,128)(4,1)"
         "S(1)}, s32[]{:S(2)}) %slice-start.90)")
SHARED = "%slice-done.3 = s8[1024,2304]{1,0:T(8,128)(4,1)S(1)} async-done("
KDA = ("%kda_update.7 = (f32[32,32,128]{2,1,0}, f32[20,32,32,128,128]{4,3,2,1,0:"
       "T(8,128)}) custom-call(s32[1]{0} %bitcast.1, f32[32,2,128,64]{3,2,1,0}")
# what XLA's own update would look like in a trace: the state an operand
FUSED = ("%add_dynamic-update-slice_fusion.10 = f32[20,32,32,128,128]{4,3,2,1,0:"
         "T(8,128)} fusion(f32[20,32,32,128,128]{4,3,2,1,0:T(8,128)} "
         "%get-tuple-element.7, f32[32,32,128]{2,1,0} %bitcast.963")
WHILE = ("%while.27 = (s32[]{:T(128)}, f32[20,32,32,128,128]{4,3,2,1,0:T(8,128)}) "
         "while((s32[]{:T(128)}, f32[20,32,32,128,128]{4,3,2,1,0:T(8,128)}) %tuple")
N2, N1, NPRE = 60, 6, 6             # flights of 2 steps, of 1 step, prefills
D2, D1, DPRE = 0.040, 0.021, 0.030  # seconds each
STEPS = N2 * 2 + N1
T_ATTN, T_MOE, T_KDA, T_SLICE = 50e-6, 0.1e-3, 0.1e-3, 85e-6


JOINED = {"out_tokens_per_s", "sched.batch_occupancy",
          "sched.slot_fill_share", "device.idle_share.sat",
          "device.peak_mem_gib", "step.decode_device_ms.hybrid",
          "step.decode_hbm_share.hybrid", "moe.held_assignment_share"}


def spec(name):
    return json.loads((CHIP_DIR / "layer_metrics" / f"{name}.json").read_text())


def reduction() -> dict:
    """What trace_reduce gives for ~3 s of the cell as the step program is
    built (deviceless compile, PR 37): a decode step calls the latent
    decode attention kernel SEVEN times, ``kda_update`` twenty times and
    the grouped matmul 78 times (26 expert layers, three matrices); a
    prefill calls the same expert kernel around flash-prefill calls."""
    def op(per_step_calls, seconds_a_call, prefill_calls=0):
        n2, n1 = N2 * 2 * per_step_calls, N1 * per_step_calls
        total = (n2 + n1 + NPRE * prefill_calls) * seconds_a_call
        return {"count": n2 + n1 + NPRE * prefill_calls, "self_s": total,
                "total_s": total, "in_program": {
                    DECODE2: [n2, n2 * seconds_a_call],
                    DECODE1: [n1, n1 * seconds_a_call],
                    **({PREFILL: [NPRE * prefill_calls,
                                  NPRE * prefill_calls * seconds_a_call]}
                       if prefill_calls else {})}}

    return {"devices": 1, "busy_s": 2.9, "window_s": 3.0,
            "programs": {DECODE2: [D2] * N2, DECODE1: [D1] * N1,
                         PREFILL: [DPRE] * NPRE},
            "ops": {ATTN: op(7, T_ATTN), MOE: op(78, T_MOE, 78),
                    SLICE: op(26, T_SLICE), SHARED: op(26, 1e-6),
                    KDA: op(20, T_KDA), WHILE: op(1, 1e-3),
                    FLASH: {"count": 7 * NPRE, "self_s": 7 * NPRE * 1e-4,
                            "total_s": 7 * NPRE * 1e-4, "in_program": {
                                PREFILL: [7 * NPRE, 7 * NPRE * 1e-4]}}}}


def run_of(occupancy=1.0):
    run = reducers.RunData(records=[], seconds=1.0, config=CONFIG)
    run.profile, run.device_kind = reduction(), "TPU v5 lite"
    run.gauge_samples = [f"crowdllama_engine_batch_occupancy {occupancy}\n"]
    return run


def test_the_bytes_of_a_step_counted_by_hand():
    """The table under ISSUE 37's Motivation, in this repo's bytes."""
    c = CONFIG
    assert costs_kimi_linear.attention_layers(c) == 7
    kda = 3 * 2304 * 4096 + 4096 * 2304 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    assert costs_kimi_linear.kda_weight_bytes(c) == 20 * kda
    assert 39.4e6 < kda < 39.6e6
    mla = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    assert costs_kimi_linear.mla_weight_bytes(c) == 7 * mla
    assert 29.0e6 < mla < 29.2e6
    expert = 3 * 2304 * 1024
    # 32 tokens: one row an expert in the mean, 20.4 of 32 banks touched
    assert costs_kimi_linear.experts_touched(c, 32) == pytest.approx(
        32 * (1 - (31 / 32) ** 32)) == pytest.approx(20.41, abs=0.01)
    assert costs_kimi_linear.ffn_weight_bytes(c, 32) == pytest.approx(
        26 * expert * 20.41, rel=1e-3)
    assert costs_kimi_linear.ffn_dense_bytes(c) == (
        3 * 2304 * 9216 + 26 * (expert + 2 * 2304 * 256))
    state = costs_kimi_linear.kda_state_bytes(c, 32)
    assert state == 20 * 32 * 2 * (32 * 128 * 128 * 4 + 12288 * 3 * 2)
    assert 2.77e9 < state < 2.78e9      # 1.34 GB + 47 MB, in and out
    assert c["bench"]["kv_bytes_per_token"] == 7 * 576 * 2 == 8064
    assert costs_kimi_linear.latent_read_bytes(c, 32, 32 * 768) == 32 * 768 * 8064
    need = costs_kimi_linear.decode_step_bytes(c, 32, 32 * 768)
    assert need == pytest.approx(
        20 * kda + 7 * mla + 26 * expert * 20.41 + 3 * 2304 * 9216
        + 26 * (expert + 2 * 2304 * 256) + 2304 * 20480 + 32 * 768 * 8064
        + state, rel=1e-4)
    assert 8.0e9 < need < 8.1e9
    # and costs.py goes on refusing to count this configuration as dense
    with pytest.raises(costs.CostsMisread, match="kv_lora_rank|expert"):
        costs.ffn_weight_bytes(c, 32)
    with pytest.raises(costs.CostsMisread):
        costs.decode_step_bytes(c, 32, 0)


def test_a_step_is_seven_latent_attention_calls_and_the_three_shares():
    run = run_of()
    step_s = (N2 * D2 + N1 * D1) / STEPS
    bw = costs.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert trace_hybrid.reduce(spec("step.decode_device_ms.hybrid"), run
                               ) == pytest.approx(1e3 * step_s)
    need = costs_kimi_linear.decode_step_bytes(CONFIG, 32, 0)
    assert trace_hybrid.reduce(spec("step.decode_hbm_share.hybrid"), run
                               ) == pytest.approx(100 * need / bw / step_s)
    state = costs_kimi_linear.kda_state_bytes(CONFIG, 32)
    assert trace_hybrid.reduce(spec("kernel.kda_state_roofline"), run
                               ) == pytest.approx(
        100 * state / bw / (20 * T_KDA))
    ffn = costs_kimi_linear.ffn_weight_bytes(CONFIG, 32)
    # the decode programs' 78 calls a step, not the prefills', and the 26
    # slices of the banks fetched ahead of them, not the shared expert's
    assert trace_hybrid.reduce(spec("kernel.moe_held_ffn_roofline"), run
                               ) == pytest.approx(
        100 * ffn / bw / (78 * T_MOE + 26 * T_SLICE))
    # no record in the window: no context, so nothing for the rows' share
    assert trace_hybrid.reduce(spec("kernel.mla_attn_roofline"), run) == 0.0
    # half the slots live: half the state
    assert trace_hybrid.reduce(spec("kernel.kda_state_roofline"), run_of(0.5)
                               ) == pytest.approx(
        50 * state / bw / (20 * T_KDA))


def test_the_banks_ops_take_their_shape_from_the_configuration():
    assert "op" not in spec("kernel.moe_held_ffn_roofline")
    rx = re.compile(costs_kimi_linear.held_ffn_ops(CONFIG))
    gate = SLICE.replace("1024,2304", "2304,1024")
    assert [bool(rx.search(k)) for k in (
        MOE, SLICE, gate, SLICE.replace("-done", "-start"), SHARED, KDA, ATTN,
        SLICE.replace("s8[", "bf16["))] == [
            True, True, True, True, False, False, False, False]
    narrow = {**CONFIG, "moe_intermediate_size": 512}
    assert not re.search(costs_kimi_linear.held_ffn_ops(narrow), SLICE)


def test_the_state_ops_take_their_shape_from_the_configuration():
    assert "op" not in spec("kernel.kda_state_roofline")
    rx = re.compile(costs_kimi_linear.kda_state_ops(CONFIG))
    assert [bool(rx.search(k)) for k in (KDA, FUSED, WHILE, MOE, ATTN)
            ] == [True, True, False, False, False]
    assert rx.search(FUSED.replace("f32[20,32,", "f32[32,"))
    wide = {**CONFIG, "bench": {**CONFIG["bench"], "slots": 64}}
    assert not re.search(costs_kimi_linear.kda_state_ops(wide), FUSED)
    run = run_of()
    run.profile["ops"].pop(KDA)
    assert trace_hybrid.reduce(spec("kernel.kda_state_roofline"), run) is None
    # the parent has none of the three kernels: nothing, and no error
    run.profile["ops"] = {WHILE: run.profile["ops"][WHILE]}
    for name in ("kernel.kda_state_roofline", "kernel.mla_attn_roofline",
                 "kernel.moe_held_ffn_roofline"):
        assert trace_hybrid.reduce(spec(name), run) is None


def test_the_cell_and_its_metrics_are_listed_as_the_issue_says():
    bench = json.loads((CHIP_DIR.parents[1] / "BENCHMARK.json").read_text())
    assert {m["name"] for g in ("end_to_end", "per_layer") for m in bench[g]
            if CELL in m.get("workloads", ())} == JOINED | {
        "kernel.kda_state_roofline", "kernel.mla_attn_roofline",
        "kernel.moe_held_ffn_roofline"}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-linear-48b-p1-ep8-int8", "reason_sat", 1)
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in bench[g] if "workloads" not in m
              or CELL in m["workloads"]}
    assert listed == {
        "itl_p95_ms", "out_tokens_per_s", "setup_s", "sched.batch_occupancy",
        "sched.slot_fill_share", "device.idle_share.sat",
        "device.peak_mem_gib", "step.decode_wall_ms",
        "engine.compiles_in_window", "setup.weights_s", "setup.warmup_s",
        "step.decode_device_ms.hybrid", "step.decode_hbm_share.hybrid",
        "moe.held_assignment_share", "kernel.kda_state_roofline",
        "kernel.mla_attn_roofline", "kernel.moe_held_ffn_roofline"}
    entry = next(c for c in bench["configs"]
                 if c["name"] == "kimi-linear-48b-p1-ep8-int8")
    assert entry["reduced"] == CONFIG["bench"]["reduced"] == [
        "num_experts", "vocab_size"]
    # the configuration: the catalog's numbers but for the two cuts
    top = {k: v for k, v in CONFIG.items() if k != "bench"}
    assert (top["hidden_size"], top["num_hidden_layers"], top["kv_lora_rank"],
            top["moe_intermediate_size"], top["num_experts_per_token"],
            top["intermediate_size"], top["routed_scaling_factor"]
            ) == (2304, 27, 512, 1024, 8, 9216, 2.446)
    assert (top["num_experts"], top["num_experts_published"],
            top["expert_parallel_size"], top["vocab_size"],
            top["vocab_size_published"]) == (32, 256, 8, 20480, 163840)
    assert len(top["linear_attn_config"]["kda_layers"]) == 20
    assert top["linear_attn_config"]["full_attn_layers"] == [
        4, 8, 12, 16, 20, 24, 27]
    traffic = json.loads((CHIP_DIR / "traffic" / "reason_sat.json").read_text())
    assert (traffic["generator"], traffic["clients_per_slot"],
            traffic["prompt_tokens"], traffic["output_tokens"]) == (
        "closed_fixed", 2, 256, 1024)


def test_the_program_reads_both_configurations_as_the_reference_does(tmp_path):
    """Both files, written as the launcher writes a model directory, through
    the worker's own reader: the pattern the reference derives is the
    program's, and the cost module counts the program's parameters."""
    from harness.reference import kimi_linear as R

    from crowdllama_tpu.engine.weights import resolve_model_config

    for doc in (CONFIG, TINY):
        hf = {k: v for k, v in doc.items() if k != "bench"}
        (tmp_path / "config.json").write_text(json.dumps(hf))
        cfg = resolve_model_config(doc["bench"]["name"], str(tmp_path))
        assert cfg.family == "kimi_linear"
        assert cfg.layer_pattern == R.hyper(hf)["pattern"]
        assert (cfg.num_experts, cfg.experts_held) == (
            hf["num_experts_published"], hf["num_experts"])
        assert cfg.resolved_head_dim() * 2 * cfg.layers_of("L") == (
            doc["bench"]["kv_bytes_per_token"])
    # at the benchmark's cut every matrix but embedding and router is int8:
    # the cost module's weights are the program's parameters
    hf = {k: v for k, v in CONFIG.items() if k != "bench"}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg = resolve_model_config("x", str(tmp_path))
    counted = (costs_kimi_linear.kda_weight_bytes(CONFIG)
               + costs_kimi_linear.mla_weight_bytes(CONFIG)
               + 26 * 32 * 3 * 2304 * 1024
               + costs_kimi_linear.ffn_dense_bytes(CONFIG) - 26 * 2304 * 256
               + 2 * 2304 * 20480)
    assert counted == pytest.approx(cfg.param_count(), rel=2e-3)


def test_the_reference_is_the_program_at_the_rehearsal_size(tmp_path):
    """Teacher-forced logits of the rehearsal model, float32 weights: the
    program's prefill against the reference's full forward pass."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness.reference import kimi_linear as R

    from crowdllama_tpu.engine.weights import resolve_model_config
    from crowdllama_tpu.models import hybrid as H
    from crowdllama_tpu.models import transformer as T

    hf = {k: v for k, v in TINY.items() if k != "bench"}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg = resolve_model_config(TINY["bench"]["name"], str(tmp_path))
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    ids = [int(t) for t in np.random.default_rng(0).integers(1, 512, 48)]
    toks = np.zeros((1, 64), np.int32)
    toks[0, :48] = ids
    got = H.prefill(params, cfg, jnp.asarray(toks),
                    jnp.minimum(jnp.arange(64), 47)[None],
                    (jnp.arange(64) < 48)[None])[0][0, :48]
    with jax.default_matmul_precision("highest"):
        ref = R.forward(params, hf, ids, list(range(48)))
    err = jnp.max(jnp.abs(got - ref), -1) / jnp.std(ref, -1)
    assert float(jnp.max(err)) < 1e-3, float(jnp.max(err))


def test_the_cell_rehearses_end_to_end():
    """A seed of this test's own: at this width (64) one of four checked
    requests in a few meets a token whose 4th and 5th expert tie within a
    bf16 rounding, the served model and the float32 reference then route
    it apart and the matrix state carries the difference on (seed
    2147483659 reads max 0.58; tests/test_hybrid.py ``ALL_CHOSEN`` has the
    same of Nemotron) — the flow is what is rehearsed here, the limits are
    the chip's."""
    p = subprocess.run(
        [sys.executable, str(CHIP_DIR / "run.py"), "--rehearse",
         "--workload", CELL, "--seed", "3000000001", "--seconds", "5",
         "--trace", "2"],
        capture_output=True, text=True, timeout=400, env=cpu_env())
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    out = p.stdout
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert "reference kimi_linear" in out and "costs costs_kimi_linear" in out
    assert out.count('path="pallas_interpret"') == 3
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert {"itl_p95_ms", "out_tokens_per_s", "setup_s",
            "step.decode_wall_ms", "sched.slot_fill_share",
            "moe.held_assignment_share"} <= set(m)
    # the tiny model holds 8 of 16 experts
    assert 35 < m["moe.held_assignment_share"] < 65
    # no device metric from a CPU
    assert not {"step.decode_device_ms.hybrid", "kernel.kda_state_roofline",
                "kernel.mla_attn_roofline", "kernel.moe_held_ffn_roofline"
                } & set(m)
