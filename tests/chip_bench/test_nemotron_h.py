"""The ``nemotron_h`` family in the benchmark (PR 27): the cell
``nemotron3super-p1.decode_sat`` rehearsed end to end on the CPU (tiny model,
its reference, its cost module), and the readers that count a decode step
as one call of the decode attention kernel per ATTENTION layer — one layer
of eleven here — on a hand-made reduction of the cell's shape: 8-step and
1-step flights, prefills between them."""

import json
import re

import pytest
from conftest import CHIP_DIR
from test_rehearse import rehearse

from harness import costs, costs_nemotron_h, reducers
from harness.reducers import trace_hybrid, trace_step_ms

CELL = "nemotron3super-p1.decode_sat"
CONFIG = json.loads((CHIP_DIR / "configs"
                     / "nemotron-3-super-p1-ep4-int8.json").read_text())
DECODE8, DECODE1 = "jit__decode_paged_impl(81)", "jit__decode_paged_impl(12)"
PREFILL = "jit__prefill_impl(1915714125240641424)"
ATTN = "%paged_decode_attention.11 = bf16[32,2,16,128]{3,2,1,0} custom-call("
FLASH = "%flash_prefill_attention.6 = bf16[1,128,2,16,128]{4,3,2,1,0} custom-call("
RAGGED = "%ragged-dot-none.4 = bf16[704,2688]{1,0:T(8,128)(2,1)S(1)} custom-call("
BANK = "%convert_multiply_fusion.47 = bf16[128,1024,2688]{2,1,0:T(8,128)(2,1)} fusion("
STATE = ("%add_dynamic-update-slice_fusion.10 = f32[5,32,128,64,128]{4,3,2,1,0:"
         "T(8,128)} fusion(f32[5,32,128,64,128]{4,3,2,1,0:T(8,128)} "
         "%get-tuple-element.11197, f32[32,128,128]{2,1,0:T(8,128)S(1)} %bitcast.963")
YREAD = ("%fusion.888 = f32[32,128,64]{2,1,0:T(8,128)S(1)} fusion(f32[5,32,128,64,"
         "128]{4,3,2,1,0:T(8,128)} %get-tuple-element.11197, f32[32,128,128]{2,1,0:"
         "T(8,128)S(1)} %bitcast.972")
WHILE = ("%while.27 = (s32[]{:T(128)}, f32[5,32,128,64,128]{4,3,2,1,0:T(8,128)}) "
         "while((s32[]{:T(128)}, f32[5,32,128,64,128]{4,3,2,1,0:T(8,128)}) %tuple")
N8, N1, NPRE = 9, 3, 3              # flights of 8 steps, of 1 step, prefills
D8, D1, DPRE = 0.264, 0.035, 0.040  # seconds each
STEPS = N8 * 8 + N1


def spec(name):
    return json.loads((CHIP_DIR / "layer_metrics" / f"{name}.json").read_text())


def reduction() -> dict:
    """What trace_reduce gives for 3 s of the cell as the step program is
    built (deviceless compile, PR 27): a decode step calls the decode
    attention kernel ONCE, each of its five expert layers dequantizes two
    banks and calls ragged-dot twice, each of its five Mamba layers
    updates the state in one fusion and reads it again for ``y`` in
    another (the loop that carries the state is no fusion); a prefill does
    the same expert ops around a flash-prefill call."""
    def op(per_step_calls, seconds_a_call, prefill_calls=0):
        n8, n1 = N8 * 8 * per_step_calls, N1 * per_step_calls
        total = (n8 + n1 + NPRE * prefill_calls) * seconds_a_call
        return {"count": n8 + n1 + NPRE * prefill_calls, "self_s": total,
                "total_s": total, "in_program": {
                    DECODE8: [n8, n8 * seconds_a_call],
                    DECODE1: [n1, n1 * seconds_a_call],
                    **({PREFILL: [NPRE * prefill_calls,
                                  NPRE * prefill_calls * seconds_a_call]}
                       if prefill_calls else {})}}

    return {"devices": 1, "busy_s": 2.9, "window_s": 3.0,
            "programs": {DECODE8: [D8] * N8, DECODE1: [D1] * N1,
                         PREFILL: [DPRE] * NPRE},
            "ops": {ATTN: op(1, 40e-6), RAGGED: op(10, 1.0e-3, 10),
                    BANK: op(10, 1.7e-3, 10), STATE: op(5, 0.4e-3),
                    YREAD: op(5, 0.18e-3), WHILE: op(1, 1e-3),
                    FLASH: {"count": NPRE, "self_s": NPRE * 1e-4,
                            "total_s": NPRE * 1e-4, "in_program": {
                                PREFILL: [NPRE, NPRE * 1e-4]}}}}


def run_of(occupancy=1.0):
    run = reducers.RunData(records=[], seconds=1.0, config=CONFIG)
    run.profile, run.device_kind = reduction(), "TPU v5 lite"
    run.gauge_samples = [f"crowdllama_engine_batch_occupancy {occupancy}\n"]
    return run


def test_a_step_is_one_attention_call_per_attention_layer():
    run = run_of()
    want_ms = 1e3 * (N8 * D8 + N1 * D1) / STEPS
    got = trace_hybrid.reduce(spec("step.decode_device_ms.hybrid"), run)
    assert got == pytest.approx(want_ms)
    # the accepted reader divides by all eleven layers: a step eleven times
    # too long, which is why step.decode_device_ms lists its cells now
    assert trace_step_ms.reduce(spec("step.decode_device_ms"), run
                                ) == pytest.approx(11 * want_ms)
    assert costs_nemotron_h.attention_layers(CONFIG) == 1
    assert CONFIG["num_hidden_layers"] == 11
    # the prefills' flash calls and expert ops are no decode step's
    assert trace_step_ms.steps_traced(
        spec("step.decode_device_ms"), run) * 11 == STEPS


def test_the_bytes_of_a_step_and_the_three_shares():
    run = run_of()
    step_s = (N8 * D8 + N1 * D1) / STEPS
    bw = costs.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    need = costs_nemotron_h.decode_step_bytes(CONFIG, 32, 0)
    assert 5.0e9 < need < 5.1e9         # the issue's arithmetic: 5.03 GB
    assert trace_hybrid.reduce(spec("step.decode_hbm_share.hybrid"), run
                               ) == pytest.approx(100 * need / bw / step_s)
    # expert ops: ten dequants and ten ragged-dots a step, the decode
    # programs' alone; 32 tokens touch 96.6 of the 128 held experts
    ffn = costs_nemotron_h.ffn_weight_bytes(CONFIG, 32)
    assert costs_nemotron_h.experts_touched(CONFIG, 32) == pytest.approx(
        96.606, abs=1e-3)
    assert trace_hybrid.reduce(spec("kernel.moe_latent_ffn_roofline"), run
                               ) == pytest.approx(
        100 * ffn / bw / (10 * 1.0e-3 + 10 * 1.7e-3))
    # the state: every live slot's state and tail in and out once, over
    # ALL the time the state is touched — five update fusions a step and
    # the five fusions that read it again for y
    ssm = costs_nemotron_h.ssm_state_bytes(CONFIG, 32)
    assert ssm == 5 * 32 * 2 * (128 * 64 * 128 * 4 + 10240 * 3 * 2)
    touched_s = 5 * 0.4e-3 + 5 * 0.18e-3
    assert trace_hybrid.reduce(spec("kernel.ssm_state_roofline"), run
                               ) == pytest.approx(100 * ssm / bw / touched_s)
    # half the slots live: half the state, fewer experts touched
    half = run_of(0.5)
    assert trace_hybrid.reduce(spec("kernel.ssm_state_roofline"), half
                               ) == pytest.approx(50 * ssm / bw / touched_s)


def test_the_state_ops_take_their_shape_from_the_configuration():
    assert "op" not in spec("kernel.ssm_state_roofline")
    rx = re.compile(costs_nemotron_h.ssm_state_ops(CONFIG))
    assert [bool(rx.search(k)) for k in (STATE, YREAD, WHILE, BANK, RAGGED)
            ] == [True, True, False, False, False]
    assert rx.search("%ssm_update.3 = f32[32,128,64,128]{3,2,1,0} custom-call(")
    # one layer's state without the leading axis is the state too
    assert rx.search(YREAD.replace("f32[5,32,", "f32[32,"))
    # other slots or another depth: the pattern follows the configuration,
    # and a trace of the old shape then gives nothing rather than a number
    wide = {**CONFIG, "bench": {**CONFIG["bench"], "slots": 64}}
    assert not re.search(costs_nemotron_h.ssm_state_ops(wide), STATE)
    assert re.search(costs_nemotron_h.ssm_state_ops(wide),
                     STATE.replace("[5,32,", "[5,64,"))
    deep = {**CONFIG, "hybrid_override_pattern": "MEMEMEM*EMEMEMEM"}
    assert "(8,)?32,128,64,128" in costs_nemotron_h.ssm_state_ops(deep)
    run = run_of()
    run.config = wide
    assert trace_hybrid.reduce(spec("kernel.ssm_state_roofline"), run) is None


def test_no_trace_or_another_family_gives_nothing():
    run = run_of()
    run.profile = None
    assert trace_hybrid.reduce(spec("step.decode_device_ms.hybrid"), run) is None
    mistral = json.loads((CHIP_DIR / "configs" / "mistral-7b-int8.json"
                          ).read_text())
    other = reducers.RunData(records=[], seconds=1.0, config=mistral)
    other.profile = reduction()
    assert trace_hybrid.reduce(spec("step.decode_device_ms.hybrid"), other
                               ) is None
    # and costs.py still refuses to count this configuration as a dense one
    with pytest.raises(costs.CostsMisread, match="mamba_"):
        costs.ffn_weight_bytes(CONFIG, 32)


def test_the_cell_and_its_metrics_are_listed_as_the_issue_says():
    bench = json.loads((CHIP_DIR.parents[1] / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron-3-super-p1-ep4-int8", "decode_sat", 1)
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in bench[g] if "workloads" not in m
              or CELL in m["workloads"]}
    assert listed == {
        "itl_p95_ms", "out_tokens_per_s", "setup_s", "sched.batch_occupancy",
        "sched.slot_fill_share", "device.idle_share.sat",
        "device.peak_mem_gib", "step.decode_wall_ms",
        "engine.compiles_in_window", "setup.weights_s", "setup.warmup_s",
        "step.decode_device_ms.hybrid", "step.decode_hbm_share.hybrid",
        "kernel.moe_latent_ffn_roofline", "kernel.ssm_state_roofline",
        "moe.held_assignment_share"}
    device_ms = next(m for m in bench["per_layer"]
                     if m["name"] == "step.decode_device_ms")
    assert device_ms["workloads"] == [
        "mistral7b.decode_sat", "mistral7b.chat_open",
        "mixtral8x7b-d4.decode_sat"]
    # the configuration: published widths, the cut stated beside them
    top = {k: v for k, v in CONFIG.items() if k != "bench"}
    assert (top["hidden_size"], top["mamba_num_heads"], top["moe_latent_size"],
            top["num_experts_per_tok"], top["moe_intermediate_size"]
            ) == (4096, 128, 1024, 22, 2688)
    assert (top["num_hidden_layers"], top["hybrid_override_pattern"],
            top["n_routed_experts"], top["n_routed_experts_published"],
            top["vocab_size"]) == (11, "MEMEMEM*EME", 128, 512, 32768)


def test_the_cell_rehearses_end_to_end():
    line, out = rehearse(CELL, 2)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert "reference nemotron_h" in out and "costs costs_nemotron_h" in out
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert {"itl_p95_ms", "out_tokens_per_s", "setup_s",
            "step.decode_wall_ms", "sched.slot_fill_share",
            "moe.held_assignment_share"} <= set(m)
    # the tiny model holds 8 of 16 experts
    assert 35 < m["moe.held_assignment_share"] < 65
    # no device metric from a CPU
    assert not {"step.decode_device_ms.hybrid", "kernel.ssm_state_roofline",
                "kernel.moe_latent_ffn_roofline"} & set(m)
