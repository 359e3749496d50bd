"""The ``sarvam_mla`` family in the benchmark (PR 54), taken as added files:
its reference against the program at the rehearsal size, its cost module
against bytes counted by hand (ISSUE 54's table), its metrics on a hand-made
reduction of the cell's shape (2-step and 1-step decode flights of nine
latent attention calls a step; ragged flights whose decode rows call the GQA
decode kernel, in ANOTHER program), and the cell ``sarvam105b-p1.long_sat``
rehearsed end to end on the CPU.

The cell's metric list is asserted with ``<=``: a later PR may add a metric
to the cell without editing this file."""

import json
import subprocess
import sys

import pytest
from conftest import CHIP_DIR, cpu_env

from harness import costs, costs_sarvam_mla, reducers
from harness.reducers import trace_hybrid, trace_step_ms

CELL = "sarvam105b-p1.long_sat"
CONFIG = json.loads((CHIP_DIR / "configs"
                     / "sarvam-105b-p1-ep8-int8.json").read_text())
TINY = json.loads((CHIP_DIR / "configs"
                   / "rehearsal-tiny-sarvam-mla.json").read_text())
DECODE2, DECODE1 = "jit__decode_paged_impl(81)", "jit__decode_paged_impl(12)"
RAGGED = "jit__ragged_step_impl(1915714125240641424)"
MLA = ("%paged_decode_attention_mla.11 = bf16[32,64,640]{2,1,0} custom-call("
       "s32[32,40]{1,0} %copy-done.3, s32[32]{0}")
ROWS = ("%paged_decode_attention.45 = bf16[32,1,64,640]{3,2,1,0} "
        "custom-call(s32[1280]{0} %fusion.1240")
CHUNK = ("%ragged_paged_attention.50 = bf16[32,1,16,64,640]{4,3,2,1,0}"
         " custom-call(s32[32,40]{1,0}")
MOE = "%moe_grouped_matmul.65 = bf16[256,2048]{1,0:T(8,128)(2,1)} custom-call("
N2, N1, NRAG = 30, 9, 12            # flights of 2 steps, of 1 step, ragged
D2, D1, DRAG = 0.032, 0.0163, 0.064  # seconds each; a ragged flight: 2 steps
STEPS = N2 * 2 + N1
T_MLA, T_MOE = 0.9e-3, 0.25e-3


def spec(name):
    return json.loads((CHIP_DIR / "layer_metrics" / f"{name}.json").read_text())


def reduction() -> dict:
    """What trace_reduce gives for ~2 s of the cell as the step programs are
    built (deviceless compile, tests/test_tpu_compile.py): a decode step
    calls the latent kernel NINE times and the grouped matmul 24 times; a
    ragged step calls the GQA decode kernel for its decode rows and the v2
    kernel for its chunk, nine times each, inside another program."""
    def op(per_step_calls, seconds_a_call, ragged_calls=0):
        n2, n1 = N2 * 2 * per_step_calls, N1 * per_step_calls
        nr = NRAG * 2 * ragged_calls
        total = (n2 + n1 + nr) * seconds_a_call
        return {"count": n2 + n1 + nr, "self_s": total, "total_s": total,
                "in_program": {
                    **({DECODE2: [n2, n2 * seconds_a_call],
                        DECODE1: [n1, n1 * seconds_a_call]}
                       if per_step_calls else {}),
                    **({RAGGED: [nr, nr * seconds_a_call]}
                       if ragged_calls else {})}}

    return {"devices": 1, "busy_s": 1.9, "window_s": 2.0,
            "programs": {DECODE2: [D2] * N2, DECODE1: [D1] * N1,
                         RAGGED: [DRAG] * NRAG},
            "ops": {MLA: op(9, T_MLA), ROWS: op(0, 1.4e-3, 9),
                    CHUNK: op(0, 1.1e-3, 9), MOE: op(24, T_MOE, 24)}}


def run_of(occupancy=1.0, context=4608):
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
    """ISSUE 54's table, in this repo's bytes: 523 MB an expert layer,
    295.9 MB the dense layer, 4.89 GB of weights, 11,520 B a token."""
    c = CONFIG
    assert costs_sarvam_mla.attention_layers(c) == 9
    attn = 4096 * 12864 + 512 * 16384 + 8192 * 4096
    assert 12864 == 64 * 192 + 576 and 16384 == 64 * (128 + 128)
    assert costs_sarvam_mla.mla_weight_bytes(c) == 9 * attn
    assert attn == pytest.approx(94.6e6, rel=1e-3)
    expert = 3 * 4096 * 2048
    assert expert == pytest.approx(25.17e6, rel=1e-3)
    assert 16 * expert == pytest.approx(402.7e6, rel=1e-3)
    router = 2 * 4096 * 128
    layer = attn + 16 * expert + expert + router
    assert layer == pytest.approx(523.5e6, rel=1e-3)
    dense = attn + 3 * 4096 * 16384
    assert dense == pytest.approx(295.9e6, rel=1e-3)
    assert costs_sarvam_mla.ffn_dense_bytes(c) == (
        3 * 4096 * 16384 + 8 * (expert + router))
    embed, head = 2 * 32768 * 4096, 32768 * 4096
    assert (embed, head) == (268435456, 134217728)
    whole = dense + 8 * layer + embed + head
    assert whole == pytest.approx(4.89e9, rel=1e-3)
    assert costs_sarvam_mla.resident_weight_bytes(c) == whole
    # the latent cache: a row of 576 needed, 640 stored, bf16, nine layers
    b = c["bench"]
    assert b["kv_bytes_per_token"] == 9 * 576 * 2 == 10368
    assert b["kv_bytes_per_token_stored"] == 9 * 640 * 2 == 11520
    pool = b["slots"] * b["context"] * b["kv_bytes_per_token_stored"]
    assert pool == pytest.approx(1.89e9, rel=2e-3)
    assert (whole + pool) / 16e9 == pytest.approx(0.42, abs=0.01)
    # 32 tokens, top-8 of 128, 16 held: 14 of 16 banks touched
    assert costs_sarvam_mla.experts_touched(c, 32) == pytest.approx(
        16 * (1 - (15 / 16) ** 32)) == pytest.approx(13.97, abs=0.01)
    assert costs_sarvam_mla.ffn_weight_bytes(c, 32) == pytest.approx(
        8 * expert * 13.97, rel=1e-3)
    # every live token's row once a layer: what a token NEEDS
    kv = 32 * 4608
    assert costs_sarvam_mla.latent_read_bytes(c, 32, kv) == kv * 10368
    need = costs_sarvam_mla.decode_step_bytes(c, 32, kv)
    assert need == pytest.approx(
        9 * attn + 8 * expert * 13.97 + costs_sarvam_mla.ffn_dense_bytes(c)
        + head + kv * 10368, rel=1e-3)
    # 7.0 ms at 819 GB/s; ISSUE 54 reckoned 6.3 GB, 7.7 ms: every held
    # bank, touched or not, and the 640 columns stored
    assert need == pytest.approx(5.74e9, rel=0.01)
    assert (need + 8 * expert * (16 - 13.97) + kv * (11520 - 10368)
            ) == pytest.approx(6.3e9, rel=0.02)
    # the whole model in int8, which fits 16 chips and not 8
    full = (31 * 128 * expert + 32 * (attn + expert + router)
            - (expert + router) + 3 * 4096 * 16384
            + 3 * 262144 * 4096)
    assert full == pytest.approx(106e9, rel=0.02)


def test_a_decode_step_is_nine_latent_calls_and_a_ragged_step_nine_of_another():
    run = run_of()
    step_s = (N2 * D2 + N1 * D1) / STEPS
    bw = costs.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    # the plain readers divide by all nine layers; so does the hybrid pair
    assert trace_step_ms.reduce(spec("step.decode_device_ms"), run
                                ) == pytest.approx(1e3 * step_s)
    assert trace_hybrid.reduce(spec("step.decode_device_ms.hybrid"), run
                               ) == pytest.approx(1e3 * step_s)
    # a ragged step: the ragged programs' time over THEIR steps, counted by
    # the decode rows' kernel (nine calls a step), not the decode step's
    assert reducers.compute("layer_metrics", "step.ragged_device_ms", run
                            ) == pytest.approx(1e3 * DRAG / 2)
    kv = 32 * 4608
    latent = costs_sarvam_mla.latent_read_bytes(CONFIG, 32, kv)
    assert trace_hybrid.reduce(spec("kernel.mla_attn_roofline"), run
                               ) == pytest.approx(
        100 * latent / bw / (9 * T_MLA), rel=1e-3)
    ffn = costs_sarvam_mla.ffn_weight_bytes(CONFIG, 32)
    assert trace_hybrid.reduce(spec("kernel.moe_held_ffn_roofline"), run
                               ) == pytest.approx(
        100 * ffn / bw / (24 * T_MOE), rel=1e-3)
    need = costs_sarvam_mla.decode_step_bytes(CONFIG, 32, kv)
    assert reducers.compute("layer_metrics", "step.decode_hbm_share", run
                            ) == pytest.approx(100 * need / bw / step_s,
                                               rel=1e-3)
    # a trace with no ragged flight (or a parent without the program): nothing
    quiet = run_of()
    del quiet.profile["programs"][RAGGED]
    for o in quiet.profile["ops"].values():
        o["in_program"].pop(RAGGED, None)
    assert reducers.compute("layer_metrics", "step.ragged_device_ms",
                            quiet) is None


def test_the_banks_ops_take_their_shape_from_the_configuration():
    import re

    rx = re.compile(costs_sarvam_mla.held_ffn_ops(CONFIG))
    assert rx.search(MOE)
    assert rx.search("%slice-done.9 = s8[8,4096,2048]{2,1,0} async-done(")
    assert rx.search("%slice-start.2 = s8[4,2048,4096]{2,1,0} async-start(")
    assert not rx.search("%slice-done.3 = s8[4096,2048]{1,0} async-done(")
    assert not rx.search(MLA)


def test_the_latent_gauge_is_read_by_its_kind():
    run = reducers.RunData(records=[], seconds=1.0, config=CONFIG)
    run.gauge_samples = [
        'crowdllama_engine_kv_pool_bytes{kind="latent"} 9000\n'
        f'crowdllama_engine_kv_live_bytes{{kind="latent"}} {n * 2 ** 20}\n'
        for n in (1600, 1640)]
    assert reducers.compute("layer_metrics", "cache.latent_live_mib", run
                            ) == pytest.approx(1620.0)
    # a program that calls the pool "full" (the parent): nothing, no error
    run.gauge_samples = ['crowdllama_engine_kv_live_bytes{kind="full"} 5\n']
    assert reducers.compute("layer_metrics", "cache.latent_live_mib",
                            run) is None


def test_the_cell_and_its_metrics_are_listed_as_the_issue_says():
    bench = json.loads((CHIP_DIR.parents[1] / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sarvam-105b-p1-ep8-int8", "long_sat", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"] for g in ("end_to_end", "per_layer")
              for m in bench[g] if "workloads" not in m
              or CELL in m["workloads"]}
    # ``<=``: a later PR may add to the cell what it reports
    assert {
        "itl_p95_ms", "out_tokens_per_s", "setup_s", "sched.batch_occupancy",
        "sched.slot_fill_share", "sched.short_flight_share",
        "sched.host_first_token_share", "device.idle_share.sat",
        "device.peak_mem_gib", "engine.compiles_in_window",
        "step.decode_device_ms", "step.decode_hbm_share",
        "step.ragged_device_ms", "moe.held_assignment_share",
        "moe.banks_routed_share", "moe.banks_fetched_share",
        "kernel.mla_attn_roofline", "kernel.moe_held_ffn_roofline",
        "cache.latent_live_mib"} <= listed
    assert not {"ttft_p80_ms", "kernel.paged_attn_roofline",
                "cache.window_live_mib", "kernel.kda_state_roofline",
                "step.decode_device_ms.hybrid"} & listed
    for name, moves in (("cache.latent_live_mib", "out_tokens_per_s"),
                        ("step.ragged_device_ms", "itl_p95_ms")):
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["moves"] == moves and CELL in entry["workloads"]
    entry = next(c for c in bench["configs"]
                 if c["name"] == "sarvam-105b-p1-ep8-int8")
    assert entry["reduced"] == CONFIG["bench"]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == CONFIG["bench"]["source"]
    # the configuration: the catalog's numbers but for the cuts
    top = {k: v for k, v in CONFIG.items() if k != "bench"}
    assert (top["hidden_size"], top["num_attention_heads"], top["head_dim"],
            top["q_head_dim"], top["kv_lora_rank"], top["qk_nope_head_dim"],
            top["qk_rope_head_dim"], top["v_head_dim"],
            top["intermediate_size"], top["moe_intermediate_size"],
            top["num_experts_per_tok"], top["routed_scaling_factor"],
            top["first_k_dense_replace"], top["num_shared_experts"]
            ) == (4096, 64, 576, 192, 512, 128, 64, 128, 16384, 2048, 8,
                  2.5, 1, 1)
    assert top["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "deepseek_yarn"}
    assert (top["num_hidden_layers"], top["num_hidden_layers_published"],
            top["num_experts"], top["num_experts_published"],
            top["expert_parallel_size"], top["expert_parallel_rank"],
            top["vocab_size"], top["vocab_size_published"]) == (
        9, 32, 16, 128, 8, 0, 32768, 262144)
    # the existing mix, unchanged: 4 and 1 parts of 5 of the served context
    traffic = json.loads((CHIP_DIR / "traffic" / "long_sat.json").read_text())
    assert (traffic["generator"], traffic["clients_per_slot"],
            traffic["ramp_s"], traffic["checked"]) == (
        "closed_context", 2, 20, 4)
    b = CONFIG["bench"]
    from harness import generators

    ctx = {"seed": 5, "seconds": 51.0, "slots": b["slots"],
           "context": b["context"], "vocab_size": CONFIG["vocab_size"]}
    plan = generators.build_plan(traffic, ctx)
    assert len(plan.actors) == 64 and plan.ramp_s == 20
    turns = [a.next_turn(None) for a in plan.actors[32:]]
    assert {(len(t.prompt_ids), t.max_tokens) for t in turns} == {(4096, 1024)}
    assert max(max(t.prompt_ids) for t in turns) < 32768
    assert (b["slots"], b["context"], b["decode_chunk"], b["reference"],
            b["costs"], b["rehearsal"]) == (
        32, 5120, 2, "sarvam_mla", "costs_sarvam_mla",
        "rehearsal-tiny-sarvam-mla")
    assert b["worker_env"]["CROWDLLAMA_TPU_DECODE_CHUNK"] == "2"
    assert b["worker_env"]["CROWDLLAMA_TPU_MAX_CONTEXT_LENGTH"] == "5120"
    # each control of the reference is either told by the chip's check or
    # named with the CPU test that holds it
    from harness.reference import limits

    tol = limits("sarvam_mla")
    assert tol["set_from"] and 0 < tol["mean_deficit"] < tol["max_deficit"]
    assert "tests/test_sarvam_mla.py" in b["not_checked"]


def test_the_program_reads_both_configurations_as_the_reference_does(tmp_path):
    """Both files, written as the launcher writes a model directory, through
    the worker's own reader: the pattern the reference derives is the
    program's, and the cost module counts the program's parameters."""
    from harness.reference import sarvam_mla as R

    from crowdllama_tpu.engine.weights import resolve_model_config
    from crowdllama_tpu.models import transformer as T

    for doc in (CONFIG, TINY):
        hf = {k: v for k, v in doc.items() if k != "bench"}
        (tmp_path / "config.json").write_text(json.dumps(hf))
        cfg = resolve_model_config(doc["bench"]["name"], str(tmp_path))
        assert cfg.family == "sarvam_mla"
        hp = R.hyper(hf)
        assert cfg.layer_pattern == hp["pattern"]
        assert (cfg.num_experts, cfg.experts_held) == (
            hf["num_experts_published"], hf["num_experts"])
        assert T.attn_scale(cfg) == pytest.approx(R.score_scale(hp), rel=1e-6)
        assert cfg.resolved_head_dim() == hf["head_dim"]
        assert (cfg.num_layers * cfg.resolved_head_dim() * 2
                == doc["bench"]["kv_bytes_per_token"])
    hf = {k: v for k, v in CONFIG.items() if k != "bench"}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg = resolve_model_config("x", str(tmp_path))
    assert cfg.layer_pattern == "RD" + "RS" * 8
    # every matrix a byte but the bf16 embedding and router: the count is
    # the program's parameters (norm gains and the bias are the rest)
    counted = (costs_sarvam_mla.resident_weight_bytes(CONFIG)
               - 32768 * 4096 - 8 * 4096 * 128)
    assert counted == pytest.approx(cfg.param_count(), rel=1e-3)


def test_the_reference_is_the_program_at_the_rehearsal_size(tmp_path):
    """Teacher-forced logits of the rehearsal model, float32 weights: the
    program's prefill against the reference's full forward pass, at
    positions beyond YaRN's original length."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness.reference import sarvam_mla as R

    from crowdllama_tpu.engine.weights import resolve_model_config
    from crowdllama_tpu.models import hybrid as H
    from crowdllama_tpu.models import transformer as T

    hf = {k: v for k, v in TINY.items() if k != "bench"}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg = resolve_model_config(TINY["bench"]["name"], str(tmp_path))
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    n = 4 * hf["rope_scaling"]["original_max_position_embeddings"] + 7
    ids = [int(t) for t in np.random.default_rng(0).integers(1, 512, n)]
    toks = np.zeros((1, 256), np.int32)
    toks[0, :n] = ids
    got = H.prefill(params, cfg, jnp.asarray(toks),
                    jnp.minimum(jnp.arange(256), n - 1)[None],
                    (jnp.arange(256) < n)[None])[0][0, :n]
    with jax.default_matmul_precision("highest"):
        ref = R.forward(params, hf, ids, list(range(n)))
        still = R.forward({**params}, {**hf, "reference_controls":
                                       ["no_k_rope"]}, ids, list(range(n)))
    err = jnp.max(jnp.abs(got - ref), -1) / jnp.std(ref, -1)
    assert float(jnp.max(err)) < 1e-3, float(jnp.max(err))
    off = jnp.max(jnp.abs(got - still), -1) / jnp.std(still, -1)
    assert float(jnp.max(off)) > 0.1


def test_the_cell_rehearses_end_to_end():
    """The whole flow at tiny size on the CPU: prompts and replies past
    YaRN's original length, admitted in chunks through the ragged step (the
    rehearsal's step token budget), the latent decode kernel and both
    ragged kernels in interpret mode, nothing compiled in the window."""
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
    # limits are set from runs at the published widths.
    assert line["failed"] == 0 and isinstance(line["correct"], bool)
    check = json.loads(out.split("info: reference check: ", 1)[1]
                       .splitlines()[0])
    assert check["tokens"] == 4 * 64 and not check["problems"]
    assert check["mean_deficit"] < 0.1 and check["max_deficit"] < 3.0
    assert check["argmax_agree_share"] > 0.7
    assert line["device"]["platform"] == "cpu"
    assert "reference sarvam_mla" in out and "costs costs_sarvam_mla" in out
    assert out.count('path="pallas_interpret"') == 3
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert {"itl_p95_ms", "out_tokens_per_s", "setup_s",
            "step.decode_wall_ms", "sched.slot_fill_share",
            "sched.host_first_token_share", "moe.held_assignment_share",
            "moe.banks_fetched_share", "cache.latent_live_mib"} <= set(m)
    assert m["engine.compiles_in_window"] == 0
    # the tiny model holds 8 of 16 experts
    assert 35 < m["moe.held_assignment_share"] < 65
    # four slots of at most 256 tokens, three layers of one 256-wide bf16
    # row a token (144 computed, whole lanes stored)
    assert 0 < m["cache.latent_live_mib"] <= 4 * 256 * 3 * 256 * 2 / 2 ** 20
    # no device metric from a CPU
    assert not {"step.decode_device_ms", "step.ragged_device_ms",
                "kernel.mla_attn_roofline", "kernel.moe_held_ffn_roofline"
                } & set(m)
