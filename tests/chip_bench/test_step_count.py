"""Decode steps are counted by the decode attention kernel's name inside
the decode programs (TRACING.md, PR 26): on the recorded fixture, renamed as
today's program names its kernel, plus a few hand-made events that carry the
op texts of today's traces."""

import json
import re

import pytest
from conftest import CHIP_DIR

from harness import reducers, trace_reduce
from harness.reducers import trace_kernel_roofline, trace_step_ms

FIX = CHIP_DIR / "fixtures" / "decode_sat.xspace.txt"
LAYERS = 32
# op texts as the program's traces carry them (my chip runs, PR 25)
ATTN = "%paged_decode_attention.8 = bf16[8,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} custom-call("
FLASH = ("%flash_prefill_attention.6 = bf16[1,128,8,4,128]{4,3,2,1,0:T(4,128)(2,1)S(1)} "
         "custom-call(s32[1,1]{1,0:T(1,128)} %p), custom_call_target=\\\"tpu_custom_call\\\"")
GROUPED = ("%moe_grouped_matmul.3 = bf16[32,14336]{1,0:T(8,128)(2,1)} custom-call(s32[1]{0} %g), "
           "custom_call_target=\\\"tpu_custom_call\\\"")
RAGGED = ("%ragged-dot-none.1 = bf16[256,14336]{1,0:T(8,128)(2,1)} custom-call(s32[1]{0} %g), "
          "custom_call_target=\\\"tpu_custom_call\\\"")
PREFILL = "jit__prefill_impl(1915714125240641424)"
US = 1_000_000          # picoseconds


def spec(name):
    return json.loads((CHIP_DIR / "layer_metrics" / f"{name}.json").read_text())


def todays_names(text: str) -> str:
    """The fixture was recorded on PR 22's program, whose decode attention
    kernel was an unnamed ``%closed_call.12``."""
    assert text.count("%closed_call.12 = ") == 1
    return text.replace("%closed_call.12 = bf16[8,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} custom-call(", ATTN)


def with_events(text: str, ops=(), modules=()) -> str:
    """``text`` with hand-made events (name, offset_ps, duration_ps)
    appended to the first device plane's op and module lines."""
    plane, rest = text.split("planes { id: 2 ", 1)
    key = max(int(k) for k in re.findall(r"event_metadata \{ key: (\d+) ", plane))
    meta = []
    for marker, events in ((' lines { id: 2 name: "XLA Ops"', modules),
                           (' lines { id: 3 name: "Async XLA Ops"', ops)):
        lines = ""
        for name, off, dur in events:
            key += 1
            meta.append(f' event_metadata {{ key: {key} value {{ id: {key} name: "{name}" }} }}\n')
            lines += f" events {{ metadata_id: {key} offset_ps: {off} duration_ps: {dur} }}\n"
        head, tail = plane.split(marker, 1)
        assert head.endswith(" }\n")
        plane = head[:-3] + lines + " }\n" + marker + tail
    assert plane.endswith("}\n")
    return plane[:-2] + "".join(meta) + "}\nplanes { id: 2 " + rest


def reduced(tmp_path, text):
    p = tmp_path / "t.xspace.txt"
    p.write_text(text)
    return trace_reduce.reduce(str(p))


def run_of(red):
    run = reducers.RunData(records=[], seconds=1.0, config={
        "num_hidden_layers": LAYERS, "bench": {"slots": 8}})
    run.profile = red
    return run


def attention_calls(text):
    """(start_ps, end_ps) of the fixture's decode attention calls."""
    plane = text.split("planes { id: 2 ", 1)[0]
    key = re.search(r'event_metadata \{ key: (\d+) value \{ id: \d+ name: "%closed_call\.12 = ', plane).group(1)
    ops = plane.split(' lines { id: 2 name: "XLA Ops"', 1)[1].split(' lines { id: 3 ', 1)[0]
    return [(int(a), int(a) + int(d)) for a, d in re.findall(
        rf"metadata_id: {key} offset_ps: (\d+) duration_ps: (\d+) ", ops)]


@pytest.fixture(scope="module")
def base():
    return FIX.read_text()


def case_events(base, case):
    calls = attention_calls(base)
    end = 2_960_000 * US                   # after the fixture's last event
    if case == "as_recorded":
        return (), ()
    if case == "a_grouped_matmul_a_layer":
        # a second tpu_custom_call for every attention call, inside a decode
        # program (the fixture's op line is cut after 400 events, its
        # module line is whole: one second in, a decode flight is running)
        return [(GROUPED, 1_000_000 * US + 10 * i * US, 2 * US)
                for i in range(len(calls))], ()
    if case == "a_prefill_between_flights":
        return ([(FLASH, end + (2 * i + 1) * US, US) for i in range(LAYERS)]
                + [(RAGGED, end + (2 * i + 2) * US, US // 2) for i in range(LAYERS)],
                [(PREFILL, end, (2 * LAYERS + 2) * US)])
    raise AssertionError(case)


@pytest.mark.parametrize("case", ["as_recorded", "a_grouped_matmul_a_layer",
                                  "a_prefill_between_flights"])
def test_steps_are_the_attention_kernels_calls_inside_the_decode_programs(
        tmp_path, base, case):
    n_attn = len(attention_calls(base))
    assert n_attn >= 8
    ops, modules = case_events(base, case)
    red = reduced(tmp_path, with_events(todays_names(base), ops, modules))
    for metric in ("step.decode_device_ms", "step.decode_hbm_share",
                   "kernel.paged_attn_roofline", "kernel.moe_ffn_roofline"):
        assert trace_step_ms.steps_traced(spec(metric), run_of(red)) == \
            pytest.approx(n_attn / LAYERS), metric
    # the rule these files had before PR 26 took every other custom call
    # for a step
    old = {"step_op": "^(?!%ragged-dot).*tpu_custom_call"}
    extra = {"as_recorded": 0, "a_grouped_matmul_a_layer": n_attn,
             "a_prefill_between_flights": LAYERS}[case]
    assert trace_step_ms.steps_traced(old, run_of(red)) == pytest.approx(
        (n_attn + extra) / LAYERS)
    # the step's time is the decode programs' whatever ran beside them
    want = sum(trace_reduce.program_durations(red, "decode_paged")) / (
        n_attn / LAYERS)
    assert trace_step_ms.step_seconds(spec("step.decode_device_ms"),
                                      run_of(red)) == pytest.approx(want)


def test_a_kernel_named_moe_is_the_expert_layers_and_a_prefills_is_not(
        tmp_path, base):
    s = spec("kernel.moe_ffn_roofline")
    n_attn = len(attention_calls(base))
    plain = reduced(tmp_path, todays_names(base))
    assert trace_reduce.op_time(plain, s["op"], s["program"]) == (0.0, 0)
    ops, _ = case_events(base, "a_grouped_matmul_a_layer")
    red = reduced(tmp_path, with_events(todays_names(base), ops))
    t, n = trace_reduce.op_time(red, s["op"], s["program"])
    assert n == n_attn and t == pytest.approx(n_attn * 2e-6)
    # ... and not the decode attention kernel's
    a = spec("kernel.paged_attn_roofline")
    assert trace_reduce.op_time(red, a["op"], a["program"])[1] == n_attn
    # a prefill's ragged-dot matches the pattern, and is not a decode step's
    ops, modules = case_events(base, "a_prefill_between_flights")
    red = reduced(tmp_path, with_events(todays_names(base), ops, modules))
    assert trace_reduce.op_time(red, s["op"])[1] == LAYERS
    assert trace_reduce.op_time(red, s["op"], s["program"]) == (0.0, 0)
    assert trace_reduce.op_time(red, "^%flash_prefill", "prefill_impl")[1] == LAYERS


def test_the_roofline_reader_counts_kernel_and_steps_in_the_same_programs(
        tmp_path, base):
    """kernel.moe_ffn_roofline end to end on a hand-made expert kernel: 2 us
    a layer for bytes that take 1 us at the published bandwidth = 50%,
    with and without a prefill's calls in the trace."""
    from harness import costs

    ops, _ = case_events(base, "a_grouped_matmul_a_layer")
    pre_ops, pre_mod = case_events(base, "a_prefill_between_flights")
    bw = costs.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    got = []
    for more_ops, modules in (((), ()), (pre_ops, pre_mod)):
        red = reduced(tmp_path, with_events(
            todays_names(base), list(ops) + list(more_ops), modules))
        run = run_of(red)
        run.device_kind = "TPU v5 lite"
        s = {**spec("kernel.moe_ffn_roofline"),
             "bytes": "costs.kv_read_bytes"}      # kv_tokens x bytes a token
        run.config["bench"]["kv_bytes_per_token"] = LAYERS * 1e-6 * bw
        run.gauge_samples = ["crowdllama_engine_batch_occupancy 0.125\n"]
        run.records = [type("R", (), {"prompt_len": 1, "frame_t": [0.5],
                                      "frame_tokens": [1]})()]
        got.append(trace_kernel_roofline.reduce(s, run))
    assert got[0] == pytest.approx(50.0) and got[1] == pytest.approx(50.0)
