"""Family ``afmoe`` on the cache of two kinds of page, beside
tests/test_afmoe.py (whose rows hold the logits to the reference on every
path): the CONTROLS those rows are shown to see, the share of the experts,
the sandwich residual, the refusals by name, the served path with its
gauges, and the family as its ``config.json`` says it."""

import json
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_afmoe import (CFG, KEY, LIMITS, R, distance, hf_of, make_runner,
                        prompt_of, run_path)

from crowdllama_tpu.engine.hybrid import HybridPagedModelRunner
from crowdllama_tpu.models import hybrid as H
from crowdllama_tpu.models import transformer as T

pytestmark = pytest.mark.usefixtures("_programs_go_with_their_test")


# the controls: what the float32 row is shown to see

@pytest.mark.parametrize("control, path", [
    ("no_window", "prefill"), ("no_window", "ragged"),
    ("rope_on_full", "prefill"), ("rope_on_full", "decode"),
    ("no_gate", "prefill"), ("bias_in_weights", "prefill"),
    ("no_post_attn_norm", "prefill"), ("no_post_mlp_norm", "prefill"),
    ("no_qk_norm", "prefill"), ("no_mup", "prefill")])
def test_a_wrong_equation_reads_over_the_limit(control, path):
    """Each is a reading of the model that a careless port would make; the
    reference so altered is over the float32 limit on every group of rows —
    the two that are about the two kinds of layer through both pools too."""
    r = make_runner("float32")
    for what, logits, ids, positions in run_path(r, path):
        worst, mean = distance(logits, ids, positions, r,
                               controls=(control,))
        assert worst > 0.02 and mean > 30 * LIMITS["float32"][1], (
            what, worst, mean)


@pytest.mark.parametrize("control", ["int8_kv", "bf16_router"])
def test_the_nearest_precision_below_reads_over_the_limit(control):
    """The two precisions the configuration states beside its matmuls': KV
    in bf16 (not int8) and the router in float32 (not bf16)."""
    r = make_runner("float32", cls=HybridPagedModelRunner)
    (_, logits, ids, positions), = run_path(r, "prefill")
    worst, _ = distance(logits, ids, positions, r, controls=(control,))
    assert worst > 3 * LIMITS["float32"][0], worst


@pytest.mark.parametrize("control", ["no_window", "no_gate",
                                     "no_post_attn_norm"])
def test_a_control_named_in_the_config_is_the_control(control):
    """``reference_controls`` in the configuration a check is handed is how
    a control goes THROUGH harness/reference/check.py (which hands the
    reference the configuration and nothing else): it is the ``controls``
    argument, and not the sound reference."""
    r = make_runner("float32")
    ids = prompt_of(40, 3)
    positions = list(range(len(ids)))
    hf = hf_of(r.cfg)
    with jax.default_matmul_precision("highest"):
        named = R.forward(r.params, {**hf, "reference_controls": [control]},
                          ids, positions)
        given = R.forward(r.params, hf, ids, positions, (control,))
        sound = R.forward(r.params, hf, ids, positions)
    np.testing.assert_array_equal(np.asarray(named), np.asarray(given))
    assert float(jnp.max(jnp.abs(named - sound))) > 1e-3


def test_one_pass_bf16_matmuls_read_over_the_limit(monkeypatch):
    """...and the matmuls' precision: float32 weights and activations
    rounded to bf16 on their way into every projection (what one pass of
    the MXU does to a float32 matmul; the CPU has no such pass to ask for)
    fail it."""
    from crowdllama_tpu.ops import quant

    def one_pass(subscript, x, w, dtype=None):
        lo = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.einsum(subscript, lo(x), lo(quant.dequant(w)))

    monkeypatch.setattr(H, "qeinsum", one_pass)
    r = make_runner("float32", cls=HybridPagedModelRunner)
    (_, logits, ids, positions), = run_path(r, "prefill")
    worst, _ = distance(logits, ids, positions, r)
    assert worst > 3 * LIMITS["float32"][0], worst


def test_a_ring_too_short_reads_over_the_limit():
    """The ring's length is part of what the tests guard: with one page
    fewer than ``window + chunk + page`` a chunk writes over keys its first
    rows still see."""
    from crowdllama_tpu.ops.pallas.paged import Ring

    r = make_runner("float32")
    r.ring = Ring(r.ring.pages - 2, r.ring.window)
    worst = max(distance(logits, ids, positions, r)[0]
                for _, logits, ids, positions in run_path(r, "ragged"))
    assert worst > 3 * LIMITS["float32"][0], worst


# the share

def test_the_shares_add_up_to_the_uncut_layer():
    """The eight ranks' routed parts, plus the shared expert counted once,
    are the uncut reference's expert layer (before its post-norm, which is
    not linear: every rank norms the SUM, after the exchange)."""
    whole = replace(CFG, experts_held=0, post_norms=False)
    params = T.init_params(whole, KEY, jnp.float32)
    lp = params["layers"]["smoe"][0]
    banks = ("w_gate", "w_up", "w_down")
    x = jax.random.normal(jax.random.PRNGKey(3), (24, CFG.hidden_size))
    h = R.rms_norm(x, R.dequant(lp["norm"]), CFG.rms_norm_eps)
    ranks, held = 8, CFG.num_experts // 8
    with jax.default_matmul_precision("highest"):
        uncut = R.mixer("S", h, lp, R.hyper(hf_of(whole)))
        shared = R.swiglu(h, lp["ws_gu"], lp["ws_down"])
        total, rows = 0.0, np.zeros(len(H.COUNTS), np.int64)
        for rank in range(ranks):
            cfg = replace(whole, experts_held=held, expert_rank=rank)
            mine = {**lp, **{b: lp[b][held * rank:held * (rank + 1)]
                             for b in banks}}
            out, counts = H.smoe_body(mine, cfg, x, jnp.ones((24,), bool))
            total = total + (out - x - shared)
            rows += np.asarray(counts)
            # and the reference, given the same share, says the same
            part = R.mixer("S", h, mine, R.hyper(hf_of(cfg)))
            np.testing.assert_allclose(out - x, part, atol=2e-5)
    np.testing.assert_allclose(total + shared, uncut, atol=5e-5)
    # every token-expert row is computed by exactly one rank
    assert rows[0] == 24 * CFG.num_experts_per_tok
    assert rows[1] == (ranks - 1) * rows[0]
    assert 0 < rows[2] <= rows[3] == rows[4] == CFG.num_experts


def test_the_sandwich_form_is_the_reference_s_sublayer():
    """With the second gain the residual takes ``RMSNorm_post`` of the
    mixer's output, for the dense MLP, the expert layer and attention."""
    params = T.init_params(CFG, KEY, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (24, CFG.hidden_size))
    hp = R.hyper(hf_of(CFG))
    gains = jax.random.uniform(jax.random.PRNGKey(6), (CFG.hidden_size,),
                               minval=0.5, maxval=1.5)
    live = jnp.ones((24,), bool)
    with jax.default_matmul_precision("highest"):
        for kind, body in (("D", lambda lp: H.mlp_body(lp, CFG, x)),
                           ("S", lambda lp: H.smoe_body(lp, CFG, x, live)[0])):
            lp = {**params["layers"][H.STACK[kind]][0], "post_norm": gains}
            np.testing.assert_allclose(
                body(lp), R.sublayer(kind, x, lp, hp), atol=2e-5)


# the refusals, by name

def test_what_rests_on_a_prefix_s_pages_declines_by_name():
    """Prefix reuse, page export and import, the drain hand-off, speculation,
    the contiguous layout, int8 KV: a window layer no longer holds a
    prefix's pages (this model has no recurrent state, so the reason is its
    own)."""
    from crowdllama_tpu.config import Configuration
    from crowdllama_tpu.engine.factory import build_runner
    from crowdllama_tpu.engine.plan import resolve_serving_plan
    from crowdllama_tpu.engine.runner import ModelRunner
    from crowdllama_tpu.engine.spec import SpecPagedModelRunner

    r = make_runner("bfloat16", cls=HybridPagedModelRunner, prefix_cache=True)
    st = r.init_state()
    assert r.no_pages == H.NO_WINDOW_PAGES != H.NO_PAGES
    assert not r.prefix_cache
    assert not r.prefill_prefers_monolithic(prompt_of(200, 1))
    with pytest.raises(ValueError, match=H.NO_WINDOW_PAGES):
        r.export_pages(st, [b"x"])
    with pytest.raises(ValueError, match=H.NO_WINDOW_PAGES):
        r.import_pages(st, {"keys": [b"x"], "k_pages": [b""],
                            "v_pages": [b""]})
    with pytest.raises(ValueError, match=H.NO_WINDOW_PAGES):
        SpecPagedModelRunner(CFG, params=r.params, max_slots=2, max_seq=64)
    with pytest.raises(ValueError, match="engine/hybrid.py"):
        ModelRunner(CFG, params=r.params, max_slots=2, max_seq=64)
    for spec in ("ngram", "draft"):
        config = Configuration(model=CFG.name, spec_decode=spec,
                               spec_draft_model="tiny-test")
        with pytest.raises(ValueError, match=H.NO_WINDOW_PAGES):
            build_runner(config, resolve_serving_plan(config, 1), CFG,
                         r.params)
    config = Configuration(model=CFG.name, kv_layout="contiguous")
    with pytest.raises(ValueError, match="paged layout only"):
        build_runner(config, resolve_serving_plan(config, 1), CFG, r.params)
    with pytest.raises(ValueError, match="no int8 KV"):
        make_runner("bfloat16", cls=HybridPagedModelRunner, kv_dtype="int8")


async def test_served_through_the_engine_with_its_gauges():
    """The normal path: JaxEngine -> scheduler -> the hybrid runner, ragged
    admission on (both flight lengths warmed up); every admission a prefix
    miss; the two pools' gauges; nothing to export for the KV plane or a
    drain."""
    from crowdllama_tpu.config import Configuration, Intervals
    from crowdllama_tpu.engine.engine import JaxEngine
    from crowdllama_tpu.obs.metrics import (ENGINE_TELEMETRY,
                                            engine_gauge_lines)

    def series(name: str, lines=None) -> float:
        return sum(float(ln.rsplit(" ", 1)[1])
                   for ln in (lines or ENGINE_TELEMETRY.expose())
                   if ln.startswith(name))

    engine = JaxEngine(Configuration(
        model=CFG.name, max_context_length=256, max_batch_slots=2,
        warmup=True, kv_page_size=8, step_token_budget=18, decode_chunk=2,
        kv_ship=True, intervals=Intervals.default()))
    await engine.start()
    try:
        r = engine._runner
        assert isinstance(r, HybridPagedModelRunner) and r.ring.pages == 5
        compiled = ENGINE_TELEMETRY.snapshot_compiles()
        width = r.max_pages_per_slot
        for k in (1, 2):    # one table width, both flight lengths
            assert compiled[("ragged_step", f"{k}x16w{width}")] >= 1
        before = series("crowdllama_xla_compiles_total")
        reused = series("crowdllama_prefix_tokens_reused_total")
        on_device = series('crowdllama_admissions_total{first_token="device"}')
        on_host = series('crowdllama_admissions_total{first_token="host"}')
        long = "one two three four five six seven eight nine ten " * 3
        n = len(engine.tokenizer.encode(long))
        assert n > 4 * r.ragged_chunk + r.cfg.sliding_window
        for prompt in (long, long):
            out = [c async for c in engine.generate(prompt, max_tokens=40)]
            assert out[-1].done and out[-1].completion_tokens == 40, out[-1]
            live = engine_gauge_lines(engine.obs_gauges())
        assert series("crowdllama_prefix_tokens_reused_total") == reused
        # a ragged finish leaves its first token on the device, as an insert
        # does: the host reads it behind the next flight's dispatch
        assert series('crowdllama_admissions_total{first_token="device"}'
                      ) == on_device + 2
        assert series('crowdllama_admissions_total{first_token="host"}'
                      ) == on_host
        # no ragged or decode program was compiled in service
        new = {k: v for k, v in ENGINE_TELEMETRY.snapshot_compiles().items()
               if v > compiled.get(k, 0)}
        assert not any(p.startswith(("ragged", "decode")) for p, _ in new), new
        assert series("crowdllama_xla_compiles_total") - before == len(new)
        assert series('crowdllama_attn_decode_path{path="gqa+window"}') == 1
        st = engine.scheduler.state
        page = 2 * CFG.num_kv_heads * 8 * CFG.head_dim * 2
        assert series('crowdllama_engine_kv_pool_bytes{kind="window"}',
                      live) == 2 * 5 * 4 * page == 2 * (
            st.wpool_k.nbytes - st.wpool_k[:, 0].nbytes)
        assert series('crowdllama_engine_kv_pool_bytes{kind="full"}',
                      live) == 2 * 32 * page
        assert series('crowdllama_engine_kv_live_bytes{kind="window"}',
                      live) == 0    # released: both pools back at their start
        assert series('crowdllama_engine_kv_live_bytes{kind="full"}',
                      live) == 0
        assert series("crowdllama_engine_kv_window_pages_recycled_total",
                      live) >= 2 * ((n + 40) // 8 - 5)
        assert series('crowdllama_engine_state_bytes{kind="kv_window_pool"}'
                      ) == 2 * st.wpool_k.nbytes
        assert await engine.export_kv_pages(CFG.name, [b"k"], 8) is None
        assert not engine._kv_ship_ready()
    finally:
        await engine.stop()


# ------------------------------------------------ the family by its config

AFMOE = {
    "model_type": "afmoe", "architectures": ["AfmoeForCausalLM"],
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 5,
    "num_dense_layers": 1, "global_attn_every_n_layers": 4,
    "layer_types": ["sliding_attention"] * 4 + ["full_attention"],
    "num_attention_heads": 12, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "rms_norm_eps": 1e-5, "sliding_window": 16,
    "mup_enabled": True, "num_experts": 8, "num_experts_published": 16,
    "expert_parallel_size": 2, "expert_parallel_rank": 0,
    "num_experts_per_tok": 4, "num_shared_experts": 1,
    "moe_intermediate_size": 32, "route_norm": True, "route_scale": 2.448,
    "score_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "num_expert_groups": 1, "num_limited_groups": 1, "hidden_act": "silu",
    "load_balance_coeff": 5e-05, "max_position_embeddings": 256,
    "rope_theta": 10000, "rope_scaling": None, "tie_word_embeddings": False,
    "use_grouped_mm": True,
}


def _dir(tmp_path, doc: dict) -> str:
    (tmp_path / "config.json").write_text(json.dumps(doc))
    return str(tmp_path)


def test_an_afmoe_config_json_is_read_as_what_it_is(tmp_path):
    from crowdllama_tpu.engine.weights import resolve_model_config

    cfg = resolve_model_config("some-dir-name", _dir(tmp_path, AFMOE))
    assert cfg == replace(CFG, name="some-dir-name")
    assert cfg.layer_pattern == "WDWSWSWSFS" and H.attn_kinds(cfg) == "WWWWF"
    assert cfg.embedding_multiplier == 8.0 and cfg.post_norms


@pytest.mark.parametrize("change, match", [
    ({"model_type": "llama"}, "moe_intermediate_size"),
    ({"model_type": "mixtral"}, "num_shared_experts"),
    ({"model_type": "afmoe2"}, "not a family"),
    ({"score_func": "softmax"}, "score_func"),
    ({"n_group": 2}, "n_group"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"layer_types": ["sliding_attention"] * 4}, "layer_types"),
    ({"layer_types": ["sliding_attention"] * 4 + ["linear_attention"]},
     "layer_types"),
    ({"sliding_window": None}, "sliding_window"),
])
def test_it_is_never_read_as_another_family(tmp_path, change, match):
    from crowdllama_tpu.engine.weights import resolve_model_config

    with pytest.raises(ValueError, match=match):
        resolve_model_config("x", _dir(tmp_path, {**AFMOE, **change}))


def test_window_layers_book_their_rings_grid_steps(monkeypatch):
    """crowdllama_attn_grid_steps_total{kind="window"}: a window layer's
    decode kernel walks its slot's RING through a table of the pages the
    window reaches, lengths counted from the page the window starts in —
    so a long context books what a short one does, and the full layer's
    list grows with it."""
    from test_afmoe import admit
    from test_paged import _grid_steps

    monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
    r = make_runner("float32-kernel", cls=HybridPagedModelRunner)
    assert r.ring.window == 64 and r.page_size == 32 and r.max_slots == 4
    st = r.init_state()
    for slot, n in ((0, 10), (3, 200)):
        _, st = admit(r, st, slot, prompt_of(n, slot))
    before = _grid_steps()
    _, st = r.decode_steps(st, 1)
    booked = {k: v - before[k] for k, v in _grid_steps().items()}
    # two pages a grid step.  Window layers (four): a table of 3 columns;
    # slot 0 reads 11 tokens (one pair), slot 3 the 73 from the page its
    # window starts in (two).  The full layer: 8 columns; 11 tokens (one
    # pair) and 201 (four).
    assert booked == {'{kind="window",walk="live"}': 4 * (1 + 2),
                      '{kind="window",walk="rectangle"}': 4 * (4 * 2),
                      '{kind="full",walk="live"}': 1 + 4,
                      '{kind="full",walk="rectangle"}': 4 * 4}
