"""Family ``sarvam_mla`` (latent attention with a decoupled, YaRN-scaled
rotary part in EVERY layer on a one-row-a-token paged cache, a dense SwiGLU
first layer, then a sigmoid-routed SwiGLU mixture of experts with a shared
expert, of which this worker holds a share) on the paged engine, against its
plain reference (benchmarks/chip/harness/reference/sarvam_mla.py) — LOGITS,
at tiny size on the CPU, seeded random weights: prefill; prefill then decode
steps through the paged state; a prompt admitted in chunks through the
ragged step beside decoding slots; a prompt over two monolithic prefill
chunks.

The program serves the ABSORBED form over rows whose rotary part is rotated
as two halves (``ops/rope.py`` ``rotate_half``) before they are cached; the
reference expands K and V a head and rotates the published interleaved
pairs: two algebraic forms of one equation, tied by
``models/convert.py`` ``rotary_halves_from_interleaved``.  The tiny model's
YaRN scaling has an original length of 32, SHORTER than every prompt here,
so positions past it are compared.

THE LIMITS (``LIMITS``), in standard deviations of the reference's logits at
the position, (worst position, mean over positions):

* float32 (1e-3, 1e-4): both sides compute the same equations in float32;
  what is left is the order of the sums; read 1e-5.  This is the row that
  holds the equations and the matmuls' precision: each control of the
  reference, one-pass bf16 matmuls and a bf16 router read over it.
* bfloat16 and int8 (0.2, 0.1): bf16 activations (and bf16 or int8 weights)
  against float32 over the same weights; these rows run a router that
  chooses all its experts (tests/test_hybrid.py ``ALL_CHOSEN`` has why).

The float32 row runs twice: as the CPU serves it (the gathered latent
view), and with the Pallas ``paged_decode_attention_mla`` and the ragged
kernels in interpret mode (``float32-kernel``: a latent width of whole
lanes, which the kernels ask for).
"""

import json
import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

from harness.reference import sarvam_mla as R  # noqa: E402

from crowdllama_tpu.engine.hybrid import HybridPagedModelRunner  # noqa: E402
from crowdllama_tpu.models import hybrid as H  # noqa: E402
from crowdllama_tpu.models import transformer as T  # noqa: E402
from crowdllama_tpu.models.config import RopeScaling, get_config  # noqa: E402
from crowdllama_tpu.models.convert import (  # noqa: E402
    rotary_halves_from_interleaved,
)
from crowdllama_tpu.ops import rope  # noqa: E402
from crowdllama_tpu.ops.quant import random_quantized_params  # noqa: E402

KEY = jax.random.PRNGKey(0)
CFG = get_config("tiny-test-sarvam-mla")
ALL_CHOSEN = replace(CFG, num_experts=8, num_experts_per_tok=8,
                     experts_held=4)
LIMITS = {"float32": (1e-3, 1e-4), "bfloat16": (0.2, 0.1),
          "int8": (0.2, 0.1)}
PATHS = ("prefill", "decode", "ragged")
ROWS = [*LIMITS, "float32-kernel"]
CELL = ROOT / "benchmarks" / "chip" / "configs" / "sarvam-105b-p1-ep8-int8.json"


pytestmark = pytest.mark.usefixtures("_programs_go_with_their_test")


@pytest.fixture
def kernels(monkeypatch):
    """``(row) -> precision``: for a ``-kernel`` row, Pallas in interpret
    mode, which a runner built afterwards takes for ``kernel_cfg``."""
    def use(row: str) -> str:
        if row.endswith("-kernel"):
            monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
        return row.removesuffix("-kernel")

    return use


def kernel_cfg(row: str):
    """The configuration a row runs: with the kernels, a latent width of
    128."""
    cfg = CFG if row.startswith("float32") else ALL_CHOSEN
    if row.endswith("-kernel"):
        cfg = replace(cfg, kv_lora_rank=128,
                      head_dim=128 + cfg.qk_rope_head_dim)
    return cfg


def hf_of(cfg) -> dict:
    """The config.json keys the reference reads, of a registry config."""
    s = cfg.rope_scaling
    return {
        "model_type": "sarvam_mla", "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers, "rms_norm_eps": cfg.rms_norm_eps,
        "num_attention_heads": cfg.num_heads, "vocab_size": cfg.vocab_size,
        "intermediate_size": cfg.intermediate_size,
        "first_k_dense_replace": cfg.layer_pattern[1::2].count("D"),
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "q_head_dim": cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
        "head_dim": cfg.kv_lora_rank + cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
        "rope_scaling": {
            "type": "deepseek_yarn", "factor": s.factor,
            "original_max_position_embeddings":
            s.original_max_position_embeddings,
            "beta_fast": s.beta_fast, "beta_slow": s.beta_slow,
            "mscale": s.mscale, "mscale_all_dim": s.mscale_all_dim},
        "num_experts": cfg.experts_held or cfg.num_experts,
        "num_experts_published": cfg.num_experts,
        "expert_parallel_size": cfg.num_experts // (cfg.experts_held
                                                    or cfg.num_experts),
        "expert_parallel_rank": cfg.expert_rank,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "num_shared_experts": 1, "use_qk_norm": True,
        "moe_router_enable_expert_bias": True,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "routed_scaling_factor": cfg.moe_routed_scaling,
        "hidden_act": "silu", "tie_word_embeddings": False,
        "max_position_embeddings": cfg.max_context_length,
    }


def make_params(precision: str, cfg):
    if precision == "int8":
        return random_quantized_params(cfg, KEY, jnp.bfloat16)
    return T.init_params(cfg, KEY, jnp.dtype(precision))


class Probe(HybridPagedModelRunner):
    """The runner, telling the test each step's decode logits [B, V]."""

    def __init__(self, *args, **kwargs):
        self.seen: list[np.ndarray] = []
        super().__init__(*args, **kwargs)

    def _sampled(self, st, logits, pools, changed):
        jax.debug.callback(lambda x: self.seen.append(np.asarray(x)), logits,
                           ordered=True)
        return super()._sampled(st, logits, pools, changed)


def make_runner(row: str, cls=Probe, cfg=None, **kwargs):
    precision = row.removesuffix("-kernel")
    cfg = cfg or kernel_cfg(row)
    dtype = jnp.float32 if precision == "float32" else jnp.bfloat16
    # 4 slots + chunks of 32 tokens: a 100-token prompt takes four steps;
    # pages of 16, or of 32 where the decode kernel has to take them
    return cls(cfg, params=make_params(precision, cfg), max_slots=4,
               max_seq=256, page_size=32 if row.endswith("-kernel") else 16,
               step_token_budget=36, dtype=dtype, **kwargs)


def prompt_of(n: int, seed: int) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n)]


def distance(got, ids: list[int], positions, r, controls=()):
    """(worst position, mean over positions) of |logits - reference| in
    standard deviations of the reference's logits at the position."""
    with jax.default_matmul_precision("highest"):
        ref = R.forward(r.params, hf_of(r.cfg), ids, list(positions),
                        controls)
    err = jnp.max(jnp.abs(jnp.asarray(got, jnp.float32) - ref), -1)
    err = err / jnp.std(ref, -1)
    return float(jnp.max(err)), float(jnp.mean(err))


def admit(r, st, slot, prompt):
    tok, ks, vs, plen = r.prefill(prompt, 0.0, 1.0, KEY)
    return tok, r.insert(st, slot, ks, vs, plen, tok, 0.0, 1.0,
                         prompt_tokens=prompt)


def slot_rows(r, slot: int) -> np.ndarray:
    jax.effects_barrier()
    rows, r.seen[:] = np.stack([x[slot] for x in r.seen]), []
    return rows


def prefill_logits(params, cfg, ids):
    n, t = len(ids), -(-len(ids) // 64) * 64
    toks = np.zeros((1, t), np.int32)
    toks[0, :n] = ids
    return H.prefill(params, cfg, jnp.asarray(toks),
                     jnp.minimum(jnp.arange(t), n - 1)[None],
                     (jnp.arange(t) < n)[None])[0][0, :n]


def run_path(r, path: str) -> list[tuple]:
    """Drive ``path`` greedily; [(what, logits [n, V], ids, positions)]: the
    system's logits and the token sequence they belong to.  The 40-token
    prompt crosses pages (16) and YaRN's original length (32); the
    100-token one is admitted in chunks of 32."""
    a = prompt_of(40, 1)
    if path == "prefill":
        return [("prefill", prefill_logits(r.params, r.cfg, a), a, range(40))]
    st = r.init_state()
    first, st = admit(r, st, 1, a)
    seq = a + [int(first)]
    out = []

    def advance(st, n):
        toks, st = r.decode_steps_device(st, n)
        return np.asarray(toks), st

    toks, st = advance(st, 8)
    seq += [int(t) for t in toks[:, 1]]
    out.append(("decode", slot_rows(r, 1), seq[:-1], range(40, 48)))
    if path == "decode":
        return out
    b = prompt_of(100, 2)
    assert r.ragged_chunk == 32
    job = r.ragged_begin(b, 2, state=st)
    n0 = len(seq)
    for k in (1, 2, 2):
        toks, st = r.ragged_step(st, job, k)
        seq += [int(t) for t in np.asarray(toks)[:, 1]]
    assert job.finished
    n = len(seq) - n0
    out.append(("decode beside chunks", slot_rows(r, 1), seq[:-1],
                range(n0 - 1, n0 - 1 + n)))
    out.append(("chunked prompt's last token", job.last_logits[None], b,
                [99]))
    first_b, st = r.ragged_finish(st, job, 0.0, 1.0, KEY)
    toks, st = advance(st, 4)
    seq_b = b + [int(first_b)] + [int(t) for t in toks[:, 2]]
    out.append(("decode after chunks", slot_rows(r, 2), seq_b[:-1],
                range(100, 104)))
    return out


# every layout against the reference's full forward pass

@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("row", ROWS)
def test_logits_match_the_reference(row, path, kernels):
    precision = kernels(row)
    r = make_runner(row)
    kernel = row.endswith("-kernel")
    want = "pallas_interpret" if kernel else "jnp"
    assert r.attention_paths["decode"] == want
    assert r.attention_paths["ragged_step"] == want
    assert r.cfg.rope_scaling.original_max_position_embeddings == 32
    worst_lim, mean_lim = LIMITS[precision]
    for what, logits, ids, positions in run_path(r, path):
        worst, mean = distance(logits, ids, positions, r)
        assert worst <= worst_lim and mean <= mean_lim, (what, worst, mean)


def test_a_prompt_over_two_monolithic_prefill_chunks():
    """The legacy chunked admission (``prefill_begin`` / ``prefill_step``):
    the second chunk's rows are rotated at THEIR positions and attend over
    the first chunk's cached, rotated rows."""
    r = make_runner("float32", cls=HybridPagedModelRunner)
    r.prefill_chunk = 64
    b = prompt_of(100, 4)
    job = r.prefill_begin(b)
    logits = None
    while True:
        done = r.prefill_step(job)
        logits = job.last_logits
        if done:
            break
    tok, ks, _, plen = r.prefill_finish(job, 0.0, 1.0, KEY)
    assert plen == 100 and ks.v is None and ks.rec == {}
    worst, _ = distance(np.asarray(logits)[None], b, [99], r)
    assert worst <= LIMITS["float32"][0], worst
    with jax.default_matmul_precision("highest"):
        ref = R.forward(r.params, hf_of(r.cfg), b, [99])
    assert int(tok) == int(jnp.argmax(ref[0]))


# what the float32 row holds

CONTROLS = ["no_k_rope", "no_rope", "plain_frequencies", "no_mscale",
            "halves", "no_kv_norm", "no_correction_bias", "no_scaling"]


@pytest.mark.parametrize("control", CONTROLS)
def test_a_wrong_equation_reads_over_the_limit(control):
    """Each is a reading of the model that a careless port would make: the
    shared key cached as projected, no rotation, plain frequencies where
    YaRN blends, the score scale without mscale^2, the published
    interleaved columns rotated as halves, the latent unnormed, the
    selection bias or the routed scaling dropped."""
    r = make_runner("float32", cls=HybridPagedModelRunner)
    (_, logits, ids, positions), = run_path(r, "prefill")
    worst, mean = distance(logits, ids, positions, r, controls=(control,))
    assert worst > 0.02 and mean > 30 * LIMITS["float32"][1], (worst, mean)


def test_a_bf16_router_reads_over_the_limit():
    """The router's input, product and scores in bf16 where float32 is
    stated: over a hundred tokens some 8th and 9th scores tie within a
    bf16 rounding and the token is routed apart."""
    r = make_runner("float32", cls=HybridPagedModelRunner)
    ids = prompt_of(100, 2)
    logits = prefill_logits(r.params, r.cfg, ids)
    assert distance(logits, ids, range(100), r)[0] <= LIMITS["float32"][0]
    worst, mean = distance(logits, ids, range(100), r,
                           controls=("bf16_router",))
    assert worst > 10 * LIMITS["float32"][0], (worst, mean)
    assert mean > 10 * LIMITS["float32"][1], (worst, mean)


def test_one_pass_bf16_matmuls_read_over_the_limit(monkeypatch):
    """...and the matmuls': float32 weights and activations rounded to bf16
    on their way into every projection (what one pass of the MXU does to a
    float32 matmul; the CPU has no such pass to ask for) fail it."""
    from crowdllama_tpu.ops import quant

    def one_pass(subscript, x, w, dtype=None):
        lo = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.einsum(subscript, lo(x), lo(quant.dequant(w)))

    monkeypatch.setattr(H, "qeinsum", one_pass)
    r = make_runner("float32", cls=HybridPagedModelRunner)
    (_, logits, ids, positions), = run_path(r, "prefill")
    worst, _ = distance(logits, ids, positions, r)
    assert worst > 3 * LIMITS["float32"][0], worst


# the rotation: YaRN from the published keys, and the two layouts

def _published() -> dict:
    doc = json.loads(CELL.read_text())
    return {k: v for k, v in doc.items() if k != "bench"}


def test_yarn_of_the_published_keys():
    """low 10, high 23 (d(32) = 10.47, d(1) = 22.51) and the score scale
    192^-1/2 (0.1 ln 40 + 1)^2 = 0.135234, by the program and by the
    reference, from the configuration file the benchmark serves."""
    from crowdllama_tpu.engine.weights import _sarvam_mla_config

    hf = _published()
    cfg = _sarvam_mla_config(hf)
    s = cfg.rope_scaling
    assert (s.rope_type, s.factor, s.original_max_position_embeddings,
            s.beta_fast, s.beta_slow, s.mscale, s.mscale_all_dim) == (
        "yarn", 40.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert rope.yarn_correction_range(s, cfg.qk_rope_head_dim,
                                      cfg.rope_theta) == (10, 23)
    hp = R.hyper(hf)
    assert R.correction_range(hp) == (10, 23)
    assert T.attn_scale(cfg) == pytest.approx(0.135234, abs=5e-7)
    assert R.score_scale(hp) == pytest.approx(T.attn_scale(cfg), rel=1e-6)
    assert R.score_scale(hp, ("no_mscale",)) == pytest.approx(192 ** -0.5)
    # the blend: plain below low, a fortieth above high, linear between;
    # cos and sin as they are (mscale / mscale_all_dim = 1)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = (1 - ramp) * plain + ramp * plain / 40
    np.testing.assert_allclose(rope._inv_freq(64, 10000.0, s), want,
                               rtol=1e-6)
    freqs, magnitude = R.frequencies(hp)
    np.testing.assert_allclose(freqs, want, rtol=1e-6)
    assert magnitude == 1.0 == rope._magnitude(s)
    pos = jnp.asarray([0, 1, 4095, 4096, 5119, 131071])
    cos, sin = rope.rope_angles(pos, 64, 10000.0, s)
    np.testing.assert_allclose(cos, np.cos(np.asarray(pos)[:, None] * want),
                               atol=2e-2)     # float32 angles at 131,071
    table = rope.rope_table(5120, 64, 10000.0, s)
    np.testing.assert_array_equal(table[0][pos[:5]], cos[:5])
    np.testing.assert_array_equal(table[1][pos[:5]], sin[:5])


def test_a_yarn_magnitude_multiplies_cos_and_sin():
    s = RopeScaling(rope_type="yarn", factor=8.0,
                    original_max_position_embeddings=32, beta_fast=4.0,
                    mscale=1.0, mscale_all_dim=0.0)
    cos, sin = rope.rope_angles(jnp.arange(4), 16, 10000.0, s)
    plain = rope.rope_angles(jnp.arange(4), 16, 10000.0,
                             replace(s, mscale_all_dim=1.0))
    m = 0.1 * np.log(8.0) + 1.0
    np.testing.assert_allclose(cos, plain[0] * m, rtol=1e-6)
    np.testing.assert_allclose(sin, plain[1] * m, rtol=1e-6)


def test_halves_of_the_converted_columns_are_the_published_pairs():
    """``rotary_halves_from_interleaved`` on W_q's and W_kva's columns,
    then ``rotate_half``, is the published rotation of interleaved pairs:
    the same numbers in the converted order, and so the same scores."""
    h, dn, dr, r = CFG.num_heads, CFG.qk_nope_head_dim, CFG.qk_rope_head_dim, \
        CFG.kv_lora_rank
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(ks[0], (12, CFG.hidden_size))
    w_q = jax.random.normal(ks[1], (CFG.hidden_size, h * (dn + dr)))
    w_kva = jax.random.normal(ks[2], (CFG.hidden_size, r + dr))
    pos = jnp.arange(100, 112)      # past the original length
    hp = R.hyper(hf_of(CFG))
    angles = rope.rope_angles(pos, dr, CFG.rope_theta, CFG.rope_scaling)
    with jax.default_matmul_precision("highest"):
        q = (x @ w_q).reshape(12, h, dn + dr)[..., dn:]
        k = (x @ w_kva)[:, r:]
        q_pub, k_pub = (R.rotate_pairs(a, pos, hp) for a in (q, k))
        q_c = (x @ rotary_halves_from_interleaved(w_q, h, dn + dr, dr)
               ).reshape(12, h, dn + dr)
        k_c = x @ rotary_halves_from_interleaved(w_kva, 1, r + dr, dr)
    np.testing.assert_allclose(q_c[..., :dn],
                               (x @ w_q).reshape(12, h, -1)[..., :dn],
                               atol=1e-5)
    q_half = rope.rotate_half(q_c[..., dn:], *angles)
    k_half = rope.rotate_half(k_c[:, None, r:], *angles)[:, 0]
    # the program's order back to the published one: the reference's reader
    np.testing.assert_allclose(R.interleaved(q_half), q_pub, atol=1e-5)
    np.testing.assert_allclose(R.interleaved(k_half), k_pub, atol=1e-5)
    np.testing.assert_allclose(jnp.einsum("qhd,kd->hqk", q_half, k_half),
                               jnp.einsum("qhd,kd->hqk", q_pub, k_pub),
                               rtol=1e-5, atol=1e-3)
    cols = rotary_halves_from_interleaved(np.arange(r + dr), 1, r + dr, dr)
    assert list(cols[:r]) == list(range(r))
    assert list(cols[r:]) == [*range(r, r + dr, 2), *range(r + 1, r + dr, 2)]


def test_absorbed_rotated_attention_is_the_unabsorbed_reference():
    """One sublayer over a sequence that starts past YaRN's original
    length: the program's absorbed form with plain causal attention over
    the rotated rows against the reference's expanded K and V."""
    from crowdllama_tpu.ops.attention import prefill_attention_ref

    cfg = CFG
    lp = T.init_params(cfg, KEY, jnp.float32)["layers"]["mla"][0]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 48, cfg.hidden_size))
    pos = jnp.arange(48)[None]
    angles = rope.rope_angles(pos, cfg.qk_rope_head_dim, cfg.rope_theta,
                              cfg.rope_scaling)

    def attn_fn(q, k, v):
        assert v is None and k.shape == (1, 48, 1, cfg.resolved_head_dim())
        kh = k.transpose(0, 2, 1, 3)
        return prefill_attention_ref(q, kh, kh, pos, T.attn_scale(cfg))

    with jax.default_matmul_precision("highest"):
        got = H.mla_body(lp, cfg, x, attn_fn, angles) - x
        plain = H.mla_body(lp, cfg, x, attn_fn) - x
        h = R.rms_norm(x[0], R.dequant(lp["norm"]), cfg.rms_norm_eps)
        ref = R.mla(h, lp, R.hyper(hf_of(cfg)))
        unrotated = R.mla(h, lp, R.hyper(hf_of(cfg)), ("no_rope",))
    np.testing.assert_allclose(got[0], ref, atol=2e-5)
    # and the one body without angles is the layer that does not rotate
    np.testing.assert_allclose(plain[0], unrotated, atol=2e-5)
    assert float(jnp.max(jnp.abs(ref - unrotated))) > 1e-2


# the share adds up

def test_the_shares_add_up_to_the_uncut_layer():
    """The eight ranks' routed parts, plus the shared expert counted once,
    are the uncut reference's expert layer."""
    whole = replace(CFG, experts_held=0)
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
            cfg = replace(CFG, experts_held=held, expert_rank=rank)
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


# ------------------------------------------------------------- the engine

def test_one_latent_pool_over_every_layer_in_one_donated_pytree():
    """One pool of rows over ALL the layers (no V twin, no recurrent state,
    no window pool); every byte handed back in place by the step program;
    the unified programs take one page-table width."""
    r = make_runner("bfloat16", cls=HybridPagedModelRunner)
    st = r.init_state()
    assert st.pool_v is None and st.ssm is None and st.kda is None
    assert st.conv is None and st.wpool_k is None and r.ring is None
    # a row [c ; k_rope] of 32 + 16, stored in whole lanes
    assert st.pool_k.shape == (3, 4 * 16 + 1, 1, 16, 128)
    assert r.kv_layers == r.pool_layers == CFG.num_layers == 3
    assert r.attn_decode_path == "mla"
    assert r.ragged_width_fixed
    assert r._ragged_window() == r.max_pages_per_slot == 16
    compiled = r._decode_paged.lower(
        r.params, st, jnp.asarray(r.page_table), 2).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= st.pool_k.nbytes
    gauges = r.kv_gauges()
    assert set(gauges) == {"kv_pool_bytes|kind=latent",
                           "kv_live_bytes|kind=latent"}
    assert gauges["kv_pool_bytes|kind=latent"] == 3 * 64 * 16 * 128 * 2
    _, st = admit(r, st, 1, prompt_of(40, 1))
    pages = len(r._slot_pages[1])
    assert 3 <= pages <= 4
    assert r.kv_gauges()["kv_live_bytes|kind=latent"] == (
        3 * pages * 16 * 128 * 2)


def test_what_rests_on_a_page_and_its_twin_declines_by_name():
    """Prefix reuse, page export and import, the drain hand-off,
    speculation, the contiguous layout, int8 latent rows: this model has no
    recurrent state and no window layer, so its reason is the third — a
    latent pool has no V twin for the prefix gathers and ``import_pages``
    to take."""
    from crowdllama_tpu.config import Configuration
    from crowdllama_tpu.engine.factory import build_runner
    from crowdllama_tpu.engine.hybrid import why_no_pages
    from crowdllama_tpu.engine.plan import resolve_serving_plan
    from crowdllama_tpu.engine.runner import ModelRunner
    from crowdllama_tpu.engine.spec import SpecPagedModelRunner

    assert why_no_pages(get_config("tiny-test-kimi-linear")) == H.NO_PAGES
    assert why_no_pages(get_config("tiny-test-nemotron-h")) == H.NO_PAGES
    assert why_no_pages(get_config("tiny-test-afmoe")) == H.NO_WINDOW_PAGES
    assert why_no_pages(CFG) == H.NO_LATENT_PAGES
    assert len({H.NO_PAGES, H.NO_WINDOW_PAGES, H.NO_LATENT_PAGES}) == 3
    r = make_runner("bfloat16", cls=HybridPagedModelRunner, prefix_cache=True)
    st = r.init_state()
    assert r.no_pages == H.NO_LATENT_PAGES
    assert not r.prefix_cache
    assert not r.prefill_prefers_monolithic(prompt_of(200, 1))
    with pytest.raises(ValueError, match=H.NO_LATENT_PAGES):
        r.export_pages(st, [b"x"])
    with pytest.raises(ValueError, match=H.NO_LATENT_PAGES):
        r.import_pages(st, {"keys": [b"x"], "k_pages": [b""],
                            "v_pages": [b""]})
    with pytest.raises(ValueError, match=H.NO_LATENT_PAGES):
        SpecPagedModelRunner(CFG, params=r.params, max_slots=2, max_seq=64)
    with pytest.raises(ValueError, match="engine/hybrid.py"):
        ModelRunner(CFG, params=r.params, max_slots=2, max_seq=64)
    for spec in ("ngram", "draft"):
        config = Configuration(model=CFG.name, spec_decode=spec,
                               spec_draft_model="tiny-test")
        with pytest.raises(ValueError, match=H.NO_LATENT_PAGES):
            build_runner(config, resolve_serving_plan(config, 1), CFG,
                         r.params)
    config = Configuration(model=CFG.name, kv_layout="contiguous")
    with pytest.raises(ValueError, match="paged layout only"):
        build_runner(config, resolve_serving_plan(config, 1), CFG, r.params)
    with pytest.raises(ValueError, match="no int8 KV"):
        make_runner("bfloat16", cls=HybridPagedModelRunner, kv_dtype="int8")


def test_sixty_four_heads_on_one_row_keep_the_ragged_kernel(monkeypatch):
    """The v2 ragged kernel's query block shrinks for 64 query heads on one
    640-wide row (32 queries took 18.7 MB of VMEM: refused by the chip's
    compiler) and stays 32 for every shape served before; the gate reckons
    with the model's own heads."""
    from crowdllama_tpu.ops.pallas.paged import (chunk_query_block,
                                                 ragged_pallas_refusal)

    assert chunk_query_block(1, 64, 640) == 16      # this family's cell
    assert chunk_query_block(1, 32, 640) == 32      # Kimi
    assert chunk_query_block(8, 6, 128) == 32       # Trinity
    assert chunk_query_block(8, 4, 128) == 32       # Mistral, Mixtral
    assert chunk_query_block(2, 16, 128) == 32      # Nemotron
    assert chunk_query_block(8, 8, 128) == 32       # Llama-3 70B
    # past the gate's backend test, which here sees the CPU
    monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
    assert ragged_pallas_refusal(128, 640, 1, 1, 2, False, 64) == ""
    assert ragged_pallas_refusal(128, 640, 1, 1, 2, False, 32) == ""
    assert ragged_pallas_refusal(128, 128, 1, 8, 2, False) == ""
    assert "VMEM" in ragged_pallas_refusal(128, 640, 1, 1, 2, False, 1024)


@pytest.mark.parametrize("chunk, refused", [(512, []),
                                            (0, [2048, 4096, 5120])])
def test_the_prefill_gate_judges_the_buckets_a_prompt_can_take(
        chunk, refused, monkeypatch):
    """Rows of 576 stay in VMEM up to 1,820 of them.  Where every prompt over
    ``prefill_chunk`` tokens is admitted in chunks, no monolithic prefill is
    dispatched at a larger bucket and the gauge does not judge one; where
    chunked admission is off (pp / sp meshes) every bucket is judged."""
    from types import SimpleNamespace

    from crowdllama_tpu.engine.runner import ModelRunner, prefill_buckets

    monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
    r = object.__new__(ModelRunner)
    r.cfg = SimpleNamespace(resolved_head_dim=lambda: 576)
    r.dtype, r.mesh, r.max_seq = jnp.bfloat16, SimpleNamespace(size=1), 5120
    r.buckets, r.prefill_chunk = prefill_buckets(5120), chunk
    why = r._attention_refusals()["prefill"]
    assert [b for b in r.buckets if f"buckets [{b}]" in why] == refused
    assert ("VMEM" in why) == bool(refused)


async def test_served_through_the_engine_with_its_gauges_and_counters():
    """The normal path: JaxEngine -> scheduler -> the hybrid runner, ragged
    admission on and warmed at both flight lengths; every admission a
    prefix miss; the expert layers' assignment counts read back with the
    flights; the latent pool's gauges; nothing to export for the KV plane
    or a drain."""
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
        decode_chunk=2, kv_page_size=16, step_token_budget=34,
        kv_ship=True, intervals=Intervals.default()))
    await engine.start()
    try:
        r = engine._runner
        assert isinstance(r, HybridPagedModelRunner)
        compiles = ENGINE_TELEMETRY.snapshot_compiles()
        names = ("crowdllama_moe_assignments_total",
                 "crowdllama_prompt_tokens_total",
                 "crowdllama_prefix_tokens_reused_total",
                 "crowdllama_admissions_total")
        before = {n: series(n) for n in names}
        long = "one two three four five six seven eight nine ten " * 2
        n = len(engine.tokenizer.encode(long))
        assert n > 2 * r.ragged_chunk      # admitted in chunks
        short = "one two three"
        m = len(engine.tokenizer.encode(short))
        for prompt in (long, long, short):
            out = [c async for c in engine.generate(prompt, max_tokens=12)]
            assert out[-1].done and out[-1].completion_tokens == 12, out[-1]
        # nothing compiled after the warm-up: one table width, both flights
        assert ENGINE_TELEMETRY.snapshot_compiles() == compiles
        grew = {k: series(k) - v for k, v in before.items()}
        assert grew["crowdllama_prompt_tokens_total"] == 2 * n + m
        assert grew["crowdllama_prefix_tokens_reused_total"] == 0
        assert grew["crowdllama_admissions_total"] == 3
        rows = grew["crowdllama_moe_assignments_total"]
        k = CFG.layers_of("S") * CFG.num_experts_per_tok
        assert (2 * n + m) * k <= rows <= (2 * n + m + 3 * 16) * k
        held = series('crowdllama_moe_assignments_total{held="yes"}')
        assert 0.3 < held / series("crowdllama_moe_assignments_total") < 0.7
        st = engine.scheduler.state
        assert series('crowdllama_attn_decode_path{path="mla"}') == 1
        assert series('crowdllama_kda_update_path{path="none"}') == 0
        assert series("crowdllama_latent_cache_bytes") == st.pool_k.nbytes
        assert series('crowdllama_latent_cache_row_width{part="row"}') == 48
        assert series('crowdllama_latent_cache_row_width{part="pad"}') == 80
        lines = engine_gauge_lines(engine.scheduler.telemetry_gauges())
        assert series('crowdllama_engine_kv_pool_bytes{kind="latent"}',
                      lines) == st.pool_k.nbytes - 3 * 16 * 128 * 2
        assert series('crowdllama_engine_kv_live_bytes{kind="latent"}',
                      lines) == 0      # every slot released
        assert not any('kind="full"' in ln for ln in lines)
        assert await engine.export_kv_pages(CFG.name, [b"k"], 16) is None
        assert not engine._kv_ship_ready()
    finally:
        await engine.stop()


# ------------------------------------------------ the family by its config

SARVAM = hf_of(CFG) | {"architectures": ["SarvamMLAForCausalLM"],
                       "default_theta": 10000, "attn_implementation": None}


def _dir(tmp_path, doc: dict) -> str:
    (tmp_path / "config.json").write_text(json.dumps(doc))
    return str(tmp_path)


def test_a_sarvam_mla_config_json_is_read_as_what_it_is(tmp_path):
    from crowdllama_tpu.engine.weights import resolve_model_config

    cfg = resolve_model_config("some-dir-name", _dir(tmp_path, SARVAM))
    assert cfg == replace(CFG, name="some-dir-name")
    assert cfg.layer_pattern == "RDRSRS" and cfg.num_kv_heads == 1
    assert cfg.resolved_head_dim() == 48
    m = 0.1 * np.log(8.0) + 1.0
    assert T.attn_scale(cfg) == pytest.approx(32 ** -0.5 * m * m, rel=1e-6)
    # the architecture alone names the family too
    doc = {k: v for k, v in SARVAM.items() if k != "model_type"}
    (tmp_path / "b").mkdir()
    assert resolve_model_config("x", _dir(tmp_path / "b", doc)).family == (
        "sarvam_mla")


def test_the_published_configuration_is_read_at_its_widths(tmp_path):
    from crowdllama_tpu.engine.weights import resolve_model_config

    cfg = resolve_model_config("cell", _dir(tmp_path, _published()))
    assert cfg.family == "sarvam_mla"
    assert cfg.layer_pattern == "RD" + "RS" * 8
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim()) == (4096, 64, 1, 576)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (512, 128, 64, 128)
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_rank,
            cfg.num_experts_per_tok) == (128, 16, 0, 8)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.moe_shared_intermediate_size) == (16384, 2048, 2048)
    assert cfg.moe_routed_scaling == 2.5 and cfg.vocab_size == 32768


@pytest.mark.parametrize("change, match", [
    ({"model_type": "llama"}, "kv_lora_rank"),
    ({"model_type": "mixtral"}, "kv_lora_rank"),
    ({"model_type": "kimi_linear"}, "linear_attn_config"),
    ({"model_type": "deepseek_v3"}, "not a family"),
    ({"q_lora_rank": 64}, "q_lora_rank"),
    ({"n_group": 2}, "n_group"),
    ({"use_qk_norm": False}, "use_qk_norm"),
    ({"head_dim": 64}, "absorbed row"),
    ({"rope_scaling": {**SARVAM["rope_scaling"], "type": "longrope"}},
     "supported: yarn, deepseek_yarn"),
    ({"rope_scaling": {**SARVAM["rope_scaling"], "type": "llama3"}},
     "supported: yarn, deepseek_yarn"),
])
def test_it_is_never_read_as_another_family(tmp_path, change, match):
    from crowdllama_tpu.engine.weights import resolve_model_config

    with pytest.raises((ValueError, KeyError), match=match):
        resolve_model_config("x", _dir(tmp_path, {**SARVAM, **change}))


def test_an_unknown_scaling_type_names_the_supported_ones():
    with pytest.raises(ValueError, match="supported: llama3, linear, yarn"):
        RopeScaling(rope_type="longrope")
    assert RopeScaling(rope_type="yarn").rope_type == "yarn"
