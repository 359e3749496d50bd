"""Family ``kimi_linear`` (delta-rule linear attention KDA, NoPE latent
attention MLA on a one-row-a-token paged cache, dense SwiGLU and a SwiGLU
mixture of experts with a shared expert, of which this worker holds a share)
on the paged engine, against its plain reference
(benchmarks/chip/harness/reference/kimi_linear.py) — LOGITS, at tiny size on
the CPU, seeded random weights: prefill; prefill then decode steps through
the paged state; a prompt admitted in chunks through the ragged step beside
decoding slots.

THE LIMITS (``LIMITS``), in standard deviations of the reference's logits at
the position, (worst position, mean over positions):

* float32 (1e-3, 1e-4): both sides compute the same equations in float32;
  what is left is the order of the sums (chunkwise delta rule against a scan
  over tokens, absorbed latent attention against expanded K and V, sorted
  dispatch against every expert masked): read 2e-5 at most.  This is the
  row that holds the STATE's precision and the matmuls': a bf16 KDA state
  reads over 3e-3 and one-pass bf16 matmuls over 1e-2 (the two tests below).
* bfloat16 and int8 (0.2, 0.1): bf16 activations (and bf16 or int8 weights)
  against float32 over the same weights, through eight sublayers; read up
  to 0.09 / 0.05.  These rows run a router that chooses all its experts
  (tests/test_hybrid.py ``ALL_CHOSEN`` has why).

The float32 row runs twice: as the CPU serves it (XLA's ``kda_update``, the
gathered latent view), and with the Pallas ``kda_update`` and
``paged_decode_attention_mla`` in interpret mode in every decode-type
program (``float32-kernel``: a value dim and a latent width of whole lanes,
which the kernels ask for).
"""

import json
import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                       / "benchmarks" / "chip"))

from harness.reference import kimi_linear as R  # noqa: E402

from crowdllama_tpu.engine.hybrid import HybridPagedModelRunner  # noqa: E402
from crowdllama_tpu.models import hybrid as H  # noqa: E402
from crowdllama_tpu.models import transformer as T  # noqa: E402
from crowdllama_tpu.models.config import get_config  # noqa: E402
from crowdllama_tpu.ops import kda  # noqa: E402
from crowdllama_tpu.ops.quant import random_quantized_params  # noqa: E402

KEY = jax.random.PRNGKey(0)
CFG = get_config("tiny-test-kimi-linear")
ALL_CHOSEN = replace(CFG, num_experts=8, num_experts_per_tok=8,
                     experts_held=4)
LIMITS = {"float32": (1e-3, 1e-4), "bfloat16": (0.2, 0.1),
          "int8": (0.2, 0.1)}
PATHS = ("prefill", "decode", "ragged")
ROWS = [*LIMITS, "float32-kernel"]


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
    """The configuration a row runs: with the kernels, a value dim and a
    latent width of 128."""
    cfg = CFG if row.startswith("float32") else ALL_CHOSEN
    if row.endswith("-kernel"):
        cfg = replace(cfg, kda_heads=2, kda_head_dim=128, kda_gate_rank=128,
                      kv_lora_rank=128,
                      head_dim=128 + cfg.qk_rope_head_dim)
    return cfg


def hf_of(cfg) -> dict:
    """The config.json keys the reference reads, of a registry config."""
    mixers = cfg.layer_pattern[0::2]
    return {
        "model_type": "kimi_linear", "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers, "rms_norm_eps": cfg.rms_norm_eps,
        "num_attention_heads": cfg.num_heads, "vocab_size": cfg.vocab_size,
        "intermediate_size": cfg.intermediate_size,
        "first_k_dense_replace": cfg.layer_pattern[1::2].count("D"),
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "q_lora_rank": None,
        "mla_use_nope": True,
        "linear_attn_config": {
            "kda_layers": [i + 1 for i, m in enumerate(mixers) if m == "K"],
            "full_attn_layers": [i + 1 for i, m in enumerate(mixers)
                                 if m == "L"],
            "head_dim": cfg.kda_head_dim, "num_heads": cfg.kda_heads,
            "short_conv_kernel_size": cfg.kda_conv_kernel},
        "num_experts": cfg.experts_held or cfg.num_experts,
        "num_experts_published": cfg.num_experts,
        "expert_parallel_rank": cfg.expert_rank,
        "num_experts_per_token": cfg.num_experts_per_tok,
        "num_shared_experts": 1,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "routed_scaling_factor": cfg.moe_routed_scaling,
        "moe_renormalize": cfg.moe_norm_topk,
        "moe_router_activation_func": "sigmoid",
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


def run_path(r, path: str) -> list[tuple]:
    """Drive ``path`` greedily; [(what, logits [n, V], ids, positions)]: the
    system's logits and the token sequence they belong to.  The 40-token
    prompt crosses KDA chunks (8) and pages (16); the 100-token one is
    admitted in chunks of 32 — a prompt split over several prefill
    chunks."""
    params, a = r.params, prompt_of(40, 1)
    if path == "prefill":
        toks = np.zeros((1, 64), np.int32)
        toks[0, :40] = a
        logits = H.prefill(
            params, r.cfg, jnp.asarray(toks),
            jnp.minimum(jnp.arange(64), 39)[None],
            (jnp.arange(64) < 40)[None])[0][0, :40]
        return [("prefill", logits, a, range(40))]
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


# (a), (c), (f): every layout against the reference's full forward pass

@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("row", ROWS)
def test_logits_match_the_reference(row, path, kernels):
    precision = kernels(row)
    r = make_runner(row)
    kernel = row.endswith("-kernel")
    assert r.kda_update_path == ("pallas" if kernel else "xla")
    assert r.attention_paths["decode"] == (
        "pallas_interpret" if kernel else "jnp")
    worst_lim, mean_lim = LIMITS[precision]
    for what, logits, ids, positions in run_path(r, path):
        worst, mean = distance(logits, ids, positions, r)
        assert worst <= worst_lim and mean <= mean_lim, (what, worst, mean)


# the latent pool's row is stored in whole lanes (engine/paged.py
# ``pool_row_width``): the pad columns change no bit and stay zero

@pytest.mark.parametrize("path", ["decode", "ragged"])
@pytest.mark.parametrize("row", ["float32", "float32-kernel"])
def test_the_stored_rows_pad_changes_no_bit_of_the_logits(row, path, kernels,
                                                          monkeypatch):
    """Prefill then insert then decode, a prompt in ragged chunks beside
    decoding slots: through a pool whose row is whole lanes
    every logit is BIT-equal to the same program's over a pool whose row is
    the 48 (144 for the kernels) entries computed — a pad term of a score
    is an exact zero and the value is the row's first ``kv_lora_rank``
    entries on both.  But one reading, which is the CPU's and not the
    pad's: the ragged kernel in INTERPRET mode multiplies a chunk block's
    128 probability rows by a page of values 256 columns wide where it was
    144, and XLA's CPU dot tiles the two widths apart (the same 32 products
    a column, summed in another order: ``p @ v`` against ``p @ pad(v)`` cut
    back differs in 76% of its entries, 4e-6 at most), so what a chunk's
    blocks fed is held to a float32 rounding, a hundredth of LIMITS."""
    from crowdllama_tpu.engine import paged

    kernels(row)
    r = make_runner(row)
    width = r.cfg.resolved_head_dim()
    assert r.init_state().pool_k.shape[-1] == -(-width // 128) * 128 > width
    padded = run_path(r, path)
    # the pool as it was: a row as wide as ``mla_body`` computes it
    monkeypatch.setattr(paged, "pool_row_width",
                        lambda cfg: cfg.resolved_head_dim())
    plain = make_runner(row)
    assert plain.init_state().pool_k.shape[-1] == width
    for (what, got, ids, _), (_, want, ids2, _) in zip(
            padded, run_path(plain, path), strict=True):
        assert ids == ids2, what
        got, want = np.asarray(got), np.asarray(want)
        chunk_fed = "chunk" in what and "beside" not in what
        if row.endswith("-kernel") and chunk_fed:
            assert np.abs(got - want).max() <= 1e-5 * want.std(), what
        else:
            np.testing.assert_array_equal(got, want, err_msg=what)


@pytest.mark.parametrize("row", ["float32", "bfloat16", "float32-kernel"])
def test_the_stored_rows_pad_columns_stay_zero(row, kernels):
    """A slot served, released and served again out of the pages it gave
    back — an insert, decode writes, ragged chunks beside another slot's
    decode writes, a flight: every pad column of the pool, the dump page's
    too, is the zero ``init_state`` drew; the columns of the row are not."""
    kernels(row)
    r = make_runner(row, cls=HybridPagedModelRunner)
    width = r.cfg.resolved_head_dim()
    st = r.init_state()
    _, st = admit(r, st, 2, prompt_of(40, 1))
    _, st = admit(r, st, 0, prompt_of(20, 3))
    _, st = r.decode_steps_device(st, 4)
    held = set(r._slot_pages[2])
    st = r.release(st, 2)
    job = r.ragged_begin(prompt_of(100, 2), 2, state=st)
    while not job.finished:
        _, st = r.ragged_step(st, job, 1)
    assert held & set(r._slot_pages[2])     # pages with a past
    _, st = r.ragged_finish(st, job, 0.0, 1.0, KEY)
    _, st = r.decode_steps_device(st, 3)
    pool = np.asarray(st.pool_k.astype(jnp.float32))
    assert pool.shape[-1] > width
    assert not pool[..., width:].any()
    written = np.abs(pool[..., :width]).sum(-1) > 0
    assert written.sum() >= 20 + 4 + 100 + 3


@pytest.mark.parametrize("row", ["float32", "float32-kernel"])
def test_bf16_state_in_place_of_float32_reads_over_the_limit(
        row, monkeypatch, kernels):
    """The float32 row of LIMITS holds the state's precision: a state
    rounded to bf16 after every update fails it, on either path."""
    from crowdllama_tpu.ops.pallas import kda as kernel

    def rounded(fn):
        def wrapped(*args, **kwargs):
            o, state = fn(*args, **kwargs)
            return o, state.astype(jnp.bfloat16).astype(jnp.float32)
        return wrapped

    monkeypatch.setattr(kda, "kda_update", rounded(kda.kda_update))
    monkeypatch.setattr(kda, "kda_chunk_scan", rounded(kda.kda_chunk_scan))
    monkeypatch.setattr(kernel, "kda_update", rounded(kernel.kda_update))
    kernels(row)
    r = make_runner(row)
    worst = max(distance(logits, ids, positions, r)[0]
                for _, logits, ids, positions in run_path(r, "ragged"))
    assert worst > 3 * LIMITS["float32"][0], worst


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


@pytest.mark.parametrize("control", [
    "no_correction_bias", "no_scaling", "no_decay", "no_l2norm",
    "rope_scale"])
def test_a_wrong_equation_reads_over_the_limit(control):
    """Each is a reading of the model that a careless port would make."""
    r = make_runner("float32", cls=HybridPagedModelRunner)
    (_, logits, ids, positions), = run_path(r, "prefill")
    worst, mean = distance(logits, ids, positions, r, controls=(control,))
    assert worst > 0.02 and mean > 30 * LIMITS["float32"][1], (worst, mean)


# (b) the chunkwise delta rule is the token recurrence

def _kda_inputs(s, t, h, dk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = kda.l2norm(jax.random.normal(ks[0], (s, t, h, dk))) * dk ** -0.5
    k = kda.l2norm(jax.random.normal(ks[1], (s, t, h, dk)))
    v = jax.random.normal(ks[2], (s, t, h, dv))
    # from a channel that forgets nothing to one that forgets all at once
    g = -jnp.exp(jax.random.uniform(ks[3], (s, t, h, dk), minval=-6.0,
                                    maxval=3.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (s, t, h)))
    return q, k, v, g, beta, jax.random.normal(ks[5], (s, h, dk, dv))


@pytest.mark.parametrize("chunk", [5, 8, 64])
def test_chunkwise_kda_is_the_token_recurrence(chunk):
    """37 tokens: across chunk boundaries, a ragged last chunk, one chunk
    longer than the sequence; from a state that is not zero, as a prompt's
    second prefill chunk starts."""
    q, k, v, g, beta, s0 = _kda_inputs(2, 37, 4, 16, 24)
    state, outs = s0, []
    for t in range(37):
        o, state = kda.kda_update(q[:, t], k[:, t], v[:, t], g[:, t],
                                  beta[:, t], state)
        outs.append(o)
    o, s1 = jax.jit(lambda *a: kda.kda_chunk_scan(*a, chunk=chunk))(
        q, k, v, g, beta, s0)
    np.testing.assert_allclose(o, jnp.stack(outs, 1), atol=2e-5)
    np.testing.assert_allclose(s1, state, atol=2e-5)
    # and split in two calls at a token that is no chunk boundary
    oa, sa = kda.kda_chunk_scan(q[:, :13], k[:, :13], v[:, :13], g[:, :13],
                                beta[:, :13], s0, chunk)
    ob, sb = kda.kda_chunk_scan(q[:, 13:], k[:, 13:], v[:, 13:], g[:, 13:],
                                beta[:, 13:], sa, chunk)
    np.testing.assert_allclose(jnp.concatenate([oa, ob], 1), o, atol=2e-5)
    np.testing.assert_allclose(sb, s1, atol=2e-5)


def test_rows_that_are_not_real_move_no_state():
    q, k, v, g, beta, s0 = _kda_inputs(1, 8, 2, 16, 16)
    still = jnp.zeros_like
    o, s1 = kda.kda_chunk_scan(q, k, v, still(g), still(beta), s0, 4)
    np.testing.assert_array_equal(s1, s0)
    _, s1 = kda.kda_update(q[:, 0], k[:, 0], v[:, 0], still(g[:, 0]),
                           still(beta[:, 0]), s0)
    np.testing.assert_array_equal(s1, s0)


# (d) the decode kernels in interpret mode are their XLA expressions

def test_kda_update_kernel_is_the_xla_update(monkeypatch):
    monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
    q, k, v, g, beta, s0 = _kda_inputs(3, 1, 4, 24, 128, seed=1)
    args = [m[:, 0] for m in (q, k, v, g, beta)]
    # slot 2 is idle: g 0, beta 0
    args[3] = args[3].at[2].set(0.0)
    args[4] = args[4].at[2].set(0.0)
    stack = jnp.stack([s0, 2.0 * s0, 3.0 * s0])
    assert kda.kda_update_path(stack.shape) == ("pallas", "")
    o, out = kda.kda_update_at(*args, stack, jnp.int32(1))
    o_ref, s_ref = kda.kda_update(*args, stack[1])
    np.testing.assert_allclose(o, o_ref, atol=1e-6)
    np.testing.assert_allclose(out[1], s_ref, atol=1e-6)
    np.testing.assert_array_equal(out[1, 2], stack[1, 2])
    np.testing.assert_array_equal(out[0], stack[0])
    np.testing.assert_array_equal(out[2], stack[2])
    monkeypatch.delenv("CROWDLLAMA_PALLAS_INTERPRET")
    assert kda.kda_update_path(stack.shape)[0] == "xla"


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)])
def test_latent_decode_kernel_is_the_gathered_attention(monkeypatch, dtype,
                                                        tol):
    from crowdllama_tpu.ops.attention import decode_attention
    from crowdllama_tpu.ops.pallas.paged import paged_decode_attention_mla

    monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
    b, h, latent, rope, page, layers, np_ = 3, 4, 128, 16, 32, 2, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    q = jax.random.normal(ks[0], (b, h, latent + rope)).astype(dtype)
    pool = jax.random.normal(
        ks[1], (layers, b * np_ + 1, 1, page, latent + rope)).astype(dtype)
    table = jnp.arange(b * np_, dtype=jnp.int32).reshape(b, np_)
    lens = jnp.array([1, 40, 128], jnp.int32)   # a page, a pair, all four
    out = paged_decode_attention_mla(q, pool, jnp.int32(1), table, lens, 0.2,
                                     latent)
    rows = pool[1, table].transpose(0, 2, 1, 3, 4).reshape(
        b, 1, np_ * page, latent + rope)
    ref = decode_attention(q, rows, rows, lens, 0.2)[..., :latent]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_absorbed_latent_attention_is_the_unabsorbed_reference():
    """(c) alone: one MLA sublayer over a sequence, the program's absorbed
    form with plain causal attention over the rows against the reference's
    expanded K and V."""
    from crowdllama_tpu.ops.attention import prefill_attention_ref

    cfg = CFG
    lp = T.init_params(cfg, KEY, jnp.float32)["layers"]["mla"][0]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, cfg.hidden_size))
    pos = jnp.arange(24)[None]

    def attn_fn(q, k, v):
        assert v is None and k.shape == (1, 24, 1, cfg.resolved_head_dim())
        kh = k.transpose(0, 2, 1, 3)
        return prefill_attention_ref(q, kh, kh, pos, T.attn_scale(cfg))

    with jax.default_matmul_precision("highest"):
        got = H.mla_body(lp, cfg, x, attn_fn) - x
        h = R.rms_norm(x[0], R.dequant(lp["norm"]), cfg.rms_norm_eps)
        ref = R.mla(h, lp, R.hyper(hf_of(cfg)))
    np.testing.assert_allclose(got[0], ref, atol=2e-5)


# (e) the share adds up

def test_the_shares_add_up_to_the_uncut_layer():
    """The ranks' routed parts, plus the shared expert counted once, are
    the uncut reference's expert layer."""
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
    # and of the 16 banks the ranks hold, none routed to twice
    assert 0 < rows[2] <= rows[3] == rows[4] == CFG.num_experts


@pytest.mark.parametrize("path", ["ragged_dot", "kernel"])
@pytest.mark.parametrize("case", ["live-only", "padded", "none-live"])
def test_a_swiglu_expert_layer_counts_a_bank_once(case, path, monkeypatch):
    """The layer's three grouped matmuls share their groups: a bank routed
    to is one bank, and a bank fetched is one bank — by the kernel's own
    rule (``ops/pallas/moe.py`` ``_visits``) where it multiplies, every
    held bank where ``lax.ragged_dot`` does; ``routed`` is a recount of the
    router's choices over every row, live or padding."""
    from crowdllama_tpu.ops.quant import quantize_weight, ragged_dot_path
    from test_hybrid import banks_visited

    if path == "kernel":
        monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
    # whole lanes, which the kernel asks for; 3 rows x 4 choices over 16
    # experts leave most of the 8 held banks without a row
    cfg = replace(CFG, hidden_size=128, moe_intermediate_size=128)
    lp = T.init_params(cfg, KEY, jnp.float32)["layers"]["smoe"][0]
    banks = ("w_gate", "w_up", "w_down")
    lp = {**lp, **{b: quantize_weight(lp[b], jnp.float32) for b in banks}}
    assert all((ragged_dot_path(lp[b])[0] == "int8_kernel")
               == (path == "kernel") for b in banks)
    n, k, held = 3, cfg.num_experts_per_tok, H.sizes(cfg)["held"]
    live = {"live-only": [True] * 3, "padded": [True, False, False],
            "none-live": [False] * 3}[case]
    x = jax.random.normal(jax.random.PRNGKey(5), (n, cfg.hidden_size))
    _, counts = H.smoe_body(lp, cfg, x, jnp.asarray(live))
    counts = dict(zip(H.COUNTS, np.asarray(counts).tolist()))
    topi = np.asarray(H.route(lp, cfg, H._normed(lp, cfg, x))[1])
    mine = topi < held
    assert counts["rows_held"] == (mine & np.asarray(live)[:, None]).sum()
    assert counts["banks_routed"] == len(np.unique(topi[mine])) < held
    assert counts["banks_held"] == held
    assert counts["banks_fetched"] == (
        banks_visited(topi[mine], held, n * k, 128) if path == "kernel"
        else held)


# ------------------------------------------------------------- the engine

def test_state_not_zeroed_on_release_reads_over_the_limit(monkeypatch):
    from crowdllama_tpu.engine.paged import PagedModelRunner

    def admit_in_chunks_after_a_release(r):
        st = r.init_state()
        _, st = admit(r, st, 2, prompt_of(40, 1))
        _, st = r.decode_steps_device(st, 4)
        st = r.release(st, 2)
        b = prompt_of(100, 2)
        job = r.ragged_begin(b, 2, state=st)
        while not job.finished:
            _, st = r.ragged_step(st, job, 1)
        return distance(job.last_logits[None], b, [99], r)[0]

    assert admit_in_chunks_after_a_release(
        make_runner("float32", cls=HybridPagedModelRunner)
    ) <= LIMITS["float32"][0]
    monkeypatch.setattr(HybridPagedModelRunner, "_release_paged_impl",
                        PagedModelRunner._release_paged_impl)
    assert admit_in_chunks_after_a_release(
        make_runner("float32", cls=HybridPagedModelRunner)
    ) > 10 * LIMITS["float32"][0]


def test_a_prompt_over_two_monolithic_prefill_chunks():
    """The legacy chunked admission (``prefill_begin`` / ``prefill_step``):
    each chunk continues the job's own latent rows and recurrent state."""
    r = make_runner("float32", cls=HybridPagedModelRunner)
    r.prefill_chunk = 64
    b = prompt_of(100, 4)
    job = r.prefill_begin(b)
    while not r.prefill_step(job):
        pass
    tok, ks, _, plen = r.prefill_finish(job, 0.0, 1.0, KEY)
    assert plen == 100 and ks.v is None and set(ks.rec) == {"kda", "conv"}
    with jax.default_matmul_precision("highest"):
        ref = R.forward(r.params, hf_of(r.cfg), b, [99])
    assert int(tok) == int(jnp.argmax(ref[0]))


def test_the_latent_pool_and_the_matrix_state_in_one_donated_pytree():
    """One pool of rows over the MLA layers only (no V twin), the KDA
    matrices and the three convolutions' tails beside it; every byte handed
    back in place by the step program."""
    r = make_runner("bfloat16", cls=HybridPagedModelRunner)
    st = r.init_state()
    assert st.pool_v is None and st.ssm is None
    # a row [c ; k_rope] of 32 + 16, stored in whole lanes
    assert st.pool_k.shape == (1, 4 * 16 + 1, 1, 16, 128)
    assert st.kda.shape == (3, 4, 4, 16, 16) and st.kda.dtype == jnp.float32
    assert st.conv.shape == (3, 4, 3, 3 * 64)
    compiled = r._decode_paged.lower(
        r.params, st, jnp.asarray(r.page_table), 2).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        st.kda.nbytes + st.conv.nbytes + st.pool_k.nbytes)


def test_what_rests_on_exportable_pages_declines_by_name():
    """(g) prefix reuse, page export and import, speculation, the
    contiguous layout, int8 latent rows."""
    from crowdllama_tpu.config import Configuration
    from crowdllama_tpu.engine.factory import build_runner
    from crowdllama_tpu.engine.plan import resolve_serving_plan
    from crowdllama_tpu.engine.runner import ModelRunner
    from crowdllama_tpu.engine.spec import SpecPagedModelRunner

    r = make_runner("bfloat16", cls=HybridPagedModelRunner, prefix_cache=True)
    st = r.init_state()
    assert not r.prefix_cache
    assert not r.prefill_prefers_monolithic(prompt_of(200, 1))
    with pytest.raises(ValueError, match=H.NO_PAGES):
        r.export_pages(st, [b"x"])
    with pytest.raises(ValueError, match=H.NO_PAGES):
        r.import_pages(st, {"keys": [b"x"], "k_pages": [b""],
                            "v_pages": [b""]})
    with pytest.raises(ValueError, match=H.NO_PAGES):
        SpecPagedModelRunner(CFG, params=r.params, max_slots=2, max_seq=64)
    with pytest.raises(ValueError, match="engine/hybrid.py"):
        ModelRunner(CFG, params=r.params, max_slots=2, max_seq=64)
    for spec in ("ngram", "draft"):
        config = Configuration(model=CFG.name, spec_decode=spec,
                               spec_draft_model="tiny-test")
        with pytest.raises(ValueError, match=H.NO_PAGES):
            build_runner(config, resolve_serving_plan(config, 1), CFG,
                         r.params)
    config = Configuration(model=CFG.name, kv_layout="contiguous")
    with pytest.raises(ValueError, match="paged layout only"):
        build_runner(config, resolve_serving_plan(config, 1), CFG, r.params)
    with pytest.raises(ValueError, match="no int8 KV"):
        make_runner("bfloat16", cls=HybridPagedModelRunner, kv_dtype="int8")


async def test_served_through_the_engine_with_its_gauges_and_counters():
    """(h) the normal path: JaxEngine -> scheduler -> the hybrid runner,
    ragged admission on; every admission a prefix miss and its
    first token the device's; the expert layers' assignment counts read back
    with the flights; the family's gauges; nothing to export for the KV
    plane or a drain."""
    from crowdllama_tpu.config import Configuration, Intervals
    from crowdllama_tpu.engine.engine import JaxEngine
    from crowdllama_tpu.obs.metrics import ENGINE_TELEMETRY
    from test_hybrid import BANKS, check_banks

    def series(name: str) -> float:
        return sum(float(ln.rsplit(" ", 1)[1])
                   for ln in ENGINE_TELEMETRY.expose()
                   if ln.startswith(name))

    engine = JaxEngine(Configuration(
        model=CFG.name, max_context_length=256, max_batch_slots=2,
        warmup=False, kv_page_size=16, step_token_budget=34,
        kv_ship=True, intervals=Intervals.default()))
    await engine.start()
    try:
        assert isinstance(engine._runner, HybridPagedModelRunner)
        names = (*BANKS, "crowdllama_moe_assignments_total",
                 "crowdllama_prompt_tokens_total",
                 "crowdllama_prefix_tokens_reused_total",
                 "crowdllama_admissions_total",
                 "crowdllama_engine_flight_steps_total")
        before = {n: series(n) for n in names}
        long = "one two three four five six seven eight nine ten " * 2
        n = len(engine.tokenizer.encode(long))
        assert n > 2 * engine._runner.ragged_chunk     # admitted in chunks
        short = "one two three"
        m = len(engine.tokenizer.encode(short))
        for prompt in (long, long, short):
            out = [c async for c in engine.generate(prompt, max_tokens=12)]
            assert out[-1].done and out[-1].completion_tokens == 12, out[-1]
        grew = {k: series(k) - v for k, v in before.items()}
        assert grew["crowdllama_prompt_tokens_total"] == 2 * n + m
        assert grew["crowdllama_prefix_tokens_reused_total"] == 0
        assert grew["crowdllama_admissions_total"] == 3
        assert series('crowdllama_admissions_total{first_token="device"}') >= 1
        assert grew["crowdllama_engine_flight_steps_total"] >= 3 * 11
        rows = grew["crowdllama_moe_assignments_total"]
        k = CFG.layers_of("S") * CFG.num_experts_per_tok
        tokens = 2 * n + m
        assert tokens * k <= rows <= (tokens + 3 * 16) * k
        held = series('crowdllama_moe_assignments_total{held="yes"}')
        assert 0.3 < held / series("crowdllama_moe_assignments_total") < 0.7
        check_banks(grew, CFG.layers_of("S") * H.sizes(CFG)["held"])
        st = engine.scheduler.state
        assert series('crowdllama_kda_update_path{path="xla"}') == 1
        assert series('crowdllama_ssm_update_path{path="none"}') == 0
        assert series('crowdllama_attn_decode_path{path="mla"}') == 1
        assert series('crowdllama_weight_layout{leaf="wq",layout="default"}'
                      ) == 1  # it has no leaf of that name at all
        assert series('crowdllama_recurrent_state_bytes{kind="kda"}'
                      ) == st.kda.nbytes
        assert series('crowdllama_recurrent_state_bytes{kind="conv"}'
                      ) == st.conv.nbytes
        # the bytes allocated: a row of 32 + 16 stored in whole lanes
        assert series("crowdllama_latent_cache_bytes") == st.pool_k.nbytes
        assert st.pool_k.shape[-1] == 128
        assert series('crowdllama_latent_cache_row_width{part="row"}') == 48
        assert series('crowdllama_latent_cache_row_width{part="pad"}') == 80
        assert await engine.export_kv_pages(CFG.name, [b"k"], 16) is None
        assert not engine._kv_ship_ready()
    finally:
        await engine.stop()


# ------------------------------------------------ the family by its config

KIMI = {
    "model_type": "kimi_linear", "architectures": ["KimiLinearForCausalLM"],
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 18,
    "vocab_size": 512, "rms_norm_eps": 1e-5, "first_k_dense_replace": 1,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
    "v_head_dim": 16, "q_lora_rank": None, "mla_use_nope": True,
    "linear_attn_config": {"kda_layers": [1, 2, 4], "full_attn_layers": [3],
                           "head_dim": 16, "num_heads": 4,
                           "short_conv_kernel_size": 4},
    "num_experts": 8, "num_experts_published": 16,
    "expert_parallel_size": 2, "expert_parallel_rank": 0,
    "num_experts_per_token": 4, "num_shared_experts": 1,
    "moe_intermediate_size": 32, "moe_layer_freq": 1,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "num_expert_group": 1, "topk_group": 1, "use_grouped_topk": True,
    "routed_scaling_factor": 2.446, "hidden_act": "silu",
    "model_max_length": 256, "rope_theta": 10000, "rope_scaling": None,
    "tie_word_embeddings": False, "num_nextn_predict_layers": 0,
}


def _dir(tmp_path, doc: dict) -> str:
    (tmp_path / "config.json").write_text(json.dumps(doc))
    return str(tmp_path)


def test_a_kimi_linear_config_json_is_read_as_what_it_is(tmp_path):
    from crowdllama_tpu.engine.weights import resolve_model_config

    cfg = resolve_model_config("some-dir-name", _dir(tmp_path, KIMI))
    assert cfg == replace(CFG, name="some-dir-name", kda_chunk=64)
    assert cfg.layer_pattern == "KDKSLSKS" and cfg.num_kv_heads == 1
    assert cfg.resolved_head_dim() == 48 and T.attn_scale(cfg) == 32 ** -0.5


@pytest.mark.parametrize("change, match", [
    ({"model_type": "llama"}, "linear_attn_config"),
    ({"model_type": "mixtral"}, "kv_lora_rank"),
    ({"model_type": "deepseek_v3"}, "not a family"),
    ({"mla_use_nope": False}, "mla_use_nope"),
    ({"q_lora_rank": 64}, "q_lora_rank"),
    ({"num_expert_group": 2}, "num_expert_group"),
    ({"linear_attn_config": {**KIMI["linear_attn_config"],
                             "full_attn_layers": [2, 3]}}, "once"),
])
def test_it_is_never_read_as_another_family(tmp_path, change, match):
    from crowdllama_tpu.engine.weights import resolve_model_config

    with pytest.raises(ValueError, match=match):
        resolve_model_config("x", _dir(tmp_path, {**KIMI, **change}))
