"""Family ``afmoe`` (Arcee Trinity: gated attention, three sliding-window
layers with rotary embedding to one full layer without, sandwich norms, a
dense SwiGLU then a sigmoid-routed SwiGLU mixture of experts with a shared
expert, of which this worker holds a share) on the paged engine's cache of
TWO kinds of page, against its plain reference
(benchmarks/chip/harness/reference/afmoe.py) — LOGITS, at tiny size on the
CPU, seeded random weights: prefill; prefill then decode steps through both
pools, far past the window; a prompt admitted in chunks through the ragged
step beside decoding slots; the legacy chunked prefill.

THE CACHE.  Window 16, page 8, ragged chunk 16: a window layer's slot owns a
ring of ``(16 + 16 + 8) / 8 = 5`` pages, 40 tokens; the decoding slot runs
to a context of ~150 and the chunked prompt to 140 — nine windows — so every
ring page is written over three times or more while the full layer keeps
all 19 pages.  The ``-kernel`` rows run the Pallas decode and ragged kernels
in interpret mode on pages of 32 with a window of 64 (a ring of four).

THE LIMITS (``LIMITS``), in standard deviations of the reference's logits at
the position, (worst position, mean over positions):

* float32 (1e-3, 1e-4): both sides compute the same equations in float32;
  what is left is the order of the sums (a ring's pages against the whole
  sequence under a mask, sorted dispatch against every expert masked): read
  6e-6 at most.  This row holds the equations: the reference with the window
  mask dropped, with rotation on the full layers, without the output gate,
  with the bias in the weights or without a post-norm reads 0.8-5.4, and
  one-pass bf16 matmuls over 1e-2 (the tests below).
* bfloat16 and int8 (0.2, 0.1): bf16 activations (and bf16 or int8 weights)
  against float32 over the same weights, through ten sublayers.  These rows
  run a router that chooses all its experts (tests/test_hybrid.py
  ``ALL_CHOSEN`` has why).
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

from harness.reference import afmoe as R  # noqa: E402

from crowdllama_tpu.engine.hybrid import HybridPagedModelRunner  # noqa: E402
from crowdllama_tpu.models import hybrid as H  # noqa: E402
from crowdllama_tpu.models import transformer as T  # noqa: E402
from crowdllama_tpu.models.config import get_config  # noqa: E402
from crowdllama_tpu.ops.quant import random_quantized_params  # noqa: E402

KEY = jax.random.PRNGKey(0)
CFG = get_config("tiny-test-afmoe")
ALL_CHOSEN = replace(CFG, num_experts=8, num_experts_per_tok=8,
                     experts_held=4)
LIMITS = {"float32": (1e-3, 1e-4), "bfloat16": (0.2, 0.1),
          "int8": (0.2, 0.1)}
PATHS = ("prefill", "decode", "ragged", "chunked")
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
    """The configuration a row runs: with the kernels (pages of 32), a
    window of two pages."""
    cfg = CFG if row.startswith("float32") else ALL_CHOSEN
    return replace(cfg, sliding_window=64) if row.endswith("-kernel") else cfg


def hf_of(cfg) -> dict:
    """The config.json keys the reference reads, of a registry config."""
    mixers = cfg.layer_pattern[0::2]
    return {
        "model_type": "afmoe", "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers, "rms_norm_eps": cfg.rms_norm_eps,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
        "intermediate_size": cfg.intermediate_size,
        "sliding_window": cfg.sliding_window,
        "layer_types": ["sliding_attention" if m == "W" else "full_attention"
                        for m in mixers],
        "num_dense_layers": cfg.layer_pattern[1::2].count("D"),
        "mup_enabled": cfg.embedding_multiplier > 0,
        "num_experts": cfg.experts_held or cfg.num_experts,
        "num_experts_published": cfg.num_experts,
        "expert_parallel_rank": cfg.expert_rank,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "num_shared_experts": 1,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "route_scale": cfg.moe_routed_scaling,
        "route_norm": cfg.moe_norm_topk, "score_func": "sigmoid",
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
    kernel = row.endswith("-kernel")
    cfg = cfg or kernel_cfg(row)
    dtype = jnp.float32 if precision == "float32" else jnp.bfloat16
    # 4 slots; pages of 8 and chunks of 16 tokens (of 32 and 32 where the
    # decode kernel has to take the pages)
    return cls(cfg, params=make_params(precision, cfg), max_slots=4,
               max_seq=256, page_size=32 if kernel else 8,
               step_token_budget=36 if kernel else 20, dtype=dtype, **kwargs)


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
    system's logits and the token sequence they belong to.  The 70-token
    prompt is already four windows; the slot then decodes 48 + 32 tokens
    more, and the 140-token prompt is admitted in chunks of a ragged chunk
    beside it."""
    params, a = r.params, prompt_of(70, 1)
    if path == "prefill":
        toks = np.zeros((1, 128), np.int32)
        toks[0, :70] = a
        logits = H.prefill(
            params, r.cfg, jnp.asarray(toks),
            jnp.minimum(jnp.arange(128), 69)[None],
            (jnp.arange(128) < 70)[None])[0][0, :70]
        return [("prefill", logits, a, range(70))]
    st = r.init_state()
    if path == "chunked":
        # the legacy chunked admission: accumulators as wide as the prompt's
        # bucket, the window a mask; insert keeps the ring's pages only
        b = prompt_of(140, 3)
        r.prefill_chunk = 64
        job = r.prefill_begin(b)
        while not r.prefill_step(job):
            pass
        out = [("chunked prompt's last token", job.last_logits[None], b,
                [139])]
        first, ks, vs, plen = r.prefill_finish(job, 0.0, 1.0, KEY)
        st = r.insert(st, 3, ks, vs, plen, first, 0.0, 1.0, prompt_tokens=b)
        toks, st = r.decode_steps_device(st, 24)
        seq = b + [int(first)] + [int(t) for t in np.asarray(toks)[:, 3]]
        out.append(("decode after a chunked prefill", slot_rows(r, 3),
                    seq[:-1], range(140, 164)))
        return out
    first, st = admit(r, st, 1, a)
    seq = a + [int(first)]
    out = []

    def advance(st, n):
        toks, st = r.decode_steps_device(st, n)
        return np.asarray(toks), st

    toks, st = advance(st, 48)
    seq += [int(t) for t in toks[:, 1]]
    out.append(("decode", slot_rows(r, 1), seq[:-1], range(70, 118)))
    # the window pool's bound held while the full pool grew
    assert r.window_pages(1) == r.ring.pages
    assert len(r._slot_pages[1]) > 2 * r.ring.pages or r.page_size > 8
    if path == "decode":
        return out
    b = prompt_of(140, 2)
    job = r.ragged_begin(b, 2, state=st)
    n0 = len(seq)
    steps = iter((1, 2, 2, 2, 2, 2, 2, 2, 2, 2))
    while not job.finished:
        toks, st = r.ragged_step(st, job, next(steps))
        seq += [int(t) for t in np.asarray(toks)[:, 1]]
    n = len(seq) - n0
    out.append(("decode beside chunks", slot_rows(r, 1), seq[:-1],
                range(n0 - 1, n0 - 1 + n)))
    out.append(("chunked prompt's last token", job.last_logits[None], b,
                [139]))
    first_b, st = r.ragged_finish(st, job, 0.0, 1.0, KEY)
    toks, st = advance(st, 24)
    seq_b = b + [int(first_b)] + [int(t) for t in toks[:, 2]]
    out.append(("decode after chunks", slot_rows(r, 2), seq_b[:-1],
                range(140, 164)))
    return out


# every layout against the reference's full forward pass

@pytest.mark.parametrize("row, path", [
    (row, path) for row in ROWS for path in PATHS])
def test_logits_match_the_reference(row, path, kernels):
    precision = kernels(row)
    r = make_runner(row)
    kernel = "pallas_interpret" if row.endswith("-kernel") else "jnp"
    assert r.attn_decode_path == "gqa+window"
    assert {r.attention_paths[p] for p in (
        "decode", "decode_window", "ragged_step", "ragged_step_window")
            } == {kernel}
    worst_lim, mean_lim = LIMITS[precision]
    for what, logits, ids, positions in run_path(r, path):
        worst, mean = distance(logits, ids, positions, r)
        assert worst <= worst_lim and mean <= mean_lim, (what, worst, mean)
