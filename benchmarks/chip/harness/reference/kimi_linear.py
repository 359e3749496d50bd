"""Plain reference of the Kimi Linear decoder (``model_type: kimi_linear``;
https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct ``config.json``
and its ``described_as``; the Kimi Linear technical report, arXiv 2510.26692:
KDA gated delta-rule linear attention in three layers of four, NoPE latent
attention (MLA) in the fourth, a 256-expert top-8 sigmoid-routed SwiGLU
mixture of experts with a shared expert behind every mixer but the first).

THE LAYERS.  Residual stream ``x`` of width d; block ``l`` (1-indexed):

    x <- x + Mix_l(RMSNorm(x))          x <- x + FFN_l(RMSNorm(x))

``Mix_l`` is KDA where ``linear_attn_config.kda_layers`` names ``l`` and MLA
where ``full_attn_layers`` does; ``FFN_l`` is a dense SwiGLU for ``l <=
first_k_dense_replace`` and the mixture of experts after.  After the last
block RMSNorm, then the untied head.

KDA (H heads, dk = dv = ``linear_attn_config.head_dim``; state ``S_h [dk,
dv]`` a head, zero before the first token).  ``q, k, v = SiLU(conv(W_q x)),
SiLU(conv(W_k x)), SiLU(conv(W_v x))``, each projection d -> H dk followed by
its own causal depthwise convolution of ``short_conv_kernel_size`` taps (no
bias); a head ``q_h <- L2norm(q_h) dk^-1/2``, ``k_h <- L2norm(k_h)`` (L2norm:
``x / sqrt(sum x^2 + 1e-6)``).  Decay, a CHANNEL of a head: ``g_t = -exp(
A_log_h) softplus(W_f_up (W_f_down x_t) + dt_bias)``, ``a_t = exp(g_t)``;
``beta_t = sigmoid(W_beta x_t)`` a head.  The gated delta rule, a head:

    S' = Diag(a_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T            o_t = S_t^T q_t

then ``o_h <- RMSNorm_head(o_h; gain of width dk) * sigmoid(W_g_up (W_g_down
x_t))_h`` and ``W_o [o_1 .. o_H]``.  Written here as a ``lax.scan`` over the
tokens, one at a time: THE DEFINITION; no chunks.

MLA, NoPE (``q_lora_rank`` null, ``mla_use_nope`` true: nothing rotates; the
KDA layers carry the order).  ``q_h = W_q x`` (qk_nope + qk_rope wide);
``[c ; k_r] = W_kva x``, ``c <- RMSNorm(c)`` (kv_lora_rank wide), ``k_r``
(qk_rope wide) shared by all heads; ``[k_n_h ; v_h] = W_kvb_h c``; scores
``(q_n_h . k_n_h + q_r_h . k_r) / sqrt(qk_nope + qk_rope)``, causal softmax,
``o_h = sum p v_h``, ``W_o [o_h]``.  Written UNABSORBED: K and V are
expanded a head (the program folds ``W_kvb`` into the query and the output
and attends over the cached row ``[c ; k_r]``).

FFN.  Dense: ``W_down (SiLU(W_gate x) * W_up x)``.  Experts: ``s = sigmoid(
W_r x)`` in float32 over ALL experts; the top k of ``s + b`` (``b`` =
``e_score_correction_bias``; ``num_expert_group = topk_group = 1``: the
grouped top-k is a plain one); ``w_e = scaling * s_e / sum_chosen s``
(``moe_renormalize``, ``routed_scaling_factor``); ``sum_chosen w_e
SwiGLU_e(x) + SwiGLU_shared(x)``.

LAYOUT OF THE WEIGHTS (the program's: ``models/hybrid.py`` ``_shapes``).
Projections of one input are one matrix, column blocks in this order — KDA
``w_in = [W_q | W_k | W_v | W_f_down | W_g_down | W_beta]`` and ONE
depthwise ``conv_w`` over ``[q | k | v]`` (three convolutions side by side);
MLA ``w_in = [W_q | W_kva]``, ``w_kvb`` a head ``[k_nope | v]``; SwiGLU
``w_gu = [W_gate | W_up]``.

DEPARTURES, each on purpose:

* THE SHARE.  ``num_experts`` counts the experts held HERE, the contiguous
  block ``expert_parallel_rank`` of ``num_experts_published`` (absent: all
  are held).  The router keeps its published width, its k and its weights;
  every held expert is computed for every token and masked by its weight;
  what the absent experts would have added is left out — here as in the
  program — and the shared expert is computed whole.  ``vocab_size`` is
  this chip's rows: a smaller vocabulary.
* Sizes the ``config.json`` does not state (``bench.assumed`` lists them):
  the two gates' low-rank width = ``linear_attn_config.head_dim``; ``q``
  scaled by ``dk^-1/2`` after its L2 norm; no convolution bias; SiLU after
  each convolution.

float32 throughout, ``jax.default_matmul_precision("highest")`` set by the
caller, one sequence at a time, no cache, no batching, no sorted dispatch,
one expert dequantized at a time.  TOLERANCE: see dense.py (the emitted
token's deficit) and ``kimi_linear.tolerance.json``; the CPU tests compare
logits (tests/test_kimi_linear.py).

``controls`` names deliberate faults (the tests and the limits file read
how far each moves the result): ``no_correction_bias``, ``no_scaling``,
``no_decay`` (a = 1), ``no_l2norm``, ``rope_scale`` (MLA scores over
sqrt(qk_nope) alone), and ``bf16_state`` (the KDA state rounded to bf16
after every token: the nearest precision below the float32 the
configuration states).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .nemotron_h import dequant, rms_norm, router_weights

F32 = jnp.float32
STACK = {"K": "kda", "L": "mla", "D": "mlp", "S": "smoe"}


def hyper(hf: dict) -> dict:
    """The sizes the equations need, from the ``config.json``."""
    lin = hf["linear_attn_config"]
    held = hf["num_experts"]
    dense = hf.get("first_k_dense_replace", 0)
    kda_at = set(lin["kda_layers"])
    return {
        "pattern": "".join(("K" if l in kda_at else "L")
                           + ("D" if l <= dense else "S")
                           for l in range(1, hf["num_hidden_layers"] + 1)),
        "eps": float(hf.get("rms_norm_eps", 1e-5)),
        "heads": hf["num_attention_heads"],
        "rank": hf["kv_lora_rank"], "nope": hf["qk_nope_head_dim"],
        "rope": hf["qk_rope_head_dim"], "v_dim": hf["v_head_dim"],
        "k_heads": lin["num_heads"], "k_dim": lin["head_dim"],
        "gate_rank": lin["head_dim"], "kernel": lin["short_conv_kernel_size"],
        "experts": hf.get("num_experts_published", held),
        "held": held, "first": hf.get("expert_parallel_rank", 0) * held,
        "top_k": hf["num_experts_per_token"],
        "scaling": float(hf.get("routed_scaling_factor", 1.0)),
        "norm_topk": bool(hf.get("moe_renormalize", True)),
    }


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def kda(h, w, hp, controls=()):
    """The KDA mixer over one sequence ``h [T, d]`` from a zero state."""
    t = h.shape[0]
    nh, dk, r, taps = hp["k_heads"], hp["k_dim"], hp["gate_rank"], hp["kernel"]
    hk = nh * dk
    qkv, f, gd, b = jnp.split(h @ dequant(w["w_in"]),
                              [3 * hk, 3 * hk + r, 3 * hk + 2 * r], axis=-1)
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    cw = dequant(w["conv_w"])                               # [3 H dk, taps]
    conv = sum(padded[j:j + t] * cw[:, j] for j in range(taps))
    q, k, v = (m.reshape(t, nh, dk)
               for m in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    if "no_l2norm" not in controls:
        q, k = l2norm(q), l2norm(k)
    q = q * dk ** -0.5
    g = (jax.nn.softplus(f @ dequant(w["w_f_up"]) + dequant(w["dt_bias"]))
         .reshape(t, nh, dk) * -jnp.exp(dequant(w["A_log"]))[:, None])
    if "no_decay" in controls:
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(b)                                # [T, H]

    def token(state, inp):                                  # [H, dk, dv]
        q_t, k_t, v_t, g_t, b_t = inp
        state = jnp.exp(g_t)[:, :, None] * state
        u = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + (b_t[:, None] * k_t)[:, :, None] * (v_t - u)[:, None]
        if "bf16_state" in controls:
            # not astype(bf16).astype(f32): the TPU compiler keeps excess
            # precision and takes that pair out (nemotron_h.py)
            state = jax.lax.reduce_precision(state, 8, 7)
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((nh, dk, dk), F32),
                        (q, k, v, g, beta))
    o = rms_norm(o, dequant(w["o_norm"]), hp["eps"]).reshape(t, hk)
    o = o * jax.nn.sigmoid(gd @ dequant(w["w_g_up"]))
    return o @ dequant(w["wo"])


def mla(h, w, hp, controls=()):
    """Latent attention, unabsorbed, no rotation."""
    t = h.shape[0]
    nh, r, dn, dr = hp["heads"], hp["rank"], hp["nope"], hp["rope"]
    zin = h @ dequant(w["w_in"])
    q = zin[:, :nh * (dn + dr)].reshape(t, nh, dn + dr)
    c = rms_norm(zin[:, nh * (dn + dr):nh * (dn + dr) + r],
                 dequant(w["kv_norm"]), hp["eps"])
    k_r = zin[:, -dr:]
    kv = (c @ dequant(w["w_kvb"])).reshape(t, nh, dn + hp["v_dim"])
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, None], (t, nh, dr))], -1)
    width = dn if "rope_scale" in controls else dn + dr
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(width))
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scores = jnp.where(seen[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), kv[..., dn:])
    return out.reshape(t, -1) @ dequant(w["wo"])


def swiglu(h, w_gu, w_down):
    gate, up = jnp.split(h @ dequant(w_gu), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ dequant(w_down)


def routed_part(h, w, hp, controls=()):
    """The held experts' part of the routed sum."""
    weights = router_weights(h, w, hp, controls)
    weights = weights[:, hp["first"]:hp["first"] + hp["held"]]

    def expert(acc, e):
        wg, wu, wd, we = e
        mid = jax.nn.silu(h @ dequant(wg)) * (h @ dequant(wu))
        return acc + we[:, None] * (mid @ dequant(wd)), None

    acc, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (w["w_gate"], w["w_up"], w["w_down"], weights.T))
    return acc


def mixer(kind, h, w, hp, controls=()):
    if kind == "K":
        return kda(h, w, hp, controls)
    if kind == "L":
        return mla(h, w, hp, controls)
    if kind == "D":
        return swiglu(h, w["w_gu"], w["w_down"])
    return routed_part(h, w, hp, controls) + swiglu(h, w["ws_gu"],
                                                   w["ws_down"])


def forward(weights: dict, hf: dict, ids, positions, controls=()):
    """Logits [len(positions), vocab] of the sequence ``ids`` at the given
    positions."""
    hp = hyper(hf)
    layer = {kind: jax.jit(lambda x, w, kind=kind: x + mixer(
        kind, rms_norm(x, dequant(w["norm"]), hp["eps"]), w, hp, controls))
        for kind in STACK}
    x = dequant(weights["embed"][jnp.asarray(ids)])
    seen = dict.fromkeys(STACK, 0)
    for kind in hp["pattern"]:
        x = layer[kind](x, weights["layers"][STACK[kind]][seen[kind]])
        seen[kind] += 1
    x = rms_norm(x[jnp.asarray(positions)], dequant(weights["final_norm"]),
                 hp["eps"])
    return x @ dequant(weights["lm_head"])
