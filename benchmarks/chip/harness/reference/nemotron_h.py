"""Plain reference of the NVIDIA Nemotron-3-Super decoder (``model_type:
nemotron_h``; https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16,
``config.json`` and its ``described_as``: Mamba-2 layers, a latent mixture of
experts with a shared expert, grouped-query attention in one layer of eleven).

THE LAYERS.  Residual stream ``x`` of width D; layer ``l`` of kind ``c =
hybrid_override_pattern[l]``:

    x <- x + mixer_c(RMSNorm(x; g_l, eps))

Every layer is ONE mixer (``M``, ``E`` or ``*``); there is no second norm and
no MLP inside a layer.  After the last layer RMSNorm, then the untied head.

``M``, Mamba-2 (Dao & Gu 2024).  d_inner = heads x head dim, G groups, state
N, conv_dim = d_inner + 2 G N.  ``[z | xBC | dt] = W_in h`` (no bias);
``xBC <- SiLU(conv1d_causal_depthwise(xBC; kernel K, bias))``; split x
(heads x head dim), B, C (G x N each; head h reads group h // (heads / G)).
``dt_h = softplus(dt_h + dt_bias_h)``, ``A_h = -exp(A_log_h)``.  State S_h
(head dim x N), zero before the first token:

    S_{h,t} = exp(dt_h A_h) S_{h,t-1} + dt_h x_{h,t} (x) B_{g,t}
    y_{h,t} = S_{h,t} C_{g,t} + D_h x_{h,t}

then ``y <- RMSNorm_grouped(y * SiLU(z); G groups, weight of d_inner)`` (gate
first, then norm) and ``W_out y``.  Written here as a ``lax.scan`` over the
tokens: no chunks.  ``time_step_min/max/floor`` parametrise the INIT of
``dt_bias`` and have no part in the forward pass.

``*``, attention.  ``q = W_q h`` (heads x head dim), ``k, v`` (kv heads x head
dim), no bias, causal softmax at 1/sqrt(head dim), ``W_o``.

``E``, latent mixture of experts.  Router in full width and float32: ``s =
sigmoid(W_r h)``; the k experts are chosen by ``s + b`` (the correction
bias; n_group = topk_group = 1, so no group limit) and weighted ``w_e =
scaling * s_e / sum_chosen s``.  Latent path ``u = W_down h``; expert e is
``f_e(u) = W2_e relu(W1_e u)^2`` (not gated, no bias); ``routed = W_up
sum_e w_e f_e(u)``.  Shared expert on h in full width: ``W2_s relu(W1_s
h)^2``.  Output ``routed + shared``.

DEPARTURES, each on purpose:

* No rotary embedding.  ``nemotron_h`` attention is position-free (the Mamba
  layers carry the order); ``rope_theta`` and ``partial_rotary_factor`` of
  the config have no reader.
* No multi-token-prediction module (``num_nextn_predict_layers`` 1,
  pattern ``*E``): a draft head for speculation; checkpoints are served
  without it, and nothing stands in for it.
* THE SHARE.  ``n_routed_experts`` counts the experts held HERE, the
  contiguous block ``expert_parallel_rank`` of ``n_routed_experts_published``
  (absent: all of them are held).  The router keeps its published width, its
  k and its weights; every held expert is computed for every token and
  masked by its weight; what the absent experts would have added is left
  out — here as in the program, and the partial sum goes on to the next
  layer.  ``vocab_size`` is this chip's rows: a smaller vocabulary.

float32 throughout, ``jax.default_matmul_precision("highest")`` set by the
caller, one sequence at a time, no cache, no batching, no sorted dispatch,
one expert dequantized at a time.  TOLERANCE: see dense.py (the emitted
token's deficit) and ``nemotron_h.tolerance.json``; the CPU tests compare
logits (tests/test_hybrid.py).

``controls`` names deliberate faults (the tests and the limits file read
how far each moves the result): ``no_correction_bias``, ``no_scaling``,
``silu_experts``, ``softmax_router``, ``no_conv_bias``, and ``bf16_state``
(the state-space state rounded to bf16 after every token: the nearest
precision below the float32 the configuration states).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
STACK = {"M": "mamba", "E": "moe", "*": "attn"}


def dequant(w) -> jax.Array:
    """A weight as float32: an int8 tensor ``q`` [.., d_in, d_out] with one
    scale per output column ``s`` [.., d_out], or a plain array."""
    if hasattr(w, "q") and hasattr(w, "s"):
        return w.q.astype(F32) * w.s.astype(F32)[..., None, :]
    return jnp.asarray(w).astype(F32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def hyper(hf: dict) -> dict:
    """The sizes the equations need, from the ``config.json``."""
    held = hf["n_routed_experts"]
    return {
        "pattern": hf["hybrid_override_pattern"],
        "eps": float(hf.get("layer_norm_epsilon", hf.get("norm_eps", 1e-5))),
        "heads": hf["num_attention_heads"],
        "kv_heads": hf["num_key_value_heads"],
        "head_dim": hf.get("head_dim")
        or hf["hidden_size"] // hf["num_attention_heads"],
        "m_heads": hf["mamba_num_heads"], "m_head_dim": hf["mamba_head_dim"],
        "groups": hf["n_groups"], "state": hf["ssm_state_size"],
        "kernel": hf["conv_kernel"],
        "experts": hf.get("n_routed_experts_published", held),
        "held": held, "first": hf.get("expert_parallel_rank", 0) * held,
        "top_k": hf["num_experts_per_tok"],
        "scaling": float(hf.get("routed_scaling_factor", 1.0)),
        "norm_topk": bool(hf.get("norm_topk_prob", True)),
    }


def mamba(h, w, hp, controls=()):
    """The Mamba-2 mixer over one sequence ``h [T, D]`` from a zero state."""
    t = h.shape[0]
    nh, p, g, n = hp["m_heads"], hp["m_head_dim"], hp["groups"], hp["state"]
    d_inner, k = nh * p, hp["kernel"]
    zxd = h @ dequant(w["w_in"])
    z, xbc, dt = jnp.split(zxd, [d_inner, 2 * d_inner + 2 * g * n], axis=-1)
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    cw = dequant(w["conv_w"])                               # [conv_dim, K]
    conv = sum(padded[j:j + t] * cw[:, j] for j in range(k))
    if "no_conv_bias" not in controls:
        conv = conv + dequant(w["conv_b"])
    xbc = jax.nn.silu(conv)
    x, b, c = jnp.split(xbc, [d_inner, d_inner + g * n], axis=-1)
    x = x.reshape(t, nh, p)
    b = jnp.repeat(b.reshape(t, g, n), nh // g, axis=1)     # [T, H, N]
    c = jnp.repeat(c.reshape(t, g, n), nh // g, axis=1)
    dt = jax.nn.softplus(dt + dequant(w["dt_bias"]))        # [T, H]
    a = -jnp.exp(dequant(w["A_log"]))
    d = dequant(w["D"])

    def token(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if "bf16_state" in controls:
            # not astype(bf16).astype(f32): the TPU compiler keeps excess
            # precision and takes that pair out (read on the chip, PR 27)
            state = jax.lax.reduce_precision(state, 8, 7)
        return state, jnp.einsum("hpn,hn->hp", state, c_t) + d[:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((nh, p, n), F32), (x, dt, b, c))
    y = y.reshape(t, d_inner) * jax.nn.silu(z)
    y = rms_norm(y.reshape(t, g, d_inner // g), 1.0, hp["eps"])
    return (y.reshape(t, d_inner) * dequant(w["gate_norm"])) @ dequant(w["w_out"])


def attention(h, w, hp):
    """Grouped-query causal attention, no rotation."""
    t = h.shape[0]
    nh, hkv, dh = hp["heads"], hp["kv_heads"], hp["head_dim"]
    q = (h @ dequant(w["wq"])).reshape(t, nh, dh)
    k = jnp.repeat((h @ dequant(w["wk"])).reshape(t, hkv, dh), nh // hkv, 1)
    v = jnp.repeat((h @ dequant(w["wv"])).reshape(t, hkv, dh), nh // hkv, 1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(dh))
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    scores = jnp.where(seen[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return out.reshape(t, nh * dh) @ dequant(w["wo"])


def router_weights(h, w, hp, controls=()):
    """``[T, experts]``: each token's weight for the k experts it chose,
    zero for the others."""
    logits = h @ dequant(w["router"])
    scores = (jax.nn.softmax(logits, -1) if "softmax_router" in controls
              else jax.nn.sigmoid(logits))
    choice = scores if "no_correction_bias" in controls else (
        scores + dequant(w["router_bias"]))
    _, idx = jax.lax.top_k(choice, hp["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(idx, hp["experts"], dtype=F32), axis=-2)
    weights = scores * chosen
    if hp["norm_topk"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return weights if "no_scaling" in controls else weights * hp["scaling"]


def routed_part(h, w, hp, controls=()):
    """The held experts' part of the routed sum, back in full width."""
    weights = router_weights(h, w, hp, controls)
    weights = weights[:, hp["first"]:hp["first"] + hp["held"]]
    u = h @ dequant(w["w_lat_down"])
    act = jax.nn.silu if "silu_experts" in controls else relu2

    def expert(acc, e):
        w1, w2, we = e
        return acc + we[:, None] * (act(u @ dequant(w1)) @ dequant(w2)), None

    acc, _ = jax.lax.scan(expert, jnp.zeros_like(u),
                          (w["w1"], w["w2"], weights.T))
    return acc @ dequant(w["w_lat_up"])


def shared_part(h, w):
    return relu2(h @ dequant(w["ws1"])) @ dequant(w["ws2"])


def mixer(kind, h, w, hp, controls=()):
    if kind == "M":
        return mamba(h, w, hp, controls)
    if kind == "*":
        return attention(h, w, hp)
    return routed_part(h, w, hp, controls) + shared_part(h, w)


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
