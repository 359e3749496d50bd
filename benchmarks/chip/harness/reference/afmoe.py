"""Plain reference of the Arcee Trinity decoder (``model_type: afmoe``;
https://huggingface.co/arcee-ai/Trinity-Large-Preview ``config.json`` and its
``described_as``: gated attention, three sliding-window layers to one full
layer, sandwich norms, a 256-expert top-4 sigmoid-routed SwiGLU mixture of
experts with a shared expert behind every attention but the first
``num_dense_layers``; what ``config.json`` does not state is from the
published ``modeling_afmoe.py`` and listed under ``bench.assumed``).

THE LAYERS.  Residual stream ``x`` of width d, ``x_0 = E[ids] sqrt(d)``
(``mup_enabled``); layer ``l`` (0-indexed), heads of ``head_dim``:

    a = RMSNorm_in(x);  q = a W_q [T, H, Dh], k = a W_k, v = a W_v [T, Hkv, Dh]
    g = a W_g [T, H Dh]
    q = RMSNorm_q(q), k = RMSNorm_k(k)        over Dh, before any rotation
    sliding_attention: q, k rotated (theta, all of Dh, two halves against
                       each other); key j seen by query i iff 0 <= i - j < W
    full_attention:    NO rotation;       key j seen by query i iff j <= i
    o = softmax(q k^T / sqrt(Dh)) v          H / Hkv query heads a kv head
    x = x + RMSNorm_post_attn((o * sigmoid(g)) W_o)
    m = RMSNorm_pre_mlp(x)
    f = SwiGLU(m)                                       l < num_dense_layers
    f = SwiGLU_shared(m) + sum_{e in top k} w_e SwiGLU_e(m)        otherwise
    x = x + RMSNorm_post_mlp(f)

The router: ``s = sigmoid(m W_r)`` in float32 over ALL experts; the top k
of ``s + b`` (``b`` = ``expert_bias``; the four group counts are 1: a plain
top-k); ``w_e = route_scale * s_e / (sum_chosen s + 1e-20)`` (``route_norm``).
After the last layer RMSNorm, then the untied head.

LAYOUT OF THE WEIGHTS (the program's: ``models/hybrid.py`` ``_shapes``):
SwiGLU ``w_gu = [W_gate | W_up]``; the expert banks ``w_gate``, ``w_up``,
``w_down`` with the held experts leading.

DEPARTURES, each on purpose:

* THE SHARE.  ``num_experts`` counts the experts held HERE, the contiguous
  block ``expert_parallel_rank`` of ``num_experts_published`` (absent: all
  are held).  The router keeps its published width, its k and its weights;
  every held expert is computed for every token and masked by its weight;
  what the absent experts would have added is left out — here as in the
  program — and the shared expert is computed whole.  ``vocab_size`` is
  this chip's rows: a smaller vocabulary.
* Attention is computed a BLOCK of queries at a time (``lax.map``), each
  block against all keys under its mask: 7,168 tokens by 48 heads by 7,168
  keys in float32 would not fit the chip at once.  The sums are the same.

float32 throughout, ``jax.default_matmul_precision("highest")`` set by the
caller, one sequence at a time, no cache, no batching, no sorted dispatch,
one expert dequantized at a time.  TOLERANCE: see dense.py (the emitted
token's deficit) and ``afmoe.tolerance.json``; the CPU tests compare logits
(tests/test_afmoe.py).

``controls`` names deliberate faults (the tests and the limits file read
how far each moves the result): ``no_window`` (the window layers see every
earlier key), ``rope_on_full`` (the full layers rotate too), ``no_rope``,
``no_gate``, ``no_qk_norm``, ``bias_in_weights`` (``w_e`` from ``s + b``),
``no_correction_bias``, ``no_scaling``, ``no_post_attn_norm``,
``no_post_mlp_norm``, ``no_mup``; and two of PRECISION, each the nearest
below what the configuration states: ``int8_kv`` (keys and values rounded to
int8 with one bf16 scale a token a kv head, as the program's int8 pools
would keep them, where the configuration states bf16 KV) and
``bf16_router`` (the router's input, product and scores in bf16 where they
are stated float32) — neither of which the token check tells from a sound
run (``afmoe.tolerance.json``) — and ``int4_weights`` (every int8 matrix
rounded on to 4 bits with one scale a group of 128 inputs, the next step
down this program's own ladder ``--quantize int8 | int4``), which it does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .dense import dequant, rms_norm, rotary

F32 = jnp.float32
STACK = {"W": "wattn", "F": "fattn", "D": "mlp", "S": "smoe"}
#: queries a block of the attention
QUERY_BLOCK = 256


def hyper(hf: dict) -> dict:
    """The sizes the equations need, from the ``config.json``."""
    held = hf["num_experts"]
    kinds = {"sliding_attention": "W", "full_attention": "F"}
    dense = hf.get("num_dense_layers", 0)
    return {
        "pattern": "".join(kinds[t] + ("D" if l < dense else "S")
                           for l, t in enumerate(hf["layer_types"])),
        "eps": float(hf.get("rms_norm_eps", 1e-5)),
        "heads": hf["num_attention_heads"],
        "kv_heads": hf["num_key_value_heads"],
        "head_dim": hf.get("head_dim")
        or hf["hidden_size"] // hf["num_attention_heads"],
        "theta": float(hf.get("rope_theta", 10000.0)),
        "window": int(hf["sliding_window"]),
        "mup": hf["hidden_size"] ** 0.5 if hf.get("mup_enabled") else 1.0,
        "experts": hf.get("num_experts_published", held),
        "held": held, "first": hf.get("expert_parallel_rank", 0) * held,
        "top_k": hf["num_experts_per_tok"],
        "scaling": float(hf.get("route_scale", 1.0)),
        "norm_topk": bool(hf.get("route_norm", True)),
    }


def _bf16(x):
    # not astype(bf16).astype(f32): the TPU compiler keeps excess precision
    # and takes that pair out (nemotron_h.py)
    return jax.lax.reduce_precision(x, 8, 7)


def _as_int4(w):
    """``w [.., d_in, d_out]`` rounded to 4 bits, one scale for each group
    of 128 inputs of an output column (ops/quant.py
    ``quantize_weight_int4``)."""
    d_in, d_out = w.shape[-2:]
    g = 128 if d_in % 128 == 0 else d_in
    x = w.reshape(*w.shape[:-2], d_in // g, g, d_out)
    s = jnp.max(jnp.abs(x), -2, keepdims=True) / 7.0 + 1e-12
    return (jnp.clip(jnp.round(x / s), -8, 7) * s).reshape(w.shape)


def weight(w, controls=()):
    """A matrix as float32; under ``int4_weights`` an int8 one a step
    further down."""
    x = dequant(w)
    return _as_int4(x) if "int4_weights" in controls and hasattr(w, "q") else x


def _as_int8_kv(x):
    """``x [T, Hkv, Dh]`` as an int8 pool would hand it back
    (ops/quant.py ``quantize_kv``)."""
    s = _bf16(jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0 + 1e-12)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def attention(kind, h, w, hp, controls=()):
    """Gated attention over one sequence ``h [T, d]``: inside the window and
    rotated (``W``), or whole and not (``F``)."""
    t = h.shape[0]
    nh, hkv, dh = hp["heads"], hp["kv_heads"], hp["head_dim"]
    q = (h @ weight(w["wq"], controls)).reshape(t, nh, dh)
    k = (h @ weight(w["wk"], controls)).reshape(t, hkv, dh)
    v = (h @ weight(w["wv"], controls)).reshape(t, hkv, dh)
    gate = jax.nn.sigmoid(h @ weight(w["wg"], controls))
    if "no_qk_norm" not in controls:
        q = rms_norm(q, dequant(w["q_norm"]), hp["eps"])
        k = rms_norm(k, dequant(w["k_norm"]), hp["eps"])
    rotate = kind == "W" or "rope_on_full" in controls
    if rotate and "no_rope" not in controls:
        q, k = rotary(q, hp["theta"]), rotary(k, hp["theta"])
    if "int8_kv" in controls:
        k, v = _as_int8_kv(k), _as_int8_kv(v)
    window = hp["window"] if kind == "W" and "no_window" not in controls else 0
    qb = min(QUERY_BLOCK, t)
    blocks = -(-t // qb)
    qx = jnp.pad(q, ((0, blocks * qb - t), (0, 0), (0, 0)))
    qx = qx.reshape(blocks, qb, hkv, nh // hkv, dh)
    ki = jnp.arange(t)[None, :]

    def block(args):
        qs, start = args
        qi = start + jnp.arange(qb)[:, None]
        seen = ki <= qi
        if window:
            seen &= ki > qi - window
        scores = jnp.einsum("qhgd,khd->hgqk", qs, k) / jnp.sqrt(F32(dh))
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(scores, -1), v)

    o = jax.lax.map(block, (qx, jnp.arange(blocks) * qb))
    o = o.reshape(blocks * qb, nh * dh)[:t]
    if "no_gate" not in controls:
        o = o * gate
    return o @ weight(w["wo"], controls)


def swiglu(h, w_gu, w_down, controls=()):
    gate, up = jnp.split(h @ weight(w_gu, controls), 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ weight(w_down, controls)


def router_weights(h, w, hp, controls=()):
    """``[T, experts]``: each token's weight for the k experts it chose,
    zero for the others."""
    if "bf16_router" in controls:
        scores = _bf16(jax.nn.sigmoid(_bf16(_bf16(h) @ dequant(w["router"]))))
    else:
        scores = jax.nn.sigmoid(h @ dequant(w["router"]))
    biased = scores + dequant(w["router_bias"])
    choice = scores if "no_correction_bias" in controls else biased
    _, idx = jax.lax.top_k(choice, hp["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(idx, hp["experts"], dtype=F32), axis=-2)
    weights = (biased if "bias_in_weights" in controls else scores) * chosen
    if hp["norm_topk"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return weights if "no_scaling" in controls else weights * hp["scaling"]


def routed_part(h, w, hp, controls=()):
    """The held experts' part of the routed sum."""
    weights = router_weights(h, w, hp, controls)
    weights = weights[:, hp["first"]:hp["first"] + hp["held"]]

    def expert(acc, e):
        wg, wu, wd, we = e
        mid = (jax.nn.silu(h @ weight(wg, controls))
               * (h @ weight(wu, controls)))
        return acc + we[:, None] * (mid @ weight(wd, controls)), None

    acc, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (w["w_gate"], w["w_up"], w["w_down"], weights.T))
    return acc


def mixer(kind, h, w, hp, controls=()):
    if kind in "WF":
        return attention(kind, h, w, hp, controls)
    if kind == "D":
        return swiglu(h, w["w_gu"], w["w_down"], controls)
    return routed_part(h, w, hp, controls) + swiglu(
        h, w["ws_gu"], w["ws_down"], controls)


def sublayer(kind, x, w, hp, controls=()):
    y = mixer(kind, rms_norm(x, dequant(w["norm"]), hp["eps"]), w, hp,
              controls)
    dropped = "no_post_attn_norm" if kind in "WF" else "no_post_mlp_norm"
    if dropped not in controls:
        y = rms_norm(y, dequant(w["post_norm"]), hp["eps"])
    return x + y


def forward(weights: dict, hf: dict, ids, positions, controls=()):
    """Logits [len(positions), vocab] of the sequence ``ids`` at the given
    positions.  ``hf["reference_controls"]`` names controls too, so that a
    control is read THROUGH ``check.py``: a run's ``check_input.json`` with
    that key added to its ``config`` has to come out as not correct."""
    controls = (*controls, *hf.get("reference_controls", ()))
    hp = hyper(hf)
    layer = {kind: jax.jit(lambda x, w, kind=kind: sublayer(
        kind, x, w, hp, controls)) for kind in STACK}
    x = dequant(weights["embed"][jnp.asarray(ids)])
    if "no_mup" not in controls:
        x = x * hp["mup"]
    seen = dict.fromkeys(STACK, 0)
    for kind in hp["pattern"]:
        x = layer[kind](x, weights["layers"][STACK[kind]][seen[kind]])
        seen[kind] += 1
    x = rms_norm(x[jnp.asarray(positions)], dequant(weights["final_norm"]),
                 hp["eps"])
    return x @ weight(weights["lm_head"], controls)
