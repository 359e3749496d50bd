"""Plain reference of the Mistral-7B decoder (Jiang et al. 2023,
arXiv:2310.06825; the layer equations are those of the public
``MistralForCausalLM``): pre-norm residual blocks of grouped-query attention
with rotary position embedding and a sliding-window causal mask, and a SwiGLU
feed-forward; RMSNorm; an untied output head.

float32 throughout, ``jax.default_matmul_precision("highest")`` set by the
caller (on a TPU a float32 matmul otherwise runs in bf16 passes), one
sequence at a time, full attention matrix, no cache, one layer's weights
dequantized at a time.

TOLERANCE.  The system computes in bf16 with float32 accumulation, over
int8 weights dequantized to bf16; the reference computes the same int8
weights in float32.  Random weights make near-ties among 32,000 logits, so
tokens are not compared for equality.  Instead the emitted sequence is
teacher-forced through the reference, and at every position the
reference's logit of the token the system emitted is compared with the
reference's best logit: the DEFICIT, >= 0, and 0 wherever the two agree on
the argmax.  Two limits, both in units of the logits' standard deviation
at that position (random weights give logits of no fixed scale):

* ``MAX_DEFICIT``: no emitted token may be further below the best.
* ``MEAN_DEFICIT``: nor may the mean over all checked tokens — the
  sharper of the two, since it averages over hundreds of tokens what a
  change of precision adds at every one of them.

Their values and the chip runs they were set from are in
``tolerance.json`` beside this file (bf16 KV as the configurations state,
against int8 KV in its place).  What the check cannot see: the
4096-token window never binds at the served context of 2048, so a wrong
window mask would pass — the mask is exercised only by the CPU test at
tiny size, where the window is 16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def dequant(w) -> jax.Array:
    """A weight as float32: an int8 tensor ``q`` [.., d_in, d_out] with one
    scale per output column ``s`` [.., d_out], or a plain array."""
    if hasattr(w, "q") and hasattr(w, "s"):
        return w.q.astype(F32) * w.s.astype(F32)[..., None, :]
    return jnp.asarray(w).astype(F32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rotary(x, theta):
    """x: [T, H, Dh], positions 0..T-1; the two halves of a head rotate
    against each other (the 'rotate_half' form of the public checkpoints)."""
    t, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]      # [T, Dh/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(x, w, hp):
    """Grouped-query causal attention with an optional sliding window."""
    t = x.shape[0]
    h, hkv, dh = hp["heads"], hp["kv_heads"], hp["head_dim"]
    q = (x @ dequant(w["wq"])).reshape(t, h, dh)
    k = (x @ dequant(w["wk"])).reshape(t, hkv, dh)
    v = (x @ dequant(w["wv"])).reshape(t, hkv, dh)
    q, k = rotary(q, hp["rope_theta"]), rotary(k, hp["rope_theta"])
    k = jnp.repeat(k, h // hkv, axis=1)       # each KV head serves a group
    v = jnp.repeat(v, h // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(dh))
    qi, ki = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = ki <= qi
    if hp["window"]:
        seen &= ki > qi - hp["window"]
    scores = jnp.where(seen[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return out.reshape(t, h * dh) @ dequant(w["wo"])


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def feed_forward(x, w, hp):
    return swiglu(x, dequant(w["w_gate"]), dequant(w["w_up"]),
                  dequant(w["w_down"]))


def layer(x, w, hp, ffn=feed_forward):
    x = x + attention(rms_norm(x, dequant(w["ln1"]), hp["eps"]), w, hp)
    return x + ffn(rms_norm(x, dequant(w["ln2"]), hp["eps"]), w, hp)


def hyper(hf: dict) -> dict:
    """The sizes the equations need, from the public ``config.json``."""
    h = hf["num_attention_heads"]
    return {
        "heads": h,
        "kv_heads": hf.get("num_key_value_heads", h),
        "head_dim": hf.get("head_dim") or hf["hidden_size"] // h,
        "rope_theta": float(hf.get("rope_theta", 10000.0)),
        "window": int(hf.get("sliding_window") or 0),
        "eps": float(hf.get("rms_norm_eps", 1e-5)),
        "layers": hf["num_hidden_layers"],
        "experts": hf.get("num_local_experts", 0),
        "top_k": hf.get("num_experts_per_tok", 0),
    }


def layer_weights(weights: dict, i: int) -> dict:
    """Layer ``i`` of the stacked layer arrays (leading axis = layer)."""
    return jax.tree_util.tree_map(lambda a: a[i], weights["layers"])


def forward(weights: dict, hf: dict, ids, positions, layer_fn=None):
    """Logits [len(positions), vocab] of the sequence ``ids`` at the given
    positions.  ``layer_fn(x, layer_weights)`` defaults to this module's
    dense layer, jitted; moe.py passes its own."""
    hp = hyper(hf)
    if layer_fn is None:
        layer_fn = jax.jit(lambda x, w: layer(x, w, hp))
    x = dequant(weights["embed"][jnp.asarray(ids)])
    for i in range(hp["layers"]):
        x = layer_fn(x, layer_weights(weights, i))
    x = rms_norm(x[jnp.asarray(positions)], dequant(weights["final_norm"]),
                 hp["eps"])
    return x @ dequant(weights["lm_head"])
