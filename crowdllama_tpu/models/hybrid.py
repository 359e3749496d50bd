"""Models whose layers differ in kind, each sublayer ONE mixer behind one
RMSNorm, joining the residual stream in one of two forms:

    x <- x + mixer_kind(RMSNorm(x))          kind = cfg.layer_pattern[i]
    x <- x + RMSNorm_post(mixer_kind(RMSNorm(x)))       (``cfg.post_norms``)

A model need not have a recurrent layer to come through here: what the
file keeps is one definition of each kind of sublayer for every layout of
its state.

Family ``nemotron_h``: Mamba-2 (``M``), a latent mixture of experts with a
shared expert (``E``), attention without rotation (``*``), one sublayer a
layer.  Family ``kimi_linear``: delta-rule linear attention (``K``, KDA:
``ops/kda.py``) or latent attention without rotation (``L``, MLA), then a
dense SwiGLU (``D``) or a SwiGLU mixture of experts with a shared expert
(``S``) — a published layer is two entries of the pattern.  Family
``afmoe``: gated attention with a per-head RMSNorm of q and k, inside a
sliding window and rotated (``W``) or over the whole context and not
(``F``), then ``D`` or ``S``; the second residual form; the embedding times
``cfg.embedding_multiplier`` (muP).  Family ``sarvam_mla``: latent
attention whose decoupled part is rotated (``R``: the ``L`` layer's body
and parameters, given each row's angles) in every layer, then ``D`` or
``S``.

The equations are written out in the plain references
(benchmarks/chip/harness/reference/nemotron_h.py, kimi_linear.py,
afmoe.py, sarvam_mla.py); this
file is the program's side of them.  Parameters are kept per KIND
(``params["layers"][STACK[kind]]``: a list, one dict of leaves for each
sublayer of that kind in order) and the layer loop is unrolled over
the pattern.  A list and not arrays with a leading layer axis: an unrolled
loop takes layer i by a static slice, and XLA materialized every such slice
(4.4 GB of temporaries at the benchmark's cut, all of the layer weights a
second time: deviceless compile, PR 27).

As ``transformer.decode_layer_body`` keeps the KV-cache policy behind
``attn_fn``, the bodies here keep every state policy behind a callable, so
that each kind's math is defined ONCE for every layout (prefill from
nothing, a prefill chunk continuing a slot, a decode step over the paged
state, the ragged step's mixed rows):

* ``attn_fn(i, q, k, v) -> attn`` — the i-th attention layer's cache write
  and read; q ``[..., H, Dh]``, k, v ``[..., Hkv, Dh]``.  A latent layer
  hands over ONE row a token as ``k`` and ``v = None``: the value is that
  row (the cache keeps no second pool), and the caller takes its share.
  :func:`attn_kinds` says which kind the i-th is: a window layer's cache
  may keep only the pages its window still reaches (engine/hybrid.py).
* ``rec_fn(kind, i, lp, *inputs) -> y`` — the i-th recurrent layer's (``M``
  or ``K``) convolution tail and state: it splits the rows into sequences
  and runs ``MIX[kind]`` (:func:`mamba_mix`, :func:`kda_mix`) on each, from
  and to wherever the layout keeps them.

The expert layer holds a SHARE of the experts (``cfg.experts_held`` of the
router's ``cfg.num_experts``, those of ``cfg.expert_rank``): it routes over
all of them, computes the token-expert rows whose expert it holds, and
leaves out what the absent experts would have added — another chip's part
of the sum, which on one chip is simply not there.
"""

from __future__ import annotations

import math
from functools import partial, reduce
from typing import Any

import jax
import jax.numpy as jnp

from crowdllama_tpu.models.config import ModelConfig
from crowdllama_tpu.ops import kda, ssm
from crowdllama_tpu.ops.attention import (
    prefill_attention,
    prefill_attention_ctx,
)
from crowdllama_tpu.ops.norms import rms_norm
from crowdllama_tpu.ops.rope import rope_angles, rotate_half
from crowdllama_tpu.ops.quant import (
    dequant, qeinsum, qragged_dot, qragged_fetched,
)

Params = dict[str, Any]
F32 = jnp.float32

#: the parameter stack of each kind of sublayer
STACK = {"M": "mamba", "E": "moe", "*": "attn",
         "K": "kda", "L": "mla", "R": "mla", "D": "mlp", "S": "smoe",
         "W": "wattn", "F": "fattn"}
#: the kinds that keep paged KV, and the per-slot state of the recurrent ones
ATTENTION = "*LRWF"
STATE = {"M": "ssm", "K": "kda"}
#: why whatever rests on "tokens done == pages of KV to hand over" declines
#: a model with recurrent layers (prefix reuse, page export and import, the
#: drain hand-off, speculation's rollback)
NO_PAGES = "recurrent state has no page to export"
#: why the same declines a model with window layers: their pool is a ring a
#: slot (engine/hybrid.py), so the pages of a prompt's first tokens are gone
#: by the time anyone could share, ship or roll back to them
NO_WINDOW_PAGES = "a window layer no longer holds a prefix's pages"
#: why the same declines a model whose every layer keeps all of its pages,
#: one latent row a token: the prefix gathers and ``import_pages`` take a
#: page of K and its twin of V (engine/paged.py ``pool_row_width``)
NO_LATENT_PAGES = ("a latent pool has no V twin for the prefix gathers and "
                   "import_pages to take")


def attn_kinds(cfg: ModelConfig) -> str:
    """The kinds of the attention layers, in the order ``attn_fn`` counts
    them."""
    return "".join(k for k in cfg.layer_pattern if k in ATTENTION)


def sizes(cfg: ModelConfig) -> dict[str, int]:
    d_inner = cfg.ssm_heads * cfg.ssm_head_dim
    bc = cfg.ssm_groups * cfg.ssm_state
    z = {"d_inner": d_inner, "bc": bc, "conv_dim": d_inner + 2 * bc,
         "in_proj": 2 * d_inner + 2 * bc + cfg.ssm_heads,
         "held": cfg.experts_held or cfg.num_experts}
    if cfg.kv_lora_rank:    # latent attention: every head's [q_nope ; q_rope]
        z["q_dim"] = cfg.num_heads * (cfg.qk_nope_head_dim
                                      + cfg.qk_rope_head_dim)
    if cfg.kda_heads:   # family kimi_linear
        hk = cfg.kda_heads * cfg.kda_head_dim
        z.update({
            "hk": hk,
            # the channels a KDA layer convolves: [q | k | v]
            "conv_dim": 3 * hk,
            "kda_in": 3 * hk + 2 * cfg.kda_gate_rank + cfg.kda_heads})
    return z


def _shapes(cfg: ModelConfig) -> dict[str, dict[str, tuple[int, ...]]]:
    """Per kind, each leaf's shape for ONE layer."""
    z = {"hk": 0, "kda_in": 0, "q_dim": 0, **sizes(cfg)}
    d, dh = cfg.hidden_size, cfg.resolved_head_dim()
    h, hkv = cfg.num_heads, cfg.num_kv_heads
    lat, f = cfg.moe_latent_size, cfg.moe_intermediate_size
    # the sandwich form: a second gain, over the sublayer's output
    post = {"post_norm": (d,)} if cfg.post_norms else {}
    gated = {"norm": (d,), "wq": (d, h * dh), "wk": (d, hkv * dh),
             "wv": (d, hkv * dh), "wg": (d, h * dh), "q_norm": (dh,),
             "k_norm": (dh,), "wo": (h * dh, d), **post}
    return {
        "wattn": gated, "fattn": gated,
        "mamba": {
            "norm": (d,), "w_in": (d, z["in_proj"]),
            "conv_w": (z["conv_dim"], cfg.ssm_conv_kernel),
            "conv_b": (z["conv_dim"],), "dt_bias": (cfg.ssm_heads,),
            "A_log": (cfg.ssm_heads,), "D": (cfg.ssm_heads,),
            "gate_norm": (z["d_inner"],), "w_out": (z["d_inner"], d)},
        "moe": {
            "norm": (d,), "router": (d, cfg.num_experts),
            "router_bias": (cfg.num_experts,), "w_lat_down": (d, lat),
            "w1": (z["held"], lat, f), "w2": (z["held"], f, lat),
            "w_lat_up": (lat, d),
            "ws1": (d, cfg.moe_shared_intermediate_size),
            "ws2": (cfg.moe_shared_intermediate_size, d)},
        "attn": {
            "norm": (d,), "wq": (d, h * dh), "wk": (d, hkv * dh),
            "wv": (d, hkv * dh), "wo": (h * dh, d)},
        # w_in = [W_q | W_k | W_v | W_f_down | W_g_down | W_beta]
        "kda": {
            "norm": (d,), "w_in": (d, z["kda_in"]),
            "conv_w": (3 * z["hk"], cfg.kda_conv_kernel),
            "w_f_up": (cfg.kda_gate_rank, z["hk"]), "dt_bias": (z["hk"],),
            "A_log": (cfg.kda_heads,),
            "w_g_up": (cfg.kda_gate_rank, z["hk"]),
            "o_norm": (cfg.kda_head_dim,), "wo": (z["hk"], d)},
        # w_in = [W_q | W_kva]; w_kvb a head [k_nope | v]
        "mla": {
            "norm": (d,), "w_in": (d, z["q_dim"] + dh),
            "kv_norm": (cfg.kv_lora_rank,),
            "w_kvb": (cfg.kv_lora_rank,
                      h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "wo": (h * cfg.v_head_dim, d)},
        # w_gu = [W_gate | W_up]
        "mlp": {
            "norm": (d,), "w_gu": (d, 2 * cfg.intermediate_size),
            "w_down": (cfg.intermediate_size, d), **post},
        "smoe": {
            "norm": (d,), "router": (d, cfg.num_experts),
            "router_bias": (cfg.num_experts,),
            "w_gate": (z["held"], d, f), "w_up": (z["held"], d, f),
            "w_down": (z["held"], f, d),
            "ws_gu": (d, 2 * cfg.moe_shared_intermediate_size),
            "ws_down": (cfg.moe_shared_intermediate_size, d), **post},
    }


def param_count(cfg: ModelConfig) -> int:
    per = {k: sum(math.prod(s) for s in leaves.values())
           for k, leaves in _shapes(cfg).items()}
    layers = sum(per[STACK[kind]] for kind in cfg.layer_pattern)
    head = 0 if cfg.tie_word_embeddings else cfg.hidden_size * cfg.vocab_size
    return (layers + cfg.vocab_size * cfg.hidden_size + head
            + cfg.hidden_size)


def special_leaf(name: str, shape, key, dtype):
    """The leaves whose init is not "a gain of ones" or "normal / sqrt(fan
    in)", for both no-checkpoint inits (transformer.init_params and
    ops.quant.random_quantized_params); None for every other leaf.  The
    state-space constants stay float32 whatever the serving dtype."""
    if name == "A_log":      # A = -exp(A_log) in [-16, -1]
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if name == "D":
        return jnp.ones(shape, F32)
    if name == "dt_bias":    # softplus(dt_bias) log-uniform in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3),
                                        math.log(1e-1)))
        dt = jnp.maximum(dt, 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "router_bias":  # the correction bias, NOT zero
        return 0.1 * jax.random.normal(key, shape, F32)
    if name == "conv_w":
        return (jax.random.normal(key, shape, F32)
                / math.sqrt(shape[-1])).astype(dtype)
    if name == "conv_b":
        return (0.1 * jax.random.normal(key, shape, F32)).astype(dtype)
    return None


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init a parameter pytree (each kind's layers a list)."""
    n_leaves = sum(cfg.layers_of(kind) * len(_shapes(cfg)[name])
                   for kind, name in STACK.items()) + 2
    keys = iter(jax.random.split(key, n_leaves))

    def dense(k, shape):
        return (jax.random.normal(k, shape, F32)
                / math.sqrt(shape[-2])).astype(dtype)

    def leaf(name, shape):
        k = next(keys)
        if name in ("norm", "gate_norm", "o_norm", "kv_norm", "post_norm",
                    "q_norm", "k_norm"):
            return jnp.ones(shape, dtype)
        special = special_leaf(name, shape, k, dtype)
        return dense(k, shape) if special is None else special

    layers = {
        name: [{k: leaf(k, shape) for k, shape in _shapes(cfg)[name].items()}
               for _ in range(cfg.layers_of(kind))]
        for kind, name in STACK.items() if cfg.layers_of(kind)}
    params: Params = {
        "embed": dense(next(keys), (cfg.vocab_size, cfg.hidden_size)),
        "layers": layers,
        "final_norm": jnp.ones((cfg.hidden_size,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense(next(keys),
                                  (cfg.hidden_size, cfg.vocab_size))
    return params


# ------------------------------------------------------------ layer bodies

def _normed(lp: Params, cfg: ModelConfig, x):
    return rms_norm(x, lp["norm"], cfg.rms_norm_eps)


def _joined(lp: Params, cfg: ModelConfig, x, y):
    """The residual stream after a sublayer whose mixer gave ``y``: ``x +
    y``, or ``x + RMSNorm_post(y)`` where the layer has the second gain."""
    if "post_norm" in lp:
        y = rms_norm(y, lp["post_norm"], cfg.rms_norm_eps)
    return x + y


def mamba_mix(lp: Params, cfg: ModelConfig, xbc, dt, tail, state, valid,
              layer=None):
    """Convolution, activation and the state-space recurrence of one Mamba
    layer for S sequences of T rows, from ``(tail, state)`` to theirs after
    each sequence's ``valid`` real rows.

    xbc ``[S, T, conv_dim]`` and dt ``[S, T, H]`` as the input projection
    gave them; tail ``[S, conv_dim, K-1]``; state ``[S, H, P, N]`` float32;
    valid ``[S]``: the chunked scan.  With ``layer`` — the decode step's
    one-step update, T = 1 — ``state`` is the carried stack ``[L_M, S, H,
    P, N]`` and that layer's slab of it is updated where it lies
    (``ssm.ssm_update_at``).  Returns (y ``[S, T, d_inner]`` float32, tail,
    state — the stack, given one)."""
    z = sizes(cfg)
    s, t = xbc.shape[:2]
    h, p, g, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    conv, tail = ssm.causal_conv(xbc, tail, lp["conv_w"], lp["conv_b"], valid)
    conv = jax.nn.silu(conv)
    x = conv[..., :z["d_inner"]].reshape(s, t, h, p)
    b = conv[..., z["d_inner"]:z["d_inner"] + z["bc"]].reshape(s, t, g, n)
    c = conv[..., z["d_inner"] + z["bc"]:].reshape(s, t, g, n)
    dt = jax.nn.softplus(dt.astype(F32) + lp["dt_bias"])
    # a row that is not real moves neither state nor tail
    dt = jnp.where(jnp.arange(t)[None, :, None] < valid[:, None, None], dt, 0.0)
    a = -jnp.exp(lp["A_log"].astype(F32))
    d = lp["D"].astype(F32)
    if layer is not None:
        assert t == 1, t
        y, state = ssm.ssm_update_at(x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0],
                                     d, state, layer)
        y = y[:, None]
    else:
        y, state = ssm.ssd_scan(x, dt, a, b, c, d, state, cfg.ssm_chunk)
    return y.reshape(s, t, z["d_inner"]), tail, state


def mamba_body(lp: Params, cfg: ModelConfig, x, rec_fn):
    """One Mamba-2 layer minus its state policy.  x ``[..., D]``."""
    z = sizes(cfg)
    with jax.named_scope("ssm_proj"):
        zxd = qeinsum("...d,dk->...k", _normed(lp, cfg, x), lp["w_in"])
        gate = zxd[..., :z["d_inner"]]
        xbc = zxd[..., z["d_inner"]:z["d_inner"] + z["conv_dim"]]
        dt = zxd[..., z["d_inner"] + z["conv_dim"]:]
    y = rec_fn(lp, xbc, dt)                     # [..., d_inner] float32
    with jax.named_scope("ssm_proj"):
        # gate first, then an RMSNorm over each group's share of d_inner
        y = y * jax.nn.silu(gate.astype(F32))
        yg = y.reshape(*y.shape[:-1], cfg.ssm_groups, -1)
        yg = yg * jax.lax.rsqrt(
            jnp.mean(yg * yg, -1, keepdims=True) + cfg.rms_norm_eps)
        y = (yg.reshape(y.shape) * lp["gate_norm"].astype(F32)).astype(x.dtype)
        return x + qeinsum("...k,kd->...d", y, lp["w_out"])


def attn_body(lp: Params, cfg: ModelConfig, x, attn_fn):
    """One attention layer minus its cache policy: no rotation (the Mamba
    layers carry the order of the tokens)."""
    dh = cfg.resolved_head_dim()
    with jax.named_scope("attn_proj"):
        h = _normed(lp, cfg, x)
        q = qeinsum("...d,dk->...k", h, lp["wq"])
        k = qeinsum("...d,dk->...k", h, lp["wk"])
        v = qeinsum("...d,dk->...k", h, lp["wv"])
        lead = x.shape[:-1]
        q = q.reshape(*lead, cfg.num_heads, dh)
        k = k.reshape(*lead, cfg.num_kv_heads, dh)
        v = v.reshape(*lead, cfg.num_kv_heads, dh)
    attn = attn_fn(q, k, v)
    with jax.named_scope("attn_proj"):
        return x + qeinsum("...k,kd->...d", attn.reshape(*lead, -1), lp["wo"])


def gattn_body(lp: Params, cfg: ModelConfig, x, attn_fn, angles=None):
    """One gated attention layer (``W``, ``F``) minus its cache policy: q
    and k normed a head before any rotation, rotated by ``angles`` (cos,
    sin ``[..., Dh/2]`` of each row's position; None: no rotation), and the
    softmax's output times ``sigmoid(W_g a)`` before ``W_o``."""
    dh = cfg.resolved_head_dim()
    lead = x.shape[:-1]
    with jax.named_scope("attn_proj"):
        h = _normed(lp, cfg, x)
        q = qeinsum("...d,dk->...k", h, lp["wq"]).reshape(
            *lead, cfg.num_heads, dh)
        k = qeinsum("...d,dk->...k", h, lp["wk"]).reshape(
            *lead, cfg.num_kv_heads, dh)
        v = qeinsum("...d,dk->...k", h, lp["wv"]).reshape(
            *lead, cfg.num_kv_heads, dh)
        with jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid(
                qeinsum("...d,dk->...k", h, lp["wg"]).astype(F32))
        with jax.named_scope("qk_norm"):
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
        if angles is not None:
            q, k = rotate_half(q, *angles), rotate_half(k, *angles)
    attn = attn_fn(q, k, v)
    with jax.named_scope("attn_proj"):
        with jax.named_scope("attn_gate"):
            y = (attn.reshape(*lead, -1).astype(F32) * gate).astype(x.dtype)
        return _joined(lp, cfg, x, qeinsum("...k,kd->...d", y, lp["wo"]))


def kda_mix(lp: Params, cfg: ModelConfig, qkv, g, beta, tail, state, valid,
            layer=None):
    """Convolutions, activation, norms and the delta-rule recurrence of one
    KDA layer for S sequences of T rows, from ``(tail, state)`` to theirs
    after each sequence's ``valid`` real rows.

    qkv ``[S, T, 3 H dk]`` as the input projection gave them (q, k and v
    each have a depthwise convolution of their own: one over the three side
    by side); g ``[S, T, H, dk]`` the log decay and beta ``[S, T, H]`` the
    step size; tail ``[S, K-1, 3 H dk]`` (rows: ``ssm.causal_conv_rows``);
    state ``[S, H, dk, dv]`` float32;
    valid ``[S]``: the chunked form.  With ``layer`` — the decode step's
    one-step update, T = 1 — ``state`` is the carried stack ``[L_K, S, H,
    dk, dv]`` and that layer's slab of it is updated where it lies
    (``kda.kda_update_at``).  Returns (o ``[S, T, H, dv]`` float32, tail,
    state — the stack, given one)."""
    s, t = qkv.shape[:2]
    h, dk = cfg.kda_heads, cfg.kda_head_dim
    conv, tail = ssm.causal_conv_rows(qkv, tail, lp["conv_w"], None, valid)
    q, k, v = (m.reshape(s, t, h, dk)
               for m in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    q, k = kda.l2norm(q) * dk ** -0.5, kda.l2norm(k)
    # a row that is not real moves neither state nor tail
    real = jnp.arange(t)[None, :] < valid[:, None]
    g = jnp.where(real[..., None, None], g.astype(F32), 0.0)
    beta = jnp.where(real[..., None], beta.astype(F32), 0.0)
    if layer is not None:
        assert t == 1, t
        o, state = kda.kda_update_at(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                     beta[:, 0], state, layer)
        return o[:, None], tail, state
    o, state = kda.kda_chunk_scan(q, k, v, g, beta, state, cfg.kda_chunk)
    return o, tail, state


def kda_body(lp: Params, cfg: ModelConfig, x, rec_fn):
    """One KDA layer minus its state policy.  x ``[..., D]``."""
    z = sizes(cfg)
    h, dk, r = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_gate_rank
    lead = x.shape[:-1]
    with jax.named_scope("kda_proj"):
        zin = qeinsum("...d,dk->...k", _normed(lp, cfg, x), lp["w_in"])
        qkv, f, gd, b = jnp.split(
            zin, [3 * z["hk"], 3 * z["hk"] + r, 3 * z["hk"] + 2 * r], axis=-1)
        # decay, a channel of a head: -exp(A_log) softplus(W_f x + dt_bias)
        f = qeinsum("...r,rk->...k", f, lp["w_f_up"]).astype(F32)
        g = jax.nn.softplus(f + lp["dt_bias"].astype(F32)).reshape(
            *lead, h, dk) * -jnp.exp(lp["A_log"].astype(F32))[:, None]
        beta = jax.nn.sigmoid(b.astype(F32))
    o = rec_fn(lp, qkv, g, beta)                # [..., H, dv] float32
    with jax.named_scope("kda_proj"):
        gate = jax.nn.sigmoid(qeinsum(
            "...r,rk->...k", gd, lp["w_g_up"]).astype(F32))
        o = o * jax.lax.rsqrt(
            jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps)
        o = (o * lp["o_norm"].astype(F32)).reshape(*lead, -1) * gate
        return x + qeinsum("...k,kd->...d", o.astype(x.dtype), lp["wo"])


def mla_body(lp: Params, cfg: ModelConfig, x, attn_fn, angles=None):
    """One latent-attention layer minus its cache policy, ABSORBED: the
    key half of the kv up-projection is folded into the query and the value
    half applied after the softmax, so that attention is every head's
    ``[q~ ; q_rope]`` against ONE row ``[c ; k_rope]`` a token, whose first
    ``kv_lora_rank`` values are also the value.  With ``angles`` (cos, sin
    ``[..., qk_rope_head_dim/2]`` of each row's position: an ``R`` layer)
    the decoupled part is rotated — every head's ``q_rope``, and ``k_rope``
    BEFORE the row goes to the cache, which so keeps rotated keys; None (an
    ``L`` layer): no rotation."""
    r, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    dq = dn + cfg.qk_rope_head_dim
    lead = x.shape[:-1]
    with jax.named_scope("attn_proj"):
        zin = qeinsum("...d,dk->...k", _normed(lp, cfg, x), lp["w_in"])
        q = zin[..., :sizes(cfg)["q_dim"]].reshape(*lead, cfg.num_heads, dq)
        c = rms_norm(zin[..., -(r + cfg.qk_rope_head_dim):-cfg.qk_rope_head_dim],
                     lp["kv_norm"], cfg.rms_norm_eps)
        k_r = zin[..., -cfg.qk_rope_head_dim:]
        if angles is not None:
            with jax.named_scope("rope"):
                k_r = rotate_half(k_r[..., None, :], *angles)[..., 0, :]
        row = jnp.concatenate([c, k_r], -1)
        w_kvb = dequant(lp["w_kvb"]).reshape(r, cfg.num_heads, -1)
        q_n = jnp.einsum("...hd,rhd->...hr", q[..., :dn], w_kvb[..., :dn])
        q_r = q[..., dn:]
        if angles is not None:
            with jax.named_scope("rope"):
                q_r = rotate_half(q_r, *angles)
        q = jnp.concatenate([q_n, q_r], -1)
    attn = attn_fn(q, row[..., None, :], None)[..., :r]
    with jax.named_scope("attn_proj"):
        o = jnp.einsum("...hr,rhd->...hd", attn, w_kvb[..., dn:])
        return x + qeinsum("...k,kd->...d", o.reshape(*lead, -1), lp["wo"])


def mlp_body(lp: Params, cfg: ModelConfig, x):
    """One dense SwiGLU feed-forward."""
    with jax.named_scope("mlp"):
        return _joined(lp, cfg, x, _swiglu(_normed(lp, cfg, x), lp["w_gu"],
                                           lp["w_down"]))


def _swiglu(h, w_gu, w_down):
    gate, up = jnp.split(qeinsum("...d,df->...f", h, w_gu), 2, axis=-1)
    mid = jax.nn.silu(gate.astype(F32)) * up.astype(F32)
    return qeinsum("...f,fd->...d", mid.astype(h.dtype), w_down)


def relu2(x):
    r = jax.nn.relu(x.astype(F32))
    return r * r


@jax.named_scope("router")
def route(lp: Params, cfg: ModelConfig, h):
    """(weights ``[N, K]`` float32, expert ids ``[N, K]``): sigmoid scores
    in full width and float32, the K experts chosen by score + correction
    bias, weighted by their own scores (normalised over the chosen, times
    the routed scaling factor)."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "nd,de->ne", h.astype(F32), lp["router"].astype(F32)))
    _, topi = jax.lax.top_k(scores + lp["router_bias"].astype(F32),
                            cfg.num_experts_per_tok)
    w = jnp.take_along_axis(scores, topi, axis=-1)
    if cfg.moe_norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return w * cfg.moe_routed_scaling, topi


#: What an expert layer counts of one call, on the device (int32, in this
#: order; the engine sums them over the expert layers and a flight's steps
#: and the scheduler reads them back with the flight's tokens):
#: token-expert rows of live tokens computed here / left to the ranks that
#: hold their expert; held banks (one expert's matrices in one layer) that
#: some row of the call, live or padding, is routed to / that the grouped
#: matmuls read from HBM, routed to or not / held at all.
COUNTS = ("rows_held", "rows_left_out", "banks_routed", "banks_fetched",
          "banks_held")


def held_sum(cfg: ModelConfig, u, topw, topi, live, experts):
    """The held experts' part of a routed sum, through the sorted grouped
    matmul.  u ``[N, W]`` the experts' input; topw, topi ``[N, K]`` each
    token's weights and choices over ALL experts; live ``[N]`` bool;
    ``experts(xs, dot) -> ys``: the held bank on rows sorted by expert,
    ``dot(x, w)`` being the grouped matmul of those rows by one of its
    matrices.  Returns (sum ``[N, W']`` float32, this call's
    :data:`COUNTS`, a scalar each)."""
    held = sizes(cfg)["held"]
    n, k = topi.shape
    local = topi - cfg.expert_rank * held
    mine = (local >= 0) & (local < held)
    # another rank's rows sort behind every held expert's group
    e_flat = jnp.where(mine, local, held).reshape(-1)
    order = jnp.argsort(e_flat)
    t_sorted = jnp.repeat(jnp.arange(n), k)[order]
    group_sizes = jnp.bincount(e_flat, length=held + 1)[:held]
    fetched = []    # of each grouped matmul, the banks it reads

    def dot(x, w):
        fetched.append(qragged_fetched(x, w, group_sizes))
        return qragged_dot(x, w, group_sizes)

    ys = experts(jnp.take(u, t_sorted, axis=0), dot)
    contrib = jnp.where(mine.reshape(-1)[order][:, None],
                        ys.astype(F32) * topw.reshape(-1)[order][:, None],
                        0.0)
    acc = jnp.zeros((n, ys.shape[-1]), F32).at[t_sorted].add(contrib)
    rows = jnp.sum(mine & live[:, None])
    # a bank is all of an expert's matrices: fetched if any of them is
    return acc, (rows, jnp.sum(live) * k - rows, jnp.sum(group_sizes > 0),
                 jnp.sum(reduce(jnp.logical_or, fetched)), held)


def moe_body(lp: Params, cfg: ModelConfig, x, live):
    """One latent expert layer: the held experts' part of the routed sum
    plus the shared expert.  x ``[..., D]``; live ``[...]`` bool marks the
    rows that are real tokens.  Returns (x, the call's :data:`COUNTS`)."""
    shape = x.shape
    h = _normed(lp, cfg, x).reshape(-1, shape[-1])
    topw, topi = route(lp, cfg, h)
    with jax.named_scope("moe_latent"):
        u = qeinsum("nd,dl->nl", h, lp["w_lat_down"])

    def experts(xs, dot):
        return dot(relu2(dot(xs, lp["w1"])).astype(xs.dtype), lp["w2"])

    with jax.named_scope("moe"):
        acc, counts = held_sum(cfg, u, topw, topi, live.reshape(-1), experts)
    with jax.named_scope("moe_latent"):
        routed = qeinsum("nl,ld->nd", acc.astype(x.dtype), lp["w_lat_up"])
    with jax.named_scope("moe_shared"):
        mid = qeinsum("nd,df->nf", h, lp["ws1"])
        shared = qeinsum("nf,fd->nd", relu2(mid).astype(x.dtype), lp["ws2"])
    return x + (routed + shared).reshape(shape), counts


def smoe_body(lp: Params, cfg: ModelConfig, x, live):
    """One SwiGLU expert layer at the model's width: the held experts' part
    of the routed sum (three grouped matmuls) plus the shared expert.
    Arguments and result as :func:`moe_body`."""
    shape = x.shape
    h = _normed(lp, cfg, x).reshape(-1, shape[-1])
    topw, topi = route(lp, cfg, h)

    def experts(xs, dot):
        gate, up = dot(xs, lp["w_gate"]), dot(xs, lp["w_up"])
        mid = jax.nn.silu(gate.astype(F32)) * up.astype(F32)
        return dot(mid.astype(xs.dtype), lp["w_down"])

    with jax.named_scope("moe"):
        acc, counts = held_sum(cfg, h, topw, topi, live.reshape(-1), experts)
    with jax.named_scope("moe_shared"):
        shared = _swiglu(h, lp["ws_gu"], lp["ws_down"])
    return _joined(lp, cfg, x,
                   (acc.astype(x.dtype) + shared).reshape(shape)), counts


#: a recurrent kind's state policy runs this on each sequence's rows
MIX = {"M": mamba_mix, "K": kda_mix}


def run_layers(layers: Params, cfg: ModelConfig, x, rec_fn, attn_fn, live,
               positions=None):
    """The layer loop, unrolled over ``cfg.layer_pattern``; ``positions``
    (as ``x`` without its last axis) for the layers that rotate.  Returns
    (x, the expert layers' :data:`COUNTS` summed, int32 ``[5]``)."""
    # a pattern has one kind that rotates: a window layer's whole head, or
    # a latent layer's decoupled part under the family's scaling
    angles = None
    if "W" in cfg.layer_pattern:
        angles = rope_angles(positions, cfg.resolved_head_dim(),
                             cfg.rope_theta)
    elif "R" in cfg.layer_pattern:
        angles = rope_angles(positions, cfg.qk_rope_head_dim, cfg.rope_theta,
                             cfg.rope_scaling)
    # scalars until the end: a vector a layer was a concatenate a layer
    # (1.2 us each on the chip: PERF.md §6, PR 40)
    counts = (0,) * len(COUNTS)
    seen = dict.fromkeys(STACK, 0)
    attn_seen = 0
    for kind in cfg.layer_pattern:
        i = seen[kind]
        seen[kind] += 1
        lp = layers[STACK[kind]][i]
        # Tie the layer's weights to the rows they meet.  The loop is
        # unrolled, so inside a program that loops over STEPS every
        # dequantized weight is loop-invariant, and XLA would hoist all of
        # them out of the step loop as bf16 copies (6.4 GB of temporaries at
        # the benchmark's cut: deviceless compile, PR 27) and stream two
        # bytes a weight a step where int8 streams one.
        lp, x = jax.lax.optimization_barrier((lp, x))
        if kind in MIX:
            body = mamba_body if kind == "M" else kda_body
            x = body(lp, cfg, x, partial(rec_fn, kind, i))
        elif kind in ATTENTION:
            fn = partial(attn_fn, attn_seen)
            attn_seen += 1
            if kind in "WF":
                x = gattn_body(lp, cfg, x, fn, angles if kind == "W" else None)
            elif kind in "LR":
                x = mla_body(lp, cfg, x, fn, angles if kind == "R" else None)
            else:
                x = attn_body(lp, cfg, x, fn)
        elif kind == "D":
            x = mlp_body(lp, cfg, x)
        else:
            x, c = (moe_body if kind == "E" else smoe_body)(lp, cfg, x, live)
            counts = tuple(a + b for a, b in zip(counts, c))
    return x, jnp.stack(counts).astype(jnp.int32)


# ------------------------------------------------------------------ prefill

def zero_recurrent(cfg: ModelConfig, seqs: int, dtype=jnp.bfloat16):
    """The recurrent layers' state of sequences that have seen nothing, by
    the name ``PagedDecodeState`` keeps it under: ``ssm`` ``[L_M, S, H, P,
    N]`` or ``kda`` ``[L_K, S, H, dk, dv]``, float32, and ``conv`` — Mamba's
    ``[L_M, S, conv_dim, K-1]``, KDA's as rows ``[L_K, S, K-1, conv_dim]``
    (a model has layers of one recurrent kind; one with none keeps
    nothing)."""
    if not any(kind in cfg.layer_pattern for kind in STATE):
        return {}
    z = sizes(cfg)
    if cfg.layers_of("K"):
        lk, dk = cfg.layers_of("K"), cfg.kda_head_dim
        return {"kda": jnp.zeros((lk, seqs, cfg.kda_heads, dk, dk), F32),
                "conv": jnp.zeros((lk, seqs, cfg.kda_conv_kernel - 1,
                                   z["conv_dim"]), dtype)}
    lm = cfg.layers_of("M")
    return {"ssm": jnp.zeros((lm, seqs, cfg.ssm_heads, cfg.ssm_head_dim,
                              cfg.ssm_state), F32),
            "conv": jnp.zeros((lm, seqs, z["conv_dim"],
                               cfg.ssm_conv_kernel - 1), dtype)}


def prefill(params: Params, cfg: ModelConfig, tokens, positions, kv_valid,
            rec0=None, ctx_k=None, ctx_v=None, ctx_valid=None,
            n_shards: int = 1, unembed: bool = True):
    """Forward over ``tokens [B, T]`` (padded; ``kv_valid [B, T]`` marks the
    real ones, which lead).  With ``rec0`` (as :func:`zero_recurrent` gives
    it) and ``ctx_k``/``ctx_v`` (``[L_A, B, Hkv, C, Dh]``; a latent cache
    has no ``ctx_v``) the rows continue sequences already seen: the
    recurrent layers from that state, the attention layers over that
    context.  Returns (logits ``[B, T, V]`` — or the final hidden states
    with ``unembed=False`` — ks, vs ``[L_A, B, Hkv, T, Dh]`` (vs None for
    latent rows), the recurrent state after the last real row, counts)."""
    from crowdllama_tpu.models import transformer as T

    b = tokens.shape[0]
    scale = T.attn_scale(cfg)
    n_valid = jnp.sum(kv_valid, axis=-1).astype(jnp.int32)
    if rec0 is None:
        rec0 = zero_recurrent(cfg, b, params["embed"].dtype)
    kv, rec = {}, {}

    windows = [cfg.sliding_window if kind == "W" else 0
               for kind in attn_kinds(cfg)]

    def attn_fn(i, q, k, v):
        kh = k.transpose(0, 2, 1, 3)
        vh = kh if v is None else v.transpose(0, 2, 1, 3)
        kv[i] = (kh, None if v is None else vh)
        with jax.named_scope("attention"):
            if ctx_k is not None:
                return prefill_attention_ctx(
                    q, kh, vh, positions, ctx_k[i],
                    (ctx_k if ctx_v is None else ctx_v)[i], ctx_valid,
                    scale, sliding_window=windows[i], kv_valid=kv_valid)
            return prefill_attention(q, kh, vh, positions, scale,
                                     sliding_window=windows[i],
                                     kv_valid=kv_valid, n_shards=n_shards)

    def rec_fn(kind, i, lp, *inputs):
        y, tail, state = MIX[kind](lp, cfg, *inputs, rec0["conv"][i],
                                   rec0[STATE[kind]][i], n_valid)
        rec[i] = (state, tail)
        return y

    x, counts = run_layers(params["layers"], cfg, T._embed(params, cfg, tokens),
                           rec_fn, attn_fn, kv_valid, positions)
    ks = jnp.stack([kv[i][0] for i in range(len(kv))])
    vs = (None if kv[0][1] is None
          else jnp.stack([kv[i][1] for i in range(len(kv))]))
    rec_out = {}
    if rec0:
        state_name, = set(rec0) - {"conv"}
        rec_out = {
            state_name: jnp.stack([rec[i][0] for i in range(len(rec))]),
            "conv": jnp.stack([rec[i][1] for i in range(len(rec))])}
    out = (T._unembed(params, cfg, x) if unembed else rms_norm(
        x, params["final_norm"], cfg.rms_norm_eps))
    return out, ks, vs, rec_out, counts
