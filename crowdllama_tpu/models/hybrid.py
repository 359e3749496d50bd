"""Layers of three kinds in one model (family ``nemotron_h``): Mamba-2,
a latent mixture of experts with a shared expert, and attention without
rotation, each layer ONE mixer behind one RMSNorm:

    x <- x + mixer_kind(RMSNorm(x))          kind = cfg.layer_pattern[layer]

The equations are written out in the plain reference
(benchmarks/chip/harness/reference/nemotron_h.py); this file is the
program's side of them.  Parameters are kept per KIND
(``params["layers"]["mamba" | "moe" | "attn"]``: a list, one dict of leaves
for each layer of that kind in order) and the layer loop is unrolled over
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
  and read; q ``[..., H, Dh]``, k, v ``[..., Hkv, Dh]``.
* ``ssm_fn(i, lp, xbc, dt) -> y`` — the i-th Mamba layer's convolution
  tail and state: it splits the rows into sequences and runs
  :func:`mamba_mix` on each, from and to wherever the layout keeps them.

The expert layer holds a SHARE of the experts (``cfg.experts_held`` of the
router's ``cfg.num_experts``, those of ``cfg.expert_rank``): it routes over
all of them, computes the token-expert rows whose expert it holds, and
leaves out what the absent experts would have added — another chip's part
of the sum, which on one chip is simply not there.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from crowdllama_tpu.models.config import ModelConfig
from crowdllama_tpu.ops import ssm
from crowdllama_tpu.ops.attention import (
    prefill_attention,
    prefill_attention_ctx,
)
from crowdllama_tpu.ops.norms import rms_norm
from crowdllama_tpu.ops.quant import qeinsum, qragged_dot

Params = dict[str, Any]
F32 = jnp.float32

#: the parameter stack of each kind of layer
STACK = {"M": "mamba", "E": "moe", "*": "attn"}
#: why whatever rests on "tokens done == pages of KV to hand over" declines
#: a model with Mamba layers (prefix reuse, page export and import, the
#: drain hand-off, speculation's rollback)
NO_PAGES = "recurrent state has no page to export"


def sizes(cfg: ModelConfig) -> dict[str, int]:
    d_inner = cfg.ssm_heads * cfg.ssm_head_dim
    bc = cfg.ssm_groups * cfg.ssm_state
    return {"d_inner": d_inner, "bc": bc, "conv_dim": d_inner + 2 * bc,
            "in_proj": 2 * d_inner + 2 * bc + cfg.ssm_heads,
            "held": cfg.experts_held or cfg.num_experts}


def _shapes(cfg: ModelConfig) -> dict[str, dict[str, tuple[int, ...]]]:
    """Per kind, each leaf's shape for ONE layer."""
    z = sizes(cfg)
    d, dh = cfg.hidden_size, cfg.resolved_head_dim()
    h, hkv = cfg.num_heads, cfg.num_kv_heads
    lat, f = cfg.moe_latent_size, cfg.moe_intermediate_size
    return {
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
        if name in ("norm", "gate_norm"):
            return jnp.ones(shape, dtype)
        special = special_leaf(name, shape, k, dtype)
        return dense(k, shape) if special is None else special

    layers = {
        name: [{k: leaf(k, shape) for k, shape in _shapes(cfg)[name].items()}
               for _ in range(cfg.layers_of(kind))]
        for kind, name in STACK.items()}
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


def mamba_body(lp: Params, cfg: ModelConfig, x, ssm_fn):
    """One Mamba-2 layer minus its state policy.  x ``[..., D]``."""
    z = sizes(cfg)
    with jax.named_scope("ssm_proj"):
        zxd = qeinsum("...d,dk->...k", _normed(lp, cfg, x), lp["w_in"])
        gate = zxd[..., :z["d_inner"]]
        xbc = zxd[..., z["d_inner"]:z["d_inner"] + z["conv_dim"]]
        dt = zxd[..., z["d_inner"] + z["conv_dim"]:]
    y = ssm_fn(lp, xbc, dt)                     # [..., d_inner] float32
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


def moe_body(lp: Params, cfg: ModelConfig, x, live):
    """One expert layer: the held experts' part of the routed sum through
    the sorted grouped matmul, plus the shared expert.  x ``[..., D]``;
    live ``[...]`` bool marks the rows that are real tokens.  Returns (x,
    [rows computed here, rows left to the other ranks]) — counted over the
    live rows."""
    z = sizes(cfg)
    held, lo = z["held"], cfg.expert_rank * z["held"]
    shape = x.shape
    h = _normed(lp, cfg, x).reshape(-1, shape[-1])
    n, k = h.shape[0], cfg.num_experts_per_tok
    topw, topi = route(lp, cfg, h)
    with jax.named_scope("moe_latent"):
        u = qeinsum("nd,dl->nl", h, lp["w_lat_down"])
    with jax.named_scope("moe"):
        local = topi - lo
        mine = (local >= 0) & (local < held)
        # another rank's rows sort behind every held expert's group
        e_flat = jnp.where(mine, local, held).reshape(-1)
        order = jnp.argsort(e_flat)
        t_sorted = jnp.repeat(jnp.arange(n), k)[order]
        xs = jnp.take(u, t_sorted, axis=0)                   # [NK, latent]
        group_sizes = jnp.bincount(e_flat, length=held + 1)[:held]
        up = qragged_dot(xs, lp["w1"], group_sizes)
        ys = qragged_dot(relu2(up).astype(xs.dtype), lp["w2"], group_sizes)
        contrib = jnp.where(mine.reshape(-1)[order][:, None],
                            ys.astype(F32) * topw.reshape(-1)[order][:, None],
                            0.0)
        acc = jnp.zeros((n, u.shape[-1]), F32).at[t_sorted].add(contrib)
    with jax.named_scope("moe_latent"):
        routed = qeinsum("nl,ld->nd", acc.astype(x.dtype), lp["w_lat_up"])
    with jax.named_scope("moe_shared"):
        mid = qeinsum("nd,df->nf", h, lp["ws1"])
        shared = qeinsum("nf,fd->nd", relu2(mid).astype(x.dtype), lp["ws2"])
    rows = jnp.sum(mine & live.reshape(-1)[:, None])
    counts = jnp.stack([rows, jnp.sum(live) * k - rows]).astype(jnp.int32)
    return x + (routed + shared).reshape(shape), counts


def run_layers(layers: Params, cfg: ModelConfig, x, ssm_fn, attn_fn, live):
    """The layer loop, unrolled over ``cfg.layer_pattern``.  Returns (x,
    the expert layers' [held, left out] assignment counts)."""
    counts = jnp.zeros((2,), jnp.int32)
    seen = dict.fromkeys(STACK, 0)
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
        if kind == "M":
            x = mamba_body(lp, cfg, x, partial(ssm_fn, i))
        elif kind == "E":
            x, c = moe_body(lp, cfg, x, live)
            counts = counts + c
        else:
            x = attn_body(lp, cfg, x, partial(attn_fn, i))
    return x, counts


# ------------------------------------------------------------------ prefill

def zero_recurrent(cfg: ModelConfig, seqs: int, dtype=jnp.bfloat16):
    """(ssm ``[L_M, S, H, P, N]`` float32, conv ``[L_M, S, conv_dim, K-1]``)
    of sequences that have seen nothing."""
    lm = cfg.layers_of("M")
    return (jnp.zeros((lm, seqs, cfg.ssm_heads, cfg.ssm_head_dim,
                       cfg.ssm_state), F32),
            jnp.zeros((lm, seqs, sizes(cfg)["conv_dim"],
                       cfg.ssm_conv_kernel - 1), dtype))


def prefill(params: Params, cfg: ModelConfig, tokens, positions, kv_valid,
            ssm0=None, conv0=None, ctx_k=None, ctx_v=None, ctx_valid=None,
            n_shards: int = 1, unembed: bool = True):
    """Forward over ``tokens [B, T]`` (padded; ``kv_valid [B, T]`` marks the
    real ones, which lead).  With ``ssm0``/``conv0`` and ``ctx_k``/``ctx_v``
    (``[L_A, B, Hkv, C, Dh]``) the rows continue sequences already seen:
    the Mamba layers from that state, the attention layers over that
    context.  Returns (logits ``[B, T, V]`` — or the final hidden states
    with ``unembed=False`` — ks, vs ``[L_A, B, Hkv, T, Dh]``, ssm, conv,
    counts)."""
    from crowdllama_tpu.models import transformer as T

    b = tokens.shape[0]
    scale = T.attn_scale(cfg)
    n_valid = jnp.sum(kv_valid, axis=-1).astype(jnp.int32)
    if ssm0 is None:
        ssm0, conv0 = zero_recurrent(cfg, b, params["embed"].dtype)
    kv, rec = {}, {}

    def attn_fn(i, q, k, v):
        kh, vh = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        kv[i] = (kh, vh)
        with jax.named_scope("attention"):
            if ctx_k is not None:
                return prefill_attention_ctx(
                    q, kh, vh, positions, ctx_k[i], ctx_v[i], ctx_valid,
                    scale, kv_valid=kv_valid)
            return prefill_attention(q, kh, vh, positions, scale,
                                     kv_valid=kv_valid, n_shards=n_shards)

    def ssm_fn(i, lp, xbc, dt):
        y, tail, state = mamba_mix(lp, cfg, xbc, dt, conv0[i], ssm0[i],
                                   n_valid)
        rec[i] = (state, tail)
        return y

    x, counts = run_layers(params["layers"], cfg, T._embed(params, cfg, tokens),
                           ssm_fn, attn_fn, kv_valid)
    ks = jnp.stack([kv[i][0] for i in range(len(kv))])
    vs = jnp.stack([kv[i][1] for i in range(len(kv))])
    ssm_out = jnp.stack([rec[i][0] for i in range(len(rec))])
    conv_out = jnp.stack([rec[i][1] for i in range(len(rec))])
    out = (T._unembed(params, cfg, x) if unembed else rms_norm(
        x, params["final_norm"], cfg.rms_norm_eps))
    return out, ks, vs, ssm_out, conv_out, counts
