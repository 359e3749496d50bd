"""The unified functional decoder core (Llama / Gemma-2 / Mixtral).

Pure functions over a parameter pytree — no module framework.  Layer
parameters are stacked along a leading layer axis and the layer loop is a
``lax.scan``, so compile time is O(1) in depth and XLA sees one fused layer
body (the idiomatic TPU pattern; contrast the reference which has no model
code at all and shells out to Ollama, /root/reference/pkg/crowdllama/api.go:108-160).

Weights live in bfloat16; norms/softmax accumulate in fp32.  All shapes are
static: prompt prefill is bucketed, decode is one token per active slot over a
fixed slot-count batch.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from crowdllama_tpu.models.config import ModelConfig
from crowdllama_tpu.ops.quant import (
    qeinsum,
    qragged_dot,
    quantize_kv,
    ride_banks,
)
from crowdllama_tpu.ops.attention import (
    decode_attention,
    decode_attention_q,
    prefill_attention,
    prefill_attention_ctx,
)
from crowdllama_tpu.ops.norms import rms_norm
from crowdllama_tpu.ops.ring import (
    ring_prefill_attention,
    sp_cache_update,
    sp_decode_attention,
)
from crowdllama_tpu.ops.rope import apply_rope, rope_table

Params = dict[str, Any]


# --------------------------------------------------------------------- init

def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.bfloat16) -> Params:
    """Random-init a parameter pytree (layers stacked on axis 0)."""
    if cfg.is_hybrid:  # a list of layers per kind: models/hybrid.py
        from crowdllama_tpu.models import hybrid

        return hybrid.init_params(cfg, key, dtype)
    dh = cfg.resolved_head_dim()
    d, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    h, hkv, nl = cfg.num_heads, cfg.num_kv_heads, cfg.num_layers

    keys = iter(jax.random.split(key, 16))

    def dense(k, *shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)

    layers: Params = {
        "ln1": jnp.ones((nl, d), dtype),
        "ln2": jnp.ones((nl, d), dtype),
        "wq": dense(next(keys), nl, d, h * dh, fan_in=d),
        "wk": dense(next(keys), nl, d, hkv * dh, fan_in=d),
        "wv": dense(next(keys), nl, d, hkv * dh, fan_in=d),
        "wo": dense(next(keys), nl, h * dh, d, fan_in=h * dh),
    }
    if cfg.attn_qkv_bias:  # Qwen2/2.5
        layers["bq"] = jnp.zeros((nl, h * dh), dtype)
        layers["bk"] = jnp.zeros((nl, hkv * dh), dtype)
        layers["bv"] = jnp.zeros((nl, hkv * dh), dtype)
    if cfg.qk_norm:  # Qwen3
        layers["q_norm"] = jnp.ones((nl, dh), dtype)
        layers["k_norm"] = jnp.ones((nl, dh), dtype)
    if cfg.is_moe:
        e = cfg.num_experts
        layers["router"] = dense(next(keys), nl, d, e, fan_in=d)
        layers["w_gate"] = dense(next(keys), nl, e, d, f, fan_in=d)
        layers["w_up"] = dense(next(keys), nl, e, d, f, fan_in=d)
        layers["w_down"] = dense(next(keys), nl, e, f, d, fan_in=f)
    else:
        layers["w_gate"] = dense(next(keys), nl, d, f, fan_in=d)
        layers["w_up"] = dense(next(keys), nl, d, f, fan_in=d)
        layers["w_down"] = dense(next(keys), nl, f, d, fan_in=f)
    if cfg.post_norms:
        layers["post_ln1"] = jnp.ones((nl, d), dtype)
        layers["post_ln2"] = jnp.ones((nl, d), dtype)

    params: Params = {
        "embed": dense(next(keys), v, d, fan_in=d),
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense(next(keys), d, v, fan_in=d)
    return params


def layer_sliding_windows(cfg: ModelConfig) -> jnp.ndarray:
    """Per-layer sliding-window size ([L] int32, 0 = global attention).

    Gemma-2 interleaves sliding (even) and global (odd) layers; Mistral
    windows EVERY layer; other families are all-global.
    """
    if cfg.sliding_window > 0:
        if cfg.family == "gemma2":
            return jnp.asarray(
                [cfg.sliding_window if i % 2 == 0 else 0
                 for i in range(cfg.num_layers)],
                jnp.int32,
            )
        return jnp.full((cfg.num_layers,), cfg.sliding_window, jnp.int32)
    return jnp.zeros((cfg.num_layers,), jnp.int32)


def attn_scale(cfg: ModelConfig) -> float:
    if cfg.query_pre_attn_scalar > 0:
        return cfg.query_pre_attn_scalar ** -0.5
    return cfg.resolved_head_dim() ** -0.5


# ------------------------------------------------------------------ helpers

def _embed(params: Params, cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    x = params["embed"][tokens]
    if cfg.embedding_multiplier > 0:
        x = (x.astype(jnp.float32) * cfg.embedding_multiplier).astype(x.dtype)
    return x


@jax.named_scope("head")
def _unembed(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps,
                 plus_one=cfg.family == "gemma2")
    if cfg.tie_word_embeddings:
        logits = jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                            params["embed"].astype(jnp.float32))
    else:
        logits = qeinsum("...d,dv->...v", x.astype(jnp.float32),
                         params["lm_head"], dtype=jnp.float32)
    if cfg.final_logit_softcap > 0:
        logits = cfg.final_logit_softcap * jnp.tanh(logits / cfg.final_logit_softcap)
    return logits


@jax.named_scope("mlp")
def _mlp(lp: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Dense SwiGLU (Llama) / GeGLU-tanh (Gemma) MLP. x: [..., D]."""
    gate = qeinsum("...d,df->...f", x, lp["w_gate"])
    up = qeinsum("...d,df->...f", x, lp["w_up"])
    act = jax.nn.gelu(gate, approximate=True) if cfg.family == "gemma2" else jax.nn.silu(gate)
    return qeinsum("...f,fd->...d", act * up, lp["w_down"])


@jax.named_scope("router")
def _route_topk(lp: Params, cfg: ModelConfig, x: jnp.ndarray):
    """Router top-k: returns (weights [..., K] fp32 softmaxed, ids [..., K])."""
    router_logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                               lp["router"].astype(jnp.float32))
    topw, topi = jax.lax.top_k(router_logits, cfg.num_experts_per_tok)
    return jax.nn.softmax(topw, axis=-1), topi


def _moe_dense(lp: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Reference-semantics MoE: computes every expert and masks by router
    weight.  Exact, compiler-friendly, ~E/K x wasted FLOPs — kept as the
    parity oracle for `_moe_sorted` and for debugging."""
    topw, topi = _route_topk(lp, cfg, x)
    # Scatter top-k probs back to a dense per-expert weighting [..., E].
    one_hot = jax.nn.one_hot(topi, cfg.num_experts, dtype=jnp.float32)  # [...,K,E]
    weights = jnp.einsum("...ke,...k->...e", one_hot, topw)

    gate = qeinsum("...d,edf->...ef", x, lp["w_gate"])
    up = qeinsum("...d,edf->...ef", x, lp["w_up"])
    act = jax.nn.silu(gate) * up
    per_expert = qeinsum("...ef,efd->...ed", act, lp["w_down"])  # [..., E, D]
    out = jnp.einsum("...ed,...e->...d", per_expert.astype(jnp.float32), weights)
    return out.astype(x.dtype)


def _moe_sorted(lp: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Sorted token-grouping MoE dispatch (grouped GEMM).

    Flatten the top-k (token, expert) pairs, sort by expert, and run the
    expert FFNs as `lax.ragged_dot` grouped matmuls — each token row is
    computed for exactly its K experts instead of all E, an E/K FLOP saving
    (4x for Mixtral E=8 K=2) with no capacity factor and no token dropping:
    results are numerically the per-expert terms of `_moe_dense`, combined
    with the same fp32 router weights.  All shapes are static (NK = N*K);
    only the group boundaries are data-dependent, which XLA's ragged dot
    handles on the MXU.
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    xf = x.reshape(-1, d)
    n = xf.shape[0]
    k = cfg.num_experts_per_tok
    topw, topi = _route_topk(lp, cfg, xf)  # [N, K]

    e_flat = topi.reshape(-1)                      # [NK]
    t_flat = jnp.repeat(jnp.arange(n), k)          # [NK]
    w_flat = topw.reshape(-1)                      # [NK] fp32
    order = jnp.argsort(e_flat)                    # group rows by expert
    xs = jnp.take(xf, t_flat[order], axis=0)       # [NK, D]
    group_sizes = jnp.bincount(e_flat, length=cfg.num_experts)

    gate = qragged_dot(xs, lp["w_gate"], group_sizes)
    up = qragged_dot(xs, lp["w_up"], group_sizes)
    act = jax.nn.silu(gate) * up
    ys = qragged_dot(act.astype(xs.dtype), lp["w_down"],
                     group_sizes)                  # [NK, D]

    contrib = ys.astype(jnp.float32) * w_flat[order][:, None]
    out = jnp.zeros((n, d), jnp.float32).at[t_flat[order]].add(contrib)
    return out.reshape(orig_shape).astype(x.dtype)


@jax.named_scope("moe")
def _moe(lp: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Mixtral top-k MoE.  x: [..., D].  Dispatches per cfg.moe_dispatch."""
    if cfg.moe_dispatch == "dense":
        return _moe_dense(lp, cfg, x)
    return _moe_sorted(lp, cfg, x)


def _layer_params(layers: Params, idx_or_slice) -> Params:
    return jax.tree_util.tree_map(lambda a: a[idx_or_slice], layers)


# ------------------------------------------------------------------ prefill

def scan_prefill_layers(
    layers: Params,          # stacked layer params, leading dim = #layers
    windows: jnp.ndarray,    # per-layer sliding windows for those layers
    cfg: ModelConfig,
    x: jnp.ndarray,          # [B, T, D] embedded input
    positions: jnp.ndarray,  # [B, T]
    kv_valid: jnp.ndarray | None = None,
    sp_mesh=None,
    sp_batch_axis: str | None = None,
    n_shards: int = 1,
    ctx_k: jnp.ndarray | None = None,   # [L, B, Hkv, C, Dh] cached prefix KV
    ctx_v: jnp.ndarray | None = None,
    ctx_valid: jnp.ndarray | None = None,  # [B, C]
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Scan the decoder-layer body over ``layers``; returns (x, ks, vs).

    Factored out of :func:`prefill` so pipeline parallelism can run it over a
    stage's local slice of the layer stack (parallel/pipeline.py).

    With ``ctx_k``/``ctx_v`` the batch is a *suffix* continuing a cached
    prefix (prefix cache): queries attend jointly over the per-layer context
    KV and the causal suffix (ops.attention.prefill_attention_ctx), and the
    returned ks/vs cover the suffix only.  Incompatible with sp_mesh.
    """
    has_ctx = ctx_k is not None
    if has_ctx:
        assert sp_mesh is None, "prefix-context prefill does not compose with sp"
    dh = cfg.resolved_head_dim()
    hkv = cfg.num_kv_heads
    scale = attn_scale(cfg)
    cos, sin = rope_table(cfg.max_context_length, dh, cfg.rope_theta,
                          scaling=cfg.rope_scaling)
    b, t = x.shape[0], x.shape[1]
    layers, bind = ride_banks(layers)  # int8 expert banks ride whole

    def body(x, scanned):
        if has_ctx:
            lp, ck, cv, window = scanned
        else:
            lp, window = scanned
        lp = bind(lp)
        with jax.named_scope("attn_proj"):
            h = rms_norm(x, lp["ln1"], cfg.rms_norm_eps,
                         plus_one=cfg.family == "gemma2")
            q = qeinsum("btd,dk->btk", h, lp["wq"])
            k = qeinsum("btd,dk->btk", h, lp["wk"])
            v = qeinsum("btd,dk->btk", h, lp["wv"])
            if "bq" in lp:  # Qwen2 qkv bias
                q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
            q = q.reshape(b, t, cfg.num_heads, dh)
            k = k.reshape(b, t, hkv, dh)
            v = v.reshape(b, t, hkv, dh)
            if "q_norm" in lp:  # Qwen3 per-head qk-norm
                q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
                k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
            q = apply_rope(q, positions, cos, sin)
            k = apply_rope(k, positions, cos, sin)
            kh = k.transpose(0, 2, 1, 3)  # [B, Hkv, T, Dh] — cache layout
            vh = v.transpose(0, 2, 1, 3)
        with jax.named_scope("attention"):
            if has_ctx:
                attn = prefill_attention_ctx(
                    q, kh, vh, positions, ck, cv, ctx_valid, scale,
                    softcap=cfg.attn_logit_softcap, sliding_window=window,
                    kv_valid=kv_valid)
            elif sp_mesh is not None:
                attn = ring_prefill_attention(
                    q, k, v, positions, scale, sp_mesh,
                    softcap=cfg.attn_logit_softcap, sliding_window=window,
                    kv_valid=kv_valid, dp_axis=sp_batch_axis)
            else:
                attn = prefill_attention(
                    q, kh, vh, positions, scale,
                    softcap=cfg.attn_logit_softcap, sliding_window=window,
                    kv_valid=kv_valid, n_shards=n_shards)
        with jax.named_scope("attn_proj"):
            attn = qeinsum("btk,kd->btd", attn.reshape(b, t, -1), lp["wo"])
            if cfg.post_norms:
                attn = rms_norm(attn, lp["post_ln1"], cfg.rms_norm_eps,
                                plus_one=True)
            x = x + attn
        h = rms_norm(x, lp["ln2"], cfg.rms_norm_eps, plus_one=cfg.family == "gemma2")
        mlp_out = _moe(lp, cfg, h) if cfg.is_moe else _mlp(lp, cfg, h)
        if cfg.post_norms:
            mlp_out = rms_norm(mlp_out, lp["post_ln2"], cfg.rms_norm_eps, plus_one=True)
        x = x + mlp_out
        return x, (kh, vh)

    if has_ctx:
        x, (ks, vs) = jax.lax.scan(body, x, (layers, ctx_k, ctx_v, windows))
    else:
        x, (ks, vs) = jax.lax.scan(body, x, (layers, windows))
    return x, ks, vs  # ks/vs: [L, B, Hkv, T, Dh]


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,     # [B, T] int32, padded
    positions: jnp.ndarray,  # [B, T] int32; padding may repeat last pos
    kv_valid: jnp.ndarray | None = None,  # [B, T] bool; False for padding
    sp_mesh=None,            # Mesh → ring attention over its "sp" axis
    sp_batch_axis: str | None = None,  # mesh axis the batch dim is sharded on
    n_shards: int = 1,       # total mesh devices (gates pallas dispatch)
    ctx_k: jnp.ndarray | None = None,   # [L, B, Hkv, C, Dh] cached prefix KV
    ctx_v: jnp.ndarray | None = None,
    ctx_valid: jnp.ndarray | None = None,  # [B, C]
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Full-prompt forward.  Returns (logits [B,T,V], k, v [L,B,Hkv,T,Dh]).

    KV comes back head-major (sequence contiguous per head) — the engine's
    cache layout (see ops/attention.py module docstring).

    With ``sp_mesh`` the sequence dim is sharded over the mesh's ``sp`` axis
    and attention runs as a ppermute ring (ops/ring.py) — the long-context
    path; T must be divisible by the sp axis size.

    With ``ctx_k``/``ctx_v`` the tokens are a suffix continuing a cached
    prefix (prefix cache); positions must be absolute (prefix length +
    offset) and the returned logits/KV cover the suffix only.
    """
    x = _embed(params, cfg, tokens)
    x, ks, vs = scan_prefill_layers(
        params["layers"], layer_sliding_windows(cfg), cfg, x, positions,
        kv_valid=kv_valid, sp_mesh=sp_mesh, sp_batch_axis=sp_batch_axis,
        ctx_k=ctx_k, ctx_v=ctx_v, ctx_valid=ctx_valid,
        n_shards=n_shards,
    )
    logits = _unembed(params, cfg, x)
    return logits, ks, vs


def hidden_states(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,     # [B, T] int32, padded
    positions: jnp.ndarray,  # [B, T]
    kv_valid: jnp.ndarray | None = None,
    n_shards: int = 1,       # total mesh devices (gates pallas dispatch)
    sp_mesh=None,            # Mesh → ring attention over its "sp" axis
    sp_batch_axis: str | None = None,
) -> jnp.ndarray:
    """Final-norm hidden states [B, T, D] — the embeddings forward.

    Same layer stack as :func:`prefill` but skips the unembed matmul (the
    vocab projection is the single most expensive op at embedding batch
    sizes and its output is unused for /api/embed).  ``n_shards`` must be
    the mesh size at the call site — like prefill, the Pallas kernel cannot
    run over GSPMD-sharded operands.  With ``sp_mesh`` attention runs as
    the same ppermute ring prefill uses (long-context embeddings on sp
    meshes)."""
    x = _embed(params, cfg, tokens)
    x, _, _ = scan_prefill_layers(
        params["layers"], layer_sliding_windows(cfg), cfg, x, positions,
        kv_valid=kv_valid, n_shards=n_shards,
        sp_mesh=sp_mesh, sp_batch_axis=sp_batch_axis,
    )
    return rms_norm(x, params["final_norm"], cfg.rms_norm_eps,
                    plus_one=cfg.family == "gemma2")


# ------------------------------------------------------------------- decode

def decode_layer_body(
    lp: Params,              # ONE layer's params
    cfg: ModelConfig,
    x: jnp.ndarray,          # [B, D] residual stream
    positions: jnp.ndarray,  # [B]
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    attn_fn,                 # (q [B,H,Dh], k [B,Hkv,Dh], v) -> attn [B,H,Dh]
) -> jnp.ndarray:
    """One decoder layer's decode-step math, minus the KV-cache policy.

    The cache write + attention read live behind ``attn_fn`` so every cache
    layout (contiguous slots, paged pool, sp-sharded — engine/runner.py,
    engine/paged.py, ops/ring.py callers) shares ONE source of truth for
    norms/projections/rope/residuals/MLP: a change to layer semantics cannot
    ship in one layout and silently miss another.
    """
    b = x.shape[0]
    dh = cfg.resolved_head_dim()
    # The named scopes are metadata on the ops (their path in a device
    # trace), at the boundaries PERF.md's breakdowns talk in.
    with jax.named_scope("attn_proj"):
        h = rms_norm(x, lp["ln1"], cfg.rms_norm_eps,
                     plus_one=cfg.family == "gemma2")
        q = qeinsum("bd,dk->bk", h, lp["wq"])
        k = qeinsum("bd,dk->bk", h, lp["wk"])
        v = qeinsum("bd,dk->bk", h, lp["wv"])
        if "bq" in lp:  # Qwen2 qkv bias
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = q.reshape(b, cfg.num_heads, dh)
        k = k.reshape(b, cfg.num_kv_heads, dh)
        v = v.reshape(b, cfg.num_kv_heads, dh)
        if "q_norm" in lp:  # Qwen3 per-head qk-norm
            q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
            k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
        q = apply_rope(q[:, None], positions[:, None], cos, sin)[:, 0]
        k = apply_rope(k[:, None], positions[:, None], cos, sin)[:, 0]
    attn = attn_fn(q, k, v)
    with jax.named_scope("attn_proj"):
        attn = qeinsum("bk,kd->bd", attn.reshape(b, -1), lp["wo"])
        if cfg.post_norms:
            attn = rms_norm(attn, lp["post_ln1"], cfg.rms_norm_eps,
                            plus_one=True)
        x = x + attn
    h = rms_norm(x, lp["ln2"], cfg.rms_norm_eps, plus_one=cfg.family == "gemma2")
    mlp_out = _moe(lp, cfg, h) if cfg.is_moe else _mlp(lp, cfg, h)
    if cfg.post_norms:
        mlp_out = rms_norm(mlp_out, lp["post_ln2"], cfg.rms_norm_eps, plus_one=True)
    return x + mlp_out


def scan_decode_layers(
    layers: Params,          # stacked layer params, leading dim = #layers
    windows: jnp.ndarray,
    cfg: ModelConfig,
    x: jnp.ndarray,          # [B, D] embedded last tokens
    positions: jnp.ndarray,  # [B]
    k_cache: jnp.ndarray,    # [#layers, B, Hkv, S, Dh]
    v_cache: jnp.ndarray,
    seq_lens: jnp.ndarray,   # [B]
    sp_mesh=None,
    dp_axis: str | None = "dp",
    n_shards: int = 1,
    k_scale: jnp.ndarray | None = None,  # [#layers, B, Hkv, S] → int8 cache
    v_scale: jnp.ndarray | None = None,
):
    """Scan the decode-layer body over ``layers``; returns (x, kc, vc) —
    plus (k_scale, v_scale) when the cache is int8-quantized.

    Factored out of :func:`decode_step` for pipeline parallelism
    (parallel/pipeline.py runs it over a stage's local layers + cache slice).

    With ``k_scale``/``v_scale`` the caches are int8 with per-(position,
    kv-head) scales: new KV entries are quantized on write and attention
    runs over the int8 cache (ops.attention.decode_attention_q), halving
    the cache bytes streamed per step.  Incompatible with sp_mesh.
    """
    quantized = k_scale is not None
    if quantized:
        assert sp_mesh is None, "int8 KV cache does not compose with sp yet"
    dh = cfg.resolved_head_dim()
    scale = attn_scale(cfg)
    cos, sin = rope_table(cfg.max_context_length, dh, cfg.rope_theta,
                          scaling=cfg.rope_scaling)
    b = x.shape[0]
    slot_idx = jnp.arange(b)
    layers, bind = ride_banks(layers)  # int8 expert banks ride whole

    def body(x, scanned):
        if quantized:
            lp, kc, vc, ks, vs, window = scanned
        else:
            lp, kc, vc, window = scanned  # kc/vc: [B, Hkv, S, Dh]
            ks = vs = None
        lp = bind(lp)
        cache = {}

        def attn_fn(q, k, v):
            if quantized:
                kq, k_sc = quantize_kv(k)  # [B,Hkv,Dh] int8, [B,Hkv]
                vq, v_sc = quantize_kv(v)
                # Mixed basic/advanced indexing: the broadcast [B] index
                # pair fronts the result, so kc[slots, :, positions] is
                # [B,Hkv,Dh] (and ks[slots, :, positions] is [B,Hkv]).
                kc2 = kc.at[slot_idx, :, positions].set(kq)
                vc2 = vc.at[slot_idx, :, positions].set(vq)
                ks2 = ks.at[slot_idx, :, positions].set(k_sc.astype(ks.dtype))
                vs2 = vs.at[slot_idx, :, positions].set(v_sc.astype(vs.dtype))
                attn = decode_attention_q(q, kc2, ks2, vc2, vs2, seq_lens,
                                          scale,
                                          softcap=cfg.attn_logit_softcap,
                                          sliding_window=window)
                cache["ks"], cache["vs"] = ks2, vs2
            elif sp_mesh is not None:
                kc2, vc2 = sp_cache_update(k, v, positions, kc, vc, sp_mesh,
                                           dp_axis=dp_axis)
                attn = sp_decode_attention(q, kc2, vc2, seq_lens, scale,
                                           sp_mesh,
                                           softcap=cfg.attn_logit_softcap,
                                           sliding_window=window,
                                           dp_axis=dp_axis)
            else:
                kc2 = kc.at[slot_idx, :, positions].set(k)
                vc2 = vc.at[slot_idx, :, positions].set(v)
                attn = decode_attention(q, kc2, vc2, seq_lens, scale,
                                        softcap=cfg.attn_logit_softcap,
                                        sliding_window=window,
                                        n_shards=n_shards)
            cache["kc"], cache["vc"] = kc2, vc2
            return attn

        x = decode_layer_body(lp, cfg, x, positions, cos, sin, attn_fn)
        if quantized:
            return x, (cache["kc"], cache["vc"], cache["ks"], cache["vs"])
        return x, (cache["kc"], cache["vc"])

    if quantized:
        x, (k_cache, v_cache, k_scale, v_scale) = jax.lax.scan(
            body, x, (layers, k_cache, v_cache, k_scale, v_scale, windows)
        )
        return x, k_cache, v_cache, k_scale, v_scale
    x, (k_cache, v_cache) = jax.lax.scan(
        body, x, (layers, k_cache, v_cache, windows)
    )
    return x, k_cache, v_cache


def decode_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,     # [B] int32 — last sampled token per slot
    positions: jnp.ndarray,  # [B] int32 — position of this token
    k_cache: jnp.ndarray,    # [L, B, Hkv, S, Dh]
    v_cache: jnp.ndarray,    # [L, B, Hkv, S, Dh]
    seq_lens: jnp.ndarray,   # [B] valid lengths AFTER appending this token
    sp_mesh=None,            # Mesh → S-sharded cache + distributed decode
    dp_axis: str | None = "dp",
    n_shards: int = 1,       # total mesh devices (gates pallas dispatch)
    k_scale: jnp.ndarray | None = None,  # [L, B, Hkv, S] → int8 KV cache
    v_scale: jnp.ndarray | None = None,
):
    """One token per slot.  Returns (logits [B,V], k_cache, v_cache), plus
    (k_scale, v_scale) when the cache is int8 (scales passed in).

    With ``sp_mesh`` the KV cache's sequence dim is sharded over ``sp``: the
    new token's KV is written shard-locally and attention is flash-decoding
    merged with pmax/psum (ops/ring.py).
    """
    x = _embed(params, cfg, tokens)  # [B, D]
    if k_scale is not None:
        x, k_cache, v_cache, k_scale, v_scale = scan_decode_layers(
            params["layers"], layer_sliding_windows(cfg), cfg, x, positions,
            k_cache, v_cache, seq_lens, sp_mesh=sp_mesh, dp_axis=dp_axis,
            n_shards=n_shards, k_scale=k_scale, v_scale=v_scale,
        )
        logits = _unembed(params, cfg, x)
        return logits, k_cache, v_cache, k_scale, v_scale
    x, k_cache, v_cache = scan_decode_layers(
        params["layers"], layer_sliding_windows(cfg), cfg, x, positions,
        k_cache, v_cache, seq_lens, sp_mesh=sp_mesh, dp_axis=dp_axis,
        n_shards=n_shards,
    )
    logits = _unembed(params, cfg, x)
    return logits, k_cache, v_cache
