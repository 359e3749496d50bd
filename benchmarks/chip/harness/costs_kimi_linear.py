"""Bytes a decode step of a ``kimi_linear`` configuration has to move, from
shapes (the family's cost module, named under ``bench.costs``; see costs.py
for the contract: the configuration file's content in, bytes of ONE decode
step of the whole model out; a metric file's ``bytes`` function is called
with ``(config, tokens, kv_tokens)``).

A published layer is a mixer and a feed-forward: KDA where
``linear_attn_config.kda_layers`` names it, latent attention (MLA) where
``full_attn_layers`` does; a dense SwiGLU in the first
``first_k_dense_replace`` layers, the mixture of experts after.  The expert
layer holds ``num_experts`` experts of the router's
``num_experts_published`` (absent: all), and a token's
``num_experts_per_token`` draws fall on the held ones with probability ``k /
published`` each.  Weights are int8 (one byte), the router bf16; the KDA
state (a float32 ``[dk, dv]`` matrix a head) and the three convolutions'
bf16 tails are read and written once for each live slot; the latent cache
is one bf16 row ``[kv_lora_rank + qk_rope_head_dim]`` a token a MLA layer,
read once (``bench.kv_bytes_per_token``).
"""

from __future__ import annotations

from .costs import head_bytes, kv_read_bytes  # noqa: F401  (the same here)


def _layers(c: dict) -> dict[str, int]:
    # not ``num_hidden_layers``: reducers/trace_hybrid.py hands the readers
    # a configuration in which that is the count of attention layers
    lin = c["linear_attn_config"]
    kda, mla = len(lin["kda_layers"]), len(lin["full_attn_layers"])
    dense = min(c.get("first_k_dense_replace", 0), kda + mla)
    return {"kda": kda, "mla": mla, "dense": dense, "moe": kda + mla - dense}


def attention_layers(c: dict) -> int:
    """Layers that run the decode attention kernel: once each a step."""
    return _layers(c)["mla"]


def _kda_dims(c: dict) -> tuple[int, int, int]:
    """(heads, head dim, heads x head dim)."""
    lin = c["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["num_heads"] * lin["head_dim"]


def kda_weight_bytes(c: dict) -> int:
    """int8 projections of the KDA layers: q, k, v and out, the two
    low-rank gates (rank = head dim), the step size."""
    h, dk, hk = _kda_dims(c)
    d = c["hidden_size"]
    return _layers(c)["kda"] * (4 * d * hk + 2 * (d * dk + dk * hk) + d * h)


def mla_weight_bytes(c: dict) -> int:
    h, d, r = c["num_attention_heads"], c["hidden_size"], c["kv_lora_rank"]
    dq = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return _layers(c)["mla"] * (
        d * h * dq + d * (r + c["qk_rope_head_dim"])
        + r * h * (c["qk_nope_head_dim"] + c["v_head_dim"])
        + h * c["v_head_dim"] * d)


def experts_touched(c: dict, tokens: float) -> float:
    """Expected distinct HELD experts a step of ``tokens`` tokens routes to
    in one layer, under uniform routing: held (1 - (1 - k/E)^tokens)."""
    held = c["num_experts"]
    e = c.get("num_experts_published", held)
    return held * (1.0 - (1.0 - c["num_experts_per_token"] / e)
                   ** max(tokens, 0.0))


def ffn_weight_bytes(c: dict, tokens: float, kv_tokens: float = 0.0) -> float:
    """int8 bytes of the expert banks a step has to read, all expert
    layers: the three matrices of each held expert its tokens are routed
    to."""
    per = 3 * c["hidden_size"] * c["moe_intermediate_size"]
    return _layers(c)["moe"] * per * experts_touched(c, tokens)


def held_ffn_ops(c: dict) -> str:
    """The traced ops that move the held experts' banks (a metric file's
    ``op_from``): the grouped-matmul kernel, ``moe_...``, and the asynchronous
    slices by which XLA fetches part of a bank ahead of the kernel's call
    (``slice-done = s8[8,1024,2304]``, 2.22 ms of an 18.97 ms step: my chip
    run, PR 37) — int8, any number of experts, an expert's ``[d, I]`` or
    ``[I, d]`` from the configuration.  Without them the banks' bytes were
    divided by part of the time they take."""
    d, i = c["hidden_size"], c["moe_intermediate_size"]
    return (rf"^%moe_|^%slice-(start|done)\S* = "
            rf"s8\[\d+,({d},{i}|{i},{d})\]")


def ffn_dense_bytes(c: dict) -> int:
    """What every step reads of the feed-forwards whatever it routes: the
    dense layers' SwiGLU and the shared experts (int8), the router (bf16)."""
    d, n = c["hidden_size"], _layers(c)
    shared = 3 * d * c["moe_intermediate_size"] * c.get("num_shared_experts", 0)
    router = 2 * d * c.get("num_experts_published", c["num_experts"])
    return (n["dense"] * 3 * d * c["intermediate_size"]
            + n["moe"] * (shared + router))


def kda_state_bytes(c: dict, tokens: float, kv_tokens: float = 0.0) -> float:
    """The recurrent state of the live slots, read and written once: the
    float32 matrix [dk, dv] a head, the bf16 tails [3 H dk, K-1]."""
    h, dk, hk = _kda_dims(c)
    tail = 3 * hk * (c["linear_attn_config"]["short_conv_kernel_size"] - 1) * 2
    return _layers(c)["kda"] * tokens * 2 * (h * dk * dk * 4 + tail)


def kda_state_ops(c: dict) -> str:
    """The traced ops that touch the KDA state (a metric file's
    ``op_from``): a Pallas kernel named ``kda_...``, and every XLA fusion
    that takes the state as an operand, with the state's shape taken from
    the configuration: [slots, heads, dk, dv], with or without the leading
    axis of the KDA layers."""
    h, dk, _ = _kda_dims(c)
    shape = f"({_layers(c)['kda']},)?{c['bench']['slots']},{h},{dk},{dk}"
    return rf"^%kda_|^%\S*fusion\S* = .* fusion\(.*f32\[{shape}\]"


def latent_read_bytes(c: dict, tokens: float, kv_tokens: float) -> float:
    """The latent rows the decode attention reads in a step: every live
    token's one row a MLA layer, once (there is no V to read beside it)."""
    return kv_read_bytes(c, tokens, kv_tokens)


def decode_step_bytes(c: dict, tokens: float, kv_tokens: float) -> float:
    """Least HBM traffic of one decode step: every weight a step needs
    once, the live latent rows once, the live slots' recurrent state in
    and out."""
    return (kda_weight_bytes(c) + mla_weight_bytes(c)
            + ffn_weight_bytes(c, tokens) + ffn_dense_bytes(c)
            + head_bytes(c) + latent_read_bytes(c, tokens, kv_tokens)
            + kda_state_bytes(c, tokens))
