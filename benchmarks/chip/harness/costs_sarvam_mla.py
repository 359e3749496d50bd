"""Bytes a decode step of a ``sarvam_mla`` configuration has to move, from
shapes (the family's cost module, named under ``bench.costs``; see costs.py
for the contract: the configuration file's content in, bytes of ONE decode
step of the whole model out; a metric file's ``bytes`` function is called
with ``(config, tokens, kv_tokens)``).

Every published layer is latent attention (MLA, its decoupled part rotated)
and a feed-forward: a dense SwiGLU in the first ``first_k_dense_replace``
layers, the mixture of experts after.  The expert layer holds
``num_experts`` experts of the router's ``num_experts_published`` (absent:
all), and a token's ``num_experts_per_tok`` draws fall on the held ones with
probability ``k / published`` each.  Weights are int8 (one byte), the router
bf16; the latent cache is one bf16 row ``[kv_lora_rank + qk_rope_head_dim]``
a token a layer, read once (``bench.kv_bytes_per_token``: the 576 columns a
token NEEDS; the pool stores and the kernel moves 640, whole lanes —
``bench.kv_bytes_per_token_stored``).
"""

from __future__ import annotations

from .costs import head_bytes  # noqa: F401  (the same here)
# the banks' traced ops (the grouped matmul and XLA's prefetch slices, the
# shapes from the configuration) and the latent rows a step reads (every
# live token's one row a latent layer, once: ``bench.kv_bytes_per_token``)
# are the other latent-attention family's
from .costs_kimi_linear import held_ffn_ops, latent_read_bytes  # noqa: F401


def _layers(c: dict) -> dict[str, int]:
    # ``num_hidden_layers`` is the published key AND what reducers/
    # trace_hybrid.py overwrites with ``attention_layers``: the same number
    # here, every layer being an attention layer
    n = c["num_hidden_layers"]
    dense = min(c.get("first_k_dense_replace", 0), n)
    return {"mla": n, "dense": dense, "moe": n - dense}


def attention_layers(c: dict) -> int:
    """Layers that run the decode attention kernel: once each a step."""
    return _layers(c)["mla"]


def mla_weight_bytes(c: dict) -> int:
    """int8 ``[W_q | W_kva]``, ``W_kvb`` and ``W_o`` of every layer."""
    h, d, r = c["num_attention_heads"], c["hidden_size"], c["kv_lora_rank"]
    dq = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return _layers(c)["mla"] * (
        d * (h * dq + r + c["qk_rope_head_dim"])
        + r * h * (c["qk_nope_head_dim"] + c["v_head_dim"])
        + h * c["v_head_dim"] * d)


def experts_touched(c: dict, tokens: float) -> float:
    """Expected distinct HELD experts a step of ``tokens`` tokens routes to
    in one layer, under uniform routing: held (1 - (1 - k/E)^tokens)."""
    held = c["num_experts"]
    e = c.get("num_experts_published", held)
    return held * (1.0 - (1.0 - c["num_experts_per_tok"] / e)
                   ** max(tokens, 0.0))


def ffn_weight_bytes(c: dict, tokens: float, kv_tokens: float = 0.0) -> float:
    """int8 bytes of the expert banks a step has to read, all expert
    layers: the three matrices of each held expert its tokens are routed
    to."""
    per = 3 * c["hidden_size"] * c["moe_intermediate_size"]
    return _layers(c)["moe"] * per * experts_touched(c, tokens)


def ffn_dense_bytes(c: dict) -> int:
    """What every step reads of the feed-forwards whatever it routes: the
    dense layers' SwiGLU and the shared experts (int8), the router (bf16)."""
    d, n = c["hidden_size"], _layers(c)
    shared = 3 * d * c["moe_intermediate_size"] * c.get("num_shared_experts", 0)
    router = 2 * d * c.get("num_experts_published", c["num_experts"])
    return (n["dense"] * 3 * d * c["intermediate_size"]
            + n["moe"] * (shared + router))


def resident_weight_bytes(c: dict) -> int:
    """Every weight this chip holds: the layers with ALL the held banks,
    the bf16 embedding and the int8 head."""
    per = 3 * c["hidden_size"] * c["moe_intermediate_size"]
    return (mla_weight_bytes(c) + ffn_dense_bytes(c)
            + _layers(c)["moe"] * c["num_experts"] * per
            + 2 * c["vocab_size"] * c["hidden_size"] + head_bytes(c))


def decode_step_bytes(c: dict, tokens: float, kv_tokens: float) -> float:
    """Least HBM traffic of one decode step: every weight a step needs
    once, the live latent rows once."""
    return (mla_weight_bytes(c) + ffn_weight_bytes(c, tokens)
            + ffn_dense_bytes(c) + head_bytes(c)
            + latent_read_bytes(c, tokens, kv_tokens))
