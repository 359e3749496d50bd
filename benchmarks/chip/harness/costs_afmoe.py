"""Bytes a decode step of an ``afmoe`` configuration has to move, from
shapes (the family's cost module, named under ``bench.costs``; see costs.py
for the contract: the configuration file's content in, bytes of ONE decode
step of the whole model out; a metric file's ``bytes`` function is called
with ``(config, tokens, kv_tokens)``).

A published layer is a gated attention and a feed-forward:
``sliding_attention`` or ``full_attention`` by ``layer_types``; a dense
SwiGLU in the first ``num_dense_layers`` layers, the mixture of experts
after.  The expert layer holds ``num_experts`` experts of the router's
``num_experts_published`` (absent: all), and a token's
``num_experts_per_tok`` draws fall on the held ones with probability ``k /
published`` each.  Weights are int8 (one byte), the router bf16.  KV is
bf16, ``2 x kv heads x head_dim x 2`` bytes a token a layer — and the two
kinds of layer read different amounts of it: a full layer every live token
of the context, a window layer the pages its window reaches, at most
``sliding_window + page`` tokens however long the context is (the program
keeps no more: engine/hybrid.py).
"""

from __future__ import annotations

from .costs import head_bytes  # noqa: F401  (the same here)
# the held banks' traced ops: the grouped-matmul kernel and XLA's slices of
# an int8 bank ``[n, d, I]`` / ``[n, I, d]``, shapes from the configuration
from .costs_kimi_linear import held_ffn_ops  # noqa: F401

#: tokens a KV page holds (the worker's default, which the configurations
#: of this family serve with; the rehearsal's smaller page only loosens the cap)
PAGE = 128


def _layers(c: dict) -> dict[str, int]:
    # not ``num_hidden_layers``: reducers/trace_hybrid.py hands the readers
    # a configuration in which that is the count of attention layers
    kinds = c["layer_types"]
    window = sum(k == "sliding_attention" for k in kinds)
    dense = min(c.get("num_dense_layers", 0), len(kinds))
    return {"window": window, "full": len(kinds) - window, "dense": dense,
            "moe": len(kinds) - dense}


def attention_layers(c: dict) -> int:
    """Layers that run a decode attention kernel, once each a step: ALL of
    them — the window layers' kernel is traced as
    ``paged_decode_attention_window``, which the step counter
    ``^%paged_decode_attention`` matches as it does the full layers'."""
    n = _layers(c)
    return n["window"] + n["full"]


def kv_token_bytes(c: dict) -> int:
    """bf16 K and V of one token in one layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * 2


def attn_weight_bytes(c: dict) -> int:
    """int8 q, k, v, o and gate projections, all layers."""
    d, hd = c["hidden_size"], c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return attention_layers(c) * (3 * d * hd + 2 * d * kv)


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


def window_read_bytes(c: dict, tokens: float, kv_tokens: float) -> float:
    """KV the window layers' kernel reads in a step: of each live slot the
    tokens its window reaches, whole pages — the context, capped at
    ``sliding_window + page``."""
    context = kv_tokens / tokens if tokens else 0.0
    reach = min(context, c["sliding_window"] + PAGE)
    return tokens * reach * kv_token_bytes(c) * _layers(c)["window"]


def full_read_bytes(c: dict, tokens: float, kv_tokens: float) -> float:
    """KV the full layers' kernel reads in a step: every live token."""
    return kv_tokens * kv_token_bytes(c) * _layers(c)["full"]


def kv_read_bytes(c: dict, tokens: float, kv_tokens: float) -> float:
    return (window_read_bytes(c, tokens, kv_tokens)
            + full_read_bytes(c, tokens, kv_tokens))


def decode_step_bytes(c: dict, tokens: float, kv_tokens: float) -> float:
    """Least HBM traffic of one decode step: every weight a step needs
    once, and of the live KV what each kind of layer reads."""
    return (attn_weight_bytes(c) + ffn_weight_bytes(c, tokens)
            + ffn_dense_bytes(c) + head_bytes(c)
            + kv_read_bytes(c, tokens, kv_tokens))
