"""Bytes a decode step of a ``nemotron_h`` configuration has to move, from
shapes (the family's cost module, named under ``bench.costs``; see costs.py
for the contract: the configuration file's content in, bytes of ONE decode
step of the whole model out; a metric file's ``bytes`` function is called
with ``(config, tokens, kv_tokens)``).

Layers are of three kinds (``hybrid_override_pattern``: ``M`` Mamba-2, ``E``
latent mixture of experts, ``*`` attention), so nothing here multiplies by
``num_hidden_layers``.  The expert layer holds ``n_routed_experts`` experts
of the router's ``n_routed_experts_published`` (absent: all), and a token's
``num_experts_per_tok`` draws fall on the held ones with probability
``k / published`` each.  Weights are int8 (one byte), the router bf16; the
recurrent state is read and written once for each live slot: the
state-space state in float32, the convolution tail in bf16.
"""

from __future__ import annotations

from .costs import head_bytes, kv_read_bytes  # noqa: F401  (the same here)


def _kinds(c: dict) -> dict[str, int]:
    p = c["hybrid_override_pattern"]
    return {k: p.count(k) for k in "ME*"}


def attention_layers(c: dict) -> int:
    """Layers that run the decode attention kernel: once each a step."""
    return _kinds(c)["*"]


def _mamba_dims(c: dict) -> tuple[int, int, int]:
    """(d_inner, conv_dim, the input projection's width [z | xBC | dt])."""
    d_inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    conv_dim = d_inner + 2 * c["n_groups"] * c["ssm_state_size"]
    return d_inner, conv_dim, d_inner + conv_dim + c["mamba_num_heads"]


def mamba_weight_bytes(c: dict) -> int:
    """int8 in and out projections of the Mamba layers."""
    d_inner, _, in_proj = _mamba_dims(c)
    return _kinds(c)["M"] * c["hidden_size"] * (in_proj + d_inner)


def attn_weight_bytes(c: dict) -> int:
    h, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    dh = c.get("head_dim") or c["hidden_size"] // h
    return _kinds(c)["*"] * c["hidden_size"] * dh * (2 * h + 2 * hkv)


def experts_touched(c: dict, tokens: float) -> float:
    """Expected distinct HELD experts a step of ``tokens`` tokens routes to
    in one layer, under uniform routing: held (1 - (1 - k/E)^tokens)."""
    held = c["n_routed_experts"]
    e = c.get("n_routed_experts_published", held)
    return held * (1.0 - (1.0 - c["num_experts_per_tok"] / e)
                   ** max(tokens, 0.0))


def ffn_weight_bytes(c: dict, tokens: float, kv_tokens: float = 0.0) -> float:
    """int8 bytes of the expert banks a step has to read, all expert
    layers: the two matrices of each held expert its tokens are routed to."""
    per = 2 * c["moe_latent_size"] * c["moe_intermediate_size"]
    return _kinds(c)["E"] * per * experts_touched(c, tokens)


def moe_dense_bytes(c: dict) -> int:
    """What every step reads of an expert layer whatever it routes: shared
    expert and latent projections (int8), router (bf16)."""
    d = c["hidden_size"]
    per = (2 * d * c["moe_shared_expert_intermediate_size"]
           + 2 * d * c["moe_latent_size"]
           + 2 * d * c.get("n_routed_experts_published",
                           c["n_routed_experts"]))
    return _kinds(c)["E"] * per


def ssm_state_bytes(c: dict, tokens: float, kv_tokens: float = 0.0) -> float:
    """The recurrent state of the live slots, read and written once: float32
    state-space state [heads, head dim, state], bf16 tail [conv_dim, K-1]."""
    _, conv_dim, _ = _mamba_dims(c)
    state = (c["mamba_num_heads"] * c["mamba_head_dim"]
             * c["ssm_state_size"] * 4)
    tail = conv_dim * (c["conv_kernel"] - 1) * 2
    return _kinds(c)["M"] * tokens * 2 * (state + tail)


def ssm_state_ops(c: dict) -> str:
    """The traced ops that touch the state-space state (a metric file's
    ``op_from``): a Pallas kernel named ``ssm_...``, and every XLA fusion
    that takes the state as an operand — the in-place update, which writes
    it too, and the second read for ``y = S C`` — with the state's shape
    taken from the configuration: [slots, heads, head dim, state], with or
    without the leading axis of the Mamba layers."""
    shape = (f"({_kinds(c)['M']},)?{c['bench']['slots']},"
             f"{c['mamba_num_heads']},{c['mamba_head_dim']},"
             f"{c['ssm_state_size']}")
    return rf"^%ssm_|^%\S*fusion\S* = .* fusion\(.*f32\[{shape}\]"


def decode_step_bytes(c: dict, tokens: float, kv_tokens: float) -> float:
    """Least HBM traffic of one decode step: every weight a step needs
    once, the live KV once, the live slots' recurrent state in and out."""
    return (mamba_weight_bytes(c) + attn_weight_bytes(c)
            + ffn_weight_bytes(c, tokens) + moe_dense_bytes(c)
            + head_bytes(c) + kv_read_bytes(c, tokens, kv_tokens)
            + ssm_state_bytes(c, tokens))
