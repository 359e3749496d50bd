"""What the algorithm has to move, computed from shapes: the yardstick's
own arithmetic for roofline and bandwidth shares, and the table of peaks.

All functions take the configuration file's content (the public
``config.json`` keys plus the ``bench`` group) and return bytes for ONE
decode step of the whole model.  A function a metric file names under
``bytes`` (as ``module.function`` inside harness/) is called with
``(config, tokens, kv_tokens)``: the live tokens of a step and the KV
tokens it reads.
"""

from __future__ import annotations

import json
from pathlib import Path


def peaks(device_kind: str) -> dict:
    """The published peaks of a device; an unknown device is an error."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device kind {device_kind!r} is not in peaks.json "
            f"({[k for k in table if not k.startswith('_')]}): add its "
            f"published peaks with their source, do not default")
    return table[device_kind]


def _dims(c: dict) -> tuple[int, int, int, int, int, int]:
    h = c["num_attention_heads"]
    dh = c.get("head_dim") or c["hidden_size"] // h
    return (c["hidden_size"], c["intermediate_size"], h,
            c.get("num_key_value_heads", h), dh, c["num_hidden_layers"])


def attn_weight_bytes(c: dict) -> int:
    """int8 bytes of q, k, v, o projections, all layers."""
    d, _, h, hkv, dh, nl = _dims(c)
    return nl * (2 * d * h * dh + 2 * d * hkv * dh)


def experts_touched(c: dict, tokens: float) -> float:
    """Expected distinct experts a step of ``tokens`` tokens routes to in
    one layer, under uniform routing: E (1 - (1 - k/E)^tokens)."""
    e, k = c["num_local_experts"], c["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** max(tokens, 0.0))


def ffn_weight_bytes(c: dict, tokens: float, kv_tokens: float = 0.0) -> float:
    """int8 bytes of the feed-forward weights a step has to read, all
    layers: the three SwiGLU matrices, for a mixture of experts those of
    the experts the step's tokens are routed to."""
    d, f, *_, nl = _dims(c)
    per = 3 * d * f
    if c.get("num_local_experts"):
        return nl * per * experts_touched(c, tokens)
    return nl * per


def head_bytes(c: dict) -> int:
    """int8 output head (the embedding rows read are negligible)."""
    return 0 if c.get("tie_word_embeddings") else (
        c["hidden_size"] * c["vocab_size"])


def kv_read_bytes(c: dict, tokens: float, kv_tokens: float) -> float:
    """KV bytes attention reads in a step: every live token's keys and
    values, all layers, in the cache's stated type."""
    return kv_tokens * c["bench"]["kv_bytes_per_token"]


def decode_step_bytes(c: dict, tokens: float, kv_tokens: float) -> float:
    """Least HBM traffic of one decode step: every weight once, the live
    KV once.  Activations and the KV written are negligible beside them."""
    return (attn_weight_bytes(c) + ffn_weight_bytes(c, tokens)
            + head_bytes(c) + kv_read_bytes(c, tokens, kv_tokens))
