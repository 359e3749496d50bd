"""What the algorithm has to move, computed from shapes: the yardstick's
own arithmetic for roofline and bandwidth shares, and the table of peaks.

All functions take the configuration file's content (the public
``config.json`` keys plus the ``bench`` group) and return bytes for ONE
decode step of the whole model.  A function a metric file names under
``bytes`` (as ``module.function`` inside harness/) is called with
``(config, tokens, kv_tokens)``: the live tokens of a step and the KV
tokens it reads.

This module describes the families it was written for: dense GQA + SwiGLU
(Mistral) and Mixtral's ``num_local_experts`` of the same width.  A
configuration of another shape names a module of its own under
``bench.costs`` (a new ``harness/<module>.py`` with ``decode_step_bytes``
and whichever of the functions below differ for it); :func:`function`
looks there first.  A configuration this module would misread is an error
(:func:`refuse_misread`), never a dense reading.
"""

from __future__ import annotations

import importlib
import json
import re
from pathlib import Path

HARNESS = Path(__file__).resolve().parent

#: keys of a public config.json that say the feed-forward or the state is
#: not what this module counts: experts under another name or of a width of
#: their own, shared experts, leading dense layers, recurrent state
_MISREAD = re.compile(
    r"expert|^moe_|^mamba_|^ssm_|^linear_|lora_rank$|^shared_intermediate|"
    r"dense_layers|^first_k_dense|^mlp_only_layers")
_KNOWN = {"num_local_experts", "num_experts_per_tok"}
_ATTENTION_KINDS = {"full_attention", "sliding_attention"}


class CostsMisread(ValueError):
    """costs.py has no bytes for this configuration's shape."""


def refuse_misread(c: dict) -> None:
    """Raise where counting ``c`` by this module's formulas would be wrong
    without an error: it would read the model as dense (or as Mixtral)."""
    odd = sorted(k for k, v in c.items() if k != "bench" and k not in _KNOWN
                 and _MISREAD.search(k) and v not in (None, 0, False, [], ""))
    kinds = sorted(set(c.get("layer_types") or []) - _ATTENTION_KINDS)
    if kinds:
        odd.append(f"layer_types {kinds}")
    if odd:
        raise CostsMisread(
            f"harness/costs.py knows a dense SwiGLU model and Mixtral's "
            f"num_local_experts, and would misread {', '.join(odd)} of "
            f"configuration {c.get('bench', {}).get('name')!r}: add a cost "
            f"module benchmarks/chip/harness/<module>.py with "
            f"decode_step_bytes(config, tokens, kv_tokens) and name it "
            f"under bench.costs in the configuration file")


def module_for(c: dict):
    """The cost module that describes configuration ``c``: the one it names
    under ``bench.costs`` (inside harness/), this one where it names none."""
    name = c.get("bench", {}).get("costs", "costs")
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", name) or not (
            HARNESS / f"{name}.py").exists():
        raise FileNotFoundError(
            f"configuration {c.get('bench', {}).get('name')!r} names the "
            f"cost module {name!r}: no file {HARNESS / (name + '.py')}")
    return importlib.import_module(f"harness.{name}")


def function(c: dict, dotted: str):
    """The function a metric file names as ``module.function``: taken from
    the configuration's own cost module where that has one of that name,
    else from the module named."""
    module, _, fn = dotted.rpartition(".")
    own = getattr(module_for(c), fn, None)
    return own or getattr(importlib.import_module(f"harness.{module}"), fn)


def peaks(device_kind: str) -> dict:
    """The published peaks of a device; an unknown device is an error."""
    table = json.loads((HARNESS / "peaks.json").read_text())
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
    refuse_misread(c)
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
    KV once.  Activations and the KV written are negligible beside them.
    (ffn_weight_bytes refuses a configuration this module would misread.)"""
    return (attn_weight_bytes(c) + ffn_weight_bytes(c, tokens)
            + head_bytes(c) + kv_read_bytes(c, tokens, kv_tokens))
