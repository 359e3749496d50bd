"""HF checkpoint ↔ native pytree conversion.

Maps HuggingFace state-dict tensors (Llama / Mixtral / Gemma-2) onto the
stacked-layer pytree used by models.transformer.  Used by the engine's
safetensors loader for offline checkpoints and by the numeric parity tests
(logits vs the torch reference implementations) — the engine-level test the
reference lacks entirely (SURVEY §4 "TPU translation").

All projection matrices are transposed: HF stores [out, in]; we store
[in, out] so forward einsums are x @ W.
"""

from __future__ import annotations

from typing import Callable, Mapping

import jax.numpy as jnp
import numpy as np

from crowdllama_tpu.models.config import ModelConfig

TensorSource = Callable[[str], np.ndarray]


def _t(get: TensorSource, name: str) -> np.ndarray:
    return np.asarray(get(name)).T


def _raw(get: TensorSource, name: str) -> np.ndarray:
    return np.asarray(get(name))


def params_from_hf(cfg: ModelConfig, get: TensorSource, dtype=jnp.bfloat16) -> dict:
    """Build the native param pytree by pulling tensors from ``get(name)``.

    ``get`` abstracts the source: an in-memory torch state_dict (tests) or a
    lazy safetensors reader (engine.weights).
    """
    nl = cfg.num_layers

    def stack(fmt: str, transpose: bool = True) -> jnp.ndarray:
        fn = _t if transpose else _raw
        return jnp.asarray(
            np.stack([fn(get, fmt.format(i=i)) for i in range(nl)]), dtype
        )

    layers: dict = {
        "wq": stack("model.layers.{i}.self_attn.q_proj.weight"),
        "wk": stack("model.layers.{i}.self_attn.k_proj.weight"),
        "wv": stack("model.layers.{i}.self_attn.v_proj.weight"),
        "wo": stack("model.layers.{i}.self_attn.o_proj.weight"),
        "ln1": stack("model.layers.{i}.input_layernorm.weight", transpose=False),
    }
    if cfg.attn_qkv_bias:  # Qwen2/2.5
        layers["bq"] = stack("model.layers.{i}.self_attn.q_proj.bias", transpose=False)
        layers["bk"] = stack("model.layers.{i}.self_attn.k_proj.bias", transpose=False)
        layers["bv"] = stack("model.layers.{i}.self_attn.v_proj.bias", transpose=False)
    if cfg.qk_norm:  # Qwen3
        layers["q_norm"] = stack("model.layers.{i}.self_attn.q_norm.weight", transpose=False)
        layers["k_norm"] = stack("model.layers.{i}.self_attn.k_norm.weight", transpose=False)

    if cfg.family == "gemma2":
        layers["post_ln1"] = stack(
            "model.layers.{i}.post_attention_layernorm.weight", transpose=False)
        layers["ln2"] = stack(
            "model.layers.{i}.pre_feedforward_layernorm.weight", transpose=False)
        layers["post_ln2"] = stack(
            "model.layers.{i}.post_feedforward_layernorm.weight", transpose=False)
    else:
        layers["ln2"] = stack(
            "model.layers.{i}.post_attention_layernorm.weight", transpose=False)

    if cfg.is_moe:
        e = cfg.num_experts
        layers["router"] = stack("model.layers.{i}.block_sparse_moe.gate.weight")

        def stack_experts(which: str) -> jnp.ndarray:
            return jnp.asarray(
                np.stack([
                    np.stack([
                        _t(get, f"model.layers.{i}.block_sparse_moe.experts.{x}.{which}.weight")
                        for x in range(e)
                    ])
                    for i in range(nl)
                ]),
                dtype,
            )

        layers["w_gate"] = stack_experts("w1")
        layers["w_down"] = stack_experts("w2")
        layers["w_up"] = stack_experts("w3")
    else:
        layers["w_gate"] = stack("model.layers.{i}.mlp.gate_proj.weight")
        layers["w_up"] = stack("model.layers.{i}.mlp.up_proj.weight")
        layers["w_down"] = stack("model.layers.{i}.mlp.down_proj.weight")

    params: dict = {
        "embed": jnp.asarray(_raw(get, "model.embed_tokens.weight"), dtype),
        "layers": layers,
        "final_norm": jnp.asarray(_raw(get, "model.norm.weight"), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(_t(get, "lm_head.weight"), dtype)
    return params


def rotary_halves_from_interleaved(w, heads: int, head_dim: int,
                                   rope_dim: int):
    """``w [..., heads * head_dim]`` — a projection (or its output) whose
    every head ends in ``rope_dim`` rotary entries in the PUBLISHED order,
    rotated as interleaved pairs ``(2i, 2i+1)`` — with those entries put
    ``[evens | odds]``, the order in which ``ops/rope.py`` ``rotate_half``
    rotates entry ``i`` against entry ``i + rope_dim/2`` by the same angle.
    A dot product of two vectors permuted alike is the one it was, so a
    latent-attention family's ``W_q`` (``heads`` heads of ``qk_nope +
    qk_rope``) and ``W_kva`` (one head of ``kv_lora_rank + qk_rope``)
    converted so serve the published equations (models/hybrid.py
    ``mla_body``; tests/test_sarvam_mla.py holds the two forms together)."""
    lead = head_dim - rope_dim
    within = np.concatenate([np.arange(lead), lead + np.arange(0, rope_dim, 2),
                             lead + np.arange(1, rope_dim, 2)])
    cols = (np.arange(heads)[:, None] * head_dim + within[None, :]).reshape(-1)
    return w[..., cols]


def state_dict_source(state_dict: Mapping[str, "object"]) -> TensorSource:
    """TensorSource over a torch state_dict (detaches to numpy)."""

    def get(name: str) -> np.ndarray:
        t = state_dict[name]
        return t.detach().to("cpu").float().numpy()  # type: ignore[attr-defined]

    return get
