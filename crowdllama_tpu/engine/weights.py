"""Weight loading: local HF safetensors checkpoints or random init.

Zero-egress by design — nothing is downloaded.  A ``model_path`` pointing at
a HuggingFace-layout directory (config.json + *.safetensors) is converted
into the native stacked-layer pytree via models.convert; an empty path yields
random weights (benchmarks measure compute, not text quality, cf. the
reference's fabricated advertisement numbers, peer.go:320-334).
"""

from __future__ import annotations

import json
import logging
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from crowdllama_tpu.models import transformer as T
from crowdllama_tpu.models.config import ModelConfig
from crowdllama_tpu.models.convert import params_from_hf

log = logging.getLogger("crowdllama.engine.weights")


def _checkpoint_kind(model_path: str) -> str:
    """"native" | "safetensors" | "" (no checkpoint: random init)."""
    if not model_path:
        return ""
    path = Path(model_path).expanduser()
    if is_native_checkpoint(path):
        return "native"
    if path.is_dir() and list(path.glob("*.safetensors")):
        return "safetensors"
    log.warning("model_path %s has no safetensors; using random init", path)
    return ""


def load_or_init_params(cfg: ModelConfig, model_path: str = "",
                        dtype=jnp.bfloat16, seed: int = 0) -> dict:
    kind = _checkpoint_kind(model_path)
    if kind == "native":
        log.info("loading native checkpoint from %s", model_path)
        return load_native_params(cfg, Path(model_path).expanduser(),
                                  dtype=dtype)
    if kind == "safetensors":
        log.info("loading weights from %s", model_path)
        return load_safetensors_params(cfg, Path(model_path).expanduser(),
                                       dtype=dtype)
    return T.init_params(cfg, jax.random.PRNGKey(seed), dtype=dtype)


# ---- native checkpoints ----------------------------------------------------
#
# train/distill.py writes checkpoints in the engine's OWN pytree layout
# (stacked-layer arrays, native key paths joined by "/"), not HF names —
# a distilled draft has no HF identity to round-trip through.  The marker
# key in config.json keeps load_or_init_params from misreading the dir as
# an HF checkpoint (both contain config.json + *.safetensors).

_NATIVE_MARKER = "crowdllama_tpu_native"


def is_native_checkpoint(path: str | Path) -> bool:
    cfg_file = Path(path).expanduser() / "config.json"
    if not cfg_file.exists():
        return False
    try:
        return bool(json.loads(cfg_file.read_text()).get(_NATIVE_MARKER))
    except (OSError, ValueError):
        return False


def _flatten_params(params: dict, prefix: str = "") -> dict[str, np.ndarray]:
    out: dict[str, np.ndarray] = {}
    for k, v in params.items():
        key = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(_flatten_params(v, key))
        else:
            # float32 on disk: bf16 is not a numpy dtype, and a tiny draft
            # checkpoint doesn't need the 2x size saving.
            out[key] = np.asarray(jnp.asarray(v), np.float32)
    return out


def save_params(cfg: ModelConfig, params: dict, out_dir: str | Path,
                meta: dict | None = None) -> Path:
    """Write a native checkpoint: config.json (marker + full ModelConfig +
    caller metadata) and model.safetensors (flattened native pytree,
    float32).  Loadable via ``load_or_init_params`` / ``--spec-draft-path``
    — ``native_config_from_dir`` reconstructs the architecture, so the
    checkpoint needs no registry entry."""
    from dataclasses import asdict

    from safetensors.numpy import save_file

    out = Path(out_dir).expanduser()
    out.mkdir(parents=True, exist_ok=True)
    doc = {_NATIVE_MARKER: True, "model_config": asdict(cfg)}
    if meta:
        doc["meta"] = meta
    (out / "config.json").write_text(json.dumps(doc, indent=2))
    save_file(_flatten_params(params), str(out / "model.safetensors"))
    return out


def native_config_from_dir(path: str | Path) -> ModelConfig:
    """Reconstruct the ModelConfig a native checkpoint was saved with."""
    from crowdllama_tpu.models.config import RopeScaling

    d = json.loads((Path(path).expanduser() / "config.json").read_text())
    if not d.get(_NATIVE_MARKER):
        raise ValueError(f"{path} is not a native checkpoint "
                         f"(missing {_NATIVE_MARKER} marker)")
    mc = dict(d["model_config"])
    if mc.get("rope_scaling") is not None:
        mc["rope_scaling"] = RopeScaling(**mc["rope_scaling"])
    return ModelConfig(**mc)


def load_native_params(cfg: ModelConfig, path: str | Path,
                       dtype=jnp.bfloat16) -> dict:
    """Load a native checkpoint into the engine pytree, casting to the
    serving dtype.  ``cfg`` must match the saved architecture — init a
    reference pytree and fill it so shape/key mismatches fail loudly."""
    from safetensors.numpy import load_file

    flat = load_file(str(Path(path).expanduser() / "model.safetensors"))

    def rebuild(ref, prefix=""):
        out = {}
        for k, v in ref.items():
            key = f"{prefix}/{k}" if prefix else str(k)
            if isinstance(v, dict):
                out[k] = rebuild(v, key)
            else:
                if key not in flat:
                    raise KeyError(f"native checkpoint {path} missing {key}")
                arr = flat[key]
                if tuple(arr.shape) != tuple(v.shape):
                    raise ValueError(
                        f"native checkpoint {path}: {key} has shape "
                        f"{tuple(arr.shape)}, config wants {tuple(v.shape)}")
                out[k] = jnp.asarray(arr, dtype)
        return out

    ref = T.init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)
    return rebuild(ref)


def load_safetensors_params(cfg: ModelConfig, path: Path, dtype=jnp.bfloat16) -> dict:
    """Lazy multi-shard safetensors reader feeding the HF-name converter."""
    from safetensors import safe_open

    index_file = path / "model.safetensors.index.json"
    handles: dict[str, "safe_open"] = {}

    if index_file.exists():
        weight_map: dict[str, str] = json.loads(index_file.read_text())["weight_map"]

        def open_shard(fname: str):
            if fname not in handles:
                handles[fname] = safe_open(path / fname, framework="np")
            return handles[fname]

        def get(name: str) -> np.ndarray:
            return _to_np(open_shard(weight_map[name]).get_tensor(name))
    else:
        shards = [safe_open(p, framework="np") for p in sorted(path.glob("*.safetensors"))]
        names = {n: s for s in shards for n in s.keys()}

        def get(name: str) -> np.ndarray:
            if name not in names:
                raise KeyError(f"tensor {name} not found in {path}")
            return _to_np(names[name].get_tensor(name))

    return params_from_hf(cfg, get, dtype=dtype)


def _to_np(arr) -> np.ndarray:
    a = np.asarray(arr)
    if a.dtype == np.dtype("V2"):  # raw bfloat16 from safetensors numpy
        import jax.numpy as _jnp

        return np.asarray(_jnp.asarray(a.view(_jnp.bfloat16)), np.float32)
    return a


def _rope_scaling_from_hf(d: dict | None,
                          supported: tuple[str, ...] = ("llama3", "linear")):
    """Map config.json ``rope_scaling`` to a RopeScaling (None passes
    through; "default" means no scaling).  A scheme outside ``supported`` —
    the family's: the dense families are served with llama3 and linear, the
    latent-attention family ``sarvam_mla`` with ``yarn`` (``deepseek_yarn``
    is the same scheme under the name its family publishes) — raises, and
    names them: serving with silently-wrong position embeddings would
    corrupt every long-context generation."""
    if not d:
        return None
    from crowdllama_tpu.models.config import RopeScaling

    kind = d.get("rope_type") or d.get("type") or ""
    if kind in ("", "default"):
        return None
    if kind not in supported:
        raise ValueError(f"unsupported rope_scaling type {kind!r} "
                         f"(supported: {', '.join(supported)})")
    if kind == "llama3":
        return RopeScaling(
            rope_type="llama3", factor=float(d["factor"]),
            low_freq_factor=float(d.get("low_freq_factor", 1.0)),
            high_freq_factor=float(d.get("high_freq_factor", 4.0)),
            original_max_position_embeddings=int(
                d.get("original_max_position_embeddings", 8192)))
    if kind == "linear":
        return RopeScaling(rope_type="linear", factor=float(d["factor"]))
    return RopeScaling(
        rope_type="yarn", factor=float(d["factor"]),
        original_max_position_embeddings=int(
            d["original_max_position_embeddings"]),
        beta_fast=float(d.get("beta_fast", 32.0)),
        beta_slow=float(d.get("beta_slow", 1.0)),
        mscale=float(d.get("mscale", 1.0)),
        mscale_all_dim=float(d.get("mscale_all_dim", 0.0)))


def resolve_clamped_model_config(config) -> ModelConfig:
    """The engine's model-config derivation from a node Configuration:
    registry-or-checkpoint resolution plus the serving context clamp.
    ONE implementation — the multi-host follower (parallel/replicated.py)
    must build a runner bit-identical to the leader engine's, so the
    derivation cannot be allowed to drift between copies."""
    from dataclasses import replace as _replace

    cfg = resolve_model_config(config.model, config.model_path)
    if config.max_context_length:
        cfg = _replace(cfg, max_context_length=min(
            cfg.max_context_length, config.max_context_length))
    return cfg


def load_params_for(config, cfg: ModelConfig):
    """Load-or-init + optional quantization, exactly as the engines do
    (shared with the multi-host follower for the same reason as
    :func:`resolve_clamped_model_config`: every process must build the
    same tree, so it is a function of config + seed alone).

    Random init under ``--quantize`` is born quantized, leaf by leaf
    (ops/quant.py random_quantized_params): building the bf16 tree first
    (14.5 GB for a 7B model) and quantizing it after would not fit beside
    its own int8 copy on the 16 GB chip the int8 model serves from."""
    from crowdllama_tpu.ops.quant import (
        quantize_params,
        random_quantized_params,
    )

    if config.quantize and not _checkpoint_kind(config.model_path):
        return random_quantized_params(cfg, jax.random.PRNGKey(0),
                                       mode=config.quantize)
    params = load_or_init_params(cfg, config.model_path)
    if config.quantize:
        params = quantize_params(params, mode=config.quantize)
    return params


def resolve_model_config(name: str, model_path: str = "",
                         **overrides) -> ModelConfig:
    """Registry lookup with a checkpoint-dir fallback: a model name not in
    the registry serves from ``model_path``'s config.json (family sniffed,
    rope scaling kept) under the requested name.  This is what lets an
    operator serve a local fine-tune directory without editing the
    registry (the reference inherits arbitrary-model serving from Ollama's
    model store, /root/reference/pkg/crowdllama/api.go:108-160)."""
    from dataclasses import replace as _replace

    from crowdllama_tpu.models.config import _REGISTRY, get_config

    if name in _REGISTRY or not model_path:
        return get_config(name, **overrides)
    path = Path(model_path).expanduser()
    if not (path / "config.json").exists():
        return get_config(name, **overrides)  # raises with the known list
    cfg = _replace(config_from_hf_dir(path), name=name)
    return _replace(cfg, **overrides) if overrides else cfg


#: config.json ``model_type`` -> family; a type that is not here is an error
_FAMILIES = {"llama": "llama", "mistral": "mistral", "mixtral": "mixtral",
             "gemma2": "gemma2", "qwen2": "qwen2", "qwen3": "qwen3",
             "qwen3_moe": "qwen3", "nemotron_h": "nemotron_h",
             "kimi_linear": "kimi_linear", "afmoe": "afmoe",
             "sarvam_mla": "sarvam_mla"}
#: keys that say the block is not the one the dense families share: reading
#: past them would serve another model under this one's name
_FOREIGN_KEYS = ("hybrid_override_pattern", "n_routed_experts",
                 "n_shared_experts", "ssm_state_size", "linear_attn_config",
                 "kv_lora_rank", "first_k_dense_replace",
                 "num_shared_experts")
_FOREIGN_PREFIXES = ("mamba_", "moe_")


def config_from_hf_dir(path: str | Path) -> ModelConfig:
    """Derive a ModelConfig from a checkpoint's config.json (for models not
    in the registry).  A ``model_type`` this program has no family for, or
    a key that only another kind of block has, is an error that names it —
    never a reading as a llama."""
    d = json.loads((Path(path) / "config.json").read_text())
    arch = (d.get("architectures") or [""])[0].lower()
    kind = d.get("model_type")
    if kind is not None and kind not in _FAMILIES:
        raise ValueError(
            f"{path}/config.json: model_type {kind!r} is not a family this "
            f"program serves ({sorted(set(_FAMILIES))})")
    family = _FAMILIES.get(kind) or (
        "gemma2" if "gemma2" in arch
        else "mixtral" if "mixtral" in arch
        else "mistral" if "mistral" in arch
        else "qwen3" if "qwen3" in arch
        else "qwen2" if "qwen2" in arch
        else "nemotron_h" if "nemotronh" in arch
        else "kimi_linear" if "kimilinear" in arch
        else "afmoe" if "afmoe" in arch
        else "sarvam_mla" if "sarvammla" in arch else "llama")
    if family == "nemotron_h":
        return _nemotron_h_config(d)
    if family == "kimi_linear":
        return _kimi_linear_config(d)
    if family == "afmoe":
        return _afmoe_config(d)
    if family == "sarvam_mla":
        return _sarvam_mla_config(d)
    odd = sorted(k for k, v in d.items() if v not in (None, 0, False)
                 and (k in _FOREIGN_KEYS or k.startswith(_FOREIGN_PREFIXES)))
    if odd:
        raise ValueError(
            f"{path}/config.json: family {family!r} has no use for "
            f"{', '.join(odd)}; serving it as a {family} would drop them")
    return ModelConfig(
        name=d.get("_name_or_path", "hf-model"),
        family=family,
        vocab_size=d["vocab_size"],
        hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_layers=d["num_hidden_layers"],
        num_heads=d["num_attention_heads"],
        num_kv_heads=d.get("num_key_value_heads", d["num_attention_heads"]),
        head_dim=d.get("head_dim", 0),
        rope_theta=d.get("rope_theta", 10000.0),
        rope_scaling=_rope_scaling_from_hf(d.get("rope_scaling")),
        rms_norm_eps=d.get("rms_norm_eps", 1e-5),
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        max_context_length=d.get("max_position_embeddings", 4096),
        attn_logit_softcap=d.get("attn_logit_softcapping") or 0.0,
        final_logit_softcap=d.get("final_logit_softcapping") or 0.0,
        query_pre_attn_scalar=d.get("query_pre_attn_scalar") or 0.0,
        # gemma2 interleaves windowed layers, mistral windows all of them
        # (transformer.layer_sliding_windows patterns by family); other
        # families ignore config.json's value — their serving paths have
        # no windowed variant.
        sliding_window=((d.get("sliding_window") or 0)
                        if family in ("gemma2", "mistral") else 0),
        post_norms=family == "gemma2",
        embedding_multiplier=(d["hidden_size"] ** 0.5) if family == "gemma2" else 0.0,
        num_experts=d.get("num_local_experts", 0),
        num_experts_per_tok=d.get("num_experts_per_tok", 2),
        attn_qkv_bias=family == "qwen2" or bool(d.get("attention_bias")),
        qk_norm=family == "qwen3",
    )



def _nemotron_h_config(d: dict) -> ModelConfig:
    """``model_type: nemotron_h``.  ``n_routed_experts`` counts the experts
    held HERE; where that is a share, ``n_routed_experts_published`` gives
    the router's width and ``expert_parallel_rank`` which share (the
    benchmark's cut states both: benchmarks/chip/configs).  The config's
    ``rope_theta`` and ``partial_rotary_factor`` have no reader: the
    attention of this family does not rotate."""
    pattern = d["hybrid_override_pattern"]
    if len(pattern) != d["num_hidden_layers"]:
        raise ValueError(
            f"hybrid_override_pattern has {len(pattern)} layers, "
            f"num_hidden_layers says {d['num_hidden_layers']}")
    served = {"mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
              "n_group": 1, "topk_group": 1, "n_shared_experts": 1,
              "use_conv_bias": True, "use_bias": False, "mlp_bias": False,
              "mamba_proj_bias": False, "attention_bias": False}
    odd = {k: d[k] for k, v in served.items() if d.get(k, v) != v}
    if odd:
        raise ValueError(f"nemotron_h is served with {served} only; this "
                         f"config.json says {odd}")
    d_inner = d["mamba_num_heads"] * d["mamba_head_dim"]
    if d_inner != d.get("expand", 2) * d["hidden_size"]:
        raise ValueError(f"mamba heads give d_inner {d_inner}, expand says "
                         f"{d.get('expand', 2) * d['hidden_size']}")
    held = d["n_routed_experts"]
    return ModelConfig(
        name=d.get("_name_or_path", "hf-model"), family="nemotron_h",
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"],
        num_layers=d["num_hidden_layers"],
        num_heads=d["num_attention_heads"],
        num_kv_heads=d["num_key_value_heads"], head_dim=d.get("head_dim", 0),
        rms_norm_eps=d.get("layer_norm_epsilon", d.get("norm_eps", 1e-5)),
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        max_context_length=d.get("max_position_embeddings", 4096),
        layer_pattern=pattern, ssm_heads=d["mamba_num_heads"],
        ssm_head_dim=d["mamba_head_dim"], ssm_groups=d["n_groups"],
        ssm_state=d["ssm_state_size"], ssm_conv_kernel=d["conv_kernel"],
        ssm_chunk=d.get("chunk_size", 128),
        num_experts=d.get("n_routed_experts_published", held),
        experts_held=held, expert_rank=d.get("expert_parallel_rank", 0),
        num_experts_per_tok=d["num_experts_per_tok"],
        moe_intermediate_size=d["moe_intermediate_size"],
        moe_latent_size=d["moe_latent_size"],
        moe_shared_intermediate_size=d["moe_shared_expert_intermediate_size"],
        moe_routed_scaling=float(d.get("routed_scaling_factor", 1.0)),
        moe_norm_topk=bool(d.get("norm_topk_prob", True)),
    )


def _kimi_linear_config(d: dict) -> ModelConfig:
    """``model_type: kimi_linear``.  A published layer is two sublayers of
    the pattern: its mixer — ``K`` where ``linear_attn_config.kda_layers``
    (1-indexed) names it, ``L`` where ``full_attn_layers`` does — then its
    feed-forward, ``D`` for the first ``first_k_dense_replace`` layers and
    ``S`` after.  ``num_experts`` counts the experts held HERE; where that
    is a share, ``num_experts_published`` gives the router's width and
    ``expert_parallel_rank`` which share (the benchmark's cut states both).
    The latent attention is served absorbed: one kv head whose row is
    ``kv_lora_rank + qk_rope_head_dim`` wide (models/config.py).  With
    ``mla_use_nope`` nothing rotates: ``rope_theta`` has no reader."""
    lin = d["linear_attn_config"]
    n = d["num_hidden_layers"]
    kda_at, mla_at = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    if kda_at & mla_at or kda_at | mla_at != set(range(1, n + 1)):
        raise ValueError(
            f"kda_layers {sorted(kda_at)} and full_attn_layers "
            f"{sorted(mla_at)} must name each of the layers 1..{n} once")
    served = {"mla_use_nope": True, "q_lora_rank": None,
              "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
              "topk_group": 1, "moe_layer_freq": 1, "hidden_act": "silu",
              "num_nextn_predict_layers": 0, "rope_scaling": None}
    odd = {k: d[k] for k, v in served.items() if d.get(k, v) != v}
    if odd:
        raise ValueError(f"kimi_linear is served with {served} only; this "
                         f"config.json says {odd}")
    if d["v_head_dim"] != d["qk_nope_head_dim"]:
        raise ValueError("the kv up-projection is read as heads of "
                         "[k_nope | v] of one width each")
    dense = d.get("first_k_dense_replace", 0)
    pattern = "".join(("K" if i in kda_at else "L")
                      + ("D" if i <= dense else "S")
                      for i in range(1, n + 1))
    held = d["num_experts"]
    return ModelConfig(
        name=d.get("_name_or_path", "hf-model"), family="kimi_linear",
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"], num_layers=n,
        num_heads=d["num_attention_heads"], num_kv_heads=1,
        head_dim=d["kv_lora_rank"] + d["qk_rope_head_dim"],
        query_pre_attn_scalar=float(d["qk_nope_head_dim"]
                                    + d["qk_rope_head_dim"]),
        rms_norm_eps=d.get("rms_norm_eps", 1e-5),
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        max_context_length=d.get("max_position_embeddings",
                                 d.get("model_max_length", 4096)),
        layer_pattern=pattern, kda_heads=lin["num_heads"],
        kda_head_dim=lin["head_dim"],
        kda_conv_kernel=lin["short_conv_kernel_size"],
        kda_gate_rank=lin["head_dim"],
        kv_lora_rank=d["kv_lora_rank"],
        qk_nope_head_dim=d["qk_nope_head_dim"],
        qk_rope_head_dim=d["qk_rope_head_dim"], v_head_dim=d["v_head_dim"],
        num_experts=d.get("num_experts_published", held),
        experts_held=held, expert_rank=d.get("expert_parallel_rank", 0),
        num_experts_per_tok=d["num_experts_per_token"],
        moe_intermediate_size=d["moe_intermediate_size"],
        moe_shared_intermediate_size=(d["num_shared_experts"]
                                      * d["moe_intermediate_size"]),
        moe_routed_scaling=float(d.get("routed_scaling_factor", 1.0)),
        moe_norm_topk=bool(d.get("moe_renormalize", True)),
    )


def _sarvam_mla_config(d: dict) -> ModelConfig:
    """``model_type: sarvam_mla``.  A published layer is two sublayers of
    the pattern: latent attention whose decoupled part is rotated (``R``),
    then its feed-forward, ``D`` for the first ``first_k_dense_replace``
    layers and ``S`` after.  ``num_experts`` counts the experts held HERE;
    where that is a share, ``num_experts_published`` gives the router's
    width and ``expert_parallel_rank`` which share (the benchmark's cut
    states both).  The latent attention is served absorbed: one kv head
    whose row is ``kv_lora_rank + qk_rope_head_dim`` wide — the config's own
    ``head_dim`` — and no low-rank stage on the query.  ``use_qk_norm`` is
    read as the family's norm of the compressed kv stream (``kv_norm``),
    which every latent layer here has; a per-head norm of the decompressed
    keys could not be folded into the query.  The softmax scale is
    ``q_head_dim^-1/2`` times the square of the yarn scaling's
    ``mscale_all_dim`` correction, kept as ``query_pre_attn_scalar``."""
    n = d["num_hidden_layers"]
    served = {"hidden_act": "silu", "q_lora_rank": None, "n_group": 1,
              "topk_group": 1, "moe_router_enable_expert_bias": True, "use_qk_norm": True,
              "attention_bias": False, "norm_topk_prob": True}
    odd = {k: d[k] for k, v in served.items() if d.get(k, v) != v}
    if odd:
        raise ValueError(f"sarvam_mla is served with {served} only; this "
                         f"config.json says {odd}")
    dq = d["qk_nope_head_dim"] + d["qk_rope_head_dim"]
    row = d["kv_lora_rank"] + d["qk_rope_head_dim"]
    if d.get("q_head_dim", dq) != dq or d.get("head_dim", row) != row:
        raise ValueError(
            f"q_head_dim {d.get('q_head_dim')} and head_dim "
            f"{d.get('head_dim')} must be qk_nope + qk_rope = {dq} and "
            f"kv_lora_rank + qk_rope = {row}: the absorbed row")
    if d["v_head_dim"] != d["qk_nope_head_dim"]:
        raise ValueError("the kv up-projection is read as heads of "
                         "[k_nope | v] of one width each")
    scaling = _rope_scaling_from_hf(d.get("rope_scaling"),
                                    supported=("yarn", "deepseek_yarn"))
    mscale = scaling.yarn_mscale(scaling.mscale_all_dim) if scaling else 1.0
    dense = d.get("first_k_dense_replace", 0)
    held = d["num_experts"]
    return ModelConfig(
        name=d.get("_name_or_path", "hf-model"), family="sarvam_mla",
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"], num_layers=n,
        num_heads=d["num_attention_heads"], num_kv_heads=1, head_dim=row,
        # scale = dq^-1/2 mscale^2, as the value whose -1/2 power it is
        query_pre_attn_scalar=dq / mscale ** 4,
        rope_theta=float(d.get("rope_theta", 10000.0)), rope_scaling=scaling,
        rms_norm_eps=d.get("rms_norm_eps", 1e-6),
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        max_context_length=d.get("max_position_embeddings", 4096),
        layer_pattern="".join("R" + ("D" if i < dense else "S")
                              for i in range(n)),
        kv_lora_rank=d["kv_lora_rank"],
        qk_nope_head_dim=d["qk_nope_head_dim"],
        qk_rope_head_dim=d["qk_rope_head_dim"], v_head_dim=d["v_head_dim"],
        num_experts=d.get("num_experts_published", held),
        experts_held=held, expert_rank=d.get("expert_parallel_rank", 0),
        num_experts_per_tok=d["num_experts_per_tok"],
        moe_intermediate_size=d["moe_intermediate_size"],
        moe_shared_intermediate_size=(d["num_shared_experts"]
                                      * d["moe_intermediate_size"]),
        moe_routed_scaling=float(d.get("routed_scaling_factor", 1.0)),
    )


def _afmoe_config(d: dict) -> ModelConfig:
    """``model_type: afmoe``.  A published layer is two sublayers of the
    pattern: its attention — ``W`` where ``layer_types`` says
    ``sliding_attention`` (inside ``sliding_window``, rotated), ``F`` where
    it says ``full_attention`` (the whole context, not rotated) — then its
    feed-forward, ``D`` for the first ``num_dense_layers`` layers and ``S``
    after.  ``num_experts`` counts the experts held HERE; where that is a
    share, ``num_experts_published`` gives the router's width and
    ``expert_parallel_rank`` which share (the benchmark's cut states both).
    With ``mup_enabled`` the embedding is scaled by ``sqrt(hidden_size)``."""
    n = d["num_hidden_layers"]
    kinds = {"sliding_attention": "W", "full_attention": "F"}
    types = d["layer_types"]
    if len(types) != n or set(types) - set(kinds):
        raise ValueError(
            f"layer_types must name each of the {n} layers "
            f"{' or '.join(kinds)}; it has {len(types)} entries of "
            f"{sorted(set(types))}")
    served = {"score_func": "sigmoid", "hidden_act": "silu", "n_group": 1,
              "topk_group": 1, "num_expert_groups": 1,
              "num_limited_groups": 1, "rope_scaling": None,
              "attention_bias": False}
    odd = {k: d[k] for k, v in served.items() if d.get(k, v) != v}
    if odd:
        raise ValueError(f"afmoe is served with {served} only; this "
                         f"config.json says {odd}")
    if "W" in map(kinds.get, types) and not d.get("sliding_window"):
        raise ValueError("layer_types names sliding_attention layers and "
                         "sliding_window gives them no width")
    dense = d.get("num_dense_layers", 0)
    pattern = "".join(kinds[t] + ("D" if i < dense else "S")
                      for i, t in enumerate(types))
    held = d["num_experts"]
    return ModelConfig(
        name=d.get("_name_or_path", "hf-model"), family="afmoe",
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        intermediate_size=d["intermediate_size"], num_layers=n,
        num_heads=d["num_attention_heads"],
        num_kv_heads=d["num_key_value_heads"], head_dim=d.get("head_dim", 0),
        rope_theta=float(d.get("rope_theta", 10000.0)),
        rms_norm_eps=d.get("rms_norm_eps", 1e-5),
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        max_context_length=d.get("max_position_embeddings", 4096),
        sliding_window=d.get("sliding_window") or 0,
        post_norms=True, qk_norm=True,
        embedding_multiplier=(d["hidden_size"] ** 0.5
                              if d.get("mup_enabled") else 0.0),
        layer_pattern=pattern,
        num_experts=d.get("num_experts_published", held),
        experts_held=held, expert_rank=d.get("expert_parallel_rank", 0),
        num_experts_per_tok=d["num_experts_per_tok"],
        moe_intermediate_size=d["moe_intermediate_size"],
        moe_shared_intermediate_size=(d.get("num_shared_experts", 1)
                                      * d["moe_intermediate_size"]),
        moe_routed_scaling=float(d.get("route_scale", 1.0)),
        moe_norm_topk=bool(d.get("route_norm", True)),
    )
