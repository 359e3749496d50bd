"""Model architecture configs and the named-model registry.

One config dataclass describes every family the program serves: the dense
and mixture-of-experts transformers of models/transformer.py (llama,
mistral, gemma2, mixtral, qwen2, qwen3) and the families whose layers
differ in kind (models/hybrid.py: nemotron_h, kimi_linear, afmoe,
sarvam_mla).  Family-specific behavior (Gemma logit softcapping,
sliding-window interleave, MoE routing, a layer pattern) is driven by
fields.  The registry holds the named production models and a tiny variant
of each family for the tests; a model that is not in it is read from its
directory's config.json (engine/weights.py).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace


@dataclass(frozen=True)
class RopeScaling:
    """Long-context RoPE scaling (HF config.json ``rope_scaling``).

    ``rope_type`` "llama3" is the Llama-3.1/3.2 frequency-dependent
    scheme; "linear" is plain position interpolation; "yarn" blends
    interpolated and original frequencies between the two correction
    dimensions that ``beta_fast`` / ``beta_slow`` rotations over
    ``original_max_position_embeddings`` give, and scales cos and sin by
    ``mscale`` over ``mscale_all_dim`` (ops/rope.py has the equations; the
    factor a latent-attention family puts on its softmax scale is that
    family's: engine/weights.py).  A frozen dataclass (not a dict) so
    ModelConfig stays hashable.
    """

    #: the schemes ops/rope.py computes
    TYPES = ("llama3", "linear", "yarn")

    rope_type: str = "llama3"
    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position_embeddings: int = 8192
    # yarn only
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def __post_init__(self) -> None:
        if self.rope_type not in self.TYPES:
            raise ValueError(
                f"unsupported rope scaling type {self.rope_type!r} "
                f"(supported: {', '.join(self.TYPES)})")

    def yarn_mscale(self, m: float) -> float:
        """YaRN's magnitude correction for a multiplier ``m`` (``mscale`` or
        ``mscale_all_dim``): ``0.1 m ln(factor) + 1``."""
        return 0.1 * m * math.log(self.factor) + 1.0 if self.factor > 1 else 1.0


@dataclass(frozen=True)
class ModelConfig:
    name: str = "custom"
    # "llama" | "mistral" | "gemma2" | "mixtral" | "qwen2" | "qwen3" |
    # "nemotron_h" | "kimi_linear" | "afmoe" | "sarvam_mla" (layers that
    # differ in kind: models/hybrid.py)
    family: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_layers: int = 22
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 0  # 0 → hidden_size // num_heads
    rope_theta: float = 10000.0
    rope_scaling: RopeScaling | None = None  # Llama-3.1-style long context
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    max_context_length: int = 4096

    # Gemma-2 specifics (family="gemma2")
    query_pre_attn_scalar: float = 0.0  # 0 → 1/sqrt(head_dim)
    attn_logit_softcap: float = 0.0  # 0 → disabled
    final_logit_softcap: float = 0.0
    # 0 → all layers global.  >0: family-patterned (gemma2 windows even
    # layers, mistral windows every layer — transformer.py
    # layer_sliding_windows is the source of truth).
    sliding_window: int = 0
    post_norms: bool = False  # post-attention/post-mlp RMSNorms (Gemma-2)
    embedding_multiplier: float = 0.0  # 0 → disabled (Gemma scales by sqrt(D))

    # Qwen specifics
    attn_qkv_bias: bool = False  # Qwen2/2.5: bias on q/k/v projections
    qk_norm: bool = False  # Qwen3: per-head RMSNorm on q and k before rope

    # MoE specifics (family="mixtral")
    num_experts: int = 0  # 0 → dense MLP
    num_experts_per_tok: int = 2
    # "sorted": grouped-GEMM dispatch via lax.ragged_dot (E/K FLOP saving,
    # exact); "dense": compute-all-experts reference semantics.
    moe_dispatch: str = "sorted"

    # Hybrid specifics (models/hybrid.py).  Every sublayer is ONE mixer
    # behind one RMSNorm, its kind a character of ``layer_pattern``:
    # family "nemotron_h" — "M" Mamba-2, "E" latent mixture of experts, "*"
    # attention (no rotary), one sublayer a layer; family "kimi_linear" —
    # "K" delta-rule linear attention (KDA), "L" latent attention (MLA, no
    # rotary), "D" dense SwiGLU, "S" SwiGLU mixture of experts with a
    # shared expert, two sublayers (mixer, then feed-forward) a layer;
    # family "afmoe" — "W" gated attention inside ``sliding_window`` with
    # rotary embedding, "F" gated full attention without it, then "D" or
    # "S", two sublayers a layer, each sublayer's output normed AGAIN before
    # it joins the residual stream (``post_norms``); family "sarvam_mla" —
    # "R" latent attention whose decoupled part is rotated (``rope_theta``,
    # ``rope_scaling``, over ``qk_rope_head_dim``), then "D" or "S".
    # ``num_experts`` is the router's width; this worker holds
    # ``experts_held`` of them (0 = all), those of ``expert_rank``.
    layer_pattern: str = ""
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state: int = 0
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128
    moe_intermediate_size: int = 0
    moe_latent_size: int = 0
    moe_shared_intermediate_size: int = 0
    moe_routed_scaling: float = 1.0
    moe_norm_topk: bool = True
    experts_held: int = 0
    expert_rank: int = 0
    # KDA: heads of a [kda_head_dim, kda_head_dim] float32 state each, a
    # causal convolution behind each of q, k, v, two low-rank gates.
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel: int = 4
    kda_gate_rank: int = 0
    kda_chunk: int = 64
    # MLA, served ABSORBED: the cache keeps one row [c ; k_rope] a token, so
    # ``num_kv_heads`` is 1 and ``head_dim`` the row's width (kv_lora_rank +
    # qk_rope_head_dim); the softmax scale (qk_nope + qk_rope)^-1/2 is
    # ``query_pre_attn_scalar`` (with a yarn scaling's mscale^2 in it).
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    def __post_init__(self) -> None:
        if self.moe_dispatch not in ("sorted", "dense"):
            raise ValueError(
                f"moe_dispatch must be 'sorted' or 'dense', "
                f"got {self.moe_dispatch!r}")
        if self.layer_pattern:
            kinds, per = {"kimi_linear": ("KLDS", 2), "afmoe": ("WFDS", 2),
                          "sarvam_mla": ("RDS", 2)}.get(self.family,
                                                        ("ME*", 1))
            odd = set(self.layer_pattern) - set(kinds)
            if odd or len(self.layer_pattern) != per * self.num_layers:
                raise ValueError(
                    f"layer_pattern {self.layer_pattern!r} must give {per} "
                    f"of {', '.join(kinds)} for each of the "
                    f"{self.num_layers} layers")
            held = self.experts_held or self.num_experts
            if held * (self.expert_rank + 1) > self.num_experts:
                raise ValueError(
                    f"rank {self.expert_rank} of shares of {held} experts "
                    f"lies outside the router's {self.num_experts}")

    @property
    def is_hybrid(self) -> bool:
        return bool(self.layer_pattern)

    def layers_of(self, kind: str) -> int:
        """How many sublayers are of ``kind`` (a character of the pattern);
        every layer of a model without a pattern is an attention layer."""
        if not self.layer_pattern:
            return self.num_layers if kind == "*" else 0
        return self.layer_pattern.count(kind)

    def resolved_head_dim(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def param_count(self) -> int:
        """Total parameters (matches models.transformer.init_params)."""
        if self.is_hybrid:
            from crowdllama_tpu.models import hybrid

            return hybrid.param_count(self)
        d, f, v = self.hidden_size, self.intermediate_size, self.vocab_size
        dh = self.resolved_head_dim()
        attn = d * self.num_heads * dh + 2 * d * self.num_kv_heads * dh \
            + self.num_heads * dh * d
        if self.attn_qkv_bias:
            attn += self.num_heads * dh + 2 * self.num_kv_heads * dh
        if self.qk_norm:
            attn += 2 * dh
        if self.is_moe:
            mlp = self.num_experts * 3 * d * f + d * self.num_experts
        else:
            mlp = 3 * d * f
        norms = 2 * d + (2 * d if self.post_norms else 0)
        head = 0 if self.tie_word_embeddings else d * v
        return self.num_layers * (attn + mlp + norms) + v * d + head + d

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0


_REGISTRY: dict[str, ModelConfig] = {}


def _register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


# ---- test-scale models ----------------------------------------------------

TINY_TEST = _register(ModelConfig(
    name="tiny-test", family="llama", vocab_size=512, hidden_size=64,
    intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
    max_context_length=256,
))

TINY_TEST_MOE = _register(ModelConfig(
    name="tiny-test-moe", family="mixtral", vocab_size=512, hidden_size=64,
    intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
    num_experts=4, num_experts_per_tok=2, max_context_length=256,
))

TINY_TEST_GEMMA = _register(ModelConfig(
    name="tiny-test-gemma", family="gemma2", vocab_size=512, hidden_size=64,
    intermediate_size=128, num_layers=4, num_heads=4, num_kv_heads=2,
    head_dim=16, attn_logit_softcap=50.0, final_logit_softcap=30.0,
    sliding_window=32, post_norms=True, embedding_multiplier=8.0,
    max_context_length=256, rms_norm_eps=1e-6,
))

TINY_TEST_QWEN3_MOE = _register(ModelConfig(
    name="tiny-test-qwen3-moe", family="qwen3", vocab_size=512,
    hidden_size=64, intermediate_size=128, num_layers=2, num_heads=4,
    num_kv_heads=2, head_dim=32, qk_norm=True, num_experts=4,
    num_experts_per_tok=2, max_context_length=256, rms_norm_eps=1e-6,
))

TINY_TEST_QWEN2 = _register(ModelConfig(
    name="tiny-test-qwen2", family="qwen2", vocab_size=512, hidden_size=64,
    intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
    attn_qkv_bias=True, rms_norm_eps=1e-6, max_context_length=256,
))

TINY_TEST_QWEN3 = _register(ModelConfig(
    name="tiny-test-qwen3", family="qwen3", vocab_size=512, hidden_size=64,
    intermediate_size=128, num_layers=2, num_heads=4, num_kv_heads=2,
    head_dim=32, qk_norm=True, rms_norm_eps=1e-6, max_context_length=256,
))

TINY_TEST_MISTRAL = _register(ModelConfig(
    name="tiny-test-mistral", family="mistral", vocab_size=512,
    hidden_size=64, intermediate_size=128, num_layers=2, num_heads=4,
    num_kv_heads=2, sliding_window=16, rms_norm_eps=1e-6,
    max_context_length=256,
))

# All three kinds of layer, 16 experts behind the router of which this
# worker holds 8 (rank 0 of 2), a latent width, more than one SSD chunk in
# the smallest prefill bucket.
TINY_TEST_NEMOTRON_H = _register(ModelConfig(
    name="tiny-test-nemotron-h", family="nemotron_h", vocab_size=512,
    hidden_size=64, intermediate_size=48, num_layers=5, num_heads=4,
    num_kv_heads=2, head_dim=16, layer_pattern="MEM*E", ssm_heads=8,
    ssm_head_dim=16, ssm_groups=2, ssm_state=16, ssm_conv_kernel=4,
    ssm_chunk=8, num_experts=16, num_experts_per_tok=4, experts_held=8,
    moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_intermediate_size=96, moe_routed_scaling=2.5,
    max_context_length=256,
))

# Both mixers and both feed-forwards of the family: a dense first layer, 16
# experts behind the router of which this worker holds 8 (rank 0 of 2),
# top-4, more than one KDA chunk in the smallest prefill bucket.
TINY_TEST_KIMI_LINEAR = _register(ModelConfig(
    name="tiny-test-kimi-linear", family="kimi_linear", vocab_size=512,
    hidden_size=64, intermediate_size=96, num_layers=4, num_heads=4,
    num_kv_heads=1, head_dim=48, query_pre_attn_scalar=32.0,
    layer_pattern="KDKSLSKS", kda_heads=4, kda_head_dim=16,
    kda_gate_rank=16, kda_chunk=8, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=16, v_head_dim=16, num_experts=16,
    num_experts_per_tok=4, experts_held=8, moe_intermediate_size=32,
    moe_shared_intermediate_size=32, moe_routed_scaling=2.446,
    max_context_length=256,
))

# Both attention kinds and both feed-forwards of the family: a dense first
# layer, three window layers to one full layer, a window of two pages of 8,
# 16 experts behind the router of which this worker holds 8 (rank 0 of 2),
# top-4, six query heads a kv head.
TINY_TEST_AFMOE = _register(ModelConfig(
    name="tiny-test-afmoe", family="afmoe", vocab_size=512, hidden_size=64,
    intermediate_size=96, num_layers=5, num_heads=12, num_kv_heads=2,
    head_dim=16, layer_pattern="WDWSWSWSFS", sliding_window=16,
    post_norms=True, qk_norm=True, embedding_multiplier=8.0,
    num_experts=16, num_experts_per_tok=4, experts_held=8,
    moe_intermediate_size=32, moe_shared_intermediate_size=32,
    moe_routed_scaling=2.448, max_context_length=256,
))

# Latent attention in every layer, its decoupled part rotated under a yarn
# scaling whose original length (32) is SHORTER than the context, so that
# positions past it are served; a dense first layer, 16 experts behind the
# router of which this worker holds 8 (rank 0 of 2), top-4.  The score
# scale is (16 + 16)^-1/2 times mscale^2, mscale = 0.1 ln 8 + 1.
_TINY_YARN = RopeScaling(
    rope_type="yarn", factor=8.0, original_max_position_embeddings=32,
    beta_fast=4.0, beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
TINY_TEST_SARVAM_MLA = _register(ModelConfig(
    name="tiny-test-sarvam-mla", family="sarvam_mla", vocab_size=512,
    hidden_size=64, intermediate_size=96, num_layers=3, num_heads=4,
    num_kv_heads=1, head_dim=48,
    query_pre_attn_scalar=32.0 / _TINY_YARN.yarn_mscale(1.0) ** 4,
    rope_scaling=_TINY_YARN,
    layer_pattern="RDRSRS", kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=16, v_head_dim=16, num_experts=16,
    num_experts_per_tok=4, experts_held=8, moe_intermediate_size=32,
    moe_shared_intermediate_size=32, moe_routed_scaling=2.5,
    max_context_length=256, rms_norm_eps=1e-6,
))

# ---- production models (BASELINE.json configs) ----------------------------

TINYLLAMA_1_1B = _register(ModelConfig(
    name="tinyllama-1.1b", family="llama", vocab_size=32000, hidden_size=2048,
    intermediate_size=5632, num_layers=22, num_heads=32, num_kv_heads=4,
    rope_theta=10000.0, max_context_length=2048,
))

LLAMA3_8B = _register(ModelConfig(
    name="llama-3-8b", family="llama", vocab_size=128256, hidden_size=4096,
    intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
    rope_theta=500000.0, max_context_length=8192,
))

# Llama-3.1: same weights shape as 3.0 plus the llama3 rope scaling that
# stretches usable context to 128k.  Serving ctx defaults far below the
# architectural maximum — one chip's KV budget is the real bound; callers
# raise max_context_length per deployment.
LLAMA31_8B = _register(ModelConfig(
    name="llama-3.1-8b", family="llama", vocab_size=128256, hidden_size=4096,
    intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
    rope_theta=500000.0, max_context_length=16384,
    rope_scaling=RopeScaling(rope_type="llama3", factor=8.0,
                             low_freq_factor=1.0, high_freq_factor=4.0,
                             original_max_position_embeddings=8192),
))

MISTRAL_7B = _register(ModelConfig(
    name="mistral-7b", family="mistral", vocab_size=32000, hidden_size=4096,
    intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
    rope_theta=10000.0, sliding_window=4096, max_context_length=8192,
))

LLAMA3_70B = _register(ModelConfig(
    name="llama-3-70b", family="llama", vocab_size=128256, hidden_size=8192,
    intermediate_size=28672, num_layers=80, num_heads=64, num_kv_heads=8,
    rope_theta=500000.0, max_context_length=8192,
))

MIXTRAL_8X7B = _register(ModelConfig(
    name="mixtral-8x7b", family="mixtral", vocab_size=32000, hidden_size=4096,
    intermediate_size=14336, num_layers=32, num_heads=32, num_kv_heads=8,
    rope_theta=1000000.0, num_experts=8, num_experts_per_tok=2,
    max_context_length=32768,
))

QWEN25_7B = _register(ModelConfig(
    name="qwen2.5-7b", family="qwen2", vocab_size=152064, hidden_size=3584,
    intermediate_size=18944, num_layers=28, num_heads=28, num_kv_heads=4,
    rope_theta=1000000.0, rms_norm_eps=1e-6, attn_qkv_bias=True,
    max_context_length=32768,
))

QWEN3_8B = _register(ModelConfig(
    name="qwen3-8b", family="qwen3", vocab_size=151936, hidden_size=4096,
    intermediate_size=12288, num_layers=36, num_heads=32, num_kv_heads=8,
    head_dim=128, rope_theta=1000000.0, rms_norm_eps=1e-6, qk_norm=True,
    max_context_length=32768,
))

GEMMA2_27B = _register(ModelConfig(
    name="gemma-2-27b", family="gemma2", vocab_size=256128, hidden_size=4608,
    intermediate_size=36864, num_layers=46, num_heads=32, num_kv_heads=16,
    head_dim=128, rope_theta=10000.0, rms_norm_eps=1e-6,
    query_pre_attn_scalar=144.0, attn_logit_softcap=50.0,
    final_logit_softcap=30.0, sliding_window=4096, post_norms=True,
    embedding_multiplier=67.882251,  # sqrt(4608)
    tie_word_embeddings=True, max_context_length=8192,
))


def get_config(name: str, **overrides) -> ModelConfig:
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; known: {sorted(_REGISTRY)}")
    cfg = _REGISTRY[name]
    return replace(cfg, **overrides) if overrides else cfg


def list_models() -> list[str]:
    return sorted(_REGISTRY)
