"""Per-parameter partition rules (GSPMD NamedShardings).

Megatron-style tensor parallelism expressed as shardings, with XLA inserting
the collectives: attention QKV and MLP up/gate are column-parallel (output
dim on ``tp``), attention output and MLP down are row-parallel (input dim on
``tp``) — each layer then needs exactly one psum after wo and one after
w_down, which GSPMD derives automatically.  MoE expert banks additionally
shard the expert dim on ``ep``.  Layer-stacked params shard their leading
layer axis on ``pp`` (pipeline stages own contiguous layer slices,
parallel/pipeline.py).  KV caches shard kv-heads on ``tp`` and layers on
``pp``.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from crowdllama_tpu.models.config import ModelConfig
from crowdllama_tpu.parallel.mesh import (
    AXIS_DP,
    AXIS_EP,
    AXIS_PP,
    AXIS_SP,
    AXIS_TP,
)

Params = dict[str, Any]


def param_pspecs(cfg: ModelConfig) -> Params:
    """PartitionSpec pytree mirroring models.transformer.init_params."""
    if cfg.is_hybrid:
        # One device (engine/hybrid.py refuses a larger mesh): replicated.
        from crowdllama_tpu.models import transformer as T

        shapes = jax.eval_shape(
            lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
        return jax.tree_util.tree_map(lambda _: P(), shapes)
    layers: Params = {
        "ln1": P(AXIS_PP, None),
        "ln2": P(AXIS_PP, None),
        # [L, D, H*Dh] column-parallel
        "wq": P(AXIS_PP, None, AXIS_TP),
        "wk": P(AXIS_PP, None, AXIS_TP),
        "wv": P(AXIS_PP, None, AXIS_TP),
        # [L, H*Dh, D] row-parallel
        "wo": P(AXIS_PP, AXIS_TP, None),
    }
    if cfg.attn_qkv_bias:  # [L, H*Dh] — follows the column-parallel output dim
        layers["bq"] = P(AXIS_PP, AXIS_TP)
        layers["bk"] = P(AXIS_PP, AXIS_TP)
        layers["bv"] = P(AXIS_PP, AXIS_TP)
    if cfg.qk_norm:  # [L, Dh] per-head norm gains, replicated across heads
        layers["q_norm"] = P(AXIS_PP, None)
        layers["k_norm"] = P(AXIS_PP, None)
    if cfg.is_moe:
        layers["router"] = P(AXIS_PP, None, None)
        layers["w_gate"] = P(AXIS_PP, AXIS_EP, None, AXIS_TP)  # [L,E,D,F]
        layers["w_up"] = P(AXIS_PP, AXIS_EP, None, AXIS_TP)
        layers["w_down"] = P(AXIS_PP, AXIS_EP, AXIS_TP, None)  # [L,E,F,D]
    else:
        layers["w_gate"] = P(AXIS_PP, None, AXIS_TP)  # [L,D,F]
        layers["w_up"] = P(AXIS_PP, None, AXIS_TP)
        layers["w_down"] = P(AXIS_PP, AXIS_TP, None)  # [L,F,D]
    if cfg.post_norms:
        layers["post_ln1"] = P(AXIS_PP, None)
        layers["post_ln2"] = P(AXIS_PP, None)
    specs: Params = {
        "embed": P(AXIS_TP, None),  # [V, D] vocab-sharded
        "layers": layers,
        "final_norm": P(),
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, AXIS_TP)  # [D, V]
    return specs


def filter_spec(spec: P, mesh: Mesh | None) -> P:
    """Drop axis names absent from ``mesh`` (legacy caller-built meshes)."""
    if mesh is None:
        return spec
    return P(*(ax if ax is None or ax in mesh.shape else None for ax in spec))


def cache_pspec(mesh: Mesh | None = None) -> P:
    """KV cache [L, B, Hkv, S, Dh] (head-major: per-head sequence planes are
    contiguous — see ops/attention.py): layers on pp, slots on dp, kv-heads
    on tp, sequence on sp (size-1 axes make those no-ops).  Axes absent from
    ``mesh`` are dropped."""
    return filter_spec(P(AXIS_PP, AXIS_DP, AXIS_TP, AXIS_SP, None), mesh)


def shard_params(params: Params, cfg: ModelConfig, mesh: Mesh) -> Params:
    """Place a param pytree onto the mesh with the PP/TP/EP partition rules.

    Quantized leaves (ops.quant.QTensor) shard ``q`` with the original
    weight's spec and ``s`` with that spec minus the input dim."""
    from crowdllama_tpu.ops.quant import (
        QTensor,
        QTensor4,
        drop_input_axis_spec,
    )

    specs = param_pspecs(cfg)

    def place(a, s):
        if isinstance(a, QTensor):
            return QTensor(
                q=jax.device_put(
                    a.q, NamedSharding(mesh, filter_spec(s, mesh))),
                s=jax.device_put(
                    a.s, NamedSharding(mesh, filter_spec(
                        drop_input_axis_spec(s, a.q.ndim), mesh))),
                mesh_devices=mesh.size,
            )
        if isinstance(a, QTensor4):
            # Group scales keep the weight's rank (input dim → group dim),
            # so the weight's spec applies to both — except axes the (much
            # smaller) scale tensor cannot divide, which replicate.
            wspec = filter_spec(s, mesh)
            axes = tuple(wspec) + (None,) * (a.s.ndim - len(tuple(wspec)))
            sspec = P(*(ax if ax is not None and dim % mesh.shape[ax] == 0
                        else None
                        for dim, ax in zip(a.s.shape, axes)))
            return QTensor4(
                q=jax.device_put(a.q, NamedSharding(mesh, wspec)),
                s=jax.device_put(a.s, NamedSharding(mesh, sspec)))
        return jax.device_put(a, NamedSharding(mesh, filter_spec(s, mesh)))

    return jax.tree_util.tree_map(
        place, params, specs,
        is_leaf=lambda x: isinstance(x, (QTensor, QTensor4)),
    )


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def cache_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, cache_pspec(mesh))
