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

import logging
from typing import Any

import jax
from jax.experimental.layout import Format, Layout
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from crowdllama_tpu.models.config import ModelConfig
from crowdllama_tpu.parallel.mesh import (
    AXIS_DP,
    AXIS_EP,
    AXIS_PP,
    AXIS_SP,
    AXIS_TP,
)
from crowdllama_tpu.utils.jaxcache import compile_cache_bypassed

Params = dict[str, Any]
log = logging.getLogger(__name__)


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


#: the stacked attention projections that every step program of a one-chip
#: TPU reads with the INPUT dimension minor
INPUT_MINOR = (0, 2, 1)


def weight_layout(name: str, leaf, devices) -> tuple[int, ...] | None:
    """Major-to-minor order in which the int8 payload ``q`` of parameter
    ``leaf`` (called ``name`` in its dict) is placed on ``devices``, or
    None for the default (row-major: output dimension minor).

    XLA's TPU layout assignment wants the int8 ``wq`` and ``wk`` of a
    stacked layer loop (``[L, d_in, d_out]``, ``decode_layer_body``) with
    d_in minor, and from a default-layout argument it copies them first: a
    layer at a time in a one-step decode, a ragged step and every prefill,
    the whole stacks once a dispatch in a ``decode_chunk``-step program (17%
    of a one-step Mistral-7B decode: PERF.md §6, PR 41).  A committed
    argument's own layout is what ``jax.jit`` compiles for, so placing the
    two leaves that way is all it takes.  ``wv``, ``wo`` and the MLP leaves
    are read where they lie; bf16 and int4 leaves, the rank-4 expert banks
    (a Pallas kernel's operands) and the rank-2 leaves of a list-of-layers
    model (models/hybrid.py) are left alone; the CPU backend keeps default
    layouts, and a mesh of several devices does too (no cell measures
    one).  The one rule ``shard_params`` and the deviceless compiles of
    tests/test_tpu_compile.py share."""
    from crowdllama_tpu.ops.quant import QTensor

    if (name in ("wq", "wk") and isinstance(leaf, QTensor)
            and leaf.q.ndim == 3 and len(devices) == 1
            and devices[0].platform == "tpu"):
        return INPUT_MINOR
    return None


def placed_layouts(params: Params) -> dict[str, str]:
    """``{"wq": .., "wk": ..}``: ``input_minor`` if the PLACED array of that
    name lies with its input dimension minor, read off its own format and
    not off the rule, else ``default`` (a model with no such leaf too) —
    what ``crowdllama_weight_layout`` exports."""
    from crowdllama_tpu.ops.quant import QTensor, QTensor4

    out = {"wq": "default", "wk": "default"}
    flat, _ = jax.tree_util.tree_flatten_with_path(
        params["layers"], is_leaf=lambda x: isinstance(x, (QTensor, QTensor4)))
    for path, leaf in flat:
        name = getattr(path[-1], "key", "")
        if name in out:
            a = getattr(leaf, "q", leaf)
            order = getattr(a.format.layout, "major_to_minor", None)
            out[name] = ("input_minor" if order and order[-1] == a.ndim - 2
                         else "default")
    return out


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
    weight's spec and ``s`` with that spec minus the input dim; ``q`` lies
    in the order :func:`weight_layout` gives it."""
    from crowdllama_tpu.ops.quant import (
        QTensor,
        QTensor4,
        drop_input_axis_spec,
    )

    specs = param_pspecs(cfg)
    devices = list(mesh.devices.flat)
    kept_default = set()

    def place(path, a, s):
        if isinstance(a, QTensor):
            name = getattr(path[-1], "key", "")
            where = NamedSharding(mesh, filter_spec(s, mesh))
            order = weight_layout(name, a, devices)
            if order is not None:
                # a relayout's executable must not come from the cache
                with compile_cache_bypassed():
                    q = jax.device_put(
                        a.q, Format(Layout(major_to_minor=order), where))
            else:
                q = jax.device_put(a.q, where)
                if weight_layout(name, a, devices[:1]) is not None:
                    kept_default.add(name)
            return QTensor(
                q=q,
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

    placed = jax.tree_util.tree_map_with_path(
        place, params, specs,
        is_leaf=lambda x: isinstance(x, (QTensor, QTensor4)),
    )
    if kept_default:
        log.info("%s keep the default layout on a mesh of %d devices: the "
                 "input-dimension-minor placement is one device's",
                 " and ".join(sorted(kept_default)), mesh.size)
    return placed


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def cache_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, cache_pspec(mesh))
