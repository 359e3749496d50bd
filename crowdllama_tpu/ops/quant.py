"""Weight-only int8 quantization (per-output-channel, symmetric).

Decode is HBM-bandwidth-bound (SURVEY §6; ROADMAP S1): every
step streams the full parameter set.  Storing matmul weights as int8 with a
per-output-channel bf16 scale halves the dominant traffic, and the MXU
still sees bf16 inputs.  Where the dequantize (convert + broadcast
multiply) happens depends on the consumer.  A dense ``qeinsum`` is an XLA
dot, and XLA fuses the dequantize into its operand read: Mistral-7B's
three MLP matmuls stream their int8 weights at 730 GB/s, 89% of a v5e's
peak (PERF.md §5).  It reads the operand where it lies only if it lies as
the dot wants it: of a stacked layer's seven dense matrices XLA's TPU
layout assignment wants ``wq`` and ``wk`` with the INPUT dimension minor
and copied them first when they lay row-major, so on one TPU device
``shard_params`` places those two payloads that way (parallel/sharding.py
``weight_layout``; PERF.md §6, PR 41).  ``lax.ragged_dot`` is a custom
call that nothing fuses into: an expert bank dequantized for it was
written whole to HBM as bf16 and read again, five times the bytes, 87% of
a Mixtral decode step (PERF_LEDGER.jsonl, PR 27).  So an int8 bank goes to
a grouped matmul that converts in VMEM (ops/pallas/moe.py);
:func:`qragged_dot` says which input takes which path.

Int8×int8 MXU matmuls (dynamic activation quantization) were measured
SLOWER at serving batch sizes (B=8: 6.5 ms/step) — the per-step activation
quant costs more than it saves; weight-only is the right point on this
hardware, so that is what ships.

The reference has no quantization (its engine is Ollama's GGUF, which
quantizes offline in formats the swarm layer never sees).
"""

from __future__ import annotations

import logging
import math
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

Params = dict[str, Any]
log = logging.getLogger(__name__)

# Weight names that carry the bulk of the bytes and tolerate int8: every
# large matmul.  Norm gains, the MoE router (tiny, routing-critical), and the
# embedding table (gather + tied-unembed accuracy) stay in bf16.
QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
              # models/hybrid.py: Mamba projections, latent projections,
              # the held expert banks, the shared expert
              "w_in", "w_out", "w_lat_down", "w_lat_up", "w1", "w2",
              "ws1", "ws2",
              # KDA's two gates, the latent kv up-projection, the dense and
              # shared SwiGLU's [gate | up]
              "w_f_up", "w_g_up", "w_kvb", "w_gu", "ws_gu", "ws_down",
              # the output gate of a gated attention layer
              "wg")


@jax.tree_util.register_dataclass
@dataclass
class QTensor:
    """int8 weight + per-output-channel scale.

    ``q`` keeps the source shape [..., d_in, d_out]; ``s`` is [..., d_out].
    A pytree node, so it flows through jit / scan / device_put like the
    plain array it replaces.

    Placement metadata lives on the weight: ``mesh_devices`` is the size of
    the mesh ``parallel/sharding.py`` ``shard_params`` placed it on (the
    one place a placed ``QTensor`` is built), which a traced program cannot
    read off its arguments and a ``pallas_call`` has to know (GSPMD does
    not partition one).  It is static, so ``tree_map``, ``jit`` and a
    ``scan`` slice keep it; a ``QTensor(q=.., s=..)`` built by hand from a
    placed one's arrays starts at 1 again and must pass it on.
    """

    q: jnp.ndarray
    s: jnp.ndarray
    mesh_devices: int = field(default=1, metadata=dict(static=True))

    @property
    def shape(self):
        return self.q.shape


@jax.tree_util.register_dataclass
@dataclass
class QTensor4:
    """Nibble-packed int4 weight + GROUP-wise scales (one per ``group``
    input rows per output channel).

    ``q`` is int8 of shape [..., d_in, d_out/2]: output columns 2j and
    2j+1 pack into one byte (low/high nibble — XLA's own little-endian
    sub-byte order, see quantize_weight_int4).  Packed int8 — not
    ``jnp.int4``
    — because (a) the bandwidth win comes from the BYTES streamed, which
    sub-byte jnp arrays only deliver through layout paths that broke
    on-chip in round 4 (device_put recursion when an int4 leaf crosses a
    jit boundary; not re-tried on jax 0.9.0), and
    (b) the in-jit unpack (bitcast + trailing reshape) is zero-movement.
    ``s`` is [..., d_in/group, d_out] — same rank as the weight, so the
    weight's PartitionSpec applies to both (a tp shard of the packed
    output dim keeps nibble pairs intact for any even per-shard extent).
    int4 needs finer scale granularity
    than int8's per-channel to hold accuracy; group-wise is the standard
    point (AWQ/GPTQ-style).
    """

    q: jnp.ndarray
    s: jnp.ndarray

    @property
    def shape(self):
        """LOGICAL (unpacked) weight shape."""
        return (*self.q.shape[:-1], self.q.shape[-1] * 2)


def quantize_weight(w: jnp.ndarray, scale_dtype=jnp.bfloat16) -> QTensor:
    """Symmetric per-output-channel int8 over the input dim (axis -2)."""
    a = jnp.asarray(w, jnp.float32)
    s = jnp.max(jnp.abs(a), axis=-2, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(a / s), -127, 127).astype(jnp.int8)
    return QTensor(q=q, s=s.squeeze(-2).astype(scale_dtype))


GROUP = 64  # int4 scale group (input rows per scale)


def quantize_weight_int4(w: jnp.ndarray, group: int = GROUP,
                         scale_dtype=jnp.bfloat16) -> QTensor4:
    """Symmetric group-wise int4 over the input dim (axis -2), packed two
    values per int8 byte (see QTensor4)."""
    a = jnp.asarray(w, jnp.float32)
    *batch, d_in, d_out = a.shape
    if d_out % 2:
        raise ValueError(f"int4 packing needs an even output dim, got {d_out}")
    g = group if d_in % group == 0 else d_in  # fall back to one group
    ar = a.reshape(*batch, d_in // g, g, d_out)
    s = jnp.max(jnp.abs(ar), axis=-2, keepdims=True) / 7.0 + 1e-12
    q = jnp.clip(jnp.round(ar / s), -7, 7).astype(jnp.int32)
    q = q.reshape(*batch, d_in, d_out)
    # COLUMN packing, matching XLA's little-endian sub-byte layout:
    # output columns 2j (low nibble) and 2j+1 (high nibble) share a byte,
    # so the unpack is ``lax.bitcast_convert_type(int8 -> int4)`` — shape
    # [..., d_in, d_out/2, 2] — plus a trailing-dims reshape: both are
    # zero-movement layout ops, and the remaining convert+scale is the
    # same pattern as int8's dequant, which fuses into the consumer
    # matmul's operand read.  Row-direction packings (interleave or
    # halves + shifts/concat) all measured as materialization barriers
    # on-chip.  The signed high nibble keeps packed values inside int8.
    packed = ((q[..., 1::2] << 4) | (q[..., 0::2] & 0xF))
    return QTensor4(q=packed.astype(jnp.int8),
                    s=s.squeeze(-2).astype(scale_dtype))


@jax.tree_util.register_dataclass
@dataclass
class LayerOf:
    """Layer ``layer`` of a stacked expert bank (``stack.q`` ``[L, E, d_in,
    d_out]``) that rides a layer loop whole (:func:`ride_banks`).  A weight
    like the others: :func:`dequant` slices the layer, and with it
    :func:`qeinsum` and :func:`qragged_dot`'s fallback."""

    stack: QTensor
    layer: jnp.ndarray  # int32 scalar


def dequant(t) -> jnp.ndarray:
    """QTensor/QTensor4 → bf16 weight; plain arrays pass through.  XLA
    fuses the convert+scale into the operand read of a consumer that is
    its own dot (``qeinsum``); a consumer that is a custom call gets the
    bf16 weight materialized in HBM (see the module docstring)."""
    if isinstance(t, LayerOf):  # the slice the layer loop would have made
        t = jax.tree_util.tree_map(lambda a: a[t.layer], t.stack)
    if isinstance(t, QTensor):
        return t.q.astype(t.s.dtype) * t.s[..., None, :]
    if isinstance(t, QTensor4):
        *batch, d_in, d_out = t.shape
        w4 = jax.lax.bitcast_convert_type(t.q, jnp.int4)  # [.., di, do/2, 2]
        n_g = t.s.shape[-2]
        w = w4.astype(t.s.dtype).reshape(*batch, n_g, d_in // n_g, d_out)
        return (w * t.s[..., :, None, :]).reshape(*batch, d_in, d_out)
    return t


def qeinsum(subscript: str, x: jnp.ndarray, w, dtype=None) -> jnp.ndarray:
    """``jnp.einsum`` against a possibly-quantized weight (QTensor,
    QTensor4, or a plain array).  The dequant is expressed so XLA fuses
    it into the matmul's operand read — for packed int4 that hinges on
    the zero-movement bitcast unpack (see QTensor4); for int8 it is the
    plain convert+scale."""
    wd = dequant(w)
    if dtype is not None:
        wd = wd.astype(dtype)
    return jnp.einsum(subscript, x, wd)


def ragged_dot_path(w) -> tuple[str, str]:
    """(path, why not the one before it) of :func:`qragged_dot` for a bank
    ``w``, from its type, the backend, the mesh it was placed on and its
    shape — the names ``crowdllama_moe_matmul_path`` exports."""
    from crowdllama_tpu.ops.pallas.moe import grouped_matmul_refusal

    if isinstance(w, LayerOf):
        w = w.stack
    if isinstance(w, QTensor):
        why = grouped_matmul_refusal(w.q.shape, w.mesh_devices)
        return ("dequant_ragged_dot" if why else "int8_kernel"), why
    if isinstance(w, QTensor4):
        return "dequant_ragged_dot", (
            "int4 scales are group-wise along d_in and cannot multiply the "
            "product")
    return "ragged_dot", "the bank is not quantized"


_logged_fallbacks: set[str] = set()


def qragged_dot(xs: jnp.ndarray, w, group_sizes: jnp.ndarray) -> jnp.ndarray:
    """``lax.ragged_dot(xs, w, group_sizes)`` against a possibly-quantized
    expert bank ([E, d_in, d_out], or a :class:`LayerOf` a stacked one).

    - ``QTensor`` (int8, per-output-channel scales) on one TPU device (or
      in forced interpret mode) with dims that are multiples of 128: the
      Pallas ``moe_grouped_matmul`` reads the bank as int8, converts in
      VMEM and scales the float32 product — no bf16 bank in HBM;
    - ``QTensor`` elsewhere (CPU, a mesh of several devices, odd dims) and
      ``QTensor4`` (its scales vary along d_in): ``dequant`` then
      ``lax.ragged_dot``, the reason logged once (a WARNING on a TPU);
    - a plain array: ``lax.ragged_dot``.
    """
    path, why = ragged_dot_path(w)
    if path == "int8_kernel":
        from crowdllama_tpu.ops.pallas.moe import moe_grouped_matmul

        if isinstance(w, LayerOf):
            return moe_grouped_matmul(xs, w.stack.q, w.stack.s, group_sizes,
                                      w.layer)
        return moe_grouped_matmul(xs, w.q, w.s, group_sizes)
    if path == "dequant_ragged_dot" and why not in _logged_fallbacks:
        _logged_fallbacks.add(why)
        log.log(logging.WARNING if jax.default_backend() == "tpu"
                else logging.INFO,
                "expert banks are dequantized to bf16 in HBM for "
                "lax.ragged_dot, not read as int8 by the grouped-matmul "
                "kernel: %s", why)
    with jax.named_scope("bank_dequant"):
        bank = dequant(w)
    with jax.named_scope("ragged_dot"):
        return jax.lax.ragged_dot(xs, bank, group_sizes)


def qragged_fetched(xs: jnp.ndarray, w, group_sizes: jnp.ndarray):
    """bool ``[E]``: the banks of ``w`` that ``qragged_dot(xs, w,
    group_sizes)`` reads from HBM.  The int8 kernel's own rule
    (ops/pallas/moe.py ``banks_fetched``); ``lax.ragged_dot`` on the other
    paths reads every bank, dequantized whole or plain."""
    if ragged_dot_path(w)[0] == "int8_kernel":
        from crowdllama_tpu.ops.pallas.moe import banks_fetched

        q = (w.stack if isinstance(w, LayerOf) else w).q
        return banks_fetched(group_sizes, xs.shape[0], q.shape,
                             xs.dtype.itemsize)
    return jnp.ones(group_sizes.shape, bool)


# the scanned entry that stands in for the banks that ride
_LAYER_INDEX = "_layer_index"


def ride_banks(layers: Params):
    """For a ``lax.scan`` over stacked layer params: ``(what to scan,
    bind)`` with ``bind(lp)`` the layer's params inside the body.  The
    int8 expert banks the kernel will read (``[L, E, d_in, d_out]``) are
    taken out of the scan and ride it whole, each layer seeing a
    :class:`LayerOf` (the layer's index is scanned in their place): the
    kernel's index map picks the layer, where a scanned slice handed to a
    custom call is a 470 MB copy a matrix.  A consumer that is no kernel
    (``moe_dispatch="dense"``: ``qeinsum``) slices the layer in
    :func:`dequant`, as the scan would have.  Without such a bank the
    layers come back as they are and the program is the one it was."""
    riding = {k: w for k, w in layers.items()
              if isinstance(w, QTensor) and w.q.ndim == 4
              and ragged_dot_path(w)[0] == "int8_kernel"}
    if not riding:
        return layers, lambda lp: lp
    n_layers = next(iter(riding.values())).q.shape[0]
    scanned = {k: v for k, v in layers.items() if k not in riding}
    scanned[_LAYER_INDEX] = jnp.arange(n_layers, dtype=jnp.int32)

    def bind(lp: Params) -> Params:
        lp = dict(lp)
        li = lp.pop(_LAYER_INDEX)
        return {**lp, **{k: LayerOf(w, li) for k, w in riding.items()}}

    return scanned, bind


def quantize_params(params: Params, extra_keys: tuple[str, ...] = ("lm_head",),
                    mode: str = "int8") -> Params:
    """Quantize the large matmul weights of a transformer param pytree
    (models.transformer.init_params layout) in place-of.

    ``mode``: "int8" (per-output-channel) or "int4" (group-wise scales).
    Runs as ONE jitted program: eager per-op quantization costs a
    dispatch and a compile per op."""
    if mode not in ("int8", "int4"):
        raise ValueError(f"unknown quantization mode {mode!r}")
    qfn = quantize_weight if mode == "int8" else quantize_weight_int4

    def _stack(layers):
        # a hybrid model's layers are a list of layers per kind of layer
        if isinstance(layers, list):
            return [_stack(layer) for layer in layers]
        return {k: _stack(v) if isinstance(v, (dict, list))
                else qfn(v) if k in QUANT_KEYS else v
                for k, v in layers.items()}

    def _quantize(p: Params) -> Params:
        out = dict(p)
        out["layers"] = _stack(p["layers"])
        for k in extra_keys:
            if k in out:
                out[k] = qfn(out[k])
        return out

    return jax.jit(_quantize)(params)


def quantize_kv(x: jnp.ndarray, scale_dtype=jnp.bfloat16):
    """Per-vector symmetric int8 over the last axis (head_dim).

    For KV-cache entries: each (position, kv-head) vector gets one scale, so
    RoPE'd key magnitude drift across positions can't smear one position's
    range onto another.  Returns (q int8 same shape, scales shape[:-1]).
    """
    a = jnp.asarray(x, jnp.float32)
    s = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(a / s), -127, 127).astype(jnp.int8)
    return q, s.squeeze(-1).astype(scale_dtype)


def random_quantized_params(cfg, key: jax.Array, dtype=jnp.bfloat16,
                            mode: str = "int8") -> Params:
    """Random parameter pytree with the matmul weights *born* int8.

    Structurally (and throughput-) equivalent to
    ``quantize_params(transformer.init_params(cfg, key))``, but the bf16
    tree is never materialized: each leaf is allocated independently, so
    peak device memory is the int8 tree plus one leaf.  That is what lets
    an 8B model (16 GB bf16 — a whole v5e chip) initialize on the same chip
    it serves from.  This is what a worker started with ``--quantize`` and
    no checkpoint serves (engine/weights.py load_params_for); the values
    are a function of ``key`` alone.
    """
    from crowdllama_tpu.models import transformer as T
    from crowdllama_tpu.models.hybrid import special_leaf

    shapes = jax.eval_shape(lambda k: T.init_params(cfg, k, dtype), key)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaf_keys = jax.random.split(key, len(flat))

    norm_names = ("ln1", "ln2", "post_ln1", "post_ln2", "q_norm", "k_norm",
                  "final_norm", "norm", "gate_norm", "o_norm", "kv_norm",
                  "post_norm")

    def build(path, sds, k):
        name = path[-1].key
        if name in QUANT_KEYS or name == "lm_head":
            d_in = sds.shape[-2]
            if mode == "int4":
                g = GROUP if d_in % GROUP == 0 else d_in
                packed_shape = sds.shape[:-1] + (sds.shape[-1] // 2,)
                q = jax.random.randint(k, packed_shape, -112, 128,
                                       dtype=jnp.int32).astype(jnp.int8)
                s = jnp.full(sds.shape[:-2] + (d_in // g, sds.shape[-1]),
                             1.0 / (7.0 * math.sqrt(d_in)), dtype)
                return QTensor4(q=q, s=s)
            q = jax.random.randint(k, sds.shape, -127, 128, dtype=jnp.int8)
            s = jnp.full(sds.shape[:-2] + (sds.shape[-1],),
                         1.0 / (127.0 * math.sqrt(d_in)), dtype)
            return QTensor(q=q, s=s)
        if name in norm_names:  # gains are ones, incl. [nl, d] stacked ones
            return jnp.ones(sds.shape, sds.dtype)
        if name in ("bq", "bk", "bv"):  # qkv biases init to zero
            return jnp.zeros(sds.shape, sds.dtype)
        special = special_leaf(name, sds.shape, k, sds.dtype)
        if special is not None:  # state-space constants, correction bias
            return special
        if sds.ndim >= 2:  # embeddings / router / any remaining dense weight
            fan = sds.shape[-2]
            return (jax.random.normal(k, sds.shape, jnp.float32)
                    / math.sqrt(fan)).astype(sds.dtype)
        return jnp.ones(sds.shape, sds.dtype)

    leaves = [build(path, sds, k) for (path, sds), k in zip(flat, leaf_keys)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def drop_input_axis_spec(spec, ndim: int):
    """PartitionSpec for a QTensor's ``s`` given the weight's spec: pad the
    weight spec to full rank and drop the input dim (axis -2)."""
    from jax.sharding import PartitionSpec as P

    axes = tuple(spec) + (None,) * (ndim - len(tuple(spec)))
    return P(*(axes[:ndim - 2] + (axes[ndim - 1],)))
