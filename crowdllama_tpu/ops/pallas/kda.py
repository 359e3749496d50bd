"""The decode step's delta-rule update as ONE pass over the state.

``ops/kda.py`` ``kda_update`` is four expressions over a layer's ``[S, H,
dk, dv]`` float32 slab (decay, ``S'^T k``, the rank-one delta, ``S^T q``),
and as XLA fusions the slab is read more than once (PR 35 read the same of
the state-space update: two fusions, 56% of the roofline).  Here a tile
``[hb, dk, dv]`` of one slot's state is DMA'd in, becomes ``S' = Diag(a) S``,
``u = S'^T k`` is reduced from it, ``S = S' + (beta k) (v - u)^T`` is stored
back to where it came from, and ``o = S^T q`` is reduced from the tile while
it is still in VMEM.

The state is the WHOLE carried stack ``[L_K, S, H, dk, dv]`` with the KDA
layer's index, aliased from input to output (as ``ssm_update`` takes the
state-space stack and the paged kernels the KV pool): layer ``i``'s slab is
updated in place, and no other slab is touched, copied or rebuilt.

Everything is float32 and the VPU's.  Both reductions run over ``dk``, the
tile's SUBLANE axis: a sum over the rows of a ``[dk, dv]`` tile is vreg
adds and one 8-row fold, exact in float32 — not the lane reduction that
paced ``ssm_update`` at a third of its DMA and had to go to the MXU in
three bf16 pieces (PR 35).  What is per key channel (``a``, ``k``, ``beta
k``, ``q``) has to meet the tile as COLUMNS, key dim on sublanes; XLA lays
the four out in the same jit as ``[S, H / hb, dk, 4 hb]`` — a block's heads
side by side on lanes, so that a head's column sits at a static lane — a
few fusions over 2 MB where the slab is 67.  ``v`` and ``o`` are rows.  A
row whose ``g`` is 0 and ``beta`` 0 leaves its tiles as they were; no slot
is skipped.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from crowdllama_tpu.ops.pallas.flash import _interpret
from crowdllama_tpu.ops.pallas.paged import _layer_operand
from crowdllama_tpu.utils.env import env_flag

F32 = jnp.float32
_LANES = 128
# Bytes of state one grid step reads (and writes), as ops/pallas/ssm.py:
# 1 and 2 MB read the same there, 0.5 MB 12 points less.
_TILE_BYTES = 1024 * 1024


def kda_update_refusal(state_shape: tuple[int, ...]) -> str:
    """Why a state ``[.., H, dk, dv]`` does NOT go to the kernel ("" when
    it does): a TPU backend (or forced interpret mode), a key dim of whole
    sublanes and a value dim of whole lanes.  Its one caller serves on one
    device (``engine/hybrid.py`` refuses a mesh)."""
    if env_flag("CROWDLLAMA_NO_PALLAS"):
        return "CROWDLLAMA_NO_PALLAS is set"
    if not _interpret() and jax.default_backend() != "tpu":
        return f"backend is {jax.default_backend()}, not tpu"
    dk, dv = state_shape[-2:]
    if dk % 8 or dv % _LANES:
        return (f"key dim {dk} is not a multiple of 8 or value dim {dv} "
                f"not a multiple of {_LANES}")
    return ""


def choose_head_block(heads: int, dk: int, dv: int) -> int:
    """Heads a grid step updates: the most that divide ``heads`` and keep
    the tile within ``_TILE_BYTES`` (16 at Kimi's 32 x 128 x 128: 1 MB in
    + 1 MB out a step, 64 steps a layer at 32 slots)."""
    cap = max(1, _TILE_BYTES // (dk * dv * 4))
    return max(hb for hb in range(1, cap + 1) if heads % hb == 0)


def _kernel(layer, cols_ref, v_ref, s_ref, o_ref, s_out_ref):
    del layer  # the index maps' alone
    hb = s_ref.shape[0]
    for j in range(hb):
        col = lambda c: cols_ref[:, c * hb + j:c * hb + j + 1]   # [dk, 1]
        new = s_ref[j] * col(0)
        u = jnp.sum(new * col(1), axis=0, keepdims=True)         # [1, dv]
        new = new + col(2) * (v_ref[j:j + 1, :] - u)
        s_out_ref[j] = new
        o_ref[j:j + 1, :] = jnp.sum(new * col(3), axis=0, keepdims=True)


def kda_update(q, k, v, g, beta, stack, layer):
    """One step of the recurrence (``ops/kda.py``) for S sequences, on layer
    ``layer``'s slab of the carried stack, in place.

    q, k, g ``[S, H, dk]``, v ``[S, H, dv]``, beta ``[S, H]``, stack ``[L_K,
    S, H, dk, dv]`` float32, layer an int32 scalar.  Returns (o ``[S, H,
    dv]`` float32, the stack)."""
    hb = choose_head_block(*stack.shape[2:])
    return _kda_update(q, k, v, g, beta, stack, layer, head_block=hb,
                       interpret=_interpret())


# Its own jit: a program's KDA layers are a Python list, and their call
# sites share ONE traced and lowered body (ops/pallas/moe.py has why).
@partial(jax.jit, static_argnames=("head_block", "interpret"))
def _kda_update(q, k, v, g, beta, stack, layer, *, head_block, interpret):
    _, s, h, dk, dv = stack.shape
    hb = head_block
    q, k, v, g, beta = (m.astype(F32) for m in (q, k, v, g, beta))
    cols = jnp.stack([jnp.exp(g), k, beta[..., None] * k, q], axis=1)
    # [S, 4, H, dk] -> [S, H/hb, dk, 4 * hb]: lane c * hb + j is column c
    # of the block's head j
    cols = cols.reshape(s, 4, h // hb, hb, dk).transpose(0, 2, 4, 1, 3)
    cols = cols.reshape(s, h // hb, dk, 4 * hb)

    def heads(s_i, blk, *_):
        return s_i, blk, 0

    def tile(s_i, blk, layer):
        return layer[0], s_i, blk, 0, 0

    state_spec = pl.BlockSpec((None, None, hb, dk, dv), tile)
    o, stack = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, h // hb),
            in_specs=[
                pl.BlockSpec((None, None, dk, 4 * hb),
                             lambda s_i, blk, *_: (s_i, blk, 0, 0)),
                pl.BlockSpec((None, hb, dv), heads),
                state_spec,
            ],
            out_specs=[pl.BlockSpec((None, hb, dv), heads), state_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((s, h, dv), F32),
                   jax.ShapeDtypeStruct(stack.shape, F32)],
        # operands count the scalar prefetch: the stack is the fourth
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=8 * s * h * dk * dv, transcendentals=0,
            bytes_accessed=2 * s * h * dk * dv * 4),
        interpret=interpret,
        name="kda_update",
    )(_layer_operand(layer), cols, v, stack)
    return o, stack
