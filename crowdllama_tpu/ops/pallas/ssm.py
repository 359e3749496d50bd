"""The decode step's state-space update as ONE pass over the state.

``ops/ssm.py`` ``ssm_update`` is two expressions, and XLA makes two
fusions of them in a step program: the update writes the new state into the
carried stack, and a second fusion reads the stack AGAIN for ``y = S C`` —
0.41 + 0.18 ms a Mamba layer where the state in and out once is 0.33
(benchmarks/chip/TRACING.nemotron_h.md; 56% of its roofline, PERF.md §6).
Here a tile ``[hb, P, N]`` of one slot's state is DMA'd in, becomes
``S' = exp(dt a) S + (dt x) (x) B``, is stored back to where it came from,
and ``y = S' C + D x`` is reduced from the tile while it is still in VMEM.

The state is the WHOLE carried stack ``[M, S, H, P, N]`` with the Mamba
layer's index, aliased from input to output (as the paged kernels take the
KV pool and ``moe_grouped_matmul`` the stacked bank): layer ``i``'s slab
is updated in place, and no other slab is touched, copied or rebuilt.

Everything is float32, in HBM and in VMEM: the state is carried over
thousands of steps (``ops/ssm.py``).  The update is the VPU's.  The
reduction over ``N`` for ``y`` is a sum of float32 numbers by the MXU: each
summand ``S'[p, n] C[n]`` is cut into three bf16 pieces that add up to it
EXACTLY (8 + 8 + 8 bits of the 24), and the pieces are multiplied by 1.0
and accumulated in float32 — a float32 sum in another order, and half the
passes of a ``HIGHEST`` matmul, which cuts the ones too.  On the VPU the
same reduction is a chain of lane rotations a vreg and paced the tile at 3x
its DMA (32% of the roofline; merged across vregs 22%: my chip runs, PR 35,
PERF.md §6).  A row whose ``dt`` is 0 leaves its tiles as they were
(``exp(0) = 1``, ``0 * x (x) B = 0``); no slot is skipped.

What is per head and small rides beside the tile in the layout the tile's
arithmetic needs, computed by XLA in the same jit (a few fusions over 1 MB
where the state is 134): ``exp(dt a)`` as SMEM scalars, and ``dt x`` and
``D x`` as COLUMNS — head dim on sublanes, heads on lanes — because a
tile's rows are the head dim and a row of ``x`` would have to be moved
across lanes, per head, inside the kernel.  ``y`` leaves the same way and
is transposed back outside.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from crowdllama_tpu.ops.pallas.flash import _interpret
from crowdllama_tpu.ops.pallas.paged import _layer_operand
from crowdllama_tpu.utils.env import env_flag

F32 = jnp.float32
_LANES = 128
# Bytes of state one grid step reads (and writes): the step is DMA-bound
# and costs ~0.4 us beside its DMA, so not small; two buffers each way and
# 2.5x a tile of scratch for y's summands and sums, so 6.5x this in VMEM,
# inside the 16 MiB a kernel is given by default.  1 and 2 MB read the same
# (77.4 / 77.5% of the roofline), 0.5 MB 65% (my chip runs, PR 35).
_TILE_BYTES = 1024 * 1024
# Heads the compiler sees at once in the loops over a tile's heads (the
# first that divides the tile's).  The loops are rolled because a start
# traces and lowers the kernel anew in every decode-type program: all 32
# at once lower in 0.34 s a program and read 78.0% of the roofline, 8 in
# 0.14 s and 77.4%, 4 75.6% (my chip runs, PR 35).
_UNROLLS = (8, 4, 2, 1)


def ssm_update_refusal(state_shape: tuple[int, ...]) -> str:
    """Why a state ``[.., H, P, N]`` does NOT go to the kernel ("" when it
    does): a TPU backend (or forced interpret mode), a head dim of whole
    sublanes and a state size of whole lanes.  Its one caller serves on one
    device (``engine/hybrid.py`` refuses a mesh)."""
    if env_flag("CROWDLLAMA_NO_PALLAS"):
        return "CROWDLLAMA_NO_PALLAS is set"
    if not _interpret() and jax.default_backend() != "tpu":
        return f"backend is {jax.default_backend()}, not tpu"
    p, n = state_shape[-2:]
    if p % 8 or n % _LANES:
        return (f"head dim {p} is not a multiple of 8 or state size {n} "
                f"not a multiple of {_LANES}")
    return ""


def choose_head_block(heads: int, p: int, n: int) -> int:
    """Heads a grid step updates: the most that divide ``heads``, fit one
    vreg's lanes and keep the tile within ``_TILE_BYTES`` (32 at Nemotron's
    128 x 64 x 128: 1 MB in + 1 MB out a step, 128 steps a layer)."""
    cap = max(1, min(_LANES, _TILE_BYTES // (p * n * 4)))
    return max(hb for hb in range(1, cap + 1) if heads % hb == 0)


def _kernel(layer, decay,                       # scalar prefetch (SMEM)
            cols_ref, b_ref, c_ref, s_ref,      # in
            y_ref, s_out_ref,                   # out
            parts_ref, sums_ref,                # scratch
            *, heads: int):
    del layer  # the index maps' alone
    s_i, blk = pl.program_id(0), pl.program_id(1)
    hb, p, n = s_ref.shape
    per_group = heads // b_ref.shape[0]
    lanes = cols_ref.shape[-1]
    h0 = blk * hb
    # this block's heads to lanes 0.., and from there ``unroll`` lanes a
    # turn of the loop: a head's column is at a static lane
    back = lax.rem(h0, jnp.int32(lanes))
    there = lax.rem(jnp.int32(lanes) - back, jnp.int32(lanes))
    bf16 = jnp.bfloat16
    unroll = next(u for u in _UNROLLS if hb % u == 0)

    def update(i, dtx):
        for u in range(unroll):
            j = i * unroll + u
            g = lax.div(h0 + j, jnp.int32(per_group))
            new = (s_ref[j] * decay[s_i * heads + h0 + j]
                   + dtx[:, u:u + 1] * b_ref[pl.ds(g, 1), :])
            s_out_ref[j] = new
            # y's summands as three bf16 pieces that add up to the float32
            # exactly (8 + 8 + 8 bits), for the MXU to sum in float32
            prod = new * c_ref[pl.ds(g, 1), :]
            hi = prod.astype(bf16)
            rest = prod - hi.astype(F32)
            mid = rest.astype(bf16)
            rows = pl.ds(pl.multiple_of(j * p, p), p)
            parts_ref[rows, 0:n] = hi
            parts_ref[rows, n:2 * n] = mid
            parts_ref[rows, 2 * n:3 * n] = (rest - mid.astype(F32)).astype(bf16)
        return pltpu.roll(dtx, lanes - unroll, 1)

    lax.fori_loop(0, hb // unroll, update,
                  pltpu.roll(cols_ref[0], there, 1))
    # every lane of a row of the product is the row's sum
    sums_ref[...] = jnp.dot(parts_ref[...], jnp.ones((3 * n, _LANES), bf16),
                            preferred_element_type=F32)
    lane = lax.broadcasted_iota(jnp.int32, (p, lanes), 1)

    def place(i, ys):
        for u in range(unroll):
            j = i * unroll + u
            y = sums_ref[pl.ds(pl.multiple_of(j * p, p), p), :]
            if lanes != _LANES:
                y = jnp.tile(y, (1, lanes // _LANES))
            ys = lax.select(lane == j, y, ys)
        return ys

    ys = lax.fori_loop(0, hb // unroll, place, jnp.zeros((p, lanes), F32))
    # the slot's [P, H] block of y stays in VMEM over its head blocks
    ys = pltpu.roll(ys, back, 1) + cols_ref[1]
    mine = (lane >= back) & (lane < back + hb)
    y_ref[...] = lax.select(mine, ys, y_ref[...])


def ssm_update(x, dt, a, b, c, d, stack, layer):
    """One step of the recurrence (``ops/ssm.py``) for S sequences, on layer
    ``layer``'s slab of the carried stack, in place.

    x ``[S, H, P]``, dt ``[S, H]`` (0 = leave the state), a ``[H]``, b, c
    ``[S, G, N]``, d ``[H]``, stack ``[M, S, H, P, N]`` float32, layer an
    int32 scalar.  Returns (y ``[S, H, P]`` float32, the stack)."""
    hb = choose_head_block(*stack.shape[2:])
    return _ssm_update(x, dt, a, b, c, d, stack, layer, head_block=hb,
                       interpret=_interpret())


# Its own jit: a program's Mamba layers are a Python list, and their call
# sites share ONE traced and lowered body (ops/pallas/moe.py has why).
@partial(jax.jit, static_argnames=("head_block", "interpret"))
def _ssm_update(x, dt, a, b, c, d, stack, layer, *, head_block, interpret):
    _, s, h, p, n = stack.shape
    g = b.shape[1]
    hb = head_block
    x, dt = x.astype(F32), dt.astype(F32)
    decay = jnp.exp(dt * a.astype(F32))
    cols = jnp.stack([dt[..., None] * x, d.astype(F32)[:, None] * x], 1)
    cols = jnp.swapaxes(cols, 2, 3)                        # [S, 2, P, H]
    lanes = -(-h // _LANES) * _LANES
    if lanes != h:
        cols = jnp.pad(cols, ((0, 0),) * 3 + ((0, lanes - h),))

    def slot(s_i, blk, *_):
        return s_i, 0, 0

    def tile(s_i, blk, layer, *_):
        return layer[0], s_i, blk, 0, 0

    state_spec = pl.BlockSpec((None, None, hb, p, n), tile)
    y, stack = pl.pallas_call(
        partial(_kernel, heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s, h // hb),
            in_specs=[
                pl.BlockSpec((None, 2, p, lanes),
                             lambda s_i, blk, *_: (s_i, 0, 0, 0)),
                pl.BlockSpec((None, g, n), slot),
                pl.BlockSpec((None, g, n), slot),
                state_spec,
            ],
            out_specs=[pl.BlockSpec((None, p, lanes), slot), state_spec],
            scratch_shapes=[pltpu.VMEM((hb * p, 3 * n), jnp.bfloat16),
                            pltpu.VMEM((hb * p, _LANES), F32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((s, p, lanes), F32),
                   jax.ShapeDtypeStruct(stack.shape, F32)],
        # operands count the scalar prefetch: the stack is the sixth
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=6 * s * h * p * n, transcendentals=0,
            bytes_accessed=2 * s * h * p * n * 4),
        interpret=interpret,
        name="ssm_update",
    )(_layer_operand(layer), decay.reshape(-1), cols, b.astype(F32),
      c.astype(F32), stack)
    return jnp.swapaxes(y[:, :, :h], 1, 2), stack
