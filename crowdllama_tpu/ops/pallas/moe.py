"""Grouped matmul over an int8 expert bank that stays int8 in HBM.

``lax.ragged_dot`` is a custom call, so XLA cannot fuse a bank's
dequantize into its operand read as it does for a dense ``qeinsum``: every
matrix of every expert layer was read as int8, written whole as bf16 and
read again as bf16, every step, touched or not — 54% + 33% of a Mixtral
decode step and 51% + 32.5% of a Nemotron one (PERF_LEDGER.jsonl, PR 27).
Here the grouped matmul reads the int8 tiles itself: a tile is DMA'd as
int8, converted to bf16 in VMEM (every int8 value is exact in bf16),
multiplied bf16 x bf16 on the MXU into a float32 accumulator, and on the
last ``d_in`` tile the accumulator is multiplied by the expert's
per-output-channel scale row in float32 and stored.  That is
``ragged_dot(xs, q * s)`` with one rounding fewer (``q * s`` is no longer
rounded to bf16 first): no activation is quantized, no row, expert or term
is left out.

The grid follows ``jax.experimental.pallas.ops.tpu.megablox.gmm`` (which
refuses int8): output-column tiles x VISITS x ``d_in`` tiles, a visit
being one (group, row tile) pair the rows of which the group owns, the
visits' group and row-tile ids scalar-prefetched.  Rows past the last
group are no group's and come out zero (as ``lax.ragged_dot`` gives them
on the CPU; on the TPU it leaves them unwritten).

A group of size 0 is visited once and its bank fetched, though nothing is
computed or stored for it: every step reads every bank of the layer, as
``lax.ragged_dot`` did (why, and what leaving those visits out gives:
PERF.md §7 item 5).

The bank may be a STACKED leaf ``[L, E, d_in, d_out]`` with a layer
index, as the paged kernels take the whole KV pool: a layer loop that
scans the stack would hand a custom call a materialized ``[E, d_in,
d_out]`` slice (470 MB read and written a matrix on Mixtral).
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

# What one grid step may hold in VMEM: the int8 bank tile twice (the
# pipeline's double buffer), its bf16 conversion a chunk at a time, the
# row tile of xs twice, the output tile twice and the float32 accumulator.
# v5e has 128 MiB of VMEM, 16 MiB of it scoped by default; the call asks
# for what its tiles need.
_VMEM_BUDGET_BYTES = 40 * 1024 * 1024
# Elements of the bank one step fetches (int8: bytes).  Large, because the
# step is DMA-bound and every step costs 0.4-1 us beside its DMA (4 MB
# steps read 87% of the roofline, 2 MB steps 70-83%: my chip run, PR 28).
_BANK_TILE_ELEMS = 4 * 1024 * 1024
# d_in rows converted and multiplied at a time inside a step: bounds the
# bf16 copy (and the unrolled code) whatever the DMA tile is.  Each chunk
# is one more read-modify-write of the accumulator: 1024 rows, not 256.
_K_CHUNK = 1024
_ROW_TILE_MIN, _ROW_TILE_MAX = 32, 128
# scale rows fetched per visit: a bf16 block's sublane tile
_SCALE_ROWS = 16


def grouped_matmul_refusal(q_shape: tuple[int, ...], n_devices: int = 1) -> str:
    """Why an int8 bank of shape ``[.., E, d_in, d_out]`` does NOT go to
    the kernel ("" when it does): a TPU backend (or forced interpret
    mode), one device (``pallas_call`` is not partitioned by GSPMD and the
    kernel is not shard_map-wrapped), lane-aligned dims."""
    if env_flag("CROWDLLAMA_NO_PALLAS"):
        return "CROWDLLAMA_NO_PALLAS is set"
    if not _interpret() and jax.default_backend() != "tpu":
        return f"backend is {jax.default_backend()}, not tpu"
    if n_devices > 1:
        return (f"the bank lies on a mesh of {n_devices} devices (the "
                f"kernel is not shard_map-wrapped)")
    d_in, d_out = q_shape[-2:]
    if d_in % 128 or d_out % 128:
        return f"bank dims {d_in} x {d_out} are not multiples of 128"
    return ""


def _divisor_tile(extent: int, cap: int) -> int:
    """Largest multiple of 128 that divides ``extent`` and is ≤ cap
    (``extent`` is a multiple of 128)."""
    units = extent // 128
    best = 1
    for u in range(1, units + 1):
        if units % u == 0 and u * 128 <= cap:
            best = u
    return best * 128


def _k_chunk(tk: int) -> int:
    return _divisor_tile(tk, _K_CHUNK)


def _vmem_bytes(tm: int, tk: int, tn: int, x_itemsize: int) -> int:
    return (2 * tk * tn                      # int8 bank tile, two buffers
            + _k_chunk(tk) * tn * (2 + 4)    # a chunk's bf16 copy, f32 stage
            + 2 * tm * tk * x_itemsize       # xs tile, two buffers
            + 2 * tm * tn * x_itemsize       # out tile, two buffers
            + tm * tn * 4                    # accumulator
            + 2 * _SCALE_ROWS * tn * 4)


def choose_tiles(m: int, groups: int, d_in: int, d_out: int,
                 x_itemsize: int = 2) -> tuple[int, int, int]:
    """(row tile, ``d_in`` tile, column tile) from the shapes alone.

    Rows: every visit pushes a whole row tile through the MXU against a
    whole bank tile, masked rows too, and a group that straddles a tile
    boundary fetches its matrix twice: twice an average group, between 32
    (decode: a few rows a group) and 128 (a long prefill, where 256 and 512
    measured slower: my chip run, PR 28).  Bank tile: all of ``d_in`` when
    that leaves 128 columns (one ``d_in`` tile: no accumulator round trip,
    and the xs tile is fetched once a row tile, not once a step), as wide
    in ``d_out`` as ``_BANK_TILE_ELEMS`` and the VMEM budget allow."""
    avg = -(-m // max(groups, 1))
    tm = _ROW_TILE_MIN
    while tm < min(2 * avg, _ROW_TILE_MAX):
        tm *= 2
    tk = d_in if d_in * 128 <= _BANK_TILE_ELEMS else _divisor_tile(
        d_in, _BANK_TILE_ELEMS // 512)
    tn = _divisor_tile(d_out, max(128, _BANK_TILE_ELEMS // tk))
    while _vmem_bytes(tm, tk, tn, x_itemsize) > _VMEM_BUDGET_BYTES and tn > 128:
        tn = _divisor_tile(d_out, tn - 128)
    while _vmem_bytes(tm, tk, tn, x_itemsize) > _VMEM_BUDGET_BYTES and tk > 128:
        tk = _divisor_tile(d_in, tk - 128)
    return tm, tk, tn


def _group_tiles(sizes, starts, ends, tm: int, tiles_m: int):
    """(first row tile, number of visits) of every group: a visit for every
    row tile the group's rows lie in, and ONE (of the tile its rows would
    start in) for a group that has none — which is what fetches an
    unrouted bank.  The one statement of that rule: :func:`_visits` lays
    the grid out from it and :func:`banks_fetched` counts from it."""
    first_tile = lax.min(lax.div(starts, jnp.int32(tm)),
                         jnp.int32(tiles_m - 1))
    tiles = lax.select(sizes > 0,
                       lax.div(ends + jnp.int32(tm - 1), jnp.int32(tm))
                       - first_tile, lax.full_like(sizes, 1))
    return first_tile, tiles


def banks_fetched(group_sizes: jnp.ndarray, m: int,
                  q_shape: tuple[int, ...], x_itemsize: int = 2):
    """bool ``[E]``: the groups whose bank ``moe_grouped_matmul`` reads
    from HBM for ``m`` rows against a bank of shape ``q_shape`` — those it
    visits at least once, by the tiles it would choose.  What the expert
    layer's ``fetched`` counter counts (models/hybrid.py ``held_sum``)."""
    tm = choose_tiles(m, q_shape[-3], *q_shape[-2:], x_itemsize)[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = lax.cumsum(sizes, axis=0)
    return _group_tiles(sizes, ends - sizes, ends, tm, -(-m // tm))[1] > 0


def _visits(group_sizes: jnp.ndarray, tm: int, tiles_m: int):
    """The (group, row tile) pairs to visit, in row order, as
    :func:`_group_tiles` counts them.  Returns (group ids [V], row-tile ids
    [V], group starts [E], group ends [E], number of visits) with V =
    tiles_m + E - 1, the most there can be; entries past the number of
    visits are never read.  Written in ``lax`` primitives: every ``jnp`` wrapper here is one
    more nested ``jit`` to trace and lower, at every call site's first use
    in every program of every start (PERF.md §6, PR 29)."""
    e = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = lax.cumsum(sizes, axis=0)
    starts = ends - sizes
    first_tile, tiles = _group_tiles(sizes, starts, ends, tm, tiles_m)
    last_visit = lax.cumsum(tiles, axis=0)   # one past the group's last
    v_max = tiles_m + e - 1
    visit = lax.iota(jnp.int32, v_max)
    # the group of visit v: how many groups' visits all lie before it
    # (entries past the last visit repeat the last group)
    before = (visit[:, None] >= last_visit[None, :]).astype(jnp.int32)
    group_ids = lax.min(lax.reduce_sum(before, (1,)), jnp.int32(e - 1))
    # first row tile of the group, less the visits that came before it
    offset = first_tile - (last_visit - tiles)
    m_tile_ids = lax.clamp(jnp.int32(0), offset[group_ids] + visit,
                           jnp.int32(tiles_m - 1))
    return group_ids, m_tile_ids, starts, ends, last_visit[-1]


def _kernel(group_ids, m_tile_ids, starts, ends, layer,   # scalar prefetch
            xs_ref, q_ref, s_ref, out_ref, acc_ref):
    del layer  # the index maps' alone
    v = pl.program_id(1)
    k_i = pl.program_id(2)
    tm, tn = out_ref.shape
    tk = q_ref.shape[0]
    chunk = _k_chunk(tk)
    g = group_ids[v]
    routed = ends[g] > starts[g]

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(routed)
    def _accumulate():
        def body(c, carry):
            k0 = pl.multiple_of(c * chunk, chunk)
            w = q_ref[pl.ds(k0, chunk), :].astype(jnp.bfloat16)
            x = xs_ref[:, pl.ds(k0, chunk)].astype(jnp.bfloat16)
            acc_ref[...] += lax.dot_general(
                x, w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return carry

        lax.fori_loop(0, tk // chunk, body, 0)

    @pl.when(routed & (k_i == pl.num_programs(2) - 1))
    def _store():
        # the expert's scale row out of its block of _SCALE_ROWS
        srows = lax.broadcasted_iota(jnp.int32, s_ref.shape, 0)
        s_all = s_ref[...].astype(jnp.float32)
        scale = jnp.sum(
            lax.select(srows == lax.rem(g, jnp.int32(s_ref.shape[0])),
                       s_all, lax.full_like(s_all, 0)),
            axis=0, keepdims=True)
        rows = (m_tile_ids[v] * tm
                + lax.broadcasted_iota(jnp.int32, (tm, tn), 0))
        mine = (rows >= starts[g]) & (rows < ends[g])
        # a row tile's visits are consecutive, so what the other groups
        # stored is still in the block; rows no group stores are zeroed
        # after the call
        out_ref[...] = lax.select(
            mine, acc_ref[...] * scale,
            out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def moe_grouped_matmul(xs: jnp.ndarray, q: jnp.ndarray, s: jnp.ndarray,
                       group_sizes: jnp.ndarray, layer=None) -> jnp.ndarray:
    """``lax.ragged_dot(xs, q * s, group_sizes)`` with ``q`` read as int8.

    xs ``[M, d_in]``; q ``[E, d_in, d_out]`` int8 with s ``[E, d_out]``,
    or the stacked ``[L, E, d_in, d_out]`` / ``[L, E, d_out]`` with
    ``layer`` an int32 scalar; group_sizes ``[E]``.  Returns ``[M,
    d_out]`` in ``xs.dtype``; rows past ``sum(group_sizes)`` are zero."""
    tiles = choose_tiles(xs.shape[0], q.shape[-3], *q.shape[-2:],
                         xs.dtype.itemsize)
    return _grouped_matmul(xs, q, s, group_sizes, layer, tiles=tiles,
                           interpret=_interpret())


# Its own jit, so that the call sites of one program that agree in shapes —
# a model whose layers are a Python list has one or two a layer — are ONE
# traced and lowered kernel body, called from each: a start traces and
# lowers every warm-up program anew, persistent compile cache or not, and
# ten kernel bodies a program were 8 s of a Nemotron worker's warm start
# (PERF.md §6, PR 29).  XLA inlines the calls: the compiled programs are
# the same.  What the trace reads beside the shapes is a static argument.
@partial(jax.jit, static_argnames=("tiles", "interpret"))
def _grouped_matmul(xs, q, s, group_sizes, layer, *, tiles, interpret):
    if layer is None:
        q, s, layer = q[None], s[None], 0
    m, d_in = xs.shape
    _, e, _, d_out = q.shape
    tm, tk, tn = tiles
    m_pad = -(-m // tm) * tm
    if m_pad != m:
        xs = jnp.pad(xs, ((0, m_pad - m), (0, 0)))
    tiles_m, tiles_k, tiles_n = m_pad // tm, d_in // tk, d_out // tn
    group_ids, m_tile_ids, starts, ends, n_visits = _visits(
        group_sizes, tm, tiles_m)
    srows = min(e, _SCALE_ROWS)

    def xs_map(n_i, v, k_i, gids, mids, *_):
        return mids[v], k_i

    def q_map(n_i, v, k_i, gids, mids, st, en, layer):
        return layer[0], gids[v], k_i, n_i

    def s_map(n_i, v, k_i, gids, mids, st, en, layer):
        return layer[0], gids[v] // srows, n_i

    def out_map(n_i, v, k_i, gids, mids, *_):
        return mids[v], n_i

    vmem = _vmem_bytes(tm, tk, tn, xs.dtype.itemsize)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(tiles_n, n_visits, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk), xs_map),
                pl.BlockSpec((None, None, tk, tn), q_map),
                pl.BlockSpec((None, srows, tn), s_map),
            ],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, d_out), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem * 1.25) + (4 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * d_in * d_out, transcendentals=0,
            bytes_accessed=(e * d_in * d_out
                            + m * (d_in + d_out) * xs.dtype.itemsize)),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(group_ids, m_tile_ids, starts, ends,
      _layer_operand(layer), xs, q, s)
    live = lax.broadcasted_iota(jnp.int32, out.shape, 0) < ends[-1]
    return lax.select(live, out, lax.full_like(out, 0))[:m]
