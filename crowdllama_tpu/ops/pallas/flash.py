"""Flash-attention Pallas TPU kernels (prefill + cached decode).

Same semantics as the jnp reference ops in ``crowdllama_tpu.ops.attention``
(GQA, fp32 online softmax, Gemma-2 logit softcap, sliding window, padding
masks) but shaped for the TPU memory hierarchy: each (batch, head) program
holds its K/V rows in VMEM (budget-gated in ``pallas_supported``), the score
matrix never materializes beyond one ``[TQ, G, TK]`` tile, and softmax runs
online (running max / denominator), so HBM traffic is one read of Q/K/V and
one write of O.  A grid-tiled KV dimension (for extents past the VMEM
budget) is future work.

Layout discipline: the engine's KV layout is head-major (``[B, Hkv, S, Dh]``)
so each (batch, head) pair's sequence is one contiguous [S, Dh] plane — the
kernels block directly into it (full-extent last two dims, satisfying
Mosaic's block constraints) and the streamed KV tiles are contiguous DMAs.
No transposed copy of the cache is ever created (the cache read IS the
decode-time HBM bottleneck).  Position/validity vectors are pre-shaped
host-side ([B,T,1,1] / [B,1,T]) so every in-kernel broadcast is layout-free
(unit sublane/lane expansion only, never a relayout).

The reference project has no kernels at all (it delegates compute to Ollama,
/root/reference/pkg/crowdllama/api.go:108-160); this file is part of what
replaces that delegation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from crowdllama_tpu.ops.attention import NEG_INF, _softcap
from crowdllama_tpu.utils.env import env_flag

# Each (batch, head) program keeps its full K and V rows resident in VMEM
# (BlockSpecs below); cap their combined footprint well under the ~16 MB of
# VMEM so Q/O/accumulators and double-buffering still fit.
_VMEM_KV_BUDGET_BYTES = 8 * 1024 * 1024
# Elements of a query block [TQ, G, Dh] (and of its float32 accumulator)
# where a kv head's queries are wider than 16 x 128.
_Q_BLOCK_ELEMS = 256 * 8 * 128


def pallas_refusal(seq_len: int, head_dim: int, itemsize: int = 2,
                   n_shards: int = 1) -> str:
    """Why the pallas path does NOT apply ("" when it does): it needs a TPU
    backend (or interpret mode forced via CROWDLLAMA_PALLAS_INTERPRET), an
    unsharded mesh (``pallas_call`` cannot be auto-partitioned by GSPMD —
    multi-chip callers stay on the XLA path until the kernels are
    shard_map-wrapped), a hardware-sized tile (≥32; odd/prime extents
    would degenerate), and DOUBLE-BUFFERED K+V rows fitting the VMEM
    budget (the 4x bound is what the decode kernel's head-batch loop
    actually requires at hb=1 — a 2x gate here let the hb=1 grid run over
    budget in the gap, ADVICE r4).  Runners log the reason at build so a
    refused shape on a chip is never silent."""
    if env_flag("CROWDLLAMA_NO_PALLAS"):
        return "CROWDLLAMA_NO_PALLAS is set"
    if not _interpret() and jax.default_backend() != "tpu":
        return f"backend is {jax.default_backend()}, not tpu"
    if n_shards > 1:
        return f"mesh has {n_shards} devices (kernel is not shard_map-wrapped)"
    if 4 * seq_len * head_dim * itemsize > _VMEM_KV_BUDGET_BYTES:
        return (f"double-buffered K+V rows ({4 * seq_len * head_dim * itemsize}"
                f" B at T={seq_len}) exceed the VMEM budget")
    if _tile(seq_len) < 32:
        return f"T={seq_len} has no power-of-two tile >= 32"
    return ""


def pallas_supported(seq_len: int, head_dim: int, itemsize: int = 2,
                     n_shards: int = 1) -> bool:
    return not pallas_refusal(seq_len, head_dim, itemsize, n_shards)


def _interpret() -> bool:
    return env_flag("CROWDLLAMA_PALLAS_INTERPRET")


def _tile(extent: int, cap: int = 512) -> int:
    """Largest power-of-two tile ≤ cap dividing ``extent`` (≥1)."""
    t = 1
    while t * 2 <= min(extent, cap) and extent % (t * 2) == 0:
        t *= 2
    return t


# ---------------------------------------------------------------- prefill

def _prefill_kernel(
    window_ref,  # SMEM [1, 1] int32 — sliding window (<=0 disables)
    q_ref,       # [TQ, G, Dh]
    k_ref,       # [T, Dh]     full K row for this (b, h)
    v_ref,       # [T, Dh]
    qpos_ref,    # [TQ, 1, 1] int32
    kpos_ref,    # [T/tk, 1, tk] int32 — tile index outer (lane dims cannot
    valid_ref,   # [T/tk, 1, tk] int32    be dynamically sliced unaligned)
    o_ref,       # [TQ, G, Dh]
    *,
    scale: float,
    softcap: float,
    tk: int,
    tq: int,
    causal_rows: bool,
):
    t = k_ref.shape[0]
    tq_, g, dh = q_ref.shape
    q = q_ref[:].astype(jnp.float32)
    qpos = qpos_ref[:]          # [TQ, 1, 1]
    window = window_ref[0, 0]

    num_tiles = t // tk
    if causal_rows:
        # positions[b, t] <= t for every caller (arange, or arange clamped to
        # plen-1), so KV tiles strictly above this Q block are fully masked.
        i = pl.program_id(2)
        num_tiles = jnp.minimum(num_tiles, pl.cdiv((i + 1) * tq, tk))

    def body(j, carry):
        acc, m, l = carry
        k_tile = k_ref[pl.ds(j * tk, tk), :].astype(jnp.float32)
        v_tile = v_ref[pl.ds(j * tk, tk), :].astype(jnp.float32)
        kpos = kpos_ref[j][None]   # [1, 1, TK]
        kval = valid_ref[j][None]  # [1, 1, TK]

        # [TQ, G, TK] = [TQ, G, Dh] · [TK, Dh]^T
        logits = jax.lax.dot_general(
            q, k_tile, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        logits = _softcap(logits, softcap)

        mask = (kpos <= qpos) & (kval > 0)
        mask &= (window <= 0) | (kpos > qpos - window)
        logits = jnp.where(mask, logits, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new) * mask.astype(jnp.float32)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # [TQ, G, Dh] += [TQ, G, TK] · [TK, Dh]
        pv = jax.lax.dot_general(
            p, v_tile, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc * alpha + pv, m_new, l_new

    acc = jnp.zeros((tq_, g, dh), jnp.float32)
    m = jnp.full((tq_, g, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((tq_, g, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, num_tiles, body, (acc, m, l))

    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[:] = (acc / l).astype(o_ref.dtype)


def flash_prefill_attention(
    q: jnp.ndarray,  # [B, T, H, Dh]
    k: jnp.ndarray,  # [B, Hkv, T, Dh]
    v: jnp.ndarray,  # [B, Hkv, T, Dh]
    positions: jnp.ndarray,  # [B, T] int32
    scale: float,
    softcap: float = 0.0,
    sliding_window: int | jnp.ndarray = 0,
    kv_valid: jnp.ndarray | None = None,  # [B, T] bool
    causal_rows: bool = True,
) -> jnp.ndarray:
    """Tiled causal prefill attention.  ``causal_rows=True`` asserts the
    caller's invariant ``positions[b, t] <= t`` (true for arange and for
    arange clamped at plen-1), enabling the upper-triangle tile skip."""
    b, t, h, dh = q.shape
    hkv = k.shape[1]
    g = h // hkv
    # 256 query rows a block for every GQA family (up to 16 query heads a kv
    # head at Dh 128: the blocks the chip has run); a latent head (32 queries
    # on ONE 576-wide row, models/hybrid.py) gets the rows that fit
    # _Q_BLOCK_ELEMS, or the block outgrows VMEM
    tq = _tile(t, 256 if g * dh <= 16 * 128
               else max(8, _Q_BLOCK_ELEMS // (g * dh)))
    tk = _tile(t, 512)

    qg = q.reshape(b, t, hkv, g, dh)
    positions = positions.astype(jnp.int32)
    qpos = positions.reshape(b, t, 1, 1)
    kpos = positions.reshape(b, t // tk, 1, tk)
    window = jnp.asarray(sliding_window, jnp.int32).reshape(1, 1)
    valid = (
        jnp.ones((b, t // tk, 1, tk), jnp.int32)
        if kv_valid is None
        else kv_valid.astype(jnp.int32).reshape(b, t // tk, 1, tk)
    )

    kernel = functools.partial(
        _prefill_kernel, scale=scale, softcap=float(softcap or 0.0),
        tk=tk, tq=tq, causal_rows=causal_rows,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, hkv, t // tq),
        in_specs=[
            pl.BlockSpec((1, 1), lambda bi, hi, qi: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, tq, None, g, dh),
                         lambda bi, hi, qi: (bi, qi, hi, 0, 0)),
            pl.BlockSpec((None, None, t, dh),
                         lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, t, dh),
                         lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, tq, 1, 1), lambda bi, hi, qi: (bi, qi, 0, 0)),
            pl.BlockSpec((None, t // tk, 1, tk),
                         lambda bi, hi, qi: (bi, 0, 0, 0)),
            pl.BlockSpec((None, t // tk, 1, tk),
                         lambda bi, hi, qi: (bi, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, tq, None, g, dh),
                               lambda bi, hi, qi: (bi, qi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, t, hkv, g, dh), q.dtype),
        interpret=_interpret(),
        name="flash_prefill_attention",
    )(window, qg, k, v, qpos, kpos, valid)
    return out.reshape(b, t, h, dh)


# ----------------------------------------------------------------- decode

def _decode_kernel(
    window_ref,   # SMEM [1, 1] int32
    seqlen_ref,   # SMEM [1, B] int32 — valid cache length per slot
    q_ref,        # [HB, G, Dh] — HB kv heads per grid step
    k_ref,        # [HB, S, Dh]
    v_ref,        # [HB, S, Dh]
    o_ref,        # [HB, G, Dh]
    *,
    scale: float,
    softcap: float,
    tk: int,
):
    hb, g, dh = q_ref.shape
    q = q_ref[...].astype(jnp.float32)
    seq_len = seqlen_ref[0, pl.program_id(0)]
    window = window_ref[0, 0]

    # Dynamic bound skips COMPUTE past seq_len (the full K/V rows are still
    # block-copied to VMEM by the BlockSpec — this saves MXU/VPU time only).
    num_tiles = pl.cdiv(jnp.maximum(seq_len, 1), tk)

    def body(j, carry):
        acc, m, l = carry
        k_tile = k_ref[:, pl.ds(j * tk, tk), :].astype(jnp.float32)
        v_tile = v_ref[:, pl.ds(j * tk, tk), :].astype(jnp.float32)
        kpos = j * tk + jax.lax.broadcasted_iota(jnp.int32, (1, 1, tk), 2)

        # [HB, G, TK] = [HB, G, Dh] · [HB, TK, Dh]^T — every kv head in
        # this grid step as one batched MXU issue (same bubble-bound
        # reasoning as the paged kernel's head batching: fewer, fatter
        # sequential grid steps).
        logits = jax.lax.dot_general(
            q, k_tile, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale
        logits = _softcap(logits, softcap)

        mask = kpos < seq_len
        mask &= (window <= 0) | (kpos > (seq_len - 1) - window)
        logits = jnp.where(mask, logits, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new) * mask.astype(jnp.float32)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_tile, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        return acc * alpha + pv, m_new, l_new

    acc = jnp.zeros((hb, g, dh), jnp.float32)
    m = jnp.full((hb, g, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((hb, g, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, num_tiles, body, (acc, m, l))

    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (acc / l).astype(o_ref.dtype)


def flash_decode_attention(
    q: jnp.ndarray,        # [B, H, Dh]
    k_cache: jnp.ndarray,  # [B, Hkv, S, Dh]
    v_cache: jnp.ndarray,  # [B, Hkv, S, Dh]
    seq_lens: jnp.ndarray,  # [B] int32
    scale: float,
    softcap: float = 0.0,
    sliding_window: int | jnp.ndarray = 0,
) -> jnp.ndarray:
    """One cached decode step, KV streamed tile-by-tile with an early exit
    past ``seq_len``."""
    b, h, dh = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    tk = _tile(s, 512)

    # Heads per sequential grid step: the largest divisor of Hkv whose
    # double-buffered K+V blocks stay inside the VMEM budget (hb=1 is the
    # old per-head grid; pallas_supported gates on the same 4x
    # double-buffered bound, so hb=1 always passes this check).
    hb = 1
    itemsize = k_cache.dtype.itemsize
    for cand in range(hkv, 0, -1):
        if (hkv % cand == 0
                and 4 * cand * s * dh * itemsize <= _VMEM_KV_BUDGET_BYTES):
            hb = cand
            break

    qg = q.reshape(b, hkv, g, dh)
    window = jnp.asarray(sliding_window, jnp.int32).reshape(1, 1)
    seq_lens = seq_lens.astype(jnp.int32).reshape(1, b)

    kernel = functools.partial(
        _decode_kernel, scale=scale, softcap=float(softcap or 0.0), tk=tk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, hkv // hb),
        in_specs=[
            pl.BlockSpec((1, 1), lambda bi, hi: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, b), lambda bi, hi: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, hb, g, dh), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, hb, s, dh), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, hb, s, dh), lambda bi, hi: (bi, hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, hb, g, dh),
                               lambda bi, hi: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
        interpret=_interpret(),
        name="flash_decode_attention",
    )(window, seq_lens, qg, k_cache, v_cache)
    return out.reshape(b, h, dh)
