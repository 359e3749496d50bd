"""Sequence/context parallelism: ring attention + distributed decode.

Long-context support the TPU way (the reference has none — context length is
whatever Ollama supports, SURVEY §5 "Long-context: ABSENT"):

- ``ring_prefill_attention``: blockwise causal attention with the KV shards
  rotating around the ``sp`` mesh axis via ``lax.ppermute`` (Ring Attention).
  Each device holds Q/K/V for T/sp tokens; softmax runs online (running max /
  running denominator) so the full [T, T] score matrix never materializes and
  per-device memory is O(T/sp · T/sp) per block pair.  ICI carries one KV
  block per step, overlapping with the block attention compute.

- ``sp_decode_attention``: flash-decoding across devices — the KV cache is
  sharded on sequence along ``sp``, every device attends its shard with local
  softmax stats (m, l, o), and one pmax + two psums merge the partials.

Both are written as shard_map bodies (per-device local math + explicit
collectives) and composed with GSPMD tensor parallelism by also splitting the
kv-head axis on ``tp`` in the in_specs — attention has no cross-head math, so
tp needs no collectives here.

Known tradeoff: with the contiguous sequence layout, causal masking makes the
ring compute-imbalanced — low-rank devices see mostly-future KV blocks whose
scores are fully masked, so up to ~2x attention FLOPs are wasted at large sp.
The fix is a zigzag/striped block layout (each device holds one low and one
mirrored high block); planned optimization, tracked here so the cost model is
explicit.  Memory behavior (no [T, T] materialization) is unaffected.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from crowdllama_tpu.ops.attention import NEG_INF, _softcap


def _block_accumulate(
    q,          # [B, Tq, Hkv, G, Dh] fp32
    k,          # [B, Tc, Hkv, Dh] fp32
    v,          # [B, Tc, Hkv, Dh] fp32
    qpos,       # [B, Tq]
    kpos,       # [B, Tc]
    kv_valid,   # [B, Tc] bool
    m,          # [B, Hkv, G, Tq]
    l,          # [B, Hkv, G, Tq]
    o,          # [B, Tq, Hkv, G, Dh]
    scale: float,
    softcap: float,
    window,
):
    """One online-softmax accumulation of a KV block into (m, l, o)."""
    logits = _softcap(jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * scale, softcap)

    qp = qpos[:, None, None, :, None]   # [B,1,1,Tq,1]
    kp = kpos[:, None, None, None, :]   # [B,1,1,1,Tc]
    mask = kp <= qp
    w = jnp.asarray(window)
    mask &= (w <= 0) | (kp > qp - w)
    mask &= kv_valid[:, None, None, None, :]
    logits = jnp.where(mask, logits, NEG_INF)

    block_max = jnp.max(logits, axis=-1)           # [B,Hkv,G,Tq]
    new_m = jnp.maximum(m, block_max)
    alpha = jnp.exp(m - new_m)                      # rescale old accumulators
    p = jnp.exp(logits - new_m[..., None])          # [B,Hkv,G,Tq,Tc]
    # Re-mask: a fully-masked row has logits == new_m == NEG_INF, where the
    # subtraction yields exp(0) = 1 and would poison the accumulators.
    p = jnp.where(mask, p, 0.0)
    new_l = l * alpha + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
    new_o = o * alpha.transpose(0, 3, 1, 2)[..., None] + pv
    return new_m, new_l, new_o


def _ring_body(q, k, v, positions, kv_valid, window, *, axis_name: str,
               n: int, scale: float, softcap: float, num_kv_heads: int):
    """shard_map body: local blocks [B, T/sp, ...]; KV rotates ``n`` times."""
    b, tq, h, dh = q.shape
    g = h // num_kv_heads
    qf = q.astype(jnp.float32).reshape(b, tq, num_kv_heads, g, dh)

    m = jnp.full((b, num_kv_heads, g, tq), NEG_INF, jnp.float32)
    l = jnp.zeros((b, num_kv_heads, g, tq), jnp.float32)
    o = jnp.zeros((b, tq, num_kv_heads, g, dh), jnp.float32)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(i, carry):
        m, l, o, k, v, kpos, kval = carry
        m, l, o = _block_accumulate(
            qf, k.astype(jnp.float32), v.astype(jnp.float32),
            positions, kpos, kval, m, l, o, scale, softcap, window,
        )
        # Rotate the KV block (+ its positions/validity) one hop; the last
        # rotation restores the original block, keeping the op shard-identical.
        k, v, kpos, kval = jax.tree_util.tree_map(
            lambda x: jax.lax.ppermute(x, axis_name, perm), (k, v, kpos, kval)
        )
        return m, l, o, k, v, kpos, kval

    m, l, o, *_ = jax.lax.fori_loop(
        0, n, step, (m, l, o, k, v, positions, kv_valid)
    )
    l = jnp.maximum(l, 1e-20)
    out = o / l.transpose(0, 3, 1, 2)[..., None]
    return out.reshape(b, tq, h, dh).astype(q.dtype)


def ring_prefill_attention(
    q: jnp.ndarray,          # [B, T, H, Dh] — T sharded on sp (global view)
    k: jnp.ndarray,          # [B, T, Hkv, Dh]
    v: jnp.ndarray,          # [B, T, Hkv, Dh]
    positions: jnp.ndarray,  # [B, T] absolute positions
    scale: float,
    mesh: Mesh,
    *,
    softcap: float = 0.0,
    sliding_window=0,
    kv_valid: jnp.ndarray | None = None,  # [B, T] bool
    axis_name: str = "sp",
    dp_axis: str | None = "dp",
    tp_axis: str | None = "tp",
) -> jnp.ndarray:
    """Causal attention with sequence sharded over ``axis_name``.

    Requires T % sp == 0 (callers pad prompts to the sp-aligned bucket).
    Composes with tensor parallelism: kv-heads stay split on ``tp``, batch on
    ``dp``; only the sequence axis communicates (ppermute ring on ICI).
    """
    if kv_valid is None:
        kv_valid = jnp.ones(positions.shape, bool)
    # The body sees tp-LOCAL shards: kv-heads are split over tp.
    tp_size = mesh.shape[tp_axis] if tp_axis else 1
    assert k.shape[2] % tp_size == 0, "kv heads must divide tp"
    local_kv_heads = k.shape[2] // tp_size

    body = partial(
        _ring_body, axis_name=axis_name, n=mesh.shape[axis_name], scale=scale,
        softcap=softcap, num_kv_heads=local_kv_heads,
    )
    qspec = P(dp_axis, axis_name, tp_axis, None)
    kspec = P(dp_axis, axis_name, tp_axis, None)
    pspec = P(dp_axis, axis_name)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(qspec, kspec, kspec, pspec, pspec, P()),
        out_specs=qspec,
        check_vma=False,
    )(q, k, v, positions, kv_valid, jnp.asarray(sliding_window, jnp.int32))


# ----------------------------------------------------------------- sp decode

def _sp_update_body(k_new, v_new, positions, k_cache, v_cache, shard_starts):
    """Write one new KV per slot into the S-sharded cache, shard-locally.

    k_new/v_new: [B, Hkv, Dh]; positions: [B]; caches: [B, Hkv, S/sp, Dh].
    Each device writes only when the absolute position lands in its shard.
    """
    shard_len = k_cache.shape[2]
    local = positions - shard_starts[0]                  # [B]
    in_range = (local >= 0) & (local < shard_len)
    idx = jnp.clip(local, 0, shard_len - 1)
    b_idx = jnp.arange(k_cache.shape[0])
    sel = in_range[:, None, None]
    # kc[b, :, idx[b]] — broadcast [B] advanced pair fronts: [B, Hkv, Dh].
    k_cache = k_cache.at[b_idx, :, idx].set(
        jnp.where(sel, k_new.astype(k_cache.dtype), k_cache[b_idx, :, idx]))
    v_cache = v_cache.at[b_idx, :, idx].set(
        jnp.where(sel, v_new.astype(v_cache.dtype), v_cache[b_idx, :, idx]))
    return k_cache, v_cache


def sp_cache_update(
    k_new: jnp.ndarray,      # [B, Hkv, Dh]
    v_new: jnp.ndarray,
    positions: jnp.ndarray,  # [B] absolute positions to write
    k_cache: jnp.ndarray,    # [B, Hkv, S, Dh] — S sharded on sp (global view)
    v_cache: jnp.ndarray,
    mesh: Mesh,
    *,
    axis_name: str = "sp",
    dp_axis: str | None = "dp",
    tp_axis: str | None = "tp",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter one token's KV into the sequence-sharded cache without any
    cross-shard communication (each sp rank masks to its own range)."""
    sp = mesh.shape[axis_name]
    s = k_cache.shape[2]
    assert s % sp == 0
    starts = jnp.arange(sp, dtype=jnp.int32) * (s // sp)
    newspec = P(dp_axis, tp_axis, None)
    cspec = P(dp_axis, tp_axis, axis_name, None)
    return jax.shard_map(
        _sp_update_body, mesh=mesh,
        in_specs=(newspec, newspec, P(dp_axis), cspec, cspec, P(axis_name)),
        out_specs=(cspec, cspec),
        check_vma=False,
    )(k_new, v_new, positions, k_cache, v_cache, starts)


def _sp_decode_body(q, k_cache, v_cache, seq_lens, shard_starts, window, *,
                    axis_name: str, scale: float, softcap: float,
                    num_kv_heads: int):
    """Local flash-decoding over an S/sp KV shard, merged with psum/pmax.

    q: [B, H, Dh] (replicated over sp); k/v_cache: [B, Hkv, S/sp, Dh];
    shard_starts: [1] — absolute position of this shard's first cache slot.
    """
    b, h, dh = q.shape
    g = h // num_kv_heads
    qg = q.astype(jnp.float32).reshape(b, num_kv_heads, g, dh)

    logits = _softcap(
        jnp.einsum("bhgd,bhkd->bhgk", qg, k_cache.astype(jnp.float32)) * scale,
        softcap)

    kpos = shard_starts[0] + jnp.arange(k_cache.shape[2])[None, :]  # [1, S/sp]
    valid = kpos < seq_lens[:, None]
    w = jnp.asarray(window)
    valid &= (w <= 0) | (kpos > (seq_lens[:, None] - 1) - w)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)

    m_local = jnp.max(logits, axis=-1)                     # [B,Hkv,G]
    m = jax.lax.pmax(m_local, axis_name)
    p = jnp.exp(logits - m[..., None])
    l = jax.lax.psum(jnp.sum(p, axis=-1), axis_name)       # [B,Hkv,G]
    o = jnp.einsum("bhgk,bhkd->bhgd", p, v_cache.astype(jnp.float32))
    o = jax.lax.psum(o, axis_name)
    out = o / jnp.maximum(l, 1e-20)[..., None]
    return out.reshape(b, h, dh).astype(q.dtype)


def sp_decode_attention(
    q: jnp.ndarray,          # [B, H, Dh]
    k_cache: jnp.ndarray,    # [B, Hkv, S, Dh] — S sharded on sp (global view)
    v_cache: jnp.ndarray,
    seq_lens: jnp.ndarray,   # [B]
    scale: float,
    mesh: Mesh,
    *,
    softcap: float = 0.0,
    sliding_window=0,
    axis_name: str = "sp",
    dp_axis: str | None = "dp",
    tp_axis: str | None = "tp",
) -> jnp.ndarray:
    """Flash-decoding with the KV cache sequence-sharded over ``axis_name``."""
    tp_size = mesh.shape[tp_axis] if tp_axis else 1
    assert k_cache.shape[1] % tp_size == 0, "kv heads must divide tp"
    local_kv_heads = k_cache.shape[1] // tp_size  # body sees tp-local shards
    sp = mesh.shape[axis_name]
    s = k_cache.shape[2]
    assert s % sp == 0, f"cache length {s} not divisible by sp={sp}"
    shard_len = s // sp
    # Each sp shard's first absolute position, laid out [sp] and sharded so
    # every device reads its own entry.
    starts = jnp.arange(sp, dtype=jnp.int32) * shard_len

    body = partial(
        _sp_decode_body, axis_name=axis_name, scale=scale, softcap=softcap,
        num_kv_heads=local_kv_heads,
    )
    qspec = P(dp_axis, tp_axis, None)
    cspec = P(dp_axis, tp_axis, axis_name, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(qspec, cspec, cspec, P(dp_axis), P(axis_name), P()),
        out_specs=qspec,
        check_vma=False,
    )(q, k_cache, v_cache, seq_lens, starts,
      jnp.asarray(sliding_window, jnp.int32))
