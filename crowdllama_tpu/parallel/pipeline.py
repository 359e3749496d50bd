"""Pipeline parallelism over the ``pp`` mesh axis (GPipe-style, TPU-native).

The layer stack is sharded on its leading axis (parallel/sharding.py puts
``pp`` first in every stacked layer param and in the KV cache), so each
pipeline stage owns a contiguous slice of layers and its slice of the cache.
Activations move stage-to-stage with ``lax.ppermute`` over ICI; microbatches
keep every stage busy after the fill bubble (utilization n_mb/(n_mb+pp-1)).

Implementation: one ``shard_map`` manual only over ``pp``
(``axis_names={"pp"}``) — dp/sp/ep/tp stay GSPMD-auto inside the stage body,
so tensor-parallel psums etc. continue to be derived by the compiler and
compose with the pipeline for free.  The stage body reuses the exact layer
scans from models/transformer.py.  Partial-manual shard_map requires a jit
context: call ``pp_prefill`` / ``pp_decode_step`` under ``jax.jit`` (the
engine always does).

The reference has no model parallelism of any kind (SURVEY §2 "zero
model-parallelism strategies"); this is part of the TPU-native superset
(BASELINE configs 3-5 demand multi-chip sharding).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from crowdllama_tpu.models import transformer as T
from crowdllama_tpu.models.config import ModelConfig
from crowdllama_tpu.parallel.mesh import AXIS_PP

Params = dict


def pick_n_microbatches(batch: int, pp: int) -> int:
    """Largest divisor of ``batch`` that is ≤ pp (pipeline utilization wants
    n_mb close to pp, correctness needs batch % n_mb == 0)."""
    for n in range(min(pp, batch), 0, -1):
        if batch % n == 0:
            return n
    return 1


def _stage_perm(npp: int) -> list[tuple[int, int]]:
    # Stage r feeds stage r+1; the last stage's output is dropped (collected
    # into `outs` before the rotate).
    return [(i, i + 1) for i in range(npp - 1)]


def _mb_slice(x: jnp.ndarray, mb: jnp.ndarray, mb_size: int) -> jnp.ndarray:
    """Dynamic microbatch slice along the leading (batch) dim."""
    start = (jnp.clip(mb, 0, x.shape[0] // mb_size - 1) * mb_size,) + (0,) * (
        x.ndim - 1)
    return jax.lax.dynamic_slice(x, start, (mb_size,) + x.shape[1:])


def pp_prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,     # [B, T] int32
    positions: jnp.ndarray,  # [B, T] int32
    mesh: Mesh,
    kv_valid: jnp.ndarray | None = None,
    n_microbatches: int = 0,  # 0 → min(pp, B)
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pipelined full-prompt forward: (logits [B,T,V], k, v [L,B,Hkv,T,Dh])."""
    out_x, ks, vs = _pp_forward(params, cfg, tokens, positions, mesh,
                                kv_valid, n_microbatches)
    logits = T._unembed(params, cfg, out_x)
    return logits, ks, vs


def pp_hidden_states(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,     # [B, T] int32
    positions: jnp.ndarray,  # [B, T] int32
    mesh: Mesh,
    kv_valid: jnp.ndarray | None = None,
    n_microbatches: int = 0,
) -> jnp.ndarray:
    """Final-norm hidden states [B, T, D] via the microbatch pipeline — the
    embeddings forward on pp meshes (the per-stage KV is computed by the
    shared pipeline body and discarded; embedding batches are small)."""
    out_x, _, _ = _pp_forward(params, cfg, tokens, positions, mesh,
                              kv_valid, n_microbatches)
    return T.rms_norm(out_x, params["final_norm"], cfg.rms_norm_eps,
                      plus_one=cfg.family == "gemma2")


def _pp_forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    mesh: Mesh,
    kv_valid: jnp.ndarray | None,
    n_microbatches: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Shared pipeline body: (pre-final-norm activations [B,T,D], k, v)."""
    npp = mesh.shape[AXIS_PP]
    b, t = tokens.shape
    n_mb = n_microbatches or pick_n_microbatches(b, npp)
    assert b % n_mb == 0, f"batch {b} must divide into {n_mb} microbatches"
    assert cfg.num_layers % npp == 0, (
        f"{cfg.num_layers} layers not divisible by pp={npp}")
    mb_size = b // n_mb
    if kv_valid is None:
        kv_valid = jnp.ones((b, t), bool)

    x = T._embed(params, cfg, tokens)  # [B, T, D]
    windows = T.layer_sliding_windows(cfg)
    hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim()
    l_local = cfg.num_layers // npp

    def body(layers_local, windows_local, x, positions, kv_valid):
        r = jax.lax.axis_index(AXIS_PP)
        carry = jnp.zeros((mb_size,) + x.shape[1:], x.dtype)
        ks = jnp.zeros((l_local, b, hkv, t, dh), x.dtype)
        vs = jnp.zeros_like(ks)
        outs = jnp.zeros((b,) + x.shape[1:], jnp.float32)

        def step(s, st):
            carry, ks, vs, outs = st
            mb_here = s - r  # microbatch at this stage (may be out of range)
            valid = (mb_here >= 0) & (mb_here < n_mb)
            x_in = jnp.where(r == 0, _mb_slice(x, jnp.int32(s), mb_size),
                             carry)
            y, k_loc, v_loc = T.scan_prefill_layers(
                layers_local, windows_local, cfg, x_in,
                _mb_slice(positions, mb_here, mb_size),
                kv_valid=_mb_slice(kv_valid, mb_here, mb_size),
                n_shards=mesh.size,  # residual axes may shard operands
            )
            # Select at microbatch granularity (write back the old slice
            # when invalid) so the big buffers stay in-place DUS carries —
            # a full-buffer jnp.where would copy them every pipeline step.
            mb_start = jnp.clip(mb_here, 0, n_mb - 1) * mb_size
            k_start = (0, mb_start, 0, 0, 0)
            k_old = jax.lax.dynamic_slice(ks, k_start, k_loc.shape)
            ks = jax.lax.dynamic_update_slice(
                ks, jnp.where(valid, k_loc.astype(ks.dtype), k_old), k_start)
            v_old = jax.lax.dynamic_slice(vs, k_start, v_loc.shape)
            vs = jax.lax.dynamic_update_slice(
                vs, jnp.where(valid, v_loc.astype(vs.dtype), v_old), k_start)
            o_start = (mb_start,) + (0,) * (outs.ndim - 1)
            o_old = jax.lax.dynamic_slice(
                outs, o_start, (mb_size,) + outs.shape[1:])
            outs = jax.lax.dynamic_update_slice(
                outs,
                jnp.where(valid & (r == npp - 1), y.astype(outs.dtype), o_old),
                o_start)
            carry = jax.lax.ppermute(y, AXIS_PP, _stage_perm(npp))
            return carry, ks, vs, outs

        _, ks, vs, outs = jax.lax.fori_loop(
            0, n_mb + npp - 1, step, (carry, ks, vs, outs))
        # Only the last stage holds the final activations; replicate them.
        outs = jax.lax.psum(
            jnp.where(r == npp - 1, outs, jnp.zeros_like(outs)), AXIS_PP)
        return outs, ks, vs

    out_x, ks, vs = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS_PP), P(AXIS_PP), P(), P(), P()),
        out_specs=(P(), P(AXIS_PP), P(AXIS_PP)),
        axis_names={AXIS_PP},
        check_vma=False,
    )(params["layers"], windows, x, positions, kv_valid)
    return out_x.astype(x.dtype), ks, vs


def pp_decode_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,     # [B] int32
    positions: jnp.ndarray,  # [B] int32
    k_cache: jnp.ndarray,    # [L, B, Hkv, S, Dh] (pp-sharded on L)
    v_cache: jnp.ndarray,
    seq_lens: jnp.ndarray,   # [B]
    mesh: Mesh,
    n_microbatches: int = 0,  # 0 → min(pp, B)
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pipelined decode: (logits [B,V], k_cache, v_cache).

    Microbatches over batch slots so all stages decode concurrently after
    the fill bubble; each stage updates only its local cache slice.
    """
    npp = mesh.shape[AXIS_PP]
    b = tokens.shape[0]
    n_mb = n_microbatches or pick_n_microbatches(b, npp)
    assert b % n_mb == 0, f"batch {b} must divide into {n_mb} microbatches"
    assert cfg.num_layers % npp == 0, (
        f"{cfg.num_layers} layers not divisible by pp={npp}")
    mb_size = b // n_mb
    l_local = cfg.num_layers // npp

    x = T._embed(params, cfg, tokens)  # [B, D]
    windows = T.layer_sliding_windows(cfg)

    def body(layers_local, windows_local, x, positions, kc, vc, seq_lens):
        r = jax.lax.axis_index(AXIS_PP)
        carry = jnp.zeros((mb_size,) + x.shape[1:], x.dtype)
        outs = jnp.zeros((b,) + x.shape[1:], jnp.float32)

        def step(s, st):
            carry, kc, vc, outs = st
            mb_here = s - r
            valid = (mb_here >= 0) & (mb_here < n_mb)
            mb_start = jnp.clip(mb_here, 0, n_mb - 1) * mb_size
            x_in = jnp.where(r == 0, _mb_slice(x, jnp.int32(s), mb_size),
                             carry)
            kc_mb = jax.lax.dynamic_slice(
                kc, (0, mb_start, 0, 0, 0),
                (l_local, mb_size) + kc.shape[2:])
            vc_mb = jax.lax.dynamic_slice(
                vc, (0, mb_start, 0, 0, 0),
                (l_local, mb_size) + vc.shape[2:])
            y, kc_mb, vc_mb = T.scan_decode_layers(
                layers_local, windows_local, cfg, x_in,
                _mb_slice(positions, mb_here, mb_size),
                kc_mb, vc_mb, _mb_slice(seq_lens, mb_here, mb_size),
                n_shards=mesh.size,
            )
            # Microbatch-granular select (see pp_prefill): the cache is the
            # big buffer here — never jnp.where over the whole thing.
            kc_old = jax.lax.dynamic_slice(
                kc, (0, mb_start, 0, 0, 0), kc_mb.shape)
            vc_old = jax.lax.dynamic_slice(
                vc, (0, mb_start, 0, 0, 0), vc_mb.shape)
            kc = jax.lax.dynamic_update_slice(
                kc, jnp.where(valid, kc_mb, kc_old), (0, mb_start, 0, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                vc, jnp.where(valid, vc_mb, vc_old), (0, mb_start, 0, 0, 0))
            o_old = jax.lax.dynamic_slice(
                outs, (mb_start, 0), (mb_size, outs.shape[1]))
            outs = jax.lax.dynamic_update_slice(
                outs,
                jnp.where(valid & (r == npp - 1), y.astype(outs.dtype), o_old),
                (mb_start, 0))
            carry = jax.lax.ppermute(y, AXIS_PP, _stage_perm(npp))
            return carry, kc, vc, outs

        _, kc, vc, outs = jax.lax.fori_loop(
            0, n_mb + npp - 1, step, (carry, kc, vc, outs))
        outs = jax.lax.psum(
            jnp.where(r == npp - 1, outs, jnp.zeros_like(outs)), AXIS_PP)
        return outs, kc, vc

    cache_spec = P(AXIS_PP)  # layer dim manual; others GSPMD-auto
    out_x, k_cache, v_cache = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS_PP), P(AXIS_PP), P(), P(), cache_spec, cache_spec,
                  P()),
        out_specs=(P(), cache_spec, cache_spec),
        axis_names={AXIS_PP},
        check_vma=False,
    )(params["layers"], windows, x, positions, k_cache, v_cache, seq_lens)
    logits = T._unembed(params, cfg, out_x.astype(x.dtype))
    return logits, k_cache, v_cache
