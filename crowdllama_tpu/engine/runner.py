"""ModelRunner: compiled prefill / insert / decode over a device mesh.

Owns the parameter pytree (sharded per parallel.sharding rules), the decode
state (slot-based KV cache), and the three jitted programs of the serving hot
path:

- ``prefill(tokens)``   — bucketed full-prompt forward; returns the prompt's
  KV and the first sampled token.  Buckets bound compilation count.
- ``insert(...)``       — writes a prefilled sequence into a batch slot.
- ``decode_step(state)``— one token for every slot (active or not: shapes are
  static), sampling on device, cache updated in place (buffers donated).

Design per SURVEY §7 hard part 1: fixed shapes, slot management, and
prefill/decode interleaving live here; the asyncio continuous-batching policy
lives in engine.scheduler.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from crowdllama_tpu.engine.sampling import (
    REPEAT_LAST_N,
    apply_repeat_penalty,
    default_slot_key,
    ring_with_first,
    sample_tokens,
    sample_tokens_slots,
    split_slot_keys,
)
from crowdllama_tpu.models import transformer as T
from crowdllama_tpu.models.config import ModelConfig
from crowdllama_tpu.obs.metrics import ENGINE_TELEMETRY
from crowdllama_tpu.parallel.mesh import (
    AXIS_DP,
    AXIS_PP,
    AXIS_SP,
    AXIS_TP,
    build_mesh,
    choose_mesh_shape,
)
from crowdllama_tpu.parallel.pipeline import (
    pp_decode_step,
    pp_hidden_states,
    pp_prefill,
)
from crowdllama_tpu.parallel.sharding import (
    cache_pspec,
    filter_spec,
    placed_layouts,
    shard_params,
)

log = logging.getLogger("crowdllama.engine.runner")

Params = dict[str, Any]


@dataclass
class DecodeState:
    """Per-slot decode state (a pytree; all arrays device-resident)."""

    k_cache: jnp.ndarray   # [L, B, Hkv, S, Dh] — head-major (ops/attention.py)
    v_cache: jnp.ndarray   # [L, B, Hkv, S, Dh]
    seq_lens: jnp.ndarray  # [B] int32 — tokens in cache (last token pending)
    tokens: jnp.ndarray    # [B] int32 — last sampled token per slot
    active: jnp.ndarray    # [B] bool
    temperature: jnp.ndarray  # [B] fp32
    top_p: jnp.ndarray     # [B] fp32
    top_k: jnp.ndarray     # [B] int32 — Ollama options.top_k (0 = off)
    # Ollama options.repeat_penalty (1.0/0 = off) + last-N emitted-token
    # ring per slot (entries >= vocab_size are padding; cursor is
    # seq_lens % N).  Applied to logits before greedy/top-k (llama.cpp).
    repeat_penalty: jnp.ndarray  # [B] f32
    recent: jnp.ndarray          # [B, REPEAT_LAST_N] int32
    # Per-slot PRNG carries [B, 2]: each slot samples with its own key
    # stream (set at insert), so a seeded request reproduces its tokens
    # regardless of slot assignment or what else shares the batch.
    keys: jnp.ndarray
    # int8 KV cache only (kv_dtype="int8"): per-(position, kv-head) scales;
    # None for the bf16 cache (None is an empty pytree — same treedef works
    # for both layouts).
    k_scale: jnp.ndarray | None = None  # [L, B, Hkv, S]
    v_scale: jnp.ndarray | None = None
    # Speculative decoding only (engine/spec.py): device-side token history
    # [B, S] — the n-gram draft source.  None otherwise.
    hist: jnp.ndarray | None = None


jax.tree_util.register_dataclass(
    DecodeState,
    data_fields=["k_cache", "v_cache", "seq_lens", "tokens", "active",
                 "temperature", "top_p", "top_k", "repeat_penalty",
                 "recent", "keys", "k_scale", "v_scale", "hist"],
    meta_fields=[],
)


def prefill_buckets(max_seq: int) -> list[int]:
    buckets, b = [], 32
    while b < max_seq:
        buckets.append(b)
        b *= 2
    buckets.append(max_seq)
    return buckets


class ModelRunner:
    #: a model whose layers differ in kind keeps state this runner has no
    #: place for; engine/hybrid.py's runner says True
    serves_hybrid = False

    def __init__(
        self,
        cfg: ModelConfig,
        params: Params | None = None,
        mesh: Mesh | None = None,
        mesh_spec: str = "",
        max_slots: int = 8,
        max_seq: int = 0,
        dtype=jnp.bfloat16,
        seed: int = 0,
        kv_dtype: str = "bf16",  # "bf16" | "int8" (quantized KV cache)
    ):
        if cfg.is_hybrid and not self.serves_hybrid:
            raise ValueError(
                f"{type(self).__name__} cannot serve {cfg.name!r}: what its "
                f"layers of several kinds keep a slot (recurrent state, a "
                f"window pool) lives in the paged runner of engine/hybrid.py "
                f"alone")
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_seq = max_seq or cfg.max_context_length
        self.dtype = dtype
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype must be 'bf16' or 'int8', got {kv_dtype!r}")
        self.kv_dtype = kv_dtype

        if mesh is None:
            n = len(jax.devices())
            if mesh_spec:
                mesh = build_mesh(mesh_spec)
            else:
                mesh = build_mesh(
                    choose_mesh_shape(n, cfg.num_kv_heads, cfg.num_experts)
                )
        self.mesh = mesh
        dp = mesh.shape[AXIS_DP]
        if self.max_slots % dp != 0:
            self.max_slots = max(dp, (self.max_slots // dp) * dp)
            log.warning("max_slots rounded to %d (dp=%d)", self.max_slots, dp)
        # Sequence parallelism: sp > 1 shards the KV cache sequence dim and
        # switches prefill to ring attention, decode to distributed flash
        # decoding (ops/ring.py).
        self.sp = mesh.shape.get(AXIS_SP, 1)
        self._sp_mesh = mesh if self.sp > 1 else None
        if self.sp > 1:
            assert self.max_seq % self.sp == 0, (
                f"max_seq {self.max_seq} must divide by sp={self.sp}")
        # Pipeline parallelism: pp > 1 shards the layer stack and runs the
        # ppermute microbatch pipeline (parallel/pipeline.py).  When pp == 1
        # the layer dim of params/cache is simply unsharded and the plain
        # scan paths run.
        self.pp = mesh.shape.get(AXIS_PP, 1)
        if self.pp > 1:
            assert self.sp == 1, "pp × sp composition not supported yet"
            assert cfg.num_layers % self.pp == 0, (
                f"{cfg.num_layers} layers not divisible by pp={self.pp}")
        if self.kv_dtype == "int8":
            assert self.sp == 1 and self.pp == 1, (
                "int8 KV cache does not compose with sp/pp meshes yet")
        if self.pp > 1 or self.sp > 1:
            # Chunked admission's _prefill_chunk runs the plain layer scan;
            # pp needs pp_prefill and sp needs ring attention — keep those
            # meshes on monolithic prefill.
            self.prefill_chunk = 0

        if params is None:
            params = T.init_params(cfg, jax.random.PRNGKey(seed), dtype=dtype)
        self.params = shard_params(params, cfg, mesh)
        # crowdllama_weight_layout: how the attention projections lie
        self.weight_layouts = placed_layouts(self.params)
        log.info("weight layouts: %s", " ".join(
            f"{k}={v}" for k, v in sorted(self.weight_layouts.items())))

        self._replicated = NamedSharding(mesh, P())
        self._cache_sharding = NamedSharding(mesh, cache_pspec(mesh))
        # Prefill KV [L, 1, Hkv, T, Dh] — layers on pp, kv-heads on tp,
        # sequence on sp.
        self._prefill_kv_sharding = NamedSharding(
            mesh, filter_spec(P(AXIS_PP, None, AXIS_TP, AXIS_SP, None), mesh))
        self.buckets = [b for b in prefill_buckets(self.max_seq)
                        if b % self.sp == 0]

        self._prefill = jax.jit(
            self._prefill_impl,
            out_shardings=(
                self._replicated, self._prefill_kv_sharding, self._prefill_kv_sharding,
            ),
        )
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1,),
                               static_argnums=(2,))
        self._insert = jax.jit(self._insert_impl, donate_argnums=(0,))
        self._release = jax.jit(self._release_impl, donate_argnums=(0,))
        self._announce_attention_paths()
        self._announce_moe_matmul_path()

    @property
    def kv_layers(self) -> int:
        """Layers that keep KV: all of them, but for a model whose layers
        differ in kind (engine/hybrid.py): its attention layers of every
        kind (``models/hybrid.py`` ``ATTENTION``)."""
        from crowdllama_tpu.models.hybrid import ATTENTION

        return sum(self.cfg.layers_of(kind) for kind in ATTENTION)

    # ------------------------------------------------------- attention paths

    def _attention_refusals(self) -> dict[str, str]:
        """program -> why its attention does NOT take the Pallas kernel
        ("" = it does), from the same gates the traced programs consult."""
        from crowdllama_tpu.ops.pallas.flash import pallas_refusal

        itemsize = jnp.dtype(self.dtype).itemsize
        dh = self.cfg.resolved_head_dim()
        # The buckets the monolithic prefill is dispatched at: the scheduler
        # admits every prompt over ``prefill_chunk`` tokens in chunks (or in
        # ragged steps), so no larger bucket ever runs.
        top = (self.bucket_for(min(self.prefill_chunk, self.max_seq))
               if self.prefill_chunk else self.max_seq)
        refused: dict[str, list[int]] = {}  # reason -> buckets it refuses
        for b in (b for b in self.buckets if b <= top):
            why = pallas_refusal(b, dh, itemsize, self.mesh.size)
            if why:
                refused.setdefault(why, []).append(b)
        return {"prefill": "; ".join(
            f"buckets {bs}: {why}" for why, bs in refused.items())}

    def _announce_attention_paths(self) -> None:
        """One startup line (and the crowdllama_engine_attention_path
        series) naming the attention implementation each program
        dispatches.  On a TPU backend a refused kernel is a WARNING with
        the gate's reason: a chip run on the jnp path must never be
        mistaken for a kernel run."""
        from crowdllama_tpu.ops.pallas.flash import _interpret

        kernel = "pallas_interpret" if _interpret() else "pallas"
        refusals = self._attention_refusals()
        self.attention_paths = {
            prog: "jnp" if why else kernel for prog, why in refusals.items()}
        ENGINE_TELEMETRY.attention_paths_set(self.attention_paths)
        log.info("attention paths: %s", " ".join(
            f"{p}={v}" for p, v in sorted(self.attention_paths.items())))
        if jax.default_backend() == "tpu":
            for prog, why in sorted(refusals.items()):
                if why:
                    log.warning("%s attention runs the jnp path on this "
                                "TPU, not the Pallas kernel: %s", prog, why)

    def _announce_moe_matmul_path(self) -> None:
        """Which path the expert layers' grouped matmuls take in every
        program this runner builds (ops/quant.py ``qragged_dot`` decides
        from the placed bank, and logs why a bank is dequantized; "" for a
        model that has none), for ``crowdllama_moe_matmul_path``."""
        from crowdllama_tpu.ops.quant import ragged_dot_path

        cfg, layers = self.cfg, self.params["layers"]
        self.moe_matmul_path = ""
        if cfg.is_hybrid:
            bank = (layers["moe"][0]["w1"] if "moe" in layers
                    else layers["smoe"][0]["w_gate"])
        elif cfg.is_moe and cfg.moe_dispatch == "sorted":
            bank = layers["w_gate"]
        else:
            return
        self.moe_matmul_path, _ = ragged_dot_path(bank)
        log.info("expert matmul path: %s", self.moe_matmul_path)

    # ------------------------------------------------------------- programs

    def _prefill_impl(self, params, tokens, plen, temperature, top_p, top_k,
                      repeat_penalty, recent_row, key):
        """tokens [1, T] padded; plen scalar; returns (first_token, ks, vs)."""
        t = tokens.shape[1]
        # Padding positions clamp to plen-1; kv_valid excludes them from
        # attention (clamped positions would otherwise pass the causal mask).
        positions = jnp.minimum(jnp.arange(t)[None, :], plen - 1)
        kv_valid = (jnp.arange(t) < plen)[None, :]
        logits, ks, vs = self._prefill_forward(params, tokens, positions,
                                               kv_valid)
        last = apply_repeat_penalty(
            logits[0, plen - 1][None, :], recent_row[None],
            repeat_penalty[None])  # [1, V]
        tok = sample_tokens(last, temperature[None], top_p[None],
                            key, top_k=top_k[None])[0]
        return tok, ks, vs

    def _prefill_forward(self, params, tokens, positions, kv_valid):
        """(logits [1, T, V], ks, vs): the forward pass of a whole prompt
        and what it leaves for ``insert`` to place."""
        if self.pp > 1:
            return pp_prefill(params, self.cfg, tokens, positions,
                              self.mesh, kv_valid=kv_valid)
        return T.prefill(params, self.cfg, tokens, positions,
                         kv_valid=kv_valid, sp_mesh=self._sp_mesh,
                         sp_batch_axis=None, n_shards=self.mesh.size)

    def _insert_impl(self, state: DecodeState, slot, ks, vs, plen, first_token,
                     temperature, top_p, top_k, repeat_penalty, recent_row,
                     slot_key) -> DecodeState:
        """Write a prefilled sequence (ks/vs [L,1,Hkv,T,Dh]) into ``slot``."""
        k_scale, v_scale = state.k_scale, state.v_scale
        if self.kv_dtype == "int8":
            from crowdllama_tpu.ops.quant import quantize_kv

            ks, k_sc = quantize_kv(ks, scale_dtype=k_scale.dtype)
            vs, v_sc = quantize_kv(vs, scale_dtype=v_scale.dtype)
            k_scale = jax.lax.dynamic_update_slice(
                k_scale, k_sc, (0, slot, 0, 0))
            v_scale = jax.lax.dynamic_update_slice(
                v_scale, v_sc, (0, slot, 0, 0))
        k_cache = jax.lax.dynamic_update_slice(
            state.k_cache, ks.astype(state.k_cache.dtype), (0, slot, 0, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            state.v_cache, vs.astype(state.v_cache.dtype), (0, slot, 0, 0, 0))
        return DecodeState(
            k_cache=k_cache,
            v_cache=v_cache,
            seq_lens=state.seq_lens.at[slot].set(plen),
            tokens=state.tokens.at[slot].set(first_token),
            active=state.active.at[slot].set(True),
            temperature=state.temperature.at[slot].set(temperature),
            top_p=state.top_p.at[slot].set(top_p),
            top_k=state.top_k.at[slot].set(top_k),
            repeat_penalty=state.repeat_penalty.at[slot].set(repeat_penalty),
            recent=state.recent.at[slot].set(
                ring_with_first(recent_row, plen, first_token)),
            keys=state.keys.at[slot].set(slot_key),
            k_scale=k_scale, v_scale=v_scale,
            hist=state.hist,
        )

    def _release_impl(self, state: DecodeState, slot) -> DecodeState:
        return DecodeState(
            k_cache=state.k_cache, v_cache=state.v_cache,
            seq_lens=state.seq_lens.at[slot].set(0),
            tokens=state.tokens.at[slot].set(0),
            active=state.active.at[slot].set(False),
            temperature=state.temperature, top_p=state.top_p,
            top_k=state.top_k, repeat_penalty=state.repeat_penalty,
            recent=state.recent, keys=state.keys,
            k_scale=state.k_scale, v_scale=state.v_scale, hist=state.hist,
        )

    def _decode_step_body(self, params):
        """One decode step as a ``lax.scan`` body closure — THE hot-path
        step of ``_decode_impl``."""

        def step(st: DecodeState, _):
            positions = jnp.minimum(st.seq_lens, self.max_seq - 1)
            lens = jnp.minimum(st.seq_lens + 1, self.max_seq)
            k_scale = v_scale = None
            if self.pp > 1:
                logits, k_cache, v_cache = pp_decode_step(
                    params, self.cfg, st.tokens, positions,
                    st.k_cache, st.v_cache, lens, self.mesh,
                )
            elif self.kv_dtype == "int8":
                logits, k_cache, v_cache, k_scale, v_scale = T.decode_step(
                    params, self.cfg, st.tokens, positions,
                    st.k_cache, st.v_cache, lens,
                    n_shards=self.mesh.size,
                    k_scale=st.k_scale, v_scale=st.v_scale,
                )
            else:
                logits, k_cache, v_cache = T.decode_step(
                    params, self.cfg, st.tokens, positions,
                    st.k_cache, st.v_cache, lens,
                    sp_mesh=self._sp_mesh, dp_axis=AXIS_DP,
                    n_shards=self.mesh.size,
                )
            carry, sub = split_slot_keys(st.keys)
            logits = apply_repeat_penalty(logits, st.recent,
                                          st.repeat_penalty)
            next_tokens = sample_tokens_slots(logits, st.temperature,
                                              st.top_p, sub, top_k=st.top_k)
            next_tokens = jnp.where(st.active, next_tokens, 0)
            # The sampled token's sequence position is seq_lens + 1 (the
            # pending token occupies seq_lens).
            bidx = jnp.arange(st.recent.shape[0])
            cursor = (st.seq_lens + 1) % REPEAT_LAST_N
            recent = st.recent.at[bidx, cursor].set(
                jnp.where(st.active, next_tokens, st.recent[bidx, cursor]))
            new_state = DecodeState(
                k_cache=k_cache, v_cache=v_cache,
                seq_lens=jnp.where(st.active, st.seq_lens + 1, st.seq_lens),
                tokens=next_tokens,
                active=st.active,
                temperature=st.temperature, top_p=st.top_p,
                top_k=st.top_k, repeat_penalty=st.repeat_penalty,
                recent=recent, keys=carry,
                k_scale=k_scale, v_scale=v_scale, hist=st.hist,
            )
            return new_state, next_tokens

        return step

    def _decode_impl(self, params, state: DecodeState, num_steps: int):
        """``num_steps`` decode steps in one dispatch; returns
        (tokens [K, B], new state).

        Multi-step decode amortizes host→device dispatch latency.  The
        scheduler picks K; EOS overshoot within a chunk is discarded host-side.
        """
        new_state, tokens = jax.lax.scan(self._decode_step_body(params),
                                         state, length=num_steps)
        return tokens, new_state

    # ------------------------------------------------------------------ API

    def init_state(self, seed: int = 0) -> DecodeState:
        l, b, s = self.cfg.num_layers, self.max_slots, self.max_seq
        hkv, dh = self.cfg.num_kv_heads, self.cfg.resolved_head_dim()
        shape = (l, b, hkv, s, dh)
        quantized = self.kv_dtype == "int8"
        cache_dtype = jnp.int8 if quantized else self.dtype
        scale_sharding = NamedSharding(
            self.mesh,
            filter_spec(P(AXIS_PP, AXIS_DP, AXIS_TP, AXIS_SP), self.mesh))
        # Two distinct buffers: device_put of one array twice may alias, and
        # aliased k/v caches break donation in the jitted insert/decode.
        return DecodeState(
            k_cache=jax.device_put(jnp.zeros(shape, cache_dtype),
                                   self._cache_sharding),
            v_cache=jax.device_put(jnp.zeros(shape, cache_dtype),
                                   self._cache_sharding),
            seq_lens=jnp.zeros((b,), jnp.int32),
            tokens=jnp.zeros((b,), jnp.int32),
            active=jnp.zeros((b,), bool),
            temperature=jnp.zeros((b,), jnp.float32),
            top_p=jnp.ones((b,), jnp.float32),
            top_k=jnp.zeros((b,), jnp.int32),
            repeat_penalty=jnp.ones((b,), jnp.float32),
            recent=jnp.full((b, REPEAT_LAST_N), self.cfg.vocab_size,
                            jnp.int32),
            # Zero keys: valid carries, always overwritten at insert (the
            # slot's stream comes from the request seed / scheduler RNG).
            keys=jnp.zeros((b, 2), jnp.uint32),
            k_scale=(jax.device_put(jnp.zeros(shape[:-1], jnp.bfloat16),
                                    scale_sharding) if quantized else None),
            v_scale=(jax.device_put(jnp.zeros(shape[:-1], jnp.bfloat16),
                                    scale_sharding) if quantized else None),
        )

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds max_seq {self.max_seq}")

    # ------------------------------------------------------- chunked prefill

    #: scheduler switches to incremental admission above this prompt length;
    #: 0 disables (only pp/sp meshes, whose prefill cannot run the plain
    #: ctx-accumulating chunk program — see __init__).  Paged runners chunk
    #: too, seeding the job from cached prefix pages (engine/paged.py).
    prefill_chunk = 512

    class PrefillJob:
        """Host handle for an in-progress chunked prefill.

        Device state: accumulated KV buffers [L, 1, Hkv, S, Dh] (the
        prompt's prefix so far) and the running last-logits row.  The
        scheduler dispatches one chunk per decode-loop iteration, so token
        streaming stalls at most one chunk — not the whole prompt.
        """

        def __init__(self, prompt_ids, ctx_k, ctx_v):
            self.prompt_ids = prompt_ids
            self.done_tokens = 0
            self.ctx_k = ctx_k
            self.ctx_v = ctx_v
            self.last_logits = None

        @property
        def finished(self) -> bool:
            return self.done_tokens >= len(self.prompt_ids)

    def prefill_begin(self, prompt_ids: list[int],
                      state=None) -> "ModelRunner.PrefillJob":
        # ``state`` is accepted (and ignored) so the scheduler can pass its
        # live decode state uniformly; the paged runner seeds the job's
        # context from cached prefix pages with it.
        if len(prompt_ids) >= self.max_seq:
            raise ValueError(
                f"prompt of {len(prompt_ids)} tokens exceeds max context "
                f"{self.max_seq}")
        l, hkv, dh = (self.kv_layers, self.cfg.num_kv_heads,
                      self.cfg.resolved_head_dim())
        # Accumulators sized to the PROMPT's bucket, not max_seq: a 600-token
        # prompt on a 32k-context model must not allocate (or attend over)
        # 32k-wide context buffers.
        width = self.bucket_for(len(prompt_ids))
        shape = (l, 1, hkv, width, dh)
        return self.PrefillJob(
            list(prompt_ids),
            jax.device_put(jnp.zeros(shape, self.dtype),
                           self._prefill_kv_sharding),
            jax.device_put(jnp.zeros(shape, self.dtype),
                           self._prefill_kv_sharding),
        )

    def prefill_step(self, job: "ModelRunner.PrefillJob") -> bool:
        """Run ONE chunk of the job's prompt; True when the prompt is done."""
        width = job.ctx_k.shape[3]
        budget = width - job.done_tokens  # write room left in the buffers
        take = min(self.prefill_chunk, len(job.prompt_ids) - job.done_tokens)
        bucket = min(self.bucket_for(take), self.prefill_chunk)
        if bucket > budget:
            # Non-power-of-two max_seq tail: a bucket-sized write would
            # CLAMP in dynamic_update_slice and corrupt earlier KV.  Shrink
            # to the largest bucket that fits, or the exact remainder.
            fitting = [b for b in self.buckets if b <= budget]
            bucket = fitting[-1] if fitting else budget
            take = min(take, bucket)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :take] = job.prompt_ids[
            job.done_tokens:job.done_tokens + take]
        # Chunk compiles are per (chunk bucket, ctx width) shape pair.
        sig = f"{bucket}x{width}"
        ENGINE_TELEMETRY.padding_inc(useful=take, waste=bucket - take)
        t_c = ENGINE_TELEMETRY.compile_begin("prefill_chunk", sig)
        job.last_logits, job.ctx_k, job.ctx_v = self._prefill_chunk(
            self.params, jnp.asarray(tokens), jnp.int32(take),
            jnp.int32(job.done_tokens), job.ctx_k, job.ctx_v)
        ENGINE_TELEMETRY.compile_end("prefill_chunk", sig, t_c)
        job.done_tokens += take
        return job.finished

    @partial(jax.jit, static_argnums=0, donate_argnums=(5, 6))
    def _prefill_chunk(self, params, tokens, chunk_len, ctx_len, ctx_k, ctx_v):
        t = tokens.shape[1]
        positions = ctx_len + jnp.minimum(jnp.arange(t)[None, :],
                                          chunk_len - 1)
        kv_valid = (jnp.arange(t) < chunk_len)[None, :]
        ctx_valid = (jnp.arange(ctx_k.shape[3]) < ctx_len)[None, :]
        logits, ks, vs = T.prefill(params, self.cfg, tokens, positions,
                                   kv_valid=kv_valid,
                                   ctx_k=ctx_k, ctx_v=ctx_v,
                                   ctx_valid=ctx_valid)
        # Append this chunk's KV to the accumulators.  Bucket padding rows
        # beyond chunk_len land past the valid region and are either
        # overwritten by the next chunk or masked by seq_lens forever.
        # prefill_step guarantees ctx_len + T <= width (no clamping).
        ctx_k = jax.lax.dynamic_update_slice(
            ctx_k, ks.astype(ctx_k.dtype), (0, 0, 0, ctx_len, 0))
        ctx_v = jax.lax.dynamic_update_slice(
            ctx_v, vs.astype(ctx_v.dtype), (0, 0, 0, ctx_len, 0))
        return logits[0, chunk_len - 1], ctx_k, ctx_v  # [V]

    def prefill_finish(self, job: "ModelRunner.PrefillJob", temperature: float,
                       top_p: float, key: jax.Array, top_k: int = 0,
                       repeat_penalty: float = 1.0):
        """Sample the first token; returns (tok, ks, vs, plen) like prefill."""
        assert job.finished and job.last_logits is not None
        logits = apply_repeat_penalty(
            job.last_logits[None, :],
            jnp.asarray(self._recent_from_prompt(job.prompt_ids))[None],
            jnp.float32(repeat_penalty)[None])
        tok = sample_tokens(logits,
                            jnp.float32(temperature)[None],
                            jnp.float32(top_p)[None], key,
                            top_k=jnp.int32(top_k)[None])[0]
        return int(tok), job.ctx_k, job.ctx_v, len(job.prompt_ids)

    def _recent_from_prompt(self, prompt_ids: list[int],
                            first_token: int | None = None,
                            plen: int | None = None) -> np.ndarray:
        """Last-N ring seeded from the prompt tail (+ the first sampled
        token, which sits at sequence position plen), padded with
        vocab_size (never penalized).  Token at sequence position ``pos``
        lives in ring slot ``pos % N`` — decode's writes (at
        (seq_lens+1) % N) then continue the ring seamlessly.  Callers
        without the prompt pass ``plen`` so the first token still lands in
        its correct ring slot."""
        row = np.full((REPEAT_LAST_N,), self.cfg.vocab_size, np.int32)
        plen = len(prompt_ids) if plen is None else plen
        seq = {plen - len(prompt_ids) + i: t
               for i, t in enumerate(prompt_ids)}
        if first_token is not None:
            seq[plen] = first_token
        for pos in sorted(seq)[-REPEAT_LAST_N:]:
            row[pos % REPEAT_LAST_N] = seq[pos]
        return row

    def prefill(self, prompt_ids: list[int], temperature: float, top_p: float,
                key: jax.Array, state: DecodeState | None = None,
                top_k: int = 0, repeat_penalty: float = 1.0):
        """Run bucketed prefill; returns (first_token, ks, vs, plen), the
        token as the device scalar the program sampled: nothing here waits
        for the device, and :meth:`insert` takes the scalar as it is.

        ``state`` is accepted (and ignored) so the scheduler can pass its
        live decode state uniformly; the paged runner uses it for prefix-
        cache context gathers."""
        plen = len(prompt_ids)
        bucket = self.bucket_for(plen)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :plen] = prompt_ids
        ENGINE_TELEMETRY.padding_inc(useful=plen, waste=bucket - plen)
        t_c = ENGINE_TELEMETRY.compile_begin("prefill", bucket)
        tok, ks, vs = self._prefill(
            self.params, jnp.asarray(tokens), jnp.int32(plen),
            jnp.float32(temperature), jnp.float32(top_p), jnp.int32(top_k),
            jnp.float32(repeat_penalty),
            jnp.asarray(self._recent_from_prompt(prompt_ids)), key,
        )
        ENGINE_TELEMETRY.compile_end("prefill", bucket, t_c)
        return tok, ks, vs, plen

    _EMBED_BATCH = (1, 2, 4, 8)  # padded batch sizes (bounds compile count)

    def embed_prompt(self, prompt_ids: list[int]) -> np.ndarray:
        """Mean-pooled, L2-normalized embedding of one prompt ([D] fp32)."""
        return self.embed_prompts([prompt_ids])[0]

    def embed_prompts(self, prompts: list[list[int]]) -> np.ndarray:
        """Embeddings for many prompts ([N, D] fp32), batched per bucket.

        Same-bucket prompts share one forward (padded to 1/2/4/8 rows) —
        bulk /api/embed costs ~N/8 dispatches instead of N.  Sequence
        padding is excluded from attention and the pooling mask.  pp meshes
        run the microbatch pipeline forward, sp meshes the ring-attention
        forward (same code paths prefill uses)."""
        out = np.zeros((len(prompts), self.cfg.hidden_size), np.float32)
        groups: dict[int, list[int]] = {}
        for i, ids in enumerate(prompts):
            groups.setdefault(self.bucket_for(len(ids)), []).append(i)
        for bucket, idxs in groups.items():
            for pos in range(0, len(idxs), self._EMBED_BATCH[-1]):
                chunk = idxs[pos:pos + self._EMBED_BATCH[-1]]
                bs = next(b for b in self._EMBED_BATCH if b >= len(chunk))
                tokens = np.zeros((bs, bucket), np.int32)
                plens = np.ones((bs,), np.int32)
                for row, i in enumerate(chunk):
                    tokens[row, :len(prompts[i])] = prompts[i]
                    plens[row] = len(prompts[i])
                useful = sum(len(prompts[i]) for i in chunk)
                ENGINE_TELEMETRY.padding_inc(
                    useful=useful, waste=bs * bucket - useful)
                sig = f"{bs}x{bucket}"
                t_c = ENGINE_TELEMETRY.compile_begin("embed", sig)
                vecs = np.asarray(self._embed_fwd(
                    self.params, jnp.asarray(tokens), jnp.asarray(plens)),
                    np.float32)
                ENGINE_TELEMETRY.compile_end("embed", sig, t_c)
                for row, i in enumerate(chunk):
                    out[i] = vecs[row]
        return out

    @partial(jax.jit, static_argnums=0)
    def _embed_fwd(self, params, tokens, plens):
        t = tokens.shape[1]
        positions = jnp.minimum(jnp.arange(t)[None, :], plens[:, None] - 1)
        kv_valid = jnp.arange(t)[None, :] < plens[:, None]  # [B, T]
        h = self._hidden_states(params, tokens, positions, kv_valid)
        mask = kv_valid[..., None].astype(jnp.float32)  # [B, T, 1]
        pooled = jnp.sum(h.astype(jnp.float32) * mask, axis=1) / jnp.maximum(
            jnp.sum(mask, axis=1), 1.0)
        return pooled / jnp.maximum(
            jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)

    def _hidden_states(self, params, tokens, positions, kv_valid):
        """Final-norm hidden states [B, T, D] of padded prompts."""
        if self.pp > 1:
            return pp_hidden_states(params, self.cfg, tokens, positions,
                                    self.mesh, kv_valid=kv_valid)
        return T.hidden_states(params, self.cfg, tokens, positions,
                               kv_valid=kv_valid, sp_mesh=self._sp_mesh,
                               n_shards=self.mesh.size)

    def insert(self, state: DecodeState, slot: int, ks, vs, plen: int,
               first_token: int, temperature: float, top_p: float,
               prompt_tokens: list[int] | None = None,
               slot_key: jax.Array | None = None,
               top_k: int = 0, repeat_penalty: float = 1.0) -> DecodeState:
        # KV buckets shorter than max_seq: pad via dynamic slice into cache.
        # ``prompt_tokens`` is accepted (and ignored) so the scheduler can
        # pass the prompt uniformly; the spec runner needs it for its
        # n-gram history (engine/spec.py).  ``slot_key`` seeds the slot's
        # private sampling stream (scheduler derives it from the request
        # seed); default keeps direct callers (tests) deterministic.
        if slot_key is None:
            slot_key = default_slot_key(slot)
        # The ring of the prompt alone: the program writes ``first_token``
        # (a Python int, or prefill's scalar still on the device) into it.
        recent_row = self._recent_from_prompt(list(prompt_tokens or []),
                                              plen=plen)
        # Insert compiles once per prefill-bucket KV width (ks [L,1,Hkv,T,Dh]).
        sig = ks.shape[3]
        t_c = ENGINE_TELEMETRY.compile_begin("insert", sig)
        out = self._insert(
            state, jnp.int32(slot), ks, vs, jnp.int32(plen),
            jnp.int32(first_token), jnp.float32(temperature),
            jnp.float32(top_p), jnp.int32(top_k),
            jnp.float32(repeat_penalty), jnp.asarray(recent_row), slot_key,
        )
        ENGINE_TELEMETRY.compile_end("insert", sig, t_c)
        return out

    def release(self, state: DecodeState, slot: int) -> DecodeState:
        t_c = ENGINE_TELEMETRY.compile_begin("release", 0)
        out = self._release(state, jnp.int32(slot))
        ENGINE_TELEMETRY.compile_end("release", 0, t_c)
        return out

    def decode_steps(self, state: DecodeState, num_steps: int = 1):
        """Run ``num_steps`` decode steps; returns (tokens [K, B] np, state)."""
        tokens, new_state = self.decode_steps_device(state, num_steps)
        return np.asarray(tokens), new_state

    def decode_steps_device(self, state: DecodeState, num_steps: int = 1):
        """Like :meth:`decode_steps` but the token block stays on device.

        No host readback: chained calls pipeline — the next chunk dispatches
        while the previous one executes, so only the final readback pays the
        host↔device round trip.  The scheduler reads tokens back with
        ``np.asarray`` when it needs them.
        """
        # Each distinct chunk length is a static arg → its own XLA program.
        t_c = ENGINE_TELEMETRY.compile_begin("decode", num_steps)
        out = self._decode(self.params, state, num_steps)
        ENGINE_TELEMETRY.compile_end("decode", num_steps, t_c)
        return out
