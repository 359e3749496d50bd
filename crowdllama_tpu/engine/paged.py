"""Block-paged KV cache: slot→page-table indirection over a shared pool.

The contiguous cache (engine/runner.py) allocates ``[L, B, Hkv, max_seq,
Dh]`` per slot regardless of actual lengths — at ctx 8192 a mostly-idle slot
wastes its full footprint (VERDICT round-1 weak #6; PAPERS.md names ragged
paged attention as the north star).  Here KV lives in a pool of fixed
``page_size``-token pages shared by all slots:

- pool:        ``[L, P, Hkv, page, Dh]`` (k and v) — P pages total,
  sized by ``pool_tokens`` (default B×max_seq: identical capacity to the
  contiguous cache, allocation can never fail; smaller = overcommit).
- page table:  host-side ``[B, max_pages]`` int32, passed into each decode
  dispatch (tiny transfer); pages are allocated at insert (prompt pages)
  and before each decode chunk (growth), freed at release.
- decode attention: the fused Pallas kernel (ops/pallas/paged.py) reads
  pages straight from the stacked pool via the scalar-prefetched page
  table and a layer index — no virtual-contiguous gather and no layer
  slice, so paging buys capacity AND streams the minimum bytes.  The
  step bodies carry the stack through their layer loop and write each
  layer's K/V into it in place (``_put_rows`` / ``_put_chunk``).  tp>1
  meshes run the kernel per-shard via shard_map (the pool is tp-sharded
  over kv heads); CPU falls back to the jnp gather view (exact,
  static-shaped, just more HBM traffic).
- int8 pools (``kv_dtype="int8"``): pages are int8 with per-(position,
  kv-head) scales; the kernel dequantizes in-flight (K on the score
  plane, V folded into probabilities), and suffix prefill dequantizes
  only the one slot's context pages.  Composes with the prefix cache.

Page exhaustion under an overcommitted pool surfaces at admission as a
ValueError (the scheduler fails that request cleanly); when growth runs
dry mid-serving, the scheduler's ``pre_decode_check`` hook finishes
starved slots one at a time with done_reason "length" (each release frees
pages that often let the remaining slots continue) — the engine itself
never fails on exhaustion.

Single-mesh path only (sp/pp compose with the contiguous layout).
"""

from __future__ import annotations

import logging
import math
from dataclasses import dataclass, replace
from functools import cached_property, partial

import jax
import jax.numpy as jnp
import numpy as np

from crowdllama_tpu.engine.runner import ModelRunner
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
from crowdllama_tpu.obs.metrics import ENGINE_TELEMETRY
from crowdllama_tpu.ops.attention import decode_attention, decode_attention_q
from crowdllama_tpu.ops.pallas.paged import (
    decode_grid_steps,
    decode_work,
    flash_paged_decode_attention,
    flash_paged_decode_attention_tp,
    paged_decode_attention_mla,
    paged_pallas_refusal,
    ragged_paged_attention,
    ragged_pallas_refusal,
)
from crowdllama_tpu.ops.quant import quantize_kv, ride_banks
from crowdllama_tpu.ops.rope import rope_table

log = logging.getLogger("crowdllama.engine.paged")

_LANES = 128


def pool_row_width(cfg) -> int:
    """Entries of one row of the pool AS IT IS STORED — the one place that
    decides it.  A head's K or V row lies ``resolved_head_dim`` wide.  A
    latent row (``cfg.kv_lora_rank``: ``[c ; k_rope]``, key and value in
    one, no V twin) is rounded up to whole lanes, 576 -> 640: the kernels
    and the row writes need the row minor, and the TPU's own layout for a
    BUFFER whose row is four and a half lane tiles puts the page's token
    axis minor instead, so every program that took the pool and handed it
    back converted all of it on the way in and again on the way out
    (PERF.md §6, PR 49).  The pad columns are zero from ``init_state`` on:
    every row is written with a zero tail and every query meets the cache
    with one (:func:`_pool_wide`), so a score is the sum it was and the
    value is still the row's first ``kv_lora_rank`` entries.

    Read by whatever sizes the pool or a view of it: the Pallas gate, the
    step bodies' views, ``init_state``, ``_page_bytes``, ``_pool_shard``.
    Not by the prefix cache's gathers (``_prefill_ctx_impl``, ``_seed_ctx``)
    nor ``import_pages``: they take a V twin, which a latent pool lacks
    (engine/hybrid.py serves it with the prefix cache off), so a row there is
    a head's; not by ``_decode_layers``' ``rope_table``, a computed width."""
    dh = cfg.resolved_head_dim()
    return -(-dh // _LANES) * _LANES if cfg.kv_lora_rank else dh


def _pool_wide(pool, *rows):
    """``rows`` (``[..., Dh]`` each) with zero columns up to the pool's
    row; as they are where the pool's row is theirs (every pool but a
    latent one whose row is not whole lanes: :func:`pool_row_width`)."""
    pad = pool.shape[-1] - rows[0].shape[-1]
    if not pad:
        return rows
    return tuple(jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
                 for a in rows)


def _put_rows(pool, rows, *, layer, pages, offsets):
    """``pool[layer, pages[i], :, offsets[i]] = rows[i]`` for every row, as
    one dynamic-update-slice per row.

    pool ``[L, P, Hkv, page, Dh]`` (or the ``[L, P, Hkv, page]`` scales),
    rows ``[N, Hkv, Dh]`` (``[N, Hkv]``).  Not ``pool.at[...].set``: the
    TPU's scatter wants the pool in a layout with the kv-head dim next to
    minor, the attention kernel reads it row-major, and XLA then converts
    the WHOLE stack between the two every layer.  A dynamic-update-slice
    takes the buffer in the layout it has and updates it in place."""
    tail = (0,) * (pool.ndim - 4)
    for i in range(rows.shape[0]):
        pool = jax.lax.dynamic_update_slice(
            pool, jnp.expand_dims(rows[i], (0, 1, 3)),
            (layer, pages[i], 0, offsets[i], *tail))
    return pool


def _put_chunk(pool, rows, *, layer, page_row, start, valid, dump_page):
    """Write a prefill chunk's rows into its slot's pages, a page at a time:
    ``pool[layer, page_row[p // page], :, p % page] = rows[:, p - start]``
    for ``start <= p < start + valid``; nothing else is touched.

    rows ``[Hkv, C, Dh]`` (``[Hkv, C]`` for the scales), kv-head-major like
    a page.  The chunk may start anywhere in a page, so it meets at most
    ``ceil(C / page) + 1`` of them; each is read, merged under the row
    mask and written back with one dynamic-update-slice (in place, in the
    pool's own layout — see :func:`_put_rows`).  A page the chunk does not
    reach goes to ``dump_page`` unchanged."""
    hkv, page = pool.shape[2:4]
    c = rows.shape[1]
    tail = (0,) * (pool.ndim - 4)  # Dh, or nothing for the scales
    tile = (1, 1, hkv, page, *pool.shape[4:])
    padded = jnp.pad(rows, ((0, 0), (page, page), *((0, 0),) * len(tail)))
    first = start // page
    for j in range(-(-c // page) + 1):
        col = first + j
        pos = col * page + jnp.arange(page)
        ok = (pos >= start) & (pos < start + valid)
        src = jax.lax.dynamic_slice(
            padded, (0, col * page - start + page, *tail), tile[2:])
        pid = jnp.where(ok.any(),
                        page_row[jnp.minimum(col, page_row.shape[0] - 1)],
                        dump_page)
        at = (layer, pid, 0, 0, *tail)
        old = jax.lax.dynamic_slice(pool, at, tile)
        mask = ok.reshape(1, 1, 1, page, *(1,) * len(tail))
        pool = jax.lax.dynamic_update_slice(
            pool, jnp.where(mask, src[None, None], old), at)
    return pool


def _write_kv(put, pk, pv, ksc, vsc, k, v):
    """The four pools after ``put(pool, rows)`` has written one layer's
    fresh K/V — quantized first, scales alongside, where the pools are
    int8 (``ksc``/``vsc`` are None otherwise and stay None)."""
    if pv is None:      # latent rows: the one pool is key and value
        return put(pk, k.astype(pk.dtype)), None, None, None
    if ksc is None:
        return (put(pk, k.astype(pk.dtype)), put(pv, v.astype(pv.dtype)),
                None, None)
    kq, k_sc = quantize_kv(k, scale_dtype=ksc.dtype)
    vq, v_sc = quantize_kv(v, scale_dtype=vsc.dtype)
    return put(pk, kq), put(pv, vq), put(ksc, k_sc), put(vsc, v_sc)


class PagesExhausted(ValueError):
    """No free KV pages (overcommitted pool) — reject the request.  The
    message names the pool: ``full`` is the one every layer's pages came
    from before a model had window layers, and the only one a slot can find
    empty (a window layer's pages are its slot's own ring: engine/hybrid.py)."""


@dataclass
class PagedDecodeState:
    pool_k: jnp.ndarray    # [L, P, Hkv, page, Dh]
    # None for latent attention (cfg.kv_lora_rank): a page of pool_k holds
    # one row [c ; k_rope] a token, key and value both, in a row of whole
    # lanes (pool_row_width)
    pool_v: jnp.ndarray | None
    seq_lens: jnp.ndarray  # [B]
    tokens: jnp.ndarray    # [B]
    active: jnp.ndarray    # [B]
    temperature: jnp.ndarray
    top_p: jnp.ndarray
    top_k: jnp.ndarray  # [B] int32 — Ollama options.top_k (0 = off)
    repeat_penalty: jnp.ndarray  # [B] f32 (runner.DecodeState semantics)
    recent: jnp.ndarray          # [B, REPEAT_LAST_N] int32
    keys: jnp.ndarray  # [B, 2] per-slot PRNG carries (see runner.DecodeState)
    # int8 pools only (kv_dtype="int8"): per-(page-position, kv-head)
    # scales [L, P, Hkv, page]; None for bf16 pools.
    k_scale: jnp.ndarray | None = None
    v_scale: jnp.ndarray | None = None
    # Speculative decoding only (engine/spec.py SpecPagedModelRunner):
    # device-side token history [B, S] — the n-gram draft source.
    hist: jnp.ndarray | None = None
    # Draft-model speculation only (DraftSpecPagedModelRunner): the draft
    # model's own contiguous KV cache [Ld, B, Hkvd, S, Dhd].
    draft_k: jnp.ndarray | None = None
    draft_v: jnp.ndarray | None = None
    # Models with recurrent layers only (engine/hybrid.py): each slot's
    # recurrent state, per recurrent layer — Mamba's state-space state [L_M,
    # B, H, P, N] or KDA's matrix a head [L_K, B, H, dk, dv], float32, and
    # the convolutions' last K-1 inputs [L, B, conv_dim, K-1] — beside
    # pools that cover the attention layers alone; and the expert layers'
    # counts (models/hybrid.py COUNTS) since the scheduler last took them.
    ssm: jnp.ndarray | None = None
    kda: jnp.ndarray | None = None
    conv: jnp.ndarray | None = None
    moe_rows: jnp.ndarray | None = None
    # Models with window layers only (engine/hybrid.py): those layers' own
    # pool [L_W, B * ring + 1, Hkv, page, Dh], a ring of pages a slot
    # (ops/pallas/paged.py ``Ring``); pool_k / pool_v then cover the layers
    # that attend to the whole context.
    wpool_k: jnp.ndarray | None = None
    wpool_v: jnp.ndarray | None = None


jax.tree_util.register_dataclass(
    PagedDecodeState,
    data_fields=["pool_k", "pool_v", "seq_lens", "tokens", "active",
                 "temperature", "top_p", "top_k", "repeat_penalty",
                 "recent", "keys", "k_scale", "v_scale", "hist",
                 "draft_k", "draft_v", "ssm", "kda", "conv", "moe_rows",
                 "wpool_k", "wpool_v"],
    meta_fields=[],
)


class PagedModelRunner(ModelRunner):
    """ModelRunner with the paged KV layout (same serving surface)."""

    #: Chunked admission works on the paged layout too: the job accumulates
    #: one prompt's bucket-sized KV buffer (exactly what monolithic prefill
    #: materializes anyway) and insert() scatters it into pages.  The
    #: scheduler consults :meth:`prefill_prefers_monolithic` first so
    #: prompts the prefix cache mostly covers keep the suffix-only path.
    prefill_chunk = 512

    #: The scheduler dispatches prefill chunks and decode tokens in ONE
    #: jitted step when this is True (docs/RAGGED_BATCH.md).  Wrapper
    #: runners that replay frames (parallel/replicated.py) opt out with an
    #: explicit False.
    supports_ragged = True
    #: the unified programs' page table has one width, not
    #: :meth:`_ragged_window`'s power of two that grows with the slots
    ragged_width_fixed = False
    #: a model's window layers' :class:`Ring` (engine/hybrid.py), or None
    ring = None

    def __init__(self, cfg, *args, page_size: int = 128, pool_tokens: int = 0,
                 prefix_cache: bool = True, step_token_budget: int = 0,
                 **kwargs):
        # Default mesh: tp-only.  The auto-chooser spills spare devices to
        # dp, but the shared page pool cannot shard over dp (pages belong
        # to no fixed slot), so unrequested dp would just replicate it.
        if kwargs.get("mesh") is None and not kwargs.get("mesh_spec"):
            from crowdllama_tpu.parallel.mesh import largest_tp

            tp = largest_tp(len(jax.devices()), cfg.num_kv_heads)
            if tp < len(jax.devices()):
                # Paged cannot absorb the spare devices as dp, so they
                # IDLE on this auto mesh.  Be loud: the operator's best
                # moves are an explicit MoE/ep mesh, or
                # --kv-layout contiguous (whose auto mesh spills to dp —
                # full device usage, no prefix cache).
                log.warning(
                    "paged auto mesh uses tp=%d of %d devices (kv heads "
                    "limit tp; the page pool cannot shard over dp) — %d "
                    "devices idle.  Consider an explicit --mesh or "
                    "--kv-layout contiguous for dp batching.",
                    tp, len(jax.devices()), len(jax.devices()) - tp)
            kwargs["mesh_spec"] = f"1x{tp}"
        # Before super().__init__: the base constructor ends by resolving
        # the attention paths, and the paged gates need the page size.
        self.page_size = page_size
        # what a page holds (crowdllama_attn_decode_path): K and V pools,
        # or one latent row a token that is both
        self.attn_decode_path = "mla" if cfg.kv_lora_rank else "gqa"
        super().__init__(cfg, *args, **kwargs)
        from crowdllama_tpu.parallel.mesh import AXIS_DP

        assert (self.sp == 1 and self.pp == 1
                and self.mesh.shape.get(AXIS_DP, 1) == 1), (
            "paged KV composes with plain/tp meshes only (the shared page "
            "pool cannot shard over dp; sp/pp use the contiguous layout)")
        self.max_pages_per_slot = math.ceil(self.max_seq / page_size)
        total_tokens = pool_tokens or self.max_slots * self.max_seq
        self.total_pages = max(self.max_pages_per_slot,
                               math.ceil(total_tokens / page_size))
        # Host-side allocator state.
        self._free_pages: list[int] = list(range(self.total_pages))
        self._slot_pages: dict[int, list[int]] = {}
        self._host_seq = np.zeros((self.max_slots,), np.int64)
        self.page_table = np.zeros(
            (self.max_slots, self.max_pages_per_slot), np.int32)
        # Prefix cache (vLLM-style automatic prefix caching): full prompt
        # pages are content-addressed by a chain hash; a later prompt sharing
        # the prefix reuses those pages as attention *context* and only the
        # suffix is prefilled.  Pages are refcounted across slots; pages held
        # only by the index are evicted LRU under pool pressure.
        self.prefix_cache = prefix_cache
        self._prefix_index: dict[bytes, int] = {}  # chain hash -> page id
        self._page_key: dict[int, bytes] = {}      # reverse map
        self._page_refs: dict[int, int] = {}       # live slot refs per page
        self._index_lru: dict[bytes, int] = {}     # key -> last-use counter
        self._key_children: dict[bytes, set[bytes]] = {}  # chain structure
        self._lru_tick = 0
        self._pending_match: tuple[list[bytes], list[int]] | None = None
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_tokens_reused = 0
        # KV shipping (docs/KV_TRANSFER.md): pages served to / seeded from
        # peers via export_pages/import_pages.
        self.kv_pages_exported = 0
        self.kv_pages_imported = 0

        # Unified ragged batch (docs/RAGGED_BATCH.md): per-step token
        # budget = one decode token per slot + one prefill chunk of
        # ``ragged_chunk`` tokens.  The chunk width stays prefill_chunk by
        # default so ragged chunk BOUNDARIES match the monolithic chunked
        # path exactly (byte-identity of the resulting streams); an
        # explicit smaller budget trades identity for smoother decode
        # steps and rounds down to a page multiple.
        budget = step_token_budget or (self.prefill_chunk + self.max_slots)
        self.step_token_budget = budget
        c = min(self.prefill_chunk, max(budget - self.max_slots, page_size))
        self.ragged_chunk = max(page_size, (c // page_size) * page_size)
        # Slot owned by an in-progress ragged prefill: the generic
        # grow/advance loops must not treat it as a decoding slot.
        self._ragged_slot: int | None = None

        self._insert_paged = jax.jit(self._insert_paged_impl,
                                     donate_argnums=(0,))
        self._decode_paged = jax.jit(self._decode_paged_impl,
                                     donate_argnums=(1,), static_argnums=(3,))
        self._release_paged = jax.jit(self._release_paged_impl,
                                      donate_argnums=(0,))
        self._prefill_ctx = jax.jit(self._prefill_ctx_impl)
        self._ragged_step_fn = jax.jit(self._ragged_step_impl,
                                       donate_argnums=(1,),
                                       static_argnums=(7,))

    @property
    def pool_layers(self) -> int:
        """Layers whose pages come from ``pool_k`` / ``pool_v``: those that
        keep KV, but for a model's window layers (engine/hybrid.py)."""
        return self.kv_layers

    def _attention_refusals(self) -> dict[str, str]:
        from crowdllama_tpu.parallel.mesh import AXIS_TP

        quant = self.kv_dtype == "int8"
        gate = (self.page_size, pool_row_width(self.cfg),
                self.mesh.shape.get(AXIS_TP, 1), self.cfg.num_kv_heads,
                jnp.dtype(jnp.int8 if quant else self.dtype).itemsize,  # pool
                quant)
        # Multi-device meshes take the jnp reference path for the ragged
        # step (GSPMD partitions the gather views; the kernel's shard_map
        # wiring is future work) — the unified step still saves the
        # dispatch, which is what the decode-jitter problem is about.
        ragged = (f"mesh has {self.mesh.size} devices (the ragged kernel is "
                  f"not shard_map-wrapped)" if self.mesh.size > 1
                  else ragged_pallas_refusal(*gate, self.cfg.num_heads))
        decode = paged_pallas_refusal(*gate)
        if not decode and self.cfg.kv_lora_rank % 128:
            decode = (f"the latent row's value is its first "
                      f"{self.cfg.kv_lora_rank} entries: not whole lanes")
        return {**super()._attention_refusals(),
                "decode": decode, "ragged_step": ragged}

    # ------------------------------------------------------------ allocator

    def _alloc(self, n: int) -> list[int]:
        if len(self._free_pages) < n:
            self._evict_cached(n - len(self._free_pages))
        if len(self._free_pages) < n:
            raise PagesExhausted(
                f"kv pool exhausted (the full pool): need {n} pages, "
                f"{len(self._free_pages)} free (pool={self.total_pages})")
        pages = [self._free_pages.pop() for _ in range(n)]
        # A recycled page starts its next life uncounted.  Growth and
        # imported pages are never given a count (``_free`` reads a missing
        # one as 1), so a stale 0 from the last life would go to -1 at
        # release, make the page's next prompt look unreferenced while it
        # is live, and leave it in the index at -1: never evictable.
        for p in pages:
            self._page_refs.pop(p, None)
        return pages

    def _evict_cached(self, n: int) -> None:
        """Drop LRU prefix-cache pages no live slot references until ``n``
        pages are freed.  Evicting a chain key cascades to its descendants:
        matching stops at the first missing key, so a descendant whose
        ancestor is gone can never hit again — freeing it too keeps the
        cache free of unreachable dead entries."""
        for key, _tick in sorted(self._index_lru.items(), key=lambda kv: kv[1]):
            if n <= 0:
                break
            if key not in self._prefix_index:
                continue  # already cascaded away by an ancestor's eviction
            if self._page_refs.get(self._prefix_index[key], 0) == 0:
                n -= self._deindex(key)

    def _deindex(self, key: bytes) -> int:
        """Remove ``key`` and its whole descendant chain from the index;
        returns how many pages went back to the free list (refcount-0 only —
        pages still held by live slots stay allocated, just unmatchable)."""
        freed = 0
        stack = [key]
        while stack:
            k = stack.pop()
            page = self._prefix_index.pop(k, None)
            if page is None:
                continue
            self._page_key.pop(page, None)
            self._index_lru.pop(k, None)
            stack.extend(self._key_children.pop(k, ()))
            if self._page_refs.get(page, 0) == 0:
                self._free_pages.append(page)
                freed += 1
        return freed

    def _free(self, slot: int) -> None:
        for page in self._slot_pages.pop(slot, []):
            refs = self._page_refs.get(page, 1) - 1
            self._page_refs[page] = refs
            if refs <= 0 and page not in self._page_key:
                # Unshared, unindexed: back to the free list.  Indexed pages
                # stay allocated (prefix cache) until evicted under pressure.
                self._free_pages.append(page)
        self._host_seq[slot] = 0
        self.page_table[slot] = 0

    # ------------------------------------------------------------- programs

    def _insert_paged_impl(self, state: PagedDecodeState, page_idx, ks, vs,
                           slot, plen, first_token, temperature, top_p,
                           top_k, repeat_penalty, recent_row, slot_key):
        """Scatter a prefilled prompt's KV pages into the pool.

        ks/vs: [L, 1, Hkv, bucket, Dh]; page_idx: [bucket/page] pool pages.
        """
        l, _, hkv, bucket, dh = ks.shape
        npages = bucket // self.page_size
        k_scale, v_scale = state.k_scale, state.v_scale
        if self.kv_dtype == "int8":
            # Quantize the prompt's KV before the page scatter; scales are
            # per (position, kv-head) like the contiguous int8 cache.
            ks, k_sc = quantize_kv(ks, scale_dtype=k_scale.dtype)
            vs, v_sc = quantize_kv(vs, scale_dtype=v_scale.dtype)
            # [L, 1, Hkv, bucket] -> [L, np, Hkv, page]
            ksp = k_sc[:, 0].reshape(l, hkv, npages, self.page_size
                                     ).transpose(0, 2, 1, 3)
            vsp = v_sc[:, 0].reshape(l, hkv, npages, self.page_size
                                     ).transpose(0, 2, 1, 3)
            k_scale = k_scale.at[:, page_idx].set(ksp)
            v_scale = v_scale.at[:, page_idx].set(vsp)
        # [L, Hkv, bucket, Dh] -> [L, np, Hkv, page, Dh] (page-major rows)
        kp = ks[:, 0].reshape(l, hkv, npages, self.page_size, dh).transpose(
            0, 2, 1, 3, 4)
        vp = vs[:, 0].reshape(l, hkv, npages, self.page_size, dh).transpose(
            0, 2, 1, 3, 4)
        pool_k = state.pool_k.at[:, page_idx].set(
            kp.astype(state.pool_k.dtype))
        pool_v = state.pool_v.at[:, page_idx].set(
            vp.astype(state.pool_v.dtype))
        return self._activated(
            replace(state, pool_k=pool_k, pool_v=pool_v,
                    k_scale=k_scale, v_scale=v_scale),
            slot, plen, first_token, temperature, top_p, top_k,
            repeat_penalty, recent_row, slot_key)

    @staticmethod
    def _activated(state: PagedDecodeState, slot, plen, first_token,
                   temperature, top_p, top_k, repeat_penalty, recent_row,
                   slot_key) -> PagedDecodeState:
        """``state`` with ``slot`` live: its KV (and whatever else the
        model carries per slot) is already in place.  ``recent_row`` is
        the ring of the prompt alone; the first token joins it here."""
        return replace(
            state,
            seq_lens=state.seq_lens.at[slot].set(plen),
            tokens=state.tokens.at[slot].set(first_token),
            active=state.active.at[slot].set(True),
            temperature=state.temperature.at[slot].set(temperature),
            top_p=state.top_p.at[slot].set(top_p),
            top_k=state.top_k.at[slot].set(top_k),
            repeat_penalty=state.repeat_penalty.at[slot].set(repeat_penalty),
            recent=state.recent.at[slot].set(
                ring_with_first(recent_row, plen, first_token)),
            keys=state.keys.at[slot].set(slot_key))

    def _release_paged_impl(self, state: PagedDecodeState, slot):
        return replace(state,
                       seq_lens=state.seq_lens.at[slot].set(0),
                       tokens=state.tokens.at[slot].set(0),
                       active=state.active.at[slot].set(False))

    def _prefill_ctx_impl(self, params, tokens, slen, ctx_len, pool_k, pool_v,
                          k_scale, v_scale, pages, temperature, top_p, top_k,
                          repeat_penalty, recent_row, key):
        """Suffix prefill attending over cached prefix pages.

        tokens [1, bucket] suffix; pages [max_pages_per_slot] pool pages
        (dump-page padded — ``ctx_len`` masks the tail), so there is ONE
        compile per suffix bucket instead of one per (bucket, #matched).
        """
        cfg = self.cfg
        pg = self.page_size
        l, hkv, dh = self.kv_layers, cfg.num_kv_heads, cfg.resolved_head_dim()
        t = tokens.shape[1]
        c = pages.shape[0] * pg
        # [L, n, Hkv, pg, Dh] -> [L, 1, Hkv, n*pg, Dh] virtual-contiguous ctx
        ck, cv = pool_k[:, pages], pool_v[:, pages]
        if self.kv_dtype == "int8":
            # Dequantize the one slot's context pages (compute-bound prefill
            # can afford the bf16 view; decode never materializes one).
            ck = (ck.astype(jnp.float32)
                  * k_scale[:, pages][..., None].astype(jnp.float32)
                  ).astype(self.dtype)
            cv = (cv.astype(jnp.float32)
                  * v_scale[:, pages][..., None].astype(jnp.float32)
                  ).astype(self.dtype)
        ck = ck.transpose(0, 2, 1, 3, 4).reshape(l, 1, hkv, c, dh)
        cv = cv.transpose(0, 2, 1, 3, 4).reshape(l, 1, hkv, c, dh)
        ctx_valid = (jnp.arange(c) < ctx_len)[None, :]
        positions = ctx_len + jnp.minimum(jnp.arange(t)[None, :], slen - 1)
        kv_valid = (jnp.arange(t) < slen)[None, :]
        logits, ks, vs = T.prefill(params, cfg, tokens, positions,
                                   kv_valid=kv_valid,
                                   ctx_k=ck, ctx_v=cv, ctx_valid=ctx_valid)
        last = apply_repeat_penalty(
            logits[0, slen - 1][None, :], recent_row[None],
            repeat_penalty[None])
        tok = sample_tokens(last, temperature[None], top_p[None],
                            key, top_k=top_k[None])[0]
        return tok, ks, vs

    def _clear_pending(self) -> None:
        """Release an unconsumed prefill match (its insert never happened)."""
        if self._pending_match is not None:
            _, shared = self._pending_match
            for p in shared:
                self._page_refs[p] = self._page_refs.get(p, 1) - 1
            self._pending_match = None

    def _chain_keys(self, prompt_ids: list[int], n: int) -> list[bytes]:
        """Chain hashes of the first ``n`` full pages: key i commits to ALL
        tokens in pages 0..i, so equal keys ⇒ equal full prefix."""
        import hashlib

        keys, h = [], hashlib.sha256()
        pg = self.page_size
        for i in range(n):
            h.update(np.asarray(prompt_ids[i * pg:(i + 1) * pg],
                                np.int32).tobytes())
            keys.append(h.digest())
        return keys

    def prefill_begin(self, prompt_ids: list[int], state=None):
        """Chunked-admission job, seeded from cached prefix pages.

        A stale pending match from a failed monolithic prefill must never
        leak into this job's insert (it would index foreign pages under the
        wrong chain keys), so pending state clears first.  With ``state``
        (the scheduler's live decode state) the cached prefix's KV is
        COPIED into the job's context accumulators and ``done_tokens``
        starts past it — the chunked path then prefills only the suffix,
        so a mostly-cached long prompt costs its uncovered tail, not the
        whole prompt."""
        self._clear_pending()
        job = super().prefill_begin(prompt_ids)
        if state is None or not self.prefix_cache:
            return job
        pg = self.page_size
        plen = len(prompt_ids)
        matched: list[int] = []
        # Cap one page early: >= 1 suffix token must remain for logits.
        for k in self._chain_keys(prompt_ids, max(0, (plen - 1) // pg)):
            page = self._prefix_index.get(k)
            if page is None:
                break
            matched.append(page)
            self._lru_tick += 1
            self._index_lru[k] = self._lru_tick
        if not matched:
            self.prefix_misses += 1
            return job
        ctx_len = len(matched) * pg
        width = job.ctx_k.shape[3]
        pages = np.full((width // pg,), self.total_pages, np.int32)
        pages[:len(matched)] = matched  # dump-page padded: one compile/bucket
        # The copy consumes the CURRENT pool arrays — XLA orders it before
        # any later donation of those buffers, and garbage beyond ctx_len
        # is masked by the job's ctx_valid.
        job.ctx_k, job.ctx_v = self._seed_ctx(
            state.pool_k, state.pool_v, state.k_scale, state.v_scale,
            jnp.asarray(pages), job.ctx_k, job.ctx_v)
        job.done_tokens = ctx_len
        self.prefix_hits += 1
        self.prefix_tokens_reused += ctx_len
        return job

    @partial(jax.jit, static_argnums=0, donate_argnums=(6, 7))
    def _seed_ctx(self, pool_k, pool_v, k_scale, v_scale, pages, ctx_k,
                  ctx_v):
        """Copy pool pages into a prefill job's context accumulators
        ([L, n, Hkv, pg, Dh] gather → [L, 1, Hkv, n*pg, Dh] prefix)."""
        l, hkv, dh = (self.kv_layers, self.cfg.num_kv_heads,
                      self.cfg.resolved_head_dim())
        c = pages.shape[0] * self.page_size
        ck, cv = pool_k[:, pages], pool_v[:, pages]
        if self.kv_dtype == "int8":
            ck = (ck.astype(jnp.float32)
                  * k_scale[:, pages][..., None].astype(jnp.float32))
            cv = (cv.astype(jnp.float32)
                  * v_scale[:, pages][..., None].astype(jnp.float32))
        ck = ck.transpose(0, 2, 1, 3, 4).reshape(l, 1, hkv, c, dh)
        cv = cv.transpose(0, 2, 1, 3, 4).reshape(l, 1, hkv, c, dh)
        return (ck.astype(ctx_k.dtype)[..., :ctx_k.shape[3], :],
                cv.astype(ctx_v.dtype)[..., :ctx_v.shape[3], :])

    def warmup_ctx_prefill(self, state: "PagedDecodeState") -> None:
        """Compile the suffix-over-cached-context program for the smallest
        suffix bucket (ctx_len=0 masks the context; shapes are what a real
        hit uses).  Owned HERE so engine warmup cannot drift from the jit
        signature."""
        pages = np.full((self.max_pages_per_slot,), self.total_pages,
                        np.int32)
        t_c = ENGINE_TELEMETRY.compile_begin("ctx_prefill", self.buckets[0])
        self._prefill_ctx(
            self.params, jnp.zeros((1, self.buckets[0]), jnp.int32),
            jnp.int32(1), jnp.int32(0), state.pool_k, state.pool_v,
            state.k_scale, state.v_scale, jnp.asarray(pages),
            jnp.float32(0.0), jnp.float32(1.0), jnp.int32(0),
            jnp.float32(1.0),
            jnp.asarray(self._recent_from_prompt([])),
            jax.random.PRNGKey(0))
        ENGINE_TELEMETRY.compile_end("ctx_prefill", self.buckets[0], t_c)

    def prefill_prefers_monolithic(self, prompt_ids: list[int],
                                   chunk: int | None = None) -> bool:
        """True when the prefix cache covers enough of the prompt that the
        suffix-only (ctx) prefill beats chunked admission: the uncovered
        suffix fits within one admission chunk (``chunk`` — the scheduler
        passes ``ragged_chunk`` under unified ragged admission, where a
        tight step token budget shrinks what one dispatch may carry)."""
        if not self.prefix_cache:
            return False
        pg = self.page_size
        plen = len(prompt_ids)
        matched = 0
        for k in self._chain_keys(prompt_ids, max(0, (plen - 1) // pg)):
            if k not in self._prefix_index:
                break
            matched += pg
        return plen - matched <= (self.prefill_chunk if chunk is None
                                  else chunk)

    def prefill(self, prompt_ids: list[int], temperature: float, top_p: float,
                key, state: PagedDecodeState | None = None, top_k: int = 0,
                repeat_penalty: float = 1.0):
        """Bucketed prefill with automatic prefix caching.

        With ``state`` (the scheduler passes its live decode state) the
        prompt's full pages are looked up in the prefix index; on a hit only
        the suffix is prefilled, attending over the cached pages as context.
        The match is stashed for the paired :meth:`insert` (admissions are
        serialized by the scheduler, so one pending match is enough).
        """
        self._clear_pending()
        pg = self.page_size
        plen = len(prompt_ids)
        if not self.prefix_cache:
            return super().prefill(prompt_ids, temperature, top_p, key,
                                   top_k=top_k,
                                   repeat_penalty=repeat_penalty)
        # Index keys for every full prompt page; matching is capped one page
        # earlier so at least one suffix token remains to produce logits.
        keys = self._chain_keys(prompt_ids, plen // pg)
        if state is None:
            self._pending_match = (keys, [])
            return super().prefill(prompt_ids, temperature, top_p, key,
                                   top_k=top_k,
                                   repeat_penalty=repeat_penalty)
        matched: list[int] = []
        for k in keys[:max(0, (plen - 1) // pg)]:
            page = self._prefix_index.get(k)
            if page is None:
                break
            matched.append(page)
            self._lru_tick += 1
            self._index_lru[k] = self._lru_tick
        # Suffix buckets round up: shrink the match until shared pages +
        # suffix-bucket pages fit the slot's page table.
        while matched:
            suffix_bucket = self.bucket_for(plen - len(matched) * pg)
            if len(matched) + suffix_bucket // pg <= self.max_pages_per_slot:
                break
            matched.pop()
        if not matched:
            self.prefix_misses += 1
            self._pending_match = (keys, [])
            return super().prefill(prompt_ids, temperature, top_p, key,
                                   top_k=top_k,
                                   repeat_penalty=repeat_penalty)
        self.prefix_hits += 1
        # Pin the matched pages NOW: their refcount may be 0 (only the index
        # holds them), and the paired insert's _alloc could otherwise evict
        # and re-hand them out as fresh suffix pages — the suffix scatter
        # would then overwrite the very prefix KV this slot attends over.
        # The pin becomes the slot's reference at insert; _clear_pending
        # releases it if the insert never happens.
        for p in matched:
            self._page_refs[p] = self._page_refs.get(p, 0) + 1
        ctx_len = len(matched) * pg
        self.prefix_tokens_reused += ctx_len
        suffix = prompt_ids[ctx_len:]
        bucket = self.bucket_for(len(suffix))
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :len(suffix)] = suffix
        pages = np.full((self.max_pages_per_slot,), self.total_pages, np.int32)
        pages[:len(matched)] = matched  # dump-page padded
        # One ctx_prefill program per SUFFIX bucket (the dump-page scatter's
        # page-table width is static) — the prefix-hit analog of prefill's
        # per-bucket compile.
        ENGINE_TELEMETRY.padding_inc(useful=len(suffix),
                                     waste=bucket - len(suffix))
        t_c = ENGINE_TELEMETRY.compile_begin("ctx_prefill", bucket)
        tok, ks, vs = self._prefill_ctx(
            self.params, jnp.asarray(tokens), jnp.int32(len(suffix)),
            jnp.int32(ctx_len), state.pool_k, state.pool_v,
            state.k_scale, state.v_scale,
            jnp.asarray(pages), jnp.float32(temperature),
            jnp.float32(top_p), jnp.int32(top_k),
            jnp.float32(repeat_penalty),
            jnp.asarray(self._recent_from_prompt(prompt_ids)), key,
        )
        ENGINE_TELEMETRY.compile_end("ctx_prefill", bucket, t_c)
        self._pending_match = (keys, matched)
        return tok, ks, vs, plen

    def _decode_layers(self, params, x, positions, pools, attend, st,
                       live, chunk=None):
        """The layer loop of a decode or ragged step over rows ``x [N, D]``:
        a ``lax.scan`` over the stacked layers that carries ``(x, pool_k,
        pool_v, k_scale, v_scale)`` with the pools at their full ``[L, P+1,
        Hkv, page, Dh]`` and scans ``(layer params, window, layer index)``.
        ``attend(pools, window, li)`` gives layer ``li``'s ``attn_fn`` —
        it writes the rows' K/V into the stack at ``li`` and attends over
        the stack at ``li`` — and where the pools are afterwards, so the
        donated pool is never copied or rebuilt.  (As scanned xs/ys XLA
        rebuilt the whole stack every step: ROADMAP S7.)

        Returns (x, pools, the state fields the layers changed besides the
        pools: none here).  ``st``, ``live`` (which rows are real tokens)
        and ``chunk`` (the ragged step's ``(slot, valid rows)``) are for a
        model that carries more per slot than KV (engine/hybrid.py)."""
        cfg = self.cfg
        cos, sin = rope_table(cfg.max_context_length,
                              cfg.resolved_head_dim(), cfg.rope_theta,
                              scaling=cfg.rope_scaling)

        # int8 expert banks ride the loop whole, as the pools do
        layers, bind = ride_banks(params["layers"])

        def body(carry, scanned):
            x, *pools = carry
            lp, window, li = scanned
            attn_fn, after = attend(tuple(pools), window, li)
            x = T.decode_layer_body(bind(lp), cfg, x, positions, cos, sin,
                                    attn_fn)
            return (x, *after["pools"]), None

        (x, *pools), _ = jax.lax.scan(
            body, (x, *pools),
            (layers, T.layer_sliding_windows(cfg),
             jnp.arange(cfg.num_layers, dtype=jnp.int32)))
        return x, tuple(pools), {}

    def _sampled(self, st: PagedDecodeState, logits, pools, changed):
        """The state after a step whose decode rows gave ``logits [B, V]``:
        one token sampled per slot, the repeat ring and the PRNG carries
        advanced, the pools (and ``changed``) as the layers left them.
        Returns (new state, next tokens)."""
        with jax.named_scope("sample"):
            carry, sub = split_slot_keys(st.keys)
            logits = apply_repeat_penalty(logits, st.recent,
                                          st.repeat_penalty)
            next_tokens = sample_tokens_slots(
                logits, st.temperature, st.top_p, sub, top_k=st.top_k)
            next_tokens = jnp.where(st.active, next_tokens, 0)
        bidx2 = jnp.arange(st.recent.shape[0])
        cursor = (st.seq_lens + 1) % REPEAT_LAST_N
        recent = st.recent.at[bidx2, cursor].set(
            jnp.where(st.active, next_tokens, st.recent[bidx2, cursor]))
        pool_k, pool_v, k_scale, v_scale = pools
        return replace(
            st, pool_k=pool_k, pool_v=pool_v, k_scale=k_scale,
            v_scale=v_scale,
            seq_lens=jnp.where(st.active, st.seq_lens + 1, st.seq_lens),
            tokens=next_tokens, recent=recent, keys=carry,
            **changed), next_tokens

    def _decode_views(self, st: PagedDecodeState, page_table, lens,
                      listed: bool) -> dict:
        """What a step's decode rows read, by the layer's ring (``None``: the
        full pool): (page table, ``lens`` counted from its first column, the
        GQA decode kernel's grid of live page pairs — ``listed`` — or None).
        Built ONCE a step, before the layer loop: the lengths are every
        layer's, and a list a layer would be a handful of small device ops
        a layer."""
        views = {None: (st.pool_k, page_table, lens)}
        if self.ring is not None:
            views[self.ring] = (
                st.wpool_k, *self.ring.decode_view(lens, self.page_size))
        return {ring: (table, klens,
                       decode_work(pool, table, klens, st.active)
                       if listed else None)
                for ring, (pool, table, klens) in views.items()}

    def _paged_step_body(self, params, page_table):
        """One paged decode step as the ``lax.scan`` body closure of
        ``_decode_paged_impl``.  The layer loop is
        :meth:`_decode_layers`."""
        cfg = self.cfg
        pg = self.page_size
        b = self.max_slots
        dh = pool_row_width(cfg)
        hkv = cfg.num_kv_heads
        scale = T.attn_scale(cfg)
        slot_idx = jnp.arange(b)
        quant = self.kv_dtype == "int8"
        # Fused kernel reads pages via the scalar-prefetched table; the jnp
        # gather view is the portable (CPU) path.  tp>1 meshes run the
        # kernel per-shard through the shard_map wrapper (the pool is
        # tp-sharded over kv heads, so shards are independent).  Any
        # multi-device mesh (ep×tp, even with tp=1) must go through the
        # shard_map wrapper: a raw pallas_call can't be partitioned by
        # GSPMD, and shard_map is also what replicates it over ep.
        sharded = self.mesh.size > 1
        use_kernel = self.attention_paths["decode"] != "jnp"

        def step(st: PagedDecodeState, _):
            positions = jnp.minimum(st.seq_lens, self.max_seq - 1)
            lens = jnp.minimum(st.seq_lens + 1, self.max_seq)
            x = T._embed(params, cfg, st.tokens)
            # Inactive slots must not scatter into page 0 (it belongs to a
            # real slot) — route their writes to the reserved dump page.
            cur_page = jnp.where(st.active,
                                 page_table[slot_idx, positions // pg],
                                 self.total_pages)  # [B]
            offset = positions % pg
            # the MLA kernel and the shard_map wrapper take no list
            views = self._decode_views(
                st, page_table, lens,
                listed=use_kernel and not sharded and st.pool_v is not None)

            def attend(pools, window, li, ring=None):
                pk, pv, ksc, vsc = pools
                after = {}
                pages = cur_page
                table, klens, work = views[ring]
                name = "paged_decode_attention"
                if ring is not None:
                    # a window layer: its own pool, a ring of it a slot,
                    # read from the page its window starts in
                    pages = jnp.where(
                        st.active, ring.page_of(slot_idx, positions // pg),
                        pk.shape[1] - 1)
                    window = ring.window
                    name += "_window"

                @jax.named_scope("kv_write")
                def write(k, v):
                    put = partial(_put_rows, layer=li, pages=pages,
                                  offsets=offset)
                    return _write_kv(put, pk, pv, ksc, vsc, k, v)

                def attn_fn(q, k, v):
                    q, k = _pool_wide(pk, q, k)
                    after["pools"] = write(k, v)
                    return read(q, *after["pools"])

                @jax.named_scope("attention")
                def read(q, pk2, pv2, ks2, vs2):
                    view_len = table.shape[1] * pg
                    if pv2 is None:     # latent rows (one device)
                        if use_kernel:
                            return paged_decode_attention_mla(
                                q, pk2, li, page_table, lens, scale,
                                cfg.kv_lora_rank)
                        kc = pk2[li, page_table].transpose(
                            0, 2, 1, 3, 4).reshape(b, hkv, view_len, dh)
                        return decode_attention(q, kc, kc, lens, scale)
                    if use_kernel:
                        if sharded:
                            return flash_paged_decode_attention_tp(
                                q, pk2, pv2, li, page_table, lens, scale,
                                self.mesh, softcap=cfg.attn_logit_softcap,
                                sliding_window=window,
                                k_scale=ks2, v_scale=vs2)
                        return flash_paged_decode_attention(
                            q, pk2, pv2, li, table, klens, scale,
                            softcap=cfg.attn_logit_softcap,
                            sliding_window=window,
                            k_scale=ks2, v_scale=vs2, name=name, work=work)
                    # Virtual-contiguous view of each slot's pages.
                    kc = pk2[li, table].transpose(
                        0, 2, 1, 3, 4).reshape(b, hkv, view_len, dh)
                    vc = pv2[li, table].transpose(
                        0, 2, 1, 3, 4).reshape(b, hkv, view_len, dh)
                    if quant:
                        ksg = ks2[li, page_table].transpose(
                            0, 2, 1, 3).reshape(b, hkv, view_len)
                        vsg = vs2[li, page_table].transpose(
                            0, 2, 1, 3).reshape(b, hkv, view_len)
                        return decode_attention_q(
                            q, kc, ksg, vc, vsg, lens, scale,
                            softcap=cfg.attn_logit_softcap,
                            sliding_window=window)
                    return decode_attention(q, kc, vc, klens, scale,
                                            softcap=cfg.attn_logit_softcap,
                                            sliding_window=window)

                return attn_fn, after

            x, pools, changed = self._decode_layers(
                params, x, positions,
                (st.pool_k, st.pool_v, st.k_scale, st.v_scale), attend, st,
                st.active)
            logits = T._unembed(params, cfg, x)
            return self._sampled(st, logits, pools, changed)

        return step

    def _decode_paged_impl(self, params, state: PagedDecodeState,
                           page_table, num_steps: int):
        new_state, tokens = jax.lax.scan(
            self._paged_step_body(params, page_table), state,
            length=num_steps)
        return tokens, new_state

    def _ragged_step_body(self, params, page_table, total_len, chunk_slot,
                          c: int):
        """One unified ragged step (docs/RAGGED_BATCH.md) as the
        ``lax.scan`` body closure of ``_ragged_step_impl``.

        One call of the returned ``step(state, (ctx_i, ctoks))`` runs ONE
        jitted forward over B+C query rows: one decode token per active
        slot (rows 0..B-1, exactly the plain decode step's math) plus one
        prefill chunk of up to C tokens for ``chunk_slot`` (rows B..,
        exactly the monolithic chunk's math with the slot's pages as
        cached context).  KV for all rows is written into the shared,
        loop-carried pool stack in the same layer pass (decode rows one by
        one, the chunk a page at a time), and attention runs through
        :func:`ragged_paged_attention` with per-sequence (q_len, kv_len)
        metadata.  Returns ``(new_state, (decode tokens [B], chunk logits
        [V], has_chunk))``.
        """
        cfg = self.cfg
        pg = self.page_size
        b = self.max_slots
        scale = T.attn_scale(cfg)
        slot_idx = jnp.arange(b)
        use_pallas = self.attention_paths["ragged_step"] != "jnp"

        def step(st: PagedDecodeState, xs):
            ctx_i, ctoks = xs
            valid = jnp.clip(total_len - ctx_i, 0, c)
            positions_dec = jnp.minimum(st.seq_lens, self.max_seq - 1)
            lens_dec = jnp.minimum(st.seq_lens + 1, self.max_seq)
            cpos = jnp.minimum(ctx_i + jnp.arange(c), self.max_seq - 1)
            x = T._embed(params, cfg, jnp.concatenate([st.tokens, ctoks]))
            positions = jnp.concatenate([positions_dec, cpos])
            # Decode rows of inactive slots (including the chunk's own
            # still-inactive decode lane) write to the dump page, exactly
            # like the plain decode step; chunk rows past the valid length
            # are not written at all.
            cur_page = jnp.where(st.active,
                                 page_table[slot_idx, positions_dec // pg],
                                 self.total_pages)
            dec_offs = positions_dec % pg
            chunk_pages = page_table[chunk_slot]
            q_lens = jnp.concatenate([
                jnp.where(st.active, 1, 0).astype(jnp.int32),
                valid.astype(jnp.int32)[None]])
            kv_lens = jnp.concatenate([
                lens_dec.astype(jnp.int32),
                (ctx_i + valid).astype(jnp.int32)[None]])
            views = self._decode_views(st, page_table, lens_dec,
                                       listed=use_pallas)

            def attend(pools, window, li, ring=None):
                pk, pv, ksc, vsc = pools
                after = {}
                pages, row, dump = cur_page, chunk_pages, self.total_pages
                if ring is not None:    # a window layer's pool (see above)
                    dump = pk.shape[1] - 1
                    pages = jnp.where(
                        st.active,
                        ring.page_of(slot_idx, positions_dec // pg), dump)
                    row = ring.page_of(
                        chunk_slot, jnp.arange(self.max_pages_per_slot))

                @jax.named_scope("kv_write")
                def write(k, v):
                    def put(pool, rows):
                        # [B + C, Hkv, ...]: decode rows, then the chunk's.
                        pool = _put_rows(pool, rows[:b], layer=li,
                                         pages=pages, offsets=dec_offs)
                        return _put_chunk(
                            pool, jnp.swapaxes(rows[b:], 0, 1), layer=li,
                            page_row=row, start=ctx_i,
                            valid=valid, dump_page=dump)

                    return _write_kv(put, pk, pv, ksc, vsc, k, v)

                def attn_fn(q, k, v):
                    q, k = _pool_wide(pk, q, k)
                    after["pools"] = write(k, v)
                    return read(q, k, v, *after["pools"])

                @jax.named_scope("attention")
                def read(q, k, v, pk2, pv2, ks2, vs2):
                    # The chunk's fresh KV rides along as explicit operands
                    # so the reference path's self block matches monolithic
                    # prefill bitwise (bf16 pools).
                    chunk_k = k[b:].transpose(1, 0, 2)[None]
                    if pv2 is None:     # latent rows: the key is the value
                        chunk_v, pv2 = chunk_k, pk2
                    else:
                        chunk_v = v[b:].transpose(1, 0, 2)[None]
                    return ragged_paged_attention(
                        q, chunk_k, chunk_v, pk2, pv2, li, page_table,
                        q_lens, kv_lens, chunk_slot, scale,
                        softcap=cfg.attn_logit_softcap,
                        sliding_window=window, k_scale=ks2, v_scale=vs2,
                        use_pallas=use_pallas, ring=ring,
                        work=views[ring][2])

                return attn_fn, after

            live = jnp.concatenate([st.active, jnp.arange(c) < valid])
            x, pools, changed = self._decode_layers(
                params, x, positions,
                (st.pool_k, st.pool_v, st.k_scale, st.v_scale), attend, st,
                live, chunk=(chunk_slot, valid))
            # Unembed the B decode rows + ONE chunk row (the last valid
            # one) — the rest of the chunk never needs logits.
            x_last = x[b + jnp.clip(valid - 1, 0, c - 1)]
            logits = T._unembed(params, cfg,
                                jnp.concatenate([x[:b], x_last[None]]))
            new_state, next_tokens = self._sampled(st, logits[:b], pools,
                                                   changed)
            return new_state, (next_tokens, logits[b], valid > 0)

        return step

    def _ragged_step_impl(self, params, state: PagedDecodeState, page_table,
                          chunk_tokens, ctx_arr, total_len, chunk_slot,
                          num_steps: int):
        """``num_steps`` unified ragged steps as a ``lax.scan`` over
        :meth:`_ragged_step_body`.

        chunk_tokens: [K, C] prompt tokens per step (0-padded);
        ctx_arr: [K] tokens already prefilled before each step;
        total_len: prompt length; chunk_slot: the reserved slot.
        Returns (decode tokens [K, B], last prompt-token logits [V], state).
        """
        step = self._ragged_step_body(params, page_table, total_len,
                                      chunk_slot, chunk_tokens.shape[1])
        new_state, (tokens, chunk_logits, flags) = jax.lax.scan(
            step, state, (ctx_arr, chunk_tokens))
        # Logits of the final prompt token = the last step that had valid
        # chunk rows (later steps past the prompt end leave it untouched).
        ridx = (num_steps - 1) - jnp.argmax(flags[::-1])
        return tokens, chunk_logits[ridx], new_state

    # ------------------------------------------------------------------ API

    def init_state(self, seed: int = 0) -> PagedDecodeState:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from crowdllama_tpu.parallel.mesh import AXIS_TP
        from crowdllama_tpu.parallel.sharding import filter_spec

        l = self.pool_layers
        hkv, dh = self.cfg.num_kv_heads, pool_row_width(self.cfg)
        # +1: reserved dump page absorbing inactive slots' decode writes.
        shape = (l, self.total_pages + 1, hkv, self.page_size, dh)
        # KV heads shard over tp like the contiguous cache (runner.py
        # cache_pspec); the page dim stays unsharded — pages are shared by
        # all slots, so dp cannot partition them.
        pool_sharding = NamedSharding(
            self.mesh, filter_spec(P(None, None, AXIS_TP, None, None),
                                   self.mesh))
        quantized = self.kv_dtype == "int8"
        pool_dtype = jnp.int8 if quantized else self.dtype
        scale_sharding = NamedSharding(
            self.mesh, filter_spec(P(None, None, AXIS_TP, None), self.mesh))
        self._free_pages = list(range(self.total_pages))
        self._slot_pages = {}
        self._host_seq[:] = 0
        self.page_table[:] = 0
        self._prefix_index.clear()
        self._page_key.clear()
        self._page_refs.clear()
        self._index_lru.clear()
        self._key_children.clear()
        self._pending_match = None
        self._ragged_slot = None
        b = self.max_slots
        return PagedDecodeState(
            pool_k=jax.device_put(jnp.zeros(shape, pool_dtype), pool_sharding),
            pool_v=(None if self.cfg.kv_lora_rank else jax.device_put(
                jnp.zeros(shape, pool_dtype), pool_sharding)),
            k_scale=(jax.device_put(jnp.zeros(shape[:-1], jnp.bfloat16),
                                    scale_sharding) if quantized else None),
            v_scale=(jax.device_put(jnp.zeros(shape[:-1], jnp.bfloat16),
                                    scale_sharding) if quantized else None),
            seq_lens=jnp.zeros((b,), jnp.int32),
            tokens=jnp.zeros((b,), jnp.int32),
            active=jnp.zeros((b,), bool),
            temperature=jnp.zeros((b,), jnp.float32),
            top_p=jnp.ones((b,), jnp.float32),
            top_k=jnp.zeros((b,), jnp.int32),
            repeat_penalty=jnp.ones((b,), jnp.float32),
            recent=jnp.full((b, REPEAT_LAST_N), self.cfg.vocab_size,
                            jnp.int32),
            keys=jnp.zeros((b, 2), jnp.uint32),
        )

    def insert(self, state: PagedDecodeState, slot: int, ks, vs, plen: int,
               first_token: int, temperature: float, top_p: float,
               prompt_tokens: list[int] | None = None,
               slot_key=None, top_k: int = 0, repeat_penalty: float = 1.0):
        """Place a prefilled sequence: shared prefix pages (from the paired
        prefill's match, refcounted) + freshly scattered suffix pages."""
        bucket = ks.shape[3]
        pg = self.page_size
        if bucket % pg != 0:
            raise ValueError(
                f"prefill bucket {bucket} not a multiple of page size "
                f"{pg} (align buckets to pages)")
        keys, shared = self._pending_match or ([], [])
        self._pending_match = None
        if not keys and self.prefix_cache and prompt_tokens:
            # Chunk-admitted prompts (scheduler's prefill_begin/step path)
            # never ran prefill()'s matching — index their pages here so
            # later prompts sharing the prefix still hit.
            keys = self._chain_keys(list(prompt_tokens),
                                    len(prompt_tokens) // self.page_size)
        self._free(slot)  # defensive: slot must not leak prior pages
        try:
            fresh = self._alloc(bucket // pg)
        except PagesExhausted:
            for p in shared:  # release the prefill-time pins
                self._page_refs[p] = self._page_refs.get(p, 1) - 1
            raise
        pages = list(shared) + fresh
        # Shared pages carry the pin taken at prefill-match time (it becomes
        # this slot's reference); only fresh pages gain a new reference.
        for p in fresh:
            self._page_refs[p] = self._page_refs.get(p, 0) + 1
        self._slot_pages[slot] = pages
        self._host_seq[slot] = plen
        self.page_table[slot] = 0
        self.page_table[slot, :len(pages)] = pages
        if self.prefix_cache:
            # Index every fresh page fully covered by prompt tokens (decode
            # writes start at plen, which lies beyond them — immutable).
            ctx_len = len(shared) * pg
            for i, page in enumerate(fresh):
                ki = len(shared) + i
                if ctx_len + (i + 1) * pg > plen or ki >= len(keys):
                    break
                if keys[ki] not in self._prefix_index:
                    self._prefix_index[keys[ki]] = page
                    self._page_key[page] = keys[ki]
                    self._lru_tick += 1
                    self._index_lru[keys[ki]] = self._lru_tick
                    if ki > 0:  # chain edge for cascade eviction
                        self._key_children.setdefault(
                            keys[ki - 1], set()).add(keys[ki])
        if slot_key is None:
            slot_key = default_slot_key(slot)
        # ``first_token`` may still be on the device (prefill's scalar):
        # the program puts it into the prompt's ring itself.
        recent_row = self._recent_from_prompt(list(prompt_tokens or []),
                                              plen=plen)
        t_c = ENGINE_TELEMETRY.compile_begin("insert_paged", ks.shape[3])
        out = self._insert_paged(
            state, jnp.asarray(fresh, jnp.int32), ks, vs, jnp.int32(slot),
            jnp.int32(plen), jnp.int32(first_token),
            jnp.float32(temperature), jnp.float32(top_p), jnp.int32(top_k),
            jnp.float32(repeat_penalty), jnp.asarray(recent_row), slot_key,
        )
        ENGINE_TELEMETRY.compile_end("insert_paged", ks.shape[3], t_c)
        return out

    @property
    def _page_bytes(self) -> int:
        """Bytes of one page of one layer, K and V (and their scales), as
        allocated."""
        hkv, dh = self.cfg.num_kv_heads, pool_row_width(self.cfg)
        if self.kv_dtype == "int8":
            return 2 * hkv * self.page_size * (dh + 2)
        twins = 1 if self.cfg.kv_lora_rank else 2
        return (twins * hkv * self.page_size * dh
                * jnp.dtype(self.dtype).itemsize)

    def kv_gauges(self) -> dict[str, float]:
        """What the pools hold and could hold, for the scheduler's gauges
        (rendered as the engine's ``kv_pool_bytes{kind}`` and
        ``kv_live_bytes{kind}``): ``full`` is this pool, whose pages a slot
        keeps for its whole context — ``latent`` where a page holds one row
        a token, key and value both, and has no twin; live = pages some
        slot or the prefix index holds."""
        page = self._page_bytes * self.pool_layers
        kind = "latent" if self.cfg.kv_lora_rank else "full"
        return {
            f"kv_pool_bytes|kind={kind}": float(self.total_pages * page),
            f"kv_live_bytes|kind={kind}": float(
                (self.total_pages - len(self._free_pages)) * page)}

    def release(self, state: PagedDecodeState, slot: int):
        self._free(slot)
        t_c = ENGINE_TELEMETRY.compile_begin("release_paged", 0)
        out = self._release_paged(state, jnp.int32(slot))
        ENGINE_TELEMETRY.compile_end("release_paged", 0, t_c)
        return out

    def _ensure_slot(self, slot: int, steps: int) -> None:
        """Grow one slot's page table to cover ``steps`` more tokens."""
        pages = self._slot_pages[slot]
        needed_tokens = min(int(self._host_seq[slot]) + steps + 1,
                            self.max_seq)
        needed = math.ceil(needed_tokens / self.page_size)
        if needed > len(pages):
            new = self._alloc(needed - len(pages))
            self.page_table[slot, len(pages):len(pages) + len(new)] = new
            pages.extend(new)

    def pre_decode_check(self, steps: int) -> list[int]:
        """Scheduler hook: grow every live slot for the coming chunk; slots
        an overcommitted pool cannot grow are returned for forced
        length-finish (their pages free at release) — one starved request
        ends instead of the whole engine failing."""
        starved = []
        for slot in list(self._slot_pages):
            if slot == self._ragged_slot:
                continue  # grows by chunk inside ragged_step, never decodes
            try:
                self._ensure_slot(slot, steps)
            except PagesExhausted:
                starved.append(slot)
        return starved

    def _ensure_capacity(self, steps: int) -> None:
        for slot in list(self._slot_pages):
            if slot == self._ragged_slot:
                continue
            self._ensure_slot(slot, steps)

    @cached_property
    def _pool_shard(self) -> jax.ShapeDtypeStruct:
        """One page of one layer of the pool as one device holds it: what
        the decode kernel sizes its grid steps from."""
        from crowdllama_tpu.parallel.mesh import AXIS_TP

        return jax.ShapeDtypeStruct(
            (1, 1, self.cfg.num_kv_heads // self.mesh.shape.get(AXIS_TP, 1),
             self.page_size, pool_row_width(self.cfg)),
            jnp.int8 if self.kv_dtype == "int8" else self.dtype)

    def _advance(self, num_steps: int, ragged_cols: int = 0) -> None:
        """Dispatch-time host bookkeeping of a flight's decode rows: every
        decoding slot (all but a ragged job's) is ``num_steps`` tokens
        longer — and, from those lengths alone (no device read), what the
        GQA decode kernel's calls of the flight walk and what the
        rectangular grid would have (crowdllama_attn_grid_steps_total;
        ``ragged_cols``: the flight is a ragged one, over a table that
        wide).  The plain step of a latent model calls the MLA kernel, a
        rectangle still: nothing to book."""
        slots = [s for s in self._slot_pages if s != self._ragged_slot]
        path = self.attention_paths["ragged_step" if ragged_cols
                                    else "decode"]
        if path != "jnp" and (ragged_cols or not self.cfg.kv_lora_rank):
            pg = self.page_size
            # the kernel's lengths count the pending token
            lens = np.minimum(
                self._host_seq[slots][None, :]
                + np.arange(1, num_steps + 1)[:, None], self.max_seq)
            kinds = {"full": (self.pool_layers, lens,
                              ragged_cols or self.max_pages_per_slot)}
            if self.ring is not None:
                kinds["window"] = (self.kv_layers - self.pool_layers,
                                   self.ring.decode_lens(lens, pg),
                                   self.ring.cols(1, pg))
            for kind, (layers, klens, cols) in kinds.items():
                live, rectangle = decode_grid_steps(
                    self._pool_shard, klens, cols, self.max_slots)
                ENGINE_TELEMETRY.attn_grid_steps_inc(
                    kind, layers * live, layers * rectangle)
        for s in slots:
            self._host_seq[s] = min(self._host_seq[s] + num_steps,
                                    self.max_seq)

    def decode_steps(self, state: PagedDecodeState, num_steps: int = 1):
        tokens, new_state = self.decode_steps_device(state, num_steps)
        return np.asarray(tokens), new_state

    def decode_steps_device(self, state: PagedDecodeState, num_steps: int = 1):
        # Page-table growth and _host_seq advance are dispatch-time host
        # bookkeeping, so chained device-side chunks stay consistent without
        # waiting for earlier chunks to finish (see ModelRunner
        # .decode_steps_device on why pipelining matters).
        self._ensure_capacity(num_steps)
        t_c = ENGINE_TELEMETRY.compile_begin("decode_paged", num_steps)
        tokens, new_state = self._decode_paged(
            self.params, state, jnp.asarray(self.page_table), num_steps)
        ENGINE_TELEMETRY.compile_end("decode_paged", num_steps, t_c)
        self._advance(num_steps)
        return tokens, new_state

    # ----------------------- unified ragged batch (docs/RAGGED_BATCH.md)

    class RaggedPrefillJob:
        """Host handle for a prefill running INSIDE the decode loop.

        Unlike the monolithic PrefillJob there are no context
        accumulators: every chunk's KV lands directly in the slot's pool
        pages, so ``done_tokens`` of progress is exactly ``done_tokens``
        of resumable, exportable KV (full pages are prefix-indexed as
        they complete — a mid-prefill migration ships them like any
        cached prefix)."""

        ragged = True  # scheduler routes abort/advance by this marker

        def __init__(self, prompt_ids, slot, keys):
            self.prompt_ids = prompt_ids
            self.slot = slot
            self.keys = keys          # chain hashes of full prompt pages
            self.done_tokens = 0
            self.last_logits = None   # [V] f32, final prompt token
            self.indexed = 0          # pages already prefix-indexed

        @property
        def finished(self) -> bool:
            return self.done_tokens >= len(self.prompt_ids)

    def ragged_begin(self, prompt_ids: list[int], slot: int,
                     state: PagedDecodeState) -> "RaggedPrefillJob":
        """Reserve ``slot`` for chunked-in-the-decode-loop prefill.

        Cached prefix pages become the slot's leading pages immediately
        (pinned as the slot's reference, same protocol as insert), so a
        mostly-cached prompt starts ``done_tokens`` deep and only the
        uncovered tail streams through the unified step."""
        if self._ragged_slot is not None:
            raise RuntimeError("one ragged prefill at a time")
        plen = len(prompt_ids)
        if plen >= self.max_seq:
            raise ValueError(
                f"prompt of {plen} tokens exceeds max context "
                f"{self.max_seq}")
        self._clear_pending()
        pg = self.page_size
        keys = self._chain_keys(list(prompt_ids), plen // pg)
        job = self.RaggedPrefillJob(list(prompt_ids), slot, keys)
        self._free(slot)  # defensive: slot must not leak prior pages
        matched: list[int] = []
        if self.prefix_cache:
            # Cap one page early: >= 1 suffix token must remain for logits.
            for k in keys[:max(0, (plen - 1) // pg)]:
                page = self._prefix_index.get(k)
                if page is None:
                    break
                matched.append(page)
                self._lru_tick += 1
                self._index_lru[k] = self._lru_tick
            if matched:
                self.prefix_hits += 1
                self.prefix_tokens_reused += len(matched) * pg
            else:
                self.prefix_misses += 1
        for p in matched:  # pin becomes the slot's reference
            self._page_refs[p] = self._page_refs.get(p, 0) + 1
        self._slot_pages[slot] = list(matched)
        self._host_seq[slot] = len(matched) * pg
        self.page_table[slot] = 0
        self.page_table[slot, :len(matched)] = matched
        job.done_tokens = len(matched) * pg
        job.indexed = len(matched)
        self._ragged_slot = slot
        return job

    def _ragged_window(self) -> int:
        """Page-table width (in pages) this dispatch actually needs:
        max pages held by any slot AFTER provisioning, rounded up to a
        power of two (bounded compile count) and floored at 4 pages.

        Passing ``page_table[:, :wp]`` instead of the full table makes
        the reference path's gathered KV views ``wp * page`` wide, so
        unified-step cost is proportional to the densest live sequence
        rather than to ``max_seq`` (the "additive chunk-flops" the v2
        layout removes).  Bitwise-invisible to the streams: columns past
        a row's ``kv_len`` mask to ``NEG_INF`` (finite), whose ``exp``
        underflows to exactly 0.0, and every live row keeps >= 1 valid
        column — trailing exact zeros don't perturb the reductions."""
        need = 4
        for pages in self._slot_pages.values():
            need = max(need, len(pages))
        wp = 4
        while wp < need:
            wp *= 2
        return min(wp, self.max_pages_per_slot)

    def _ragged_provision(self, job: "RaggedPrefillJob", num_steps: int):
        """Dispatch-time host bookkeeping of :meth:`ragged_step`: grow
        the chunk slot's pages to the dispatch end (so ``done_tokens ==
        exportable KV`` holds even while the flight is still running on
        device), grow every decoding slot for ``num_steps`` tokens, and
        build the [K, C] chunk-token block + per-step context array.
        Returns ``(chunk_tokens, ctx_arr, end, wp)``."""
        c = self.ragged_chunk
        pg = self.page_size
        slot = job.slot
        total = len(job.prompt_ids)
        ctx0 = job.done_tokens
        end = min(ctx0 + num_steps * c, total)
        # Grow the chunk slot for this dispatch's writes...
        pages = self._slot_pages[slot]
        needed = math.ceil(end / pg)
        if needed > len(pages):
            new = self._alloc(needed - len(pages))
            self.page_table[slot, len(pages):len(pages) + len(new)] = new
            pages.extend(new)
        # ...and every decoding slot for its num_steps tokens.
        for s in list(self._slot_pages):
            if s != slot:
                self._ensure_slot(s, num_steps)
        chunk_tokens = np.zeros((num_steps, c), np.int32)
        flat = job.prompt_ids[ctx0:end]
        chunk_tokens.reshape(-1)[:len(flat)] = flat
        ctx_arr = ctx0 + np.arange(num_steps, dtype=np.int32) * c
        ENGINE_TELEMETRY.padding_inc(useful=end - ctx0,
                                     waste=num_steps * c - (end - ctx0))
        return chunk_tokens, ctx_arr, end, self._ragged_window()

    def _ragged_commit(self, job: "RaggedPrefillJob", end: int,
                       num_steps: int, last, wp: int) -> None:
        """Post-dispatch host bookkeeping of :meth:`ragged_step`: bank
        the dispatch-end progress and the final prompt token's logits,
        advance every slot's host sequence mirror, and prefix-index the
        job's freshly completed pages."""
        job.done_tokens = end
        job.last_logits = last
        self._host_seq[job.slot] = end
        self._advance(num_steps, ragged_cols=wp)
        self._ragged_index(job)

    def ragged_step(self, state: PagedDecodeState, job: "RaggedPrefillJob",
                    num_steps: int = 1):
        """Dispatch ``num_steps`` unified steps: every active decode slot
        advances one token per step AND the job prefills up to
        ``ragged_chunk`` prompt tokens per step.  Returns (decode tokens
        [num_steps, B] device array, new state) — the same contract as
        decode_steps_device, so the scheduler's double-buffered retire
        path consumes it unchanged.  Raises PagesExhausted when the pool
        cannot cover the job's next pages (the scheduler fails the
        request and aborts the job)."""
        c = self.ragged_chunk
        chunk_tokens, ctx_arr, end, wp = self._ragged_provision(job,
                                                                num_steps)
        sig = f"{num_steps}x{c}w{wp}"
        t_c = ENGINE_TELEMETRY.compile_begin("ragged_step", sig)
        tokens, last, new_state = self._ragged_step_fn(
            self.params, state, jnp.asarray(self.page_table[:, :wp]),
            jnp.asarray(chunk_tokens), jnp.asarray(ctx_arr),
            jnp.int32(len(job.prompt_ids)), jnp.int32(job.slot), num_steps)
        ENGINE_TELEMETRY.compile_end("ragged_step", sig, t_c)
        self._ragged_commit(job, end, num_steps, last, wp)
        return tokens, new_state

    def _ragged_index(self, job: "RaggedPrefillJob") -> None:
        """Prefix-index the job's freshly completed full pages.

        Incremental (vs insert's after-the-fact pass) so a mid-prefill
        export/migration already finds the finished pages under their
        chain keys — replayed_prefill_tokens then counts only the
        unshipped tail."""
        if not self.prefix_cache:
            return
        pages = self._slot_pages.get(job.slot, [])
        pg = self.page_size
        limit = min(len(job.keys), len(pages))
        while (job.indexed < limit
               and (job.indexed + 1) * pg <= job.done_tokens):
            i = job.indexed
            key, page = job.keys[i], pages[i]
            if key not in self._prefix_index:
                self._prefix_index[key] = page
                self._page_key[page] = key
                self._lru_tick += 1
                self._index_lru[key] = self._lru_tick
                if i > 0:  # chain edge for cascade eviction
                    self._key_children.setdefault(
                        job.keys[i - 1], set()).add(key)
            job.indexed += 1

    @partial(jax.jit, static_argnums=0, donate_argnums=(1,))
    def _ragged_finish(self, state: PagedDecodeState, last_logits, slot,
                       plen, temperature, top_p, top_k, repeat_penalty,
                       recent_row, key, slot_key):
        """Sample a ragged-prefilled prompt's first token
        (prefill_finish's exact math) and flip its slot live — the KV is
        already in its pages, so this is _insert_paged minus the pool
        scatter — in ONE program: as a dozen eager ones behind the job's
        last flight the device idled ~20 ms an admission between them
        (PERF.md section 6, PR 42)."""
        logits = apply_repeat_penalty(last_logits[None, :], recent_row[None],
                                      repeat_penalty[None])
        tok = sample_tokens(logits, temperature[None], top_p[None], key,
                            top_k=top_k[None])[0]
        return tok, self._activated(state, slot, plen, tok, temperature,
                                    top_p, top_k, repeat_penalty, recent_row,
                                    slot_key)

    def ragged_finish(self, state: PagedDecodeState, job: "RaggedPrefillJob",
                      temperature: float, top_p: float, key,
                      slot_key=None, top_k: int = 0,
                      repeat_penalty: float = 1.0):
        """Sample the first token and activate the slot.  Returns
        (first_token, new_state); the token is the sampled scalar still on
        the device, as ``prefill`` hands its own back, so that whoever asks
        next need not wait for the device's queue to empty."""
        assert job.finished and job.last_logits is not None
        plen = len(job.prompt_ids)
        if slot_key is None:
            slot_key = default_slot_key(job.slot)
        t_c = ENGINE_TELEMETRY.compile_begin("ragged_finish", 0)
        tok, state = self._ragged_finish(
            state, job.last_logits, jnp.int32(job.slot), jnp.int32(plen),
            jnp.float32(temperature), jnp.float32(top_p), jnp.int32(top_k),
            jnp.float32(repeat_penalty),
            jnp.asarray(self._recent_from_prompt(job.prompt_ids)), key,
            slot_key)
        ENGINE_TELEMETRY.compile_end("ragged_finish", 0, t_c)
        self._host_seq[job.slot] = plen
        self._ragged_index(job)
        self._ragged_slot = None
        return tok, state

    def ragged_abort(self, job: "RaggedPrefillJob") -> None:
        """Abandon a mid-flight ragged prefill (cancel / migrate / error):
        the slot was never activated, so freeing its pages is the whole
        cleanup.  Completed pages already indexed stay cached — a
        resubmission (or a migration successor's fetch) reuses them."""
        if self._ragged_slot == job.slot:
            self._free(job.slot)
            self._ragged_slot = None

    # -------------------------------------- KV shipping (docs/KV_TRANSFER.md)

    def kv_wire_dtype(self) -> str:
        """Pool dtype as it appears in KvPages.kv_dtype ("int8" pools ship
        raw int8 pages + bf16 scales; bf16/f32 pools ship raw pool bytes)."""
        return ("int8" if self.kv_dtype == "int8"
                else jnp.dtype(self.dtype).name)

    def chain_keys_for_prompt(self, prompt_ids: list[int]) -> list[bytes]:
        """Chain hashes a fetch for ``prompt_ids`` asks a donor about — the
        same one-page-early cap prefill matching uses (>= 1 suffix token
        must remain to produce logits)."""
        return self._chain_keys(prompt_ids,
                                max(0, (len(prompt_ids) - 1) // self.page_size))

    def local_prefix_coverage(self, keys: list[bytes]) -> int:
        """How many leading chain keys the local index already holds (a
        fetch only pays for the uncovered tail)."""
        m = 0
        for k in keys:
            if k not in self._prefix_index:
                break
            m += 1
        return m

    def export_pages(self, state: PagedDecodeState, chain_hashes: list[bytes],
                     page_size: int = 0) -> dict | None:
        """Serve a peer's KvFetchRequest: host-gather the K/V pages of the
        longest indexed prefix of ``chain_hashes``.

        Ref-pinning protocol: matched pages are pinned (+1 ref) for the
        duration of the device→host gather so a concurrent admission's
        ``_alloc`` cannot evict-and-reuse them mid-copy; the pin drops in
        the ``finally``.  Runs at the scheduler's exclusive point (no
        in-flight dispatch donates the pool while we read it).  int8 pools
        ship pages + scales verbatim — no requantization on either side.

        Returns None when nothing matched, the prefix cache is off, or the
        requester's page geometry differs (pages would not be
        interchangeable)."""
        if not self.prefix_cache or (page_size and page_size != self.page_size):
            return None
        pages: list[int] = []
        for k in chain_hashes:
            page = self._prefix_index.get(bytes(k))
            if page is None:
                break
            pages.append(page)
            self._lru_tick += 1
            self._index_lru[bytes(k)] = self._lru_tick
        if not pages:
            return None
        for p in pages:
            self._page_refs[p] = self._page_refs.get(p, 0) + 1
        try:
            idx = jnp.asarray(np.asarray(pages, np.int32))
            k_host = np.asarray(state.pool_k[:, idx])  # [L, n, Hkv, pg, Dh]
            v_host = np.asarray(state.pool_v[:, idx])
            k_scales: list[bytes] = []
            v_scales: list[bytes] = []
            if self.kv_dtype == "int8":
                ks_host = np.asarray(state.k_scale[:, idx])  # [L, n, Hkv, pg]
                vs_host = np.asarray(state.v_scale[:, idx])
                k_scales = [ks_host[:, i].tobytes()
                            for i in range(len(pages))]
                v_scales = [vs_host[:, i].tobytes()
                            for i in range(len(pages))]
        finally:
            for p in pages:
                self._page_refs[p] = self._page_refs.get(p, 1) - 1
        self.kv_pages_exported += len(pages)
        return {
            "matched": len(pages),
            "kv_dtype": self.kv_wire_dtype(),
            "k_pages": [k_host[:, i].tobytes() for i in range(len(pages))],
            "v_pages": [v_host[:, i].tobytes() for i in range(len(pages))],
            "k_scales": k_scales,
            "v_scales": v_scales,
        }

    @partial(jax.jit, static_argnums=0, donate_argnums=(1,))
    def _import_paged(self, state: PagedDecodeState, page_idx, kp, vp,
                      ksp, vsp):
        """Scatter fetched pages ([L, n, Hkv, pg, Dh], already pool dtype)
        into freshly allocated pool pages (dump-page padded — one compile
        per import-size bucket, like the other paged scatters)."""
        pool_k = state.pool_k.at[:, page_idx].set(kp)
        pool_v = state.pool_v.at[:, page_idx].set(vp)
        k_scale, v_scale = state.k_scale, state.v_scale
        if self.kv_dtype == "int8":
            k_scale = k_scale.at[:, page_idx].set(ksp)
            v_scale = v_scale.at[:, page_idx].set(vsp)
        return replace(state, pool_k=pool_k, pool_v=pool_v,
                       k_scale=k_scale, v_scale=v_scale)

    def import_pages(self, state: PagedDecodeState,
                     payload: dict) -> tuple[PagedDecodeState, int]:
        """Seed the prefix index from a donor's exported pages.

        ``payload``: ``keys`` (chain hashes aligned with the page lists),
        ``k_pages``/``v_pages`` (+ ``k_scales``/``v_scales`` for int8) and
        ``kv_dtype``.  Locally covered leading keys are skipped (coverage
        is always a prefix); the rest are allocated, scattered, and indexed
        at refcount 0 — exactly the state a locally inserted-then-released
        prefix leaves behind, so the ordinary suffix-only prefill consumes
        them with no new code path.  Raises on dtype/shape mismatch or
        ``PagesExhausted``; the caller falls back to plain prefill."""
        keys = [bytes(k) for k in payload["keys"]]
        k_pages, v_pages = payload["k_pages"], payload["v_pages"]
        n = min(len(keys), len(k_pages), len(v_pages))
        if not self.prefix_cache or n == 0:
            return state, 0
        want = self.kv_wire_dtype()
        got = payload.get("kv_dtype", "")
        if got != want:
            raise ValueError(f"kv dtype mismatch: donor ships {got!r}, "
                             f"local pool is {want!r}")
        skip = self.local_prefix_coverage(keys[:n])
        if skip >= n:
            return state, 0
        cfg = self.cfg
        l, hkv, dh = (self.kv_layers, cfg.num_kv_heads,
                      cfg.resolved_head_dim())
        pg = self.page_size
        quant = self.kv_dtype == "int8"
        pool_np = np.dtype(jnp.int8 if quant else self.dtype)
        page_nbytes = l * hkv * pg * dh * pool_np.itemsize
        scale_nbytes = l * hkv * pg * np.dtype(jnp.bfloat16).itemsize
        for buf in (*k_pages[skip:n], *v_pages[skip:n]):
            if len(buf) != page_nbytes:
                raise ValueError(f"kv page payload is {len(buf)} bytes, "
                                 f"expected {page_nbytes}")
        if quant:
            for buf in (*payload["k_scales"][skip:n],
                        *payload["v_scales"][skip:n]):
                if len(buf) != scale_nbytes:
                    raise ValueError(
                        f"kv scale payload is {len(buf)} bytes, "
                        f"expected {scale_nbytes}")
        n_imp = n - skip
        fresh = self._alloc(n_imp)  # PagesExhausted -> caller falls back
        # Dump-page padding buckets the scatter's compile like _prefill_ctx:
        # one program per power-of-two import size, not one per count.
        width = 1 << (n_imp - 1).bit_length() if n_imp > 1 else 1
        page_idx = np.full((width,), self.total_pages, np.int32)
        page_idx[:n_imp] = fresh

        def stack(bufs, dt, shape):
            rows = [np.frombuffer(b, dt).reshape(shape) for b in bufs]
            rows += [np.zeros(shape, dt)] * (width - len(rows))
            return jnp.asarray(np.stack(rows, axis=1))

        kp = stack(k_pages[skip:n], pool_np, (l, hkv, pg, dh))
        vp = stack(v_pages[skip:n], pool_np, (l, hkv, pg, dh))
        ksp = vsp = None
        if quant:
            sc_np = np.dtype(jnp.bfloat16)
            ksp = stack(payload["k_scales"][skip:n], sc_np, (l, hkv, pg))
            vsp = stack(payload["v_scales"][skip:n], sc_np, (l, hkv, pg))
        t_c = ENGINE_TELEMETRY.compile_begin("import_paged", width)
        state = self._import_paged(state, jnp.asarray(page_idx), kp, vp,
                                   ksp, vsp)
        ENGINE_TELEMETRY.compile_end("import_paged", width, t_c)
        for i, page in enumerate(fresh):
            key = keys[skip + i]
            self._prefix_index[key] = page
            self._page_key[page] = key
            self._lru_tick += 1
            self._index_lru[key] = self._lru_tick
            if skip + i > 0:  # chain edge for cascade eviction
                self._key_children.setdefault(
                    keys[skip + i - 1], set()).add(key)
        self.kv_pages_imported += n_imp
        return state, n_imp

    # -------------------------------------------------------------- buckets

    def bucket_for(self, n: int) -> int:
        """Prefill buckets must align to pages so prompt KV scatters whole
        pages; round the base bucket up to a page multiple."""
        base = super().bucket_for(n)
        return math.ceil(base / self.page_size) * self.page_size