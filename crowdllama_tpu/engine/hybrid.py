"""The paged runner for a model whose layers differ in kind
(models/hybrid.py): several kinds of state in one cache manager.  A model
with NO recurrent layer comes through here too (family ``afmoe``: window
and full attention layers, a cache of two kinds of page).

* Paged KV, for the ATTENTION layers only: the pools' leading axis is the
  attention layers (``kv_layers``), not ``num_layers``; page table,
  allocator, Pallas decode / ragged / flash-prefill kernels are the
  parent's.  A latent layer (``L``, MLA served absorbed) keeps ONE row
  ``[c ; k_rope]`` a token: ``pool_k`` is ``[L_A, P+1, 1, page, row]``
  with ``row`` whole lanes (engine/paged.py ``pool_row_width``: the
  columns behind ``k_rope`` are zero and stay zero), ``pool_v`` is None,
  and the decode kernel is ``paged_decode_attention_mla``
  (ops/pallas/paged.py).
* A second pool for WINDOW layers (``W``: attention inside
  ``cfg.sliding_window``), ``PagedDecodeState.wpool_k`` / ``wpool_v``
  ``[L_W, B * ring + 1, Hkv, page, Dh]``, whose size does not grow with the
  context: a slot owns a RING of ``ring = ceil((window + ragged_chunk +
  page) / page)`` pages of it from admission to release (``ops/pallas/
  paged.py`` ``Ring``), its logical page ``p`` lies in ring page ``p % ring``
  and is written over once it has left every window, and the decode kernel,
  the ragged step and the CPU's gathered view all read a slot's ring through
  a page table of their own that starts where the window does — at most
  ``window + page`` tokens a slot a layer.  The layers that see the whole
  context (``F``) keep ``pool_k`` / ``pool_v``, the page table and the
  allocator.  Nothing on the host allocates ring pages, so a slot's need in
  the window pool is met by construction and only the full pool can be
  found exhausted (``PagesExhausted`` names it); what the host keeps is the
  count (``kv_gauges``: live bytes of each kind, ring pages written over).
  Both prefills (monolithic, and the chunked one's accumulators) compute
  over the whole prompt with the window as a mask and ``insert`` places only
  the pages the ring still holds.
* Per-slot recurrent state for the Mamba or KDA layers
  (``PagedDecodeState.ssm`` or ``.kda``, and ``.conv``): written by prefill
  (``insert`` places it, or the ragged step's chunk continues the slot's
  own), carried IN PLACE through the decode and ragged steps as part of
  the donated state, zeroed on release.

What rests on "tokens done == pages of KV that can be handed over" cannot
be right for such a slot — a page of KV says nothing of the state the
recurrent layers have reached — so the prefix cache is off (every admission is
a miss), ``export_pages`` / ``import_pages`` raise, the drain hand-off ships
no pages (the successor replays the tokens), and speculation is refused at
construction (engine/factory.py, engine/spec.py): each with
``hybrid.NO_PAGES`` in its message.  ROADMAP "state snapshots" is what
would lift them.  A model with window layers declines the same four for a
reason of its own, ``hybrid.NO_WINDOW_PAGES``: the ring has written over a
prefix's pages by the time anyone could share, ship or roll back to them.
A model whose every layer is latent attention (family ``sarvam_mla``) keeps
all the pages of all its layers, and still declines the four in this
runner, for a third reason, ``hybrid.NO_LATENT_PAGES``: the prefix gathers
and ``import_pages`` take a page of K and its twin of V, and a latent pool
has one row a token and no twin (:func:`why_no_pages` picks the reason).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, replace
from functools import partial

import jax
import jax.numpy as jnp

from crowdllama_tpu.engine.paged import PagedDecodeState, PagedModelRunner
from crowdllama_tpu.models import hybrid as H
from crowdllama_tpu.obs.metrics import ENGINE_TELEMETRY
from crowdllama_tpu.ops.kda import kda_update_path
from crowdllama_tpu.ops.pallas.paged import Ring
from crowdllama_tpu.ops.ssm import ssm_update_path

log = logging.getLogger("crowdllama.engine.hybrid")


@jax.tree_util.register_dataclass
@dataclass
class HybridPrefill:
    """What a prompt's prefill leaves to be placed in a slot: the attention
    layers' KV ``[L_A, 1, Hkv, T, Dh]`` (``v`` None for latent rows) and
    the recurrent layers' state after the last prompt token, under the
    names ``PagedDecodeState`` keeps it (``models/hybrid.py``
    ``zero_recurrent``).  It travels where the parent's ``ks`` does
    (``prefill`` -> ``insert``, and as a chunked job's accumulator), hence
    ``shape``."""

    k: jnp.ndarray
    v: jnp.ndarray | None
    rec: dict[str, jnp.ndarray]

    @property
    def shape(self):
        return self.k.shape


def why_no_pages(cfg) -> str:
    """The reason a hybrid model's slots have no pages to share, ship or
    roll back to."""
    if any(kind in cfg.layer_pattern for kind in H.STATE):
        return H.NO_PAGES
    return H.NO_WINDOW_PAGES if "W" in cfg.layer_pattern else H.NO_LATENT_PAGES


def refuse_speculation(cfg, what: str) -> None:
    """Speculation rolls rejected tokens back by forgetting their KV; the
    recurrent layers' state has already absorbed them, a window layer's
    ring may have written them over what they replaced, and the
    speculating runners verify over pages of K and V, which a latent pool
    does not keep."""
    if cfg.is_hybrid:
        raise ValueError(
            f"{what} cannot serve {cfg.name!r}: a rejected draft token "
            f"cannot be rolled back out of its layers' state "
            f"({why_no_pages(cfg)})")


class HybridPagedModelRunner(PagedModelRunner):
    serves_hybrid = True

    def __init__(self, cfg, *args, prefix_cache: bool = True, **kwargs):
        assert cfg.is_hybrid, cfg
        if kwargs.get("mesh") is None and not kwargs.get("mesh_spec"):
            kwargs["mesh_spec"] = "1"
        self.no_pages = why_no_pages(cfg)
        if prefix_cache:
            log.info("prefix cache off for %s: %s", cfg.name, self.no_pages)
        super().__init__(cfg, *args, prefix_cache=False, **kwargs)
        if self.mesh.size > 1:
            raise ValueError(
                f"{cfg.name!r} is served on one device: the recurrent state "
                f"and the per-kind parameter stacks have no partition rules "
                f"(mesh {dict(self.mesh.shape)})")
        if cfg.kv_lora_rank and self.kv_dtype == "int8":
            raise ValueError(f"{cfg.name!r} keeps latent rows, key and "
                             f"value in one: no int8 KV for them")
        # the window layers' ring (None: the model has none) and, for each
        # attention layer in ``attn_fn``'s order, (it is a window layer,
        # its index in its own pool)
        self.ring = None
        kinds = H.attn_kinds(cfg)
        self._attn_at = [(k == "W", kinds[:i].count("W") if k == "W"
                          else i - kinds[:i].count("W"))
                         for i, k in enumerate(kinds)]
        if "W" in kinds:
            if self.kv_dtype == "int8":
                raise ValueError(f"{cfg.name!r} keeps its window layers in "
                                 f"a pool of their own: no int8 KV for it")
            self.ring = Ring(
                min(self.max_pages_per_slot,
                    -(-(cfg.sliding_window + self.ragged_chunk
                        + self.page_size) // self.page_size)),
                cfg.sliding_window)
            self.attn_decode_path = "gqa+window"
            log.info("window layers: %d, a ring of %d pages a slot (window "
                     "%d, page %d)", kinds.count("W"), self.ring.pages,
                     cfg.sliding_window, self.page_size)
        # ring pages written over, of slots released since init_state
        self._recycled = 0
        # which path the recurrent layers' one-step update takes in every
        # decode-type program (ops/ssm.py ssm_update_at, ops/kda.py
        # kda_update_at decide from the backend and the state's shape):
        # crowdllama_ssm_update_path, crowdllama_kda_update_path
        self.ssm_update_path = self.kda_update_path = why = ""
        if cfg.layers_of("M"):
            self.ssm_update_path, why = ssm_update_path(
                (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state))
            log.info("state-space update path: %s", self.ssm_update_path)
        if cfg.layers_of("K"):
            self.kda_update_path, why = kda_update_path(
                (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim))
            log.info("delta-rule update path: %s", self.kda_update_path)
        if why and jax.default_backend() == "tpu":
            log.warning("the recurrent state's update runs as XLA fusions "
                        "on this TPU, not the Pallas kernel: %s", why)
        # the parent's out_shardings name three arrays
        self._prefill = jax.jit(self._prefill_impl)
        self._take_counts = jax.jit(self._take_counts_impl,
                                    donate_argnums=(0,))
        self._flight_counts = None
        # the state fields the recurrent layers keep (ssm | kda, conv)
        self._rec_names = tuple(H.zero_recurrent(cfg, 0))
        # slots a cancelled ragged prefill left with a half-advanced state
        self._dirty: set[int] = set()

    @property
    def ragged_width_fixed(self) -> bool:
        """A model with no recurrent layer (window and full attention, or
        latent attention throughout) admits every prompt longer than a
        chunk through the unified programs, so they take ONE page-table
        width, compiled at both flight lengths by the warm-up."""
        return not self._rec_names

    def _ragged_window(self) -> int:
        """Such a model's unified programs take the whole table: window
        layers never read it, the other layers' kernels skip the columns
        past a slot's length, and every admission of a long prompt then
        dispatches a program the warm-up compiled."""
        if self.ragged_width_fixed:
            return self.max_pages_per_slot
        return super()._ragged_window()

    @property
    def pool_layers(self) -> int:
        return self.kv_layers - self.cfg.layers_of("W")

    def _attention_refusals(self) -> dict[str, str]:
        """The window kind runs the same kernels under names of its own
        (``paged_decode_attention_window``, ``ragged_paged_attention_window``)
        behind the same gates: said apart, so that a scrape sees both."""
        refusals = super()._attention_refusals()
        if self.cfg.layers_of("W"):
            refusals["decode_window"] = refusals["decode"]
            refusals["ragged_step_window"] = refusals["ragged_step"]
        return refusals

    # ------------------------------------------------------------- programs

    def _prefill_forward(self, params, tokens, positions, kv_valid):
        logits, ks, vs, rec, _ = H.prefill(
            params, self.cfg, tokens, positions, kv_valid,
            n_shards=self.mesh.size)
        return logits, HybridPrefill(ks, vs, rec), None

    @partial(jax.jit, static_argnums=0, donate_argnums=(5,))
    def _prefill_chunk(self, params, tokens, chunk_len, ctx_len,
                       ctx: HybridPrefill, _):
        t = tokens.shape[1]
        positions = ctx_len + jnp.minimum(jnp.arange(t)[None, :],
                                          chunk_len - 1)
        kv_valid = (jnp.arange(t) < chunk_len)[None, :]
        ctx_valid = (jnp.arange(ctx.k.shape[3]) < ctx_len)[None, :]
        logits, ks, vs, rec, _ = H.prefill(
            params, self.cfg, tokens, positions, kv_valid, rec0=ctx.rec,
            ctx_k=ctx.k, ctx_v=ctx.v, ctx_valid=ctx_valid)
        k = jax.lax.dynamic_update_slice(
            ctx.k, ks.astype(ctx.k.dtype), (0, 0, 0, ctx_len, 0))
        v = None if vs is None else jax.lax.dynamic_update_slice(
            ctx.v, vs.astype(ctx.v.dtype), (0, 0, 0, ctx_len, 0))
        return logits[0, chunk_len - 1], HybridPrefill(k, v, rec), None

    def _hidden_states(self, params, tokens, positions, kv_valid):
        return H.prefill(params, self.cfg, tokens, positions, kv_valid,
                         n_shards=self.mesh.size, unembed=False)[0]

    def _insert_paged_impl(self, state, page_idx, ks: HybridPrefill, vs,
                           slot, *rest):
        if self.ring is not None:
            state, ks = self._insert_window(state, ks, slot, rest[0])
        if ks.v is None:    # latent rows: pages of the one pool
            # a page at a time, in place (engine/paged.py ``_put_rows`` has
            # why not a scatter: it copied the whole pool twice an insert);
            # the prefill's rows are as wide as computed and fill a stored
            # row's first columns: its pad columns are not written
            pool, pg = state.pool_k, self.page_size
            for j in range(ks.k.shape[3] // pg):
                pool = jax.lax.dynamic_update_slice(
                    pool, ks.k[:, :, :, j * pg:(j + 1) * pg].astype(
                        pool.dtype), (0, page_idx[j], 0, 0, 0))
            state = self._activated(replace(state, pool_k=pool), slot, *rest)
        else:
            state = super()._insert_paged_impl(state, page_idx, ks.k, ks.v,
                                               slot, *rest)
        return replace(state, **{
            name: getattr(state, name).at[:, slot].set(
                a[:, 0].astype(getattr(state, name).dtype))
            for name, a in ks.rec.items()})

    def _insert_window(self, state, ks: HybridPrefill, slot, plen):
        """The window layers' share of a prefilled prompt goes into the
        slot's ring — of the bucket's pages the ``ring.pages`` newest that
        hold a prompt token, the others to the dump page — a page at a
        time, in place.  Returns (state, the full layers' share)."""
        ring, pg = self.ring, self.page_size
        at = {w: jnp.asarray([i for i, (is_w, _) in enumerate(self._attn_at)
                              if is_w == w]) for w in (True, False)}
        wk, wv = ks.k[at[True]], ks.v[at[True]]
        npages = wk.shape[3] // pg
        logical = jnp.arange(npages)
        last = (plen - 1) // pg
        page_of = jnp.where(
            (logical <= last) & (logical > last - ring.pages),
            ring.page_of(slot, logical), state.wpool_k.shape[1] - 1)
        pool_k, pool_v = state.wpool_k, state.wpool_v
        for j in range(npages):
            to, rows = (0, page_of[j], 0, 0, 0), slice(j * pg, (j + 1) * pg)
            pool_k = jax.lax.dynamic_update_slice(
                pool_k, wk[:, :, :, rows].astype(pool_k.dtype), to)
            pool_v = jax.lax.dynamic_update_slice(
                pool_v, wv[:, :, :, rows].astype(pool_v.dtype), to)
        return (replace(state, wpool_k=pool_k, wpool_v=pool_v),
                HybridPrefill(ks.k[at[False]], ks.v[at[False]], ks.rec))

    def _release_paged_impl(self, state, slot):
        """A slot's next prompt may arrive in chunks, which continue from
        the slot's own state: it starts from zero because it ended so."""
        state = super()._release_paged_impl(state, slot)
        return replace(state, **{
            name: getattr(state, name).at[:, slot].set(0.0)
            for name in self._rec_names})

    def _take_counts_impl(self, state):
        return state.moe_rows + 0, replace(
            state, moe_rows=jnp.zeros_like(state.moe_rows))

    def _decode_layers(self, params, x, positions, pools, attend, st, live,
                       chunk=None):
        """The unrolled layer loop over decode rows ``x[:B]`` (one token a
        slot, the slot's state moved only where it is active) and, in the
        ragged step, a prefill chunk ``x[B:]`` that continues
        ``chunk = (slot, valid rows)``'s own state.  ``positions`` is read by
        the layers that rotate (``W``)."""
        cfg, b = self.cfg, self.max_slots
        box = {"pools": pools,
               **{name: getattr(st, name) for name in self._rec_names}}
        active = st.active.astype(jnp.int32)

        if self.ring is not None:
            box["wpools"] = (st.wpool_k, st.wpool_v, None, None)

        def attn_fn(i, q, k, v):
            window, li = self._attn_at[i]
            name = "wpools" if window else "pools"
            fn, after = attend(box[name], jnp.int32(0), li,
                               ring=self.ring if window else None)
            out = fn(q, k, v)
            box[name] = after["pools"]
            return out

        def rec_fn(kind, i, lp, *inputs):
            mix, name = H.MIX[kind], H.STATE[kind]
            y, tail, stack = mix(
                lp, cfg, *(a[:b, None] for a in inputs), box["conv"][i],
                box[name], active, layer=i)
            y = y[:, 0]
            if chunk is not None:
                slot, valid = chunk
                at = (i, slot, 0, 0, 0)
                yc, tc, sc = mix(
                    lp, cfg, *(a[None, b:] for a in inputs),
                    jax.lax.dynamic_index_in_dim(tail, slot, 0),
                    jax.lax.dynamic_slice(stack, at, (1, 1) + stack.shape[2:])[0],
                    valid[None].astype(jnp.int32))
                tail = jax.lax.dynamic_update_index_in_dim(tail, tc[0], slot, 0)
                stack = jax.lax.dynamic_update_slice(stack, sc[None], at)
                y = jnp.concatenate([y, yc[0]])
            box[name] = stack
            box["conv"] = box["conv"].at[i].set(tail)
            return y

        x, counts = H.run_layers(params["layers"], cfg, x, rec_fn, attn_fn,
                                 live, positions)
        changed = {name: box[name] for name in self._rec_names}
        if self.ring is not None:
            changed["wpool_k"], changed["wpool_v"] = box["wpools"][:2]
        return x, box["pools"], {**changed,
                                 "moe_rows": st.moe_rows + counts}

    # ------------------------------------------------------------------ API

    def init_state(self, seed: int = 0) -> PagedDecodeState:
        state = super().init_state(seed)
        self._dirty.clear()
        rec = H.zero_recurrent(self.cfg, self.max_slots, self.dtype)
        state = replace(state, **rec,
                        moe_rows=jnp.zeros((len(H.COUNTS),), jnp.int32))
        by_kind = {
            "latent_cache" if state.pool_v is None else "kv_pool":
            sum(a.nbytes for a in (state.pool_k, state.pool_v,
                                   state.k_scale, state.v_scale)
                if a is not None),
            **{name: a.nbytes for name, a in rec.items()}}
        if self.ring is not None:
            self._recycled = 0
            # +1: the dump page, as the full pool's
            shape = (self.cfg.layers_of("W"),
                     self.max_slots * self.ring.pages + 1,
                     *state.pool_k.shape[2:])
            state = replace(state,
                            wpool_k=jnp.zeros(shape, state.pool_k.dtype),
                            wpool_v=jnp.zeros(shape, state.pool_k.dtype))
            by_kind["kv_window_pool"] = 2 * state.wpool_k.nbytes
        latent_row = (0, 0)
        if state.pool_v is None:    # what of a stored row is pad
            row = self.cfg.resolved_head_dim()
            latent_row = (row, state.pool_k.shape[-1] - row)
        ENGINE_TELEMETRY.state_bytes_set(by_kind, latent_row)
        return state

    # --------------------------------------------- the window pool's counts

    def window_pages(self, slot: int) -> int:
        """Ring pages that hold a token of ``slot``: never over the ring."""
        if self.ring is None or slot not in self._slot_pages:
            return 0
        return min(self.ring.pages,
                   -(-int(self._host_seq[slot]) // self.page_size))

    def _written_over(self, slot: int) -> int:
        return max(0, -(-int(self._host_seq[slot]) // self.page_size)
                   - self.ring.pages)

    def _free(self, slot: int) -> None:
        if self.ring is not None and slot in self._slot_pages:
            self._recycled += self._written_over(slot)
        super()._free(slot)

    def kv_gauges(self) -> dict[str, float]:
        gauges = super().kv_gauges()
        if self.ring is None:
            return gauges
        page = self._page_bytes * self.cfg.layers_of("W")
        live = sum(self.window_pages(s) for s in self._slot_pages)
        return {
            **gauges,
            "kv_pool_bytes|kind=window":
            float(self.max_slots * self.ring.pages * page),
            "kv_live_bytes|kind=window": float(live * page),
            "kv_window_pages_recycled_total": float(
                self._recycled
                + sum(self._written_over(s) for s in self._slot_pages))}

    def prefill_begin(self, prompt_ids, state=None):
        job = super().prefill_begin(prompt_ids, state)
        job.ctx_k = HybridPrefill(
            job.ctx_k, None if self.cfg.kv_lora_rank else job.ctx_v,
            H.zero_recurrent(self.cfg, 1, self.dtype))
        job.ctx_v = None
        return job

    def insert(self, state, slot, *args, **kwargs):
        self._dirty.discard(slot)     # the insert overwrites all of it
        return super().insert(state, slot, *args, **kwargs)

    def _bank(self, state):
        """Per flight: take the expert layers' assignment counts out of the
        state it returned (one small program, queued behind the flight;
        the scheduler reads them back with the flight's tokens)."""
        self._flight_counts, state = self._take_counts(state)
        return state

    def flight_counters(self):
        """Device array of the newest flight's ``models/hybrid.py``
        ``COUNTS``, once."""
        counts, self._flight_counts = self._flight_counts, None
        return counts

    def _clean(self, state, job):
        if job.slot in self._dirty:
            self._dirty.discard(job.slot)
            state = self._release_paged(state, jnp.int32(job.slot))
        return state

    def decode_steps_device(self, state, num_steps: int = 1):
        tokens, state = super().decode_steps_device(state, num_steps)
        return tokens, self._bank(state)

    def ragged_step(self, state, job, num_steps: int = 1):
        tokens, state = super().ragged_step(self._clean(state, job), job,
                                            num_steps)
        return tokens, self._bank(state)

    def ragged_abort(self, job) -> None:
        if self._ragged_slot == job.slot and job.done_tokens:
            self._dirty.add(job.slot)
        super().ragged_abort(job)

    # ---------------------------------------------------------- the refusals

    def export_pages(self, state, chain_hashes, page_size: int = 0):
        raise ValueError(f"{self.cfg.name!r} exports no KV pages: "
                         f"{self.no_pages}")

    def import_pages(self, state, payload):
        raise ValueError(f"{self.cfg.name!r} imports no KV pages: "
                         f"{self.no_pages}")
