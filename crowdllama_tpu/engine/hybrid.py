"""The paged runner for a model whose layers differ in kind
(models/hybrid.py): two kinds of state in one cache manager.

* Paged KV, for the ATTENTION layers only: the pools' leading axis is
  ``cfg.layers_of("*")``, not ``num_layers``; page table, allocator,
  Pallas decode / ragged / flash-prefill kernels are the parent's.
* Per-slot recurrent state for the Mamba layers
  (``PagedDecodeState.ssm`` / ``.conv``): written by prefill (``insert``
  places it, or the ragged step's chunk continues the slot's own), carried
  IN PLACE through the decode and ragged steps and both megasteps as part
  of the donated state, zeroed on release.

What rests on "tokens done == pages of KV that can be handed over" cannot
be right for such a slot — a page of KV says nothing of the state the
Mamba layers have reached — so the prefix cache is off (every admission is
a miss), ``export_pages`` / ``import_pages`` raise, the drain hand-off ships
no pages (the successor replays the tokens), and speculation is refused at
construction (engine/factory.py, engine/spec.py): each with
``hybrid.NO_PAGES`` in its message.  ROADMAP "state snapshots" is what
would lift them.
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
from crowdllama_tpu.ops.ssm import ssm_update_path

log = logging.getLogger("crowdllama.engine.hybrid")


@jax.tree_util.register_dataclass
@dataclass
class HybridPrefill:
    """What a prompt's prefill leaves to be placed in a slot: the attention
    layers' KV ``[L_A, 1, Hkv, T, Dh]`` and the Mamba layers' state after
    the last prompt token.  It travels where the parent's ``ks`` does
    (``prefill`` -> ``insert``, and as a chunked job's accumulator), hence
    ``shape``."""

    k: jnp.ndarray
    v: jnp.ndarray
    ssm: jnp.ndarray    # [L_M, 1, H, P, N] float32
    conv: jnp.ndarray   # [L_M, 1, conv_dim, K-1]

    @property
    def shape(self):
        return self.k.shape


def refuse_speculation(cfg, what: str) -> None:
    """Speculation rolls rejected tokens back by forgetting their KV; the
    Mamba layers' state has already absorbed them."""
    if cfg.is_hybrid:
        raise ValueError(
            f"{what} cannot serve {cfg.name!r}: a rejected draft token "
            f"cannot be rolled back out of the Mamba layers' state "
            f"({H.NO_PAGES})")


class HybridPagedModelRunner(PagedModelRunner):
    serves_hybrid = True

    def __init__(self, cfg, *args, prefix_cache: bool = True, **kwargs):
        assert cfg.is_hybrid, cfg
        if kwargs.get("mesh") is None and not kwargs.get("mesh_spec"):
            kwargs["mesh_spec"] = "1"
        if prefix_cache:
            log.info("prefix cache off for %s: %s", cfg.name, H.NO_PAGES)
        super().__init__(cfg, *args, prefix_cache=False, **kwargs)
        if self.mesh.size > 1:
            raise ValueError(
                f"{cfg.name!r} is served on one device: the recurrent state "
                f"and the per-kind parameter stacks have no partition rules "
                f"(mesh {dict(self.mesh.shape)})")
        # which path the Mamba layers' one-step update takes in every
        # decode-type program (ops/ssm.py ssm_update_at decides from the
        # backend and the state's shape): crowdllama_ssm_update_path
        self.ssm_update_path, why = ssm_update_path(
            (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state))
        log.info("state-space update path: %s", self.ssm_update_path)
        if why and jax.default_backend() == "tpu":
            log.warning("the state-space update runs as two XLA fusions on "
                        "this TPU, not the Pallas kernel: %s", why)
        # the parent's out_shardings name three arrays
        self._prefill = jax.jit(self._prefill_impl)
        self._take_counts = jax.jit(self._take_counts_impl,
                                    donate_argnums=(0,))
        self._flight_counts = None
        # slots a cancelled ragged prefill left with a half-advanced state
        self._dirty: set[int] = set()

    # ------------------------------------------------------------- programs

    def _prefill_forward(self, params, tokens, positions, kv_valid):
        logits, ks, vs, ssm, conv, _ = H.prefill(
            params, self.cfg, tokens, positions, kv_valid,
            n_shards=self.mesh.size)
        return logits, HybridPrefill(ks, vs, ssm, conv), None

    @partial(jax.jit, static_argnums=0, donate_argnums=(5,))
    def _prefill_chunk(self, params, tokens, chunk_len, ctx_len,
                       ctx: HybridPrefill, _):
        t = tokens.shape[1]
        positions = ctx_len + jnp.minimum(jnp.arange(t)[None, :],
                                          chunk_len - 1)
        kv_valid = (jnp.arange(t) < chunk_len)[None, :]
        ctx_valid = (jnp.arange(ctx.k.shape[3]) < ctx_len)[None, :]
        logits, ks, vs, ssm, conv, _ = H.prefill(
            params, self.cfg, tokens, positions, kv_valid, ssm0=ctx.ssm,
            conv0=ctx.conv, ctx_k=ctx.k, ctx_v=ctx.v, ctx_valid=ctx_valid)
        k = jax.lax.dynamic_update_slice(
            ctx.k, ks.astype(ctx.k.dtype), (0, 0, 0, ctx_len, 0))
        v = jax.lax.dynamic_update_slice(
            ctx.v, vs.astype(ctx.v.dtype), (0, 0, 0, ctx_len, 0))
        return logits[0, chunk_len - 1], HybridPrefill(k, v, ssm, conv), None

    def _hidden_states(self, params, tokens, positions, kv_valid):
        return H.prefill(params, self.cfg, tokens, positions, kv_valid,
                         n_shards=self.mesh.size, unembed=False)[0]

    def _insert_paged_impl(self, state, page_idx, ks: HybridPrefill, vs,
                           slot, *rest):
        state = super()._insert_paged_impl(state, page_idx, ks.k, ks.v, slot,
                                           *rest)
        return replace(
            state, ssm=state.ssm.at[:, slot].set(ks.ssm[:, 0]),
            conv=state.conv.at[:, slot].set(
                ks.conv[:, 0].astype(state.conv.dtype)))

    def _release_paged_impl(self, state, slot):
        """A slot's next prompt may arrive in chunks, which continue from
        the slot's own state: it starts from zero because it ended so."""
        state = super()._release_paged_impl(state, slot)
        return replace(state, ssm=state.ssm.at[:, slot].set(0.0),
                       conv=state.conv.at[:, slot].set(0.0))

    def _take_counts_impl(self, state):
        return state.moe_rows + 0, replace(
            state, moe_rows=jnp.zeros_like(state.moe_rows))

    def _decode_layers(self, params, x, positions, pools, attend, st, live,
                       chunk=None):
        """The unrolled layer loop over decode rows ``x[:B]`` (one token a
        slot, the slot's state moved only where it is active) and, in the
        ragged step, a prefill chunk ``x[B:]`` that continues
        ``chunk = (slot, valid rows)``'s own state.  ``positions`` has no
        reader here: nothing rotates."""
        cfg, b = self.cfg, self.max_slots
        box = {"pools": pools, "ssm": st.ssm, "conv": st.conv}
        active = st.active.astype(jnp.int32)

        def attn_fn(i, q, k, v):
            fn, after = attend(box["pools"], jnp.int32(0), i)
            out = fn(q, k, v)
            box["pools"] = after["pools"]
            return out

        def ssm_fn(i, lp, xbc, dt):
            y, tail, stack = H.mamba_mix(
                lp, cfg, xbc[:b, None], dt[:b, None], box["conv"][i],
                box["ssm"], active, layer=i)
            y = y[:, 0]
            if chunk is not None:
                slot, valid = chunk
                at = (i, slot, 0, 0, 0)
                yc, tc, sc = H.mamba_mix(
                    lp, cfg, xbc[None, b:], dt[None, b:],
                    jax.lax.dynamic_index_in_dim(tail, slot, 0),
                    jax.lax.dynamic_slice(stack, at, (1, 1) + stack.shape[2:])[0],
                    valid[None].astype(jnp.int32))
                tail = jax.lax.dynamic_update_index_in_dim(tail, tc[0], slot, 0)
                stack = jax.lax.dynamic_update_slice(stack, sc[None], at)
                y = jnp.concatenate([y, yc[0]])
            box["ssm"] = stack
            box["conv"] = box["conv"].at[i].set(tail)
            return y

        x, counts = H.run_layers(params["layers"], cfg, x, ssm_fn, attn_fn,
                                 live)
        return x, box["pools"], {"ssm": box["ssm"], "conv": box["conv"],
                                 "moe_rows": st.moe_rows + counts}

    # ------------------------------------------------------------------ API

    def init_state(self, seed: int = 0) -> PagedDecodeState:
        state = super().init_state(seed)
        self._dirty.clear()
        ssm, conv = H.zero_recurrent(self.cfg, self.max_slots, self.dtype)
        state = replace(state, ssm=ssm, conv=conv,
                        moe_rows=jnp.zeros((2,), jnp.int32))
        ENGINE_TELEMETRY.state_bytes_set({
            "kv_pool": sum(a.nbytes for a in (state.pool_k, state.pool_v,
                                              state.k_scale, state.v_scale)
                           if a is not None),
            "ssm": ssm.nbytes, "conv": conv.nbytes})
        return state

    def prefill_begin(self, prompt_ids, state=None):
        job = super().prefill_begin(prompt_ids, state)
        job.ctx_k = HybridPrefill(
            job.ctx_k, job.ctx_v, *H.zero_recurrent(self.cfg, 1, self.dtype))
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
        """Device array [held, left out] of the newest flight, once."""
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

    def decode_megastep(self, state, num_steps, eos_ids=None, budgets=None):
        tokens, done, state = super().decode_megastep(state, num_steps,
                                                      eos_ids, budgets)
        return tokens, done, self._bank(state)

    def ragged_step(self, state, job, num_steps: int = 1):
        tokens, state = super().ragged_step(self._clean(state, job), job,
                                            num_steps)
        return tokens, self._bank(state)

    def ragged_megastep(self, state, job, num_steps: int = 1, eos_ids=None,
                        budgets=None):
        tokens, done, state = super().ragged_megastep(
            self._clean(state, job), job, num_steps, eos_ids, budgets)
        return tokens, done, self._bank(state)

    def ragged_abort(self, job) -> None:
        if self._ragged_slot == job.slot and job.done_tokens:
            self._dirty.add(job.slot)
        super().ragged_abort(job)

    # ---------------------------------------------------------- the refusals

    def export_pages(self, state, chain_hashes, page_size: int = 0):
        raise ValueError(f"{self.cfg.name!r} exports no KV pages: "
                         f"{H.NO_PAGES}")

    def import_pages(self, state, payload):
        raise ValueError(f"{self.cfg.name!r} imports no KV pages: "
                         f"{H.NO_PAGES}")
