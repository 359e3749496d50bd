"""Leader-replicated dispatch: the async serving engine on a multi-host
mesh (parallel/multihost.py's driving model, made real).

JAX's multi-controller rule: every process must issue the SAME jitted
calls in the SAME order, or the first cross-host collective deadlocks.
The serving engine is an asyncio scheduler making load-dependent
decisions (admission order, chunk sizes, slot placement) — so those
decisions are made ONCE, on process 0, and replicated as a stream of
fixed-shape command frames:

- the leader's engine wraps its runner in :class:`ReplicatedRunner`,
  which broadcasts one frame (op + scalar args + padded prompt + PRNG
  key data) before delegating each device-touching call to the real
  runner;
- every follower process runs :func:`run_follower`: build the identical
  runner (same config, same params — checkpoint bytes or seeded init),
  then replay frames forever.  Host-side bookkeeping (buckets, repeat
  rings, page growth) is derived only from frame contents, so it stays
  bit-identical everywhere.

Frames ride ``multihost_utils.broadcast_one_to_all`` — the same DCN
control plane as the mesh itself, no extra sockets.  Decode tokens come
back via a tiled ``process_allgather`` (collective, so it appears in the
frame stream symmetrically); that readback is synchronous, which gives
up the single-host double-buffered chunk overlap — the documented v1
cost of multi-host serving.

Scope: EVERY runner the single-host matrix serves — contiguous, paged,
and the speculative runners.  All replicated host state (the paged
allocator's free-page list / prefix-cache index / LRU ticks, the spec
runners' hist rows and per-slot prompt lengths, the draft model's
cache) is derived ONLY from the op stream, so replaying frames keeps
every process bit-identical: pre_decode_check growth and the warmup
ctx-prefill compile broadcast as their own ops, batch embeddings ride
one length-prefixed EMBED frame, and the spec runners' packed
[K, 2+J, B] emission block rides the same collective readback as plain
tokens.  The reference has no analog at any scope — its worker is
always one host (/root/reference/pkg/peer/peer.go:42-68).
"""

from __future__ import annotations

import logging

import numpy as np

log = logging.getLogger("crowdllama.parallel.replicated")

_OP_NOOP = 0
_OP_INIT = 1
_OP_PREFILL = 2
_OP_INSERT = 3
_OP_RELEASE = 4
_OP_DECODE = 5
_OP_PREFILL_BEGIN = 6
_OP_PREFILL_STEP = 7
_OP_PREFILL_FINISH = 8
_OP_STOP = 9
_OP_PREFILL_ABORT = 10
_OP_EMBED = 11
_OP_PRE_DECODE = 12
_OP_WARMUP_CTX = 13

_NI, _NF, _NK = 8, 4, 4  # frame scalar-int / float / key-word capacities

# Which header slot carries the prompt length for ops that stream one.
# EMBED streams a length-prefixed FLAT batch ([len0, t0.., len1, t1..]);
# slot 1 holds the flat array's total length (slot 0 = prompt count).
_PROMPT_LEN_SLOT = {_OP_PREFILL: 0, _OP_PREFILL_BEGIN: 0, _OP_INSERT: 4,
                    _OP_EMBED: 1}


def _prompt_len_of(op: int, i32) -> int:
    slot = _PROMPT_LEN_SLOT.get(int(op))
    return 0 if slot is None else int(i32[slot])


def _key_words(key) -> np.ndarray:
    import jax

    try:
        raw = np.asarray(jax.random.key_data(key))
    except TypeError:  # raw legacy uint32 key array
        raw = np.asarray(key)
    out = np.zeros((_NK,), np.uint32)
    out[: raw.size] = raw.ravel().astype(np.uint32)
    return out


_KEY_SIZE: int | None = None


def _default_key_size() -> int:
    """Word count of the configured PRNG impl's key (2 for threefry,
    4 for rbg) — identical on leader and followers (same jax config)."""
    global _KEY_SIZE
    if _KEY_SIZE is None:
        import jax

        probe = jax.random.PRNGKey(0)
        try:
            probe = jax.random.key_data(probe)
        except TypeError:
            pass
        _KEY_SIZE = int(np.asarray(probe).size)
    return _KEY_SIZE


def _key_from_words(words):
    import jax.numpy as jnp

    size = _default_key_size()
    return jnp.asarray(np.asarray(words)[:size].astype(np.uint32))


class ReplicatedRunner:
    """Leader-side proxy: broadcast a frame, then run the real call.

    Implements exactly the runner surface the Scheduler uses
    (engine/scheduler.py): init_state, prefill, prefill_begin/step/
    finish, insert, release, decode_steps_device — plus attribute
    passthrough for max_slots/max_seq/cfg/mesh.
    """

    defer_release = True  # releases broadcast; scheduler defers them
    # Adaptive draft-length retuning is leader-local state; followers
    # replay decode frames traced with their construction-time draft_len,
    # so a leader-side set_draft_len would silently diverge the replicated
    # programs.  Explicit class attribute (not __getattr__ passthrough)
    # so the scheduler's feature gate sees False even when the inner
    # runner supports it.
    supports_adaptive_draft = False
    # Ragged chunked prefill dispatches are leader-local (no replay frame
    # op yet); same explicit-False pattern keeps the scheduler on the
    # monolithic/legacy-chunked path for replicated engines.
    supports_ragged = False

    def __init__(self, inner):
        self.inner = inner
        if not hasattr(inner, "pre_decode_check"):
            # The scheduler feature-gates on this attribute being present
            # and non-None; shadow the class method for contiguous inners
            # (instance attribute wins the lookup).
            self.pre_decode_check = None

    def __getattr__(self, name):
        return getattr(self.inner, name)

    # ------------------------------------------------------------ frames

    def _bcast(self, op: int, ints=(), floats=(), key=None, prompt=()):
        """Two-phase frame: a fixed ~100-byte header always, the prompt
        as a second exact-length broadcast ONLY for ops that carry one —
        a max_seq-wide buffer on every decode dispatch would put 100s of
        KB of zeros on the DCN hot path at long contexts.  Both sides
        derive the second broadcast's shape from the header
        (_prompt_len_of), so the collective shapes always agree."""
        from crowdllama_tpu.parallel.multihost import broadcast_from_leader

        i32 = np.zeros((_NI,), np.int32)
        i32[: len(ints)] = list(ints)
        f32 = np.zeros((_NF,), np.float32)
        f32[: len(floats)] = list(floats)
        kw = _key_words(key) if key is not None else np.zeros((_NK,),
                                                             np.uint32)
        broadcast_from_leader({
            "op": np.int32(op), "i32": i32, "f32": f32, "key": kw,
        })
        n = _prompt_len_of(op, i32)
        if n:
            assert len(prompt) == n, (op, len(prompt), n)
            broadcast_from_leader(np.asarray(list(prompt), np.int32))

    def shutdown(self) -> None:
        """Release follower loops (engine stop)."""
        self._bcast(_OP_STOP)

    # ----------------------------------------------------- runner surface

    def init_state(self, seed: int = 0):
        self._bcast(_OP_INIT, ints=(int(seed),))
        return self.inner.init_state(seed)

    def prefill(self, prompt_ids, temperature, top_p, key, state=None,
                top_k: int = 0, repeat_penalty: float = 1.0):
        self._bcast(_OP_PREFILL, ints=(len(prompt_ids), int(top_k)),
                    floats=(float(temperature), float(top_p),
                            float(repeat_penalty)),
                    key=key, prompt=prompt_ids)
        tok, ks, vs, plen = self.inner.prefill(
            prompt_ids, temperature, top_p, key, state=state, top_k=top_k,
            repeat_penalty=repeat_penalty)
        # The insert frame carries the token to every process as an int,
        # so each of them builds the same insert program from it.
        return int(tok), ks, vs, plen

    def prefill_begin(self, prompt_ids, state=None):
        self._bcast(_OP_PREFILL_BEGIN, ints=(len(prompt_ids),),
                    prompt=prompt_ids)
        return self.inner.prefill_begin(prompt_ids, state=state)

    def prefill_step(self, job) -> bool:
        self._bcast(_OP_PREFILL_STEP)
        return self.inner.prefill_step(job)

    def prefill_finish(self, job, temperature, top_p, key, top_k: int = 0,
                       repeat_penalty: float = 1.0):
        self._bcast(_OP_PREFILL_FINISH, ints=(int(top_k),),
                    floats=(float(temperature), float(top_p),
                            float(repeat_penalty)), key=key)
        return self.inner.prefill_finish(job, temperature, top_p, key,
                                         top_k=top_k,
                                         repeat_penalty=repeat_penalty)

    def prefill_abort(self, job) -> None:
        """Leader abandoned a chunked prefill (client cancelled mid-
        admission): tell followers to drop the job, or they keep its KV
        accumulators pinned until the next PREFILL_BEGIN replaces them."""
        self._bcast(_OP_PREFILL_ABORT)

    def insert(self, state, slot, ks, vs, plen, first, temperature, top_p,
               prompt_tokens=None, slot_key=None, top_k: int = 0,
               repeat_penalty: float = 1.0):
        prompt = list(prompt_tokens or [])
        self._bcast(_OP_INSERT, ints=(int(slot), int(plen), int(first),
                                      int(top_k), len(prompt),
                                      1 if slot_key is not None else 0),
                    floats=(float(temperature), float(top_p),
                            float(repeat_penalty)),
                    key=slot_key, prompt=prompt)
        return self.inner.insert(state, slot, ks, vs, plen, first,
                                 temperature, top_p,
                                 prompt_tokens=prompt_tokens,
                                 slot_key=slot_key, top_k=top_k,
                                 repeat_penalty=repeat_penalty)

    def release(self, state, slot):
        self._bcast(_OP_RELEASE, ints=(int(slot),))
        return self.inner.release(state, slot)

    def decode_steps_device(self, state, num_steps: int = 1):
        from jax.experimental import multihost_utils

        self._bcast(_OP_DECODE, ints=(int(num_steps),))
        toks, state = self.inner.decode_steps_device(state, num_steps)
        # Collective readback: followers mirror this gather (see
        # run_follower).  Returning HOST tokens keeps the scheduler's
        # np.asarray retirement a no-op.
        host = np.asarray(
            multihost_utils.process_allgather(toks, tiled=True))
        return host, state

    def decode_steps(self, state, num_steps: int = 1):
        tokens, state = self.decode_steps_device(state, num_steps)
        return np.asarray(tokens), state

    def pre_decode_check(self, steps: int):
        """Paged page-table growth is dispatch-time HOST bookkeeping that
        allocates pool pages — followers must replay it in stream order or
        their free lists (and thus page ids) diverge from the leader's."""
        self._bcast(_OP_PRE_DECODE, ints=(int(steps),))
        return self.inner.pre_decode_check(steps)

    def warmup_ctx_prefill(self, state) -> None:
        """Engine warmup compiles the suffix-over-cached-context program —
        a device computation, so every process must issue it."""
        self._bcast(_OP_WARMUP_CTX)
        return self.inner.warmup_ctx_prefill(state)

    def embed_prompts(self, prompts):
        """Batch embeddings (multi-host v2): the whole batch rides one
        frame as a length-prefixed flat token stream, so the follower's
        inner call keeps the same per-bucket batching as the leader's."""
        if not prompts:
            # No frame for an empty batch: the follower's decode of the
            # flat stream assumes at least one length prefix.
            return self.inner.embed_prompts(prompts)
        flat: list[int] = []
        for ids in prompts:
            flat.append(len(ids))
            flat.extend(int(t) for t in ids)
        self._bcast(_OP_EMBED, ints=(len(prompts), len(flat)), prompt=flat)
        return self.inner.embed_prompts(prompts)

    def embed_prompt(self, prompt_ids):
        return self.embed_prompts([prompt_ids])[0]


def run_follower(config) -> None:
    """Follower main loop: build the identical runner, replay the
    leader's frame stream until STOP.

    ``config`` must match the leader's engine-relevant fields (model,
    model_path, mesh, slots, context, quantize) — params are identical by
    construction (same checkpoint bytes or same seeded init).
    """
    import jax
    from jax.experimental import multihost_utils

    from crowdllama_tpu.engine.factory import build_runner
    from crowdllama_tpu.engine.plan import resolve_serving_plan
    from crowdllama_tpu.engine.weights import (
        load_params_for,
        resolve_clamped_model_config,
    )
    from crowdllama_tpu.parallel.multihost import broadcast_from_leader

    # The SAME plan/config/params derivation as the leader's engine, via
    # the shared factory (engine/factory.py) — the frame protocol depends
    # on both sides building bit-identical runners (contiguous, paged,
    # or speculative; draft params come from the same seeded init or
    # checkpoint bytes).
    plan = resolve_serving_plan(config, len(jax.devices()),
                                n_processes=jax.process_count())
    cfg = resolve_clamped_model_config(config)
    params = load_params_for(config, cfg)
    runner = build_runner(config, plan, cfg, params)
    log.info("follower %d up: %s (%s) on %d global devices",
             jax.process_index(), cfg.name, plan.runner,
             len(jax.devices()))

    state = None
    pending = None  # last prefill result awaiting insert
    job = None      # current chunked-prefill job
    # Set when an op failed here: a DETERMINISTIC error is mirrored on the
    # leader, whose recovery broadcasts INIT as its very next frame — so a
    # poisoned follower accepts only INIT (and NOOP/STOP).  Any other op
    # means the failure was follower-local (transient device error, local
    # OOM): per-shard state has diverged, and replaying frames against it
    # would make every collectively-computed decode silently corrupt the
    # tokens the LEADER serves.  Fail loudly instead — terminating the
    # follower turns the leader's next broadcast into a distributed-runtime
    # error rather than wrong output (ADVICE r4 medium).
    poisoned = False
    zero = {"op": np.int32(0), "i32": np.zeros((_NI,), np.int32),
            "f32": np.zeros((_NF,), np.float32),
            "key": np.zeros((_NK,), np.uint32)}
    while True:
        frame = broadcast_from_leader(zero)
        op = int(frame["op"])
        i32 = np.asarray(frame["i32"])
        f32 = np.asarray(frame["f32"])
        n_prompt = _prompt_len_of(op, i32)
        if n_prompt:
            frame = dict(frame)
            frame["prompt"] = np.asarray(broadcast_from_leader(
                np.zeros((n_prompt,), np.int32)))
        if op == _OP_STOP:
            log.info("follower %d: stop", jax.process_index())
            return
        if op in (_OP_NOOP,):
            continue
        if poisoned and op != _OP_INIT:
            raise RuntimeError(
                f"follower {jax.process_index()} state diverged from the "
                f"leader (a local op failure was not mirrored — next frame "
                f"was op {op}, not INIT); terminating so the divergence "
                f"fails loudly instead of serving corrupted tokens")
        try:
            state, pending, job = _apply(runner, state, pending, job, op,
                                         frame, i32, f32)
            poisoned = False
        except ValueError:
            # Request-level deterministic error: the leader catches exactly
            # ValueError at its admission sites (engine/scheduler.py), fails
            # only that request, and does NOT broadcast INIT — so the same
            # error here is mirrored, state has not diverged, and the
            # follower must keep replaying (poisoning would kill the
            # cluster on the next frame).  Device-local transients raise
            # XlaRuntimeError/OOM classes, never ValueError.
            log.warning("follower op %d: request-level error (mirrored on "
                        "the leader); continuing", op, exc_info=True)
            pending = None
            job = None
        except Exception:
            # Engine-level error: IF it was deterministic, the leader's
            # loop recovery mirrors it and broadcasts INIT, which rebuilds
            # state here.  Mark poisoned and clear transient op state; the
            # check above decides on the NEXT frame whether the leader
            # actually mirrored the failure.
            log.exception("follower op %d failed; awaiting leader recovery",
                          op)
            poisoned = True
            pending = None
            job = None


def _apply(runner, state, pending, job, op, frame, i32, f32):
    """Execute one frame; returns the updated (state, pending, job)."""
    from jax.experimental import multihost_utils

    if op == _OP_INIT:
        state = runner.init_state(int(i32[0]))
    elif op == _OP_PREFILL:
        n, top_k = int(i32[0]), int(i32[1])
        prompt = [int(t) for t in np.asarray(frame.get("prompt", []))[:n]]
        pending = runner.prefill(
            prompt, float(f32[0]), float(f32[1]),
            _key_from_words(frame["key"]), state=state, top_k=top_k,
            repeat_penalty=float(f32[2]))
    elif op == _OP_PREFILL_BEGIN:
        n = int(i32[0])
        prompt = [int(t) for t in np.asarray(frame["prompt"])[:n]]
        job = runner.prefill_begin(prompt, state=state)
    elif op == _OP_PREFILL_STEP:
        runner.prefill_step(job)
    elif op == _OP_PREFILL_ABORT:
        job = None
    elif op == _OP_PREFILL_FINISH:
        pending = runner.prefill_finish(
            job, float(f32[0]), float(f32[1]),
            _key_from_words(frame["key"]), top_k=int(i32[0]),
            repeat_penalty=float(f32[2]))
        job = None
    elif op == _OP_INSERT:
        slot, plen, first = int(i32[0]), int(i32[1]), int(i32[2])
        n_prompt, has_key = int(i32[4]), int(i32[5])
        prompt = ([int(t) for t in np.asarray(frame["prompt"])[:n_prompt]]
                  if n_prompt else None)
        slot_key = _key_from_words(frame["key"]) if has_key else None
        _tok, ks, vs, _plen = pending
        state = runner.insert(state, slot, ks, vs, plen, first,
                              float(f32[0]), float(f32[1]),
                              prompt_tokens=prompt, slot_key=slot_key,
                              top_k=int(i32[3]),
                              repeat_penalty=float(f32[2]))
        pending = None
    elif op == _OP_RELEASE:
        state = runner.release(state, int(i32[0]))
    elif op == _OP_DECODE:
        toks, state = runner.decode_steps_device(state, int(i32[0]))
        multihost_utils.process_allgather(toks, tiled=True)
    elif op == _OP_PRE_DECODE:
        runner.pre_decode_check(int(i32[0]))
    elif op == _OP_WARMUP_CTX:
        runner.warmup_ctx_prefill(state)
    elif op == _OP_EMBED:
        n, total = int(i32[0]), int(i32[1])
        flat = ([int(t) for t in np.asarray(frame["prompt"])[:total]]
                if n else [])
        prompts, pos = [], 0
        for _ in range(n):
            ln = flat[pos]
            prompts.append(flat[pos + 1: pos + 1 + ln])
            pos += 1 + ln
        runner.embed_prompts(prompts)
    else:
        raise RuntimeError(f"unknown replicated op {op}")
    return state, pending, job
