"""Continuous batching scheduler.

The async policy layer over ModelRunner: admit pending requests into free
batch slots (bucketed prefill), run the shared decode loop while any slot is
active, stream each new token to its request's queue, retire slots on EOS /
max-tokens.  This is the component the reference outsources to Ollama's
internal server loop; here it is explicit and TPU-shaped (fixed-shape decode
batch, prefill interleaved between steps).

JAX dispatch runs on a dedicated single-flight executor thread, never on the
event loop: a decode chunk or a long-prompt prefill blocks until its host
transfer completes, and parking that wait on the loop would stall the whole
control plane (DHT RPCs, metadata serving, health probes — the reference
worker serves all of these concurrently via goroutines).  The scheduler
coroutine awaits each dispatch, so device state is still mutated by exactly
one in-flight program at a time.

Decode is double-buffered: chunk k+1 is dispatched (async, device-side)
before chunk k's tokens are read back, so the host↔device readback and the
Python emit loop overlap the next chunk's compute instead of serializing
with it.  Each chunk carries a snapshot of the slots it was dispatched for;
emission checks slot identity against the snapshot, so a slot retired (or
retired-and-readmitted) between dispatch and readback never receives
another chunk's tokens.

A flight's length follows slot occupancy (``_chunk_size``): with a flight
in the air and the next queued behind it, an arrival's prefill waits for
both, so a flight is ``decode_chunk`` steps long only while every slot is
taken — nothing could be admitted during it — and one step long while a
slot is free.  ``crowdllama_engine_flights_total{length}`` counts each.

An admission does not drain the device either: prefill hands its sampled
token back as a device scalar, insert takes it unread, and the host reads
and emits it only after the NEXT flight — which already carries the new row
— is in the device's queue (``_place``, ``_emit_firsts``).
"""

from __future__ import annotations

import asyncio
import itertools
import logging
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import jax
import numpy as np

from crowdllama_tpu.engine.runner import ModelRunner
from crowdllama_tpu.obs.metrics import DISPATCH_CLASSES, ENGINE_TELEMETRY
from crowdllama_tpu.obs.trace import (
    SCHED_ADMIT,
    SCHED_DISPATCH,
    SCHED_EMIT,
    SCHED_READBACK,
    SCHED_WAIT_FOR_WORK,
    SCHED_YIELD,
)
from crowdllama_tpu.testing import faults

log = logging.getLogger("crowdllama.engine.scheduler")

_DONE = object()
# Remote-draft verify payload marker on a request's out queue (ISSUE 20,
# docs/SPECULATIVE.md): the paired value is a dict the engine turns into a
# VerifyResult wire frame interleaved with the stream's text frames.
_VERIFY = object()
# Slot sentinel: reserved for an in-progress chunked admission — occupied
# (skipped by _free_slot) but carrying no request yet.
_RESERVED = object()


class OverloadedError(RuntimeError):
    """Admission rejected: pending depth crossed the configured threshold.

    The message starts with "overloaded" on purpose — the gateway matches
    that word in worker error strings to translate the failure into an
    HTTP 503 with a Retry-After hint (load shedding, docs/ROBUSTNESS.md)
    instead of a generic inference error.
    """


class WedgedError(RuntimeError):
    """The dispatch self-watchdog declared the engine wedged: a flight
    stayed in device_get far past its dispatch-class EWMA (gray failure —
    the device hung, not crashed).  Requests failed under this carry a
    reason starting with ``"error: wedged"`` so the engine seam can
    re-raise the typed error instead of a generic RuntimeError
    (docs/ROBUSTNESS.md gray-failure section)."""


@dataclass(eq=False)  # identity semantics (slot/queue tracking, WeakSet)
class GenRequest:
    prompt_ids: list[int]
    max_tokens: int = 128
    temperature: float = 0.0
    top_p: float = 1.0
    top_k: int = 0  # Ollama options.top_k (0 = disabled)
    repeat_penalty: float = 1.0  # Ollama options.repeat_penalty (1 = off)
    eos_id: int = -1
    # 0 = unseeded (scheduler RNG); non-zero makes sampling reproducible:
    # identical seeded requests yield identical tokens (Ollama honors seed;
    # proto/llama_v1.proto carries it).
    seed: int = 0
    id: int = field(default_factory=itertools.count().__next__)
    # queue of (token_id | _DONE sentinel, finish_reason)
    out: asyncio.Queue = field(default_factory=asyncio.Queue)
    submitted_at: float = field(default_factory=time.monotonic)
    # Tracing stamps (crowdllama_tpu/obs): admitted_at is set when the
    # scheduler pops the request for prefill, so worker_queue =
    # admitted_at - submitted_at and prefill = first_token_at - admitted_at.
    admitted_at: float = 0.0
    first_token_at: float = 0.0
    # The host's estimate of when the device could start this request's
    # first program: the later of its own dispatch and the end of the
    # flight queued ahead of it (``dispatch_watch`` resolves to that end).
    # Splits prefill into dispatch_wait (admitted_at → here) and
    # prefill_exec (here → first_token_at).
    exec_start_at: float = 0.0
    dispatch_watch: object | None = None
    cancelled: bool = False  # client went away: drop at admission / free slot
    # KV shipping (docs/KV_TRANSFER.md): pages fetched from a donor peer,
    # applied via runner.import_pages right before this request's prefill
    # (the suffix-only path then consumes them like locally cached pages).
    # Any import failure falls back to plain prefill — never fails the
    # request.
    kv_import: dict | None = None
    # Claim-or-skip terminal delivery (docs/ROBUSTNESS.md): set by the
    # FIRST path to deliver this request's terminal frame.  The retire
    # path (_emit on EOS/budget) and the migrate safe point both reach
    # completing streams — without the claim a drain landing on a stream's
    # final chunk could deliver BOTH a "stop" and a "migrate" terminal,
    # and the consumer/gateway would see a phantom second completion.
    finished: bool = False
    # Gateway-drafted speculation (ISSUE 20, docs/SPECULATIVE.md): the
    # request rides a paced remote-draft stream, and ``feed`` is its
    # DraftFeed (core/spec_pipeline.py, duck-typed here) — one credit
    # consumed per verify round, one _VERIFY payload pushed back per
    # credit.  None = ordinary stream.
    remote_draft: bool = False
    feed: object | None = None

    def finish(self, reason: str) -> bool:
        """Atomically claim this request's terminal: exactly one
        ``(_DONE, reason)`` is ever queued, whichever of the racing
        paths (retire/EOS, migrate safe point, loop recovery, admit
        failure, wedge watchdog) gets here first wins.  Returns False
        when another path already claimed it — callers skip their own
        accounting (a migrate must not count an already-served stream
        as moved)."""
        if self.finished:
            return False
        self.finished = True
        self.out.put_nowait((_DONE, reason))
        return True


@dataclass
class _SlotInfo:
    req: GenRequest
    prompt_len: int = 0
    generated: int = 0
    # The request's first token while it is still on the device and not
    # yet emitted (``Scheduler._place``); None from its emission on.
    first_dev: object = None


@dataclass
class _InFlightChunk:
    """A dispatched-but-not-yet-read-back decode chunk."""

    tokens_dev: object                  # device array [K, B]
    snapshot: list["_SlotInfo | None"]  # slot infos at dispatch time
    dispatched_at: float
    # Unified ragged dispatch (docs/RAGGED_BATCH.md): how many prefill
    # chunks rode along in this decode chunk (0 = plain decode): the
    # flight's dispatch class (``Scheduler._flight_class``).
    ragged_steps: int = 0
    # Remote-draft pacing (docs/SPECULATIVE.md): the (slot, chunk_id)
    # credits this flight consumed — retire answers each with a _VERIFY
    # payload carrying the tokens that slot emitted in the flight.
    verify_meta: list | None = None
    # Counters the flight's programs kept on the device (a runner's
    # ``flight_counters``; engine/hybrid.py: the expert layers' rows and
    # banks), read back in the same transfer as the tokens.
    counters_dev: object = None


class Scheduler:
    def __init__(self, runner: ModelRunner, max_queue: int = 256,
                 decode_chunk: int = 8, admission_pending_max: int = 0,
                 spec_draft_max: int = 0, ragged: bool = True,
                 wedge_multiplier: float = 0.0,
                 clock=time.monotonic):
        self.runner = runner
        self.decode_chunk = max(1, decode_chunk)
        # Load shedding (docs/ROBUSTNESS.md): reject at submit() once the
        # pending depth reaches this, instead of queueing work whose
        # deadline will expire before admission.  0 = no threshold (the
        # bounded pending queue still applies backpressure by blocking).
        self.admission_pending_max = max(0, admission_pending_max)
        self.shed_requests = 0
        self.state = runner.init_state()
        self.slots: list[_SlotInfo | None] = [None] * runner.max_slots
        self.pending: asyncio.Queue[GenRequest] = asyncio.Queue(max_queue)
        self._wake = asyncio.Event()
        self._task: asyncio.Task | None = None
        # Single dispatch thread: keeps device programs single-flight while
        # freeing the event loop during blocking host transfers.
        self._exec: ThreadPoolExecutor | None = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="jax-dispatch")
        self._rng = jax.random.PRNGKey(int(time.time()) & 0x7FFFFFFF)
        self._inflight: _InFlightChunk | None = None
        self._last_retire_at = 0.0
        self._admitting = 0  # popped from pending, not yet in a slot
        # In-progress chunked admission: (req, slot, PrefillJob).  One chunk
        # runs per loop iteration so decode chunks interleave with a long
        # prompt's prefill instead of stalling behind all of it.
        self._chunking: tuple[GenRequest, int, object] | None = None
        import collections

        # Long prompts popped while another chunked admission is running
        # (kept FIFO ahead of pending).
        self._deferred: collections.deque[GenRequest] = collections.deque()
        # Exclusive runner access (KV export, docs/KV_TRANSFER.md): queued
        # (fn, future) pairs the loop runs on the dispatch executor between
        # device dispatches — see run_exclusive.
        self._exclusive: list[tuple] = []
        self._to_release: list[int] = []
        self._draining = False
        # Live migration (docs/ROBUSTNESS.md): a pending migrate() call —
        # the loop resolves the future at its next safe point after
        # retiring every admitted/queued request with reason "migrate".
        self._migrating: "asyncio.Future | None" = None
        self._embeds = 0  # embedding forwards in flight on the executor
        # Requests whose output queues drain must also see consumed (the
        # consumer may still be flushing final frames to the client after
        # the slot retires); weak so retired requests don't accumulate.
        import weakref

        self._tracked: "weakref.WeakSet[GenRequest]" = weakref.WeakSet()
        # Telemetry for Resource advertisement + /api/health.
        self.tokens_generated = 0
        self.throughput_ema = 0.0  # tokens/sec across the batch
        self.requests_served = 0
        self.spec_steps = 0    # speculative verify dispatches retired
        self.spec_emitted = 0  # tokens those dispatches emitted
        # Accepted-draft split by proposal source (packed row -1): echo =
        # the match replayed PROMPT content, generative = it matched
        # generated history.  Operators need the split — echo dividends
        # exist only on templated/retrieval traffic (VERDICT r4 weak #4).
        self.spec_accept_echo = 0
        self.spec_accept_gen = 0
        # Acceptance-adaptive draft length (ISSUE 4 tentpole #2): retune
        # the runner's draft_len BETWEEN dispatches from a windowed
        # acceptance rate.  k shrinks toward 0 when drafts mostly miss
        # (k = 0 pauses speculation entirely — the runner dispatches its
        # parent's PLAIN decode program, so a bad draft costs plain-decode
        # throughput plus only rare probes), grows toward spec_draft_max
        # when windows fully accept.  Greedy exactness is untouched:
        # drafts decide how MANY tokens emit per dispatch, never which.
        # Feature-gated on the runner (ReplicatedRunner pins
        # supports_adaptive_draft False: a leader-side retune would
        # diverge follower replay programs).
        self.spec_draft_max = max(0, spec_draft_max)
        self._spec_adaptive = (
            self.spec_draft_max > 0
            and getattr(runner, "supports_adaptive_draft", False)
            and getattr(runner, "draft_len", 0) > 0)
        self.spec_retunes = 0    # draft_len changes applied
        self.spec_probes = 0     # paused→k=1 probe dispatches
        self.spec_shrink_rate = 0.25   # window rate at/below → shrink
        self.spec_grow_rate = 0.8      # window rate at/above → grow
        self.spec_probe_interval = 64  # plain steps between paused probes
        self._accept_acc = 0     # window: draft tokens accepted
        self._accept_off = 0     # window: draft tokens offered
        self._plain_since_probe = 0
        self._spec_probing = False
        # Gateway-drafted pipeline (ISSUE 20, docs/SPECULATIVE.md): slots
        # whose request carries a DraftFeed advance one verify round per
        # wire credit.  spec_pipeline_depth is the depth hint advertised
        # back on every VerifyResult; the stall budget releases a
        # creditless stream to full speed (free_run) so a dead gateway
        # pump can never park a batch.
        self.spec_pipeline_depth = 8
        self.spec_pipeline_stall_s = 2.0
        self.spec_verifies = 0         # hosted/ack verify rounds answered
        self.spec_stale_chunks = 0     # draft chunks nacked unverified
        self.spec_pipeline_freeruns = 0  # paced streams released
        # Unified ragged batch (ISSUE 9, docs/RAGGED_BATCH.md): when the
        # runner supports it, long prompts prefill INSIDE the decode
        # dispatch (fixed-token chunks riding the per-step token budget)
        # instead of alternating whole prefill steps with decode chunks.
        self._ragged = ragged and getattr(runner, "supports_ragged", False)
        # Host-dispatch accounting: every decode flight (plain / ragged /
        # spec) counts one dispatch; tokens_per_dispatch is what the last
        # retired flight actually emitted.
        self.host_dispatches = 0
        self._tokens_per_dispatch = 0.0
        # Duty-cycle profiler (PR 13, docs/OBSERVABILITY.md): per dispatch
        # class, an EWMA of device-window / (device-window + host-gap) —
        # both sides measured from host timestamps already on the retire
        # path (no new device syncs).  ~1.0 = the device never waits on
        # the host between flights.
        self._duty: dict[str, float] = {}
        self.ragged_chunks = 0  # prefill chunks dispatched unified
        # Chaos hook: the "scheduler.ragged_chunk" fault site's "drain"
        # action calls this to start a graceful drain mid-chunked-prefill
        # (the engine points it at the peer's drain, like the
        # "engine.stream_chunk" site does for mid-stream drains).
        self.drain_requested_cb = None
        # Dispatch self-watchdog (docs/ROBUSTNESS.md gray-failure
        # section): a flight whose age exceeds wedge_multiplier × its
        # dispatch-class flight-duration EWMA marks the ENGINE wedged —
        # the device hung inside a transfer/program, a failure the decode
        # loop cannot observe about itself because it is parked on that
        # very executor await.  A separate watchdog task runs
        # check_wedged() on the injected clock (unit-testable without
        # waiting out real thresholds).  0 = watchdog off.
        self.wedge_multiplier = max(0.0, float(wedge_multiplier))
        self._clock = clock
        # Absolute floor under the multiplied EWMA: sub-second EWMAs must
        # not let scheduler jitter (GC pause, CPU contention) read as a
        # wedge — a real device hang is seconds, not milliseconds.
        self.wedge_floor_s = 5.0
        self.wedge_check_interval_s = 0.25
        self._flight_ewma: dict[str, float] = {}  # cls -> flight seconds
        self.wedged = False
        self.wedged_events = 0
        self._wedge_drain_fired = False
        self._watchdog_task: asyncio.Task | None = None

    # ---------------------------------------------------------------- public

    def start(self) -> None:
        self._draining = False
        self.wedged = False
        self._wedge_drain_fired = False
        if self._exec is None:  # restarted after stop(): fresh dispatcher
            self._exec = ThreadPoolExecutor(max_workers=1,
                                            thread_name_prefix="jax-dispatch")
        if self._task is None:
            self._task = asyncio.create_task(self._loop(), name="decode-loop")
        if self.wedge_multiplier > 0 and self._watchdog_task is None:
            self._watchdog_task = asyncio.create_task(
                self._watchdog_loop(), name="wedge-watchdog")

    async def stop(self) -> None:
        if self._watchdog_task is not None:
            self._watchdog_task.cancel()
            try:
                await self._watchdog_task
            except asyncio.CancelledError:
                pass
            self._watchdog_task = None
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        if self._exec is not None:
            self._exec.shutdown(wait=False)
            self._exec = None

    async def submit(self, req: GenRequest) -> None:
        if self._draining:
            # Shutting down: reject so the caller's error surfaces quickly
            # and the gateway fails over to another worker, instead of
            # accepting work we would hard-drop at the drain deadline.
            raise RuntimeError("worker is draining for shutdown")
        if len(req.prompt_ids) >= self.runner.max_seq:
            raise ValueError(
                f"prompt of {len(req.prompt_ids)} tokens exceeds max context "
                f"{self.runner.max_seq}"
            )
        if self.admission_pending_max:
            depth = (self.pending.qsize() + len(self._deferred)
                     + self._admitting)
            if depth >= self.admission_pending_max:
                self.shed_requests += 1
                raise OverloadedError(
                    f"overloaded: {depth} requests pending (admission "
                    f"threshold {self.admission_pending_max})")
        if req.feed is not None:
            # Credits pushed by the peer's chunk reader must wake a parked
            # dispatch loop (same event loop: a plain callback suffices).
            req.feed._waker = self._wake.set
        await self.pending.put(req)
        self._track(req)
        self._wake.set()

    def _track(self, req: GenRequest) -> None:
        self._tracked.add(req)

    def cancel(self, req: GenRequest) -> None:
        """Stop generating for a request whose client went away.

        Only marks: the decode loop frees the slot at its next safe point
        (a disconnected stream would otherwise burn batch throughput until
        max_tokens); a request still in the pending queue is dropped at
        admission.  The slot stays OCCUPIED until the loop drains it —
        freeing it here would let a new admission reuse the slot while the
        deferred device-side release is still queued, corrupting the new
        request's KV; and calling runner.release from outside the loop can
        donate the very state buffers a just-scheduled dispatch is about to
        read (observed as "Array has been deleted").
        """
        req.cancelled = True
        self._wake.set()

    async def drain(self, timeout: float = 30.0) -> bool:
        """Wait for every admitted and pending request to finish (graceful
        shutdown); True when fully drained, False on timeout.

        Entering drain rejects new submissions (callers fail over).
        ``_admitting`` covers the popped-but-not-yet-inserted window (a
        request mid-prefill is in neither pending nor slots); tracked
        output queues cover the retire-to-client-flush window — the
        consumer coroutine may still be writing final frames after the
        slot clears.  Cancelled requests' queues are exempt (no consumer).
        """
        self._draining = True
        deadline = time.monotonic() + timeout
        while True:
            # _inflight: the final overshoot chunk may still be queued on
            # device after every slot retired — stop() must not cancel the
            # loop with a program in flight (ADVICE r2).  _embeds covers
            # embedding forwards on the dispatch executor.
            done = (all(s is None for s in self.slots)
                    and self.pending.empty() and self._admitting == 0
                    and not self._deferred
                    and self._inflight is None
                    and self._embeds == 0
                    and all(r.out.empty() or r.cancelled
                            for r in list(self._tracked)))
            if done:
                return True
            if time.monotonic() >= deadline:
                return False
            await asyncio.sleep(0.1)

    async def migrate(self) -> int:
        """Hand off every admitted and queued request for live migration
        (graceful drain, docs/ROBUSTNESS.md); returns how many were moved.

        Enters draining (new submits are rejected), then retires every
        request — active slots, the in-progress chunked admission,
        deferred long prompts, and the pending queue — with a
        ``"migrate"`` terminal reason at the decode loop's next safe
        point (between device dispatches, so no program is reading the
        slots being cleared).  Released slots return their pages through
        the runner's prefix cache, so this worker keeps serving them to
        the successor as a KV donor until the drain deadline.
        """
        self._draining = True
        if self.wedged:
            # The decode loop is stuck inside a device transfer — its safe
            # point may never run, and touching the runner here could block
            # on the same hung device.  _declare_wedged already failed
            # every request with the typed reason; nothing left to move.
            return 0
        if self._task is None:
            # Loop not running (unit tests drive the runner directly):
            # nothing can be in flight, process immediately.
            return self._migrate_now()
        fut = asyncio.get_running_loop().create_future()
        self._migrating = fut
        self._wake.set()
        return await fut

    def _migrate_now(self) -> int:
        """Synchronous migration body; only safe between dispatches (the
        loop's safe point, or with no loop running)."""
        moved = 0
        if self._chunking is not None:
            req, slot, job = self._chunking
            self._chunking = None
            self._admitting -= 1
            self.slots[slot] = None  # release the _RESERVED slot
            abort = self._abort_fn(job)
            if abort is not None:
                abort(job)
            if req.finish("migrate"):
                moved += 1
        for i, info in enumerate(self.slots):
            if isinstance(info, _SlotInfo):
                self.slots[i] = None
                self.state = self.runner.release(self.state, i)
                self.requests_served += 1
                if info.req.finish("migrate"):
                    moved += 1
        while self._deferred:
            if self._deferred.popleft().finish("migrate"):
                moved += 1
        while not self.pending.empty():
            if self.pending.get_nowait().finish("migrate"):
                moved += 1
        return moved

    # --------------------------------------------- dispatch self-watchdog

    @staticmethod
    def _flight_class(fl: _InFlightChunk) -> str:
        """Dispatch class of an in-flight chunk, from host-side metadata
        only (the watchdog must never touch the device — tokens_dev may
        belong to a hung transfer).  Same classification _retire_inflight
        applies after readback: a jax device array reports the same ndim
        before and after device_get."""
        if fl.ragged_steps:
            return "ragged"
        return "spec" if getattr(fl.tokens_dev, "ndim", 2) == 3 else "plain"

    def check_wedged(self, now: float | None = None) -> bool:
        """One watchdog probe: is the current flight stuck past its
        dispatch-class threshold?  Pure host math on the injected clock —
        callable from a unit test with a fake clock, and from the
        watchdog task.  Idempotent once tripped.

        The threshold is ``wedge_multiplier × flight-duration EWMA`` for
        the flight's dispatch class (floored at wedge_floor_s), so a
        ragged flight that legitimately runs longer than a plain chunk
        is judged against ragged history, not a global constant.
        A class with NO retired flight yet is never judged: its first
        flight may legitimately include XLA compilation."""
        if self.wedged:
            return True
        fl = self._inflight
        if self.wedge_multiplier <= 0 or fl is None:
            return False
        cls = self._flight_class(fl)
        ewma = self._flight_ewma.get(cls)
        if ewma is None:
            return False
        if now is None:
            now = self._clock()
        age = now - fl.dispatched_at
        threshold = max(self.wedge_floor_s, self.wedge_multiplier * ewma)
        if age <= threshold:
            return False
        self._declare_wedged(cls, age, threshold)
        return True

    def _declare_wedged(self, cls: str, age: float,
                        threshold: float) -> None:
        """The engine is wedged: fail every request a terminal can still
        reach with the typed ``error: wedged`` reason (the engine seam
        raises WedgedError from it), then trigger self-drain ONCE so the
        gateway learns through the drain plane — a typed draining reject
        within one probe interval — instead of burning its full request
        budget against a silent worker.

        Deliberately does NOT touch device state (release/init_state):
        the dispatch executor is stuck inside the hung transfer, and any
        runner call here could block the watchdog on the same device.
        Slots stay occupied and _draining rejects new submissions, so no
        new request can land on the wedged engine."""
        self.wedged = True
        self.wedged_events += 1
        self._draining = True
        reason = (f"error: wedged: {cls} flight stuck for {age:.1f}s "
                  f"(threshold {threshold:.1f}s = "
                  f"{self.wedge_multiplier:g}x class EWMA)")
        log.error("dispatch self-watchdog: %s — failing in-flight "
                  "requests and self-draining", reason[len("error: "):])
        if self._chunking is not None:
            self._chunking[0].finish(reason)
        for info in self.slots:
            if isinstance(info, _SlotInfo):
                info.req.finish(reason)
        while self._deferred:
            self._deferred.popleft().finish(reason)
        while not self.pending.empty():
            self.pending.get_nowait().finish(reason)
        if self._migrating is not None:
            # A migrate() racing the wedge must not hang on a safe point
            # the stuck loop will never reach.
            fut, self._migrating = self._migrating, None
            if not fut.cancelled():
                fut.set_result(0)
        if self.drain_requested_cb is not None \
                and not self._wedge_drain_fired:
            self._wedge_drain_fired = True
            try:
                self.drain_requested_cb()
            except Exception:
                log.exception("wedge self-drain callback failed")

    async def _watchdog_loop(self) -> None:
        """A task SEPARATE from the decode loop on purpose: a wedged
        flight parks the decode loop inside its executor await, so the
        loop cannot self-check — only an independent task still gets
        scheduled while the device hangs."""
        while not self.wedged:
            await asyncio.sleep(self.wedge_check_interval_s)
            try:
                self.check_wedged()
            except Exception:
                log.exception("wedge watchdog probe failed")

    async def run_exclusive(self, fn):
        """Run ``fn(state) -> result`` on the dispatch executor at the
        decode loop's next safe point (between device dispatches).

        Reading ``self.state`` from outside the loop coroutine is unsafe:
        an in-flight dispatch may already have DONATED those buffers, and
        the loop reassigns ``self.state`` only when its executor await
        resolves (observed as "Array has been deleted").  ``fn`` must treat
        the state as read-only — KV export qualifies (host gathers plus
        allocator bookkeeping, no donation)."""
        if self._task is None:
            # Loop not running (unit tests drive the runner directly):
            # nothing can be in flight, execute immediately.
            loop = asyncio.get_running_loop()
            return await loop.run_in_executor(self._exec, fn, self.state)
        fut = asyncio.get_running_loop().create_future()
        self._exclusive.append((fn, fut))
        self._wake.set()
        return await fut

    @property
    def load(self) -> float:
        busy = sum(1 for s in self.slots if s is not None)
        return busy / max(1, len(self.slots))

    def telemetry_gauges(self) -> dict:
        """Scheduler gauges for the /metrics exposition (obs plane):
        queue depth, batch occupancy, and KV-cache utilization — the
        Orca-style knobs continuous batching is tuned by."""
        active = sum(1 for s in self.slots if isinstance(s, _SlotInfo))
        total = max(1, len(self.slots))
        g = {
            "pending_depth": float(self.pending.qsize() + len(self._deferred)
                                   + self._admitting),
            "active_slots": float(active),
            "batch_occupancy": active / total,
        }
        r = self.runner
        total_pages = getattr(r, "total_pages", 0)
        free_pages = getattr(r, "_free_pages", None)
        if total_pages and free_pages is not None:
            # Paged KV: exact page-pool occupancy (includes cached prefix
            # pages awaiting reuse/eviction).
            g["kv_cache_utilization"] = 1.0 - len(free_pages) / total_pages
        else:
            # Contiguous KV: tokens materialized over total capacity.
            used = sum(s.prompt_len + s.generated for s in self.slots
                       if isinstance(s, _SlotInfo))
            g["kv_cache_utilization"] = used / (total * max(1, r.max_seq))
        # Paged KV by kind of pool (engine/paged.py, engine/hybrid.py):
        # capacity, bytes live, window pages written over.
        g.update(getattr(r, "kv_gauges", dict)())
        # Host-dispatch economy: the counter measures device programs
        # launched, the gauge what the LAST retired flight emitted.
        g["host_dispatches_total"] = float(self.host_dispatches)
        g["tokens_per_dispatch"] = float(self._tokens_per_dispatch)
        # Duty cycle per dispatch class (PR 13): always present (zeros
        # for classes this engine never dispatched).
        duty = getattr(self, "_duty", {})
        for cls in DISPATCH_CLASSES:
            g[f"duty_cycle|dispatch={cls}"] = float(duty.get(cls, 0.0))
        # Dispatch self-watchdog (docs/ROBUSTNESS.md): level gauge (1 =
        # this engine declared itself wedged and self-drained) + the
        # monotonic trip counter, always present so absent()-alerts work.
        g["wedged"] = 1.0 if getattr(self, "wedged", False) else 0.0
        g["wedged_events_total"] = float(getattr(self, "wedged_events", 0))
        # Remote-draft pipeline plane (ISSUE 20, docs/SPECULATIVE.md):
        # always present so the crowdllama_spec_pipeline_* families exist
        # on every worker (absent()-alert invariant) — zeros until a
        # gateway opens a paced stream.
        g["spec_pipeline_depth"] = float(
            getattr(self, "spec_pipeline_depth", 0))
        g["spec_pipeline_verifies"] = float(
            getattr(self, "spec_verifies", 0))
        g["spec_pipeline_stale"] = float(
            getattr(self, "spec_stale_chunks", 0))
        g["spec_pipeline_freeruns"] = float(
            getattr(self, "spec_pipeline_freeruns", 0))
        if hasattr(r, "draft_len"):
            # Speculation acceptance on BOTH /metrics surfaces (gateway
            # aggregates worker gauges): emitted/steps is the live
            # tokens-per-verify-dispatch dividend; the echo/gen split
            # keeps the echo dividend from being read as general; the
            # live draft_len shows what the adaptive controller chose.
            g["spec_steps"] = float(self.spec_steps)
            g["spec_emitted"] = float(self.spec_emitted)
            g["spec_accept_echo"] = float(self.spec_accept_echo)
            g["spec_accept_gen"] = float(self.spec_accept_gen)
            g["spec_draft_len"] = float(r.draft_len)
        return g

    # ------------------------------------------------------------------ loop

    def _free_slot(self) -> int | None:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _call(self, loop, what: str, fn, *args, note: dict | None = None,
              **kwargs):
        """Await ``fn(*args, **kwargs)`` on the dispatch executor under a
        ``sched.dispatch.<what>`` annotation (``note``: its arguments in
        the trace) — a host event on the profiler's clock, one flag test
        while no profiler runs."""
        def run():
            with jax.profiler.TraceAnnotation(f"{SCHED_DISPATCH}.{what}",
                                              **(note or {})):
                return fn(*args, **kwargs)

        return loop.run_in_executor(self._exec, run)

    @staticmethod
    def _watch_ready(loop, fl: "_InFlightChunk | None"):
        """A future of the host's clock at the moment the device finished
        flight ``fl``, waited for on a pool thread: the dispatch stream is
        not touched, and the device runs its queue in order, so the program
        dispatched next starts then.  None when nothing is queued."""
        if fl is None:
            return None

        def wait() -> float:
            try:
                jax.block_until_ready(fl.tokens_dev)
            except Exception:
                return 0.0   # the flight's own retire reports the failure
            return time.monotonic()

        return loop.run_in_executor(None, wait)

    @staticmethod
    async def _stamp_first_token(req: GenRequest) -> None:
        req.first_token_at = time.monotonic()
        watch, req.dispatch_watch = req.dispatch_watch, None
        if watch is not None:
            req.exec_start_at = max(req.exec_start_at, await watch)

    def _emit_first(self, req: GenRequest, first: int,
                    info: "_SlotInfo") -> None:
        """The first token to its request.  The trace event carries the
        host's estimate of the request's own execution (``exec_us``), so a
        device trace can be held against it (PERF.md §7)."""
        exec_us = int(1e6 * (req.first_token_at - req.exec_start_at))
        with jax.profiler.TraceAnnotation(SCHED_EMIT, first_token=1,
                                          exec_us=exec_us):
            self._emit(req, first, info)

    def _flight_counters(self):
        take = getattr(self.runner, "flight_counters", None)
        return take() if take is not None else None

    def _prefix_mark(self) -> tuple[int, int]:
        r = self.runner
        return (getattr(r, "prefix_hits", 0),
                getattr(r, "prefix_tokens_reused", 0))

    def _count_admission(self, req: GenRequest,
                         mark: tuple[int, int]) -> None:
        """The prefix-cache counters of one admission: what the runner's
        own counters grew by across its admission call."""
        hits, reused = self._prefix_mark()
        ENGINE_TELEMETRY.prefix_inc(
            prompt_tokens=len(req.prompt_ids),
            tokens_reused=reused - mark[1], hits=hits - mark[0])

    def _abort_fn(self, job):
        """Runner abort for a parked admission job: ragged jobs (marker
        attribute) abort via ragged_abort, monolithic chunked jobs via
        prefill_abort; None when the runner has neither."""
        name = ("ragged_abort" if getattr(job, "ragged", False)
                else "prefill_abort")
        return getattr(self.runner, name, None)

    def _req_key(self, req: GenRequest, lane: int) -> jax.Array:
        """PRNG key for one sampling lane of a request (0 = prefill's first
        token, 1 = the slot's decode stream).  Seeded requests derive both
        from the seed alone, so identical seeded requests reproduce exactly;
        unseeded ones draw from the scheduler RNG."""
        if req.seed:
            # Full 64-bit seed: low 31 bits seed the key, the remaining 33
            # fold in (two words), so seeds differing only above bit 31 —
            # including bit 63 — don't collide (ADVICE r3).  Clients may
            # send negative or oversized JSON ints — reduce to uint64 first
            # (fold_in rejects values outside uint32).
            seed = req.seed & 0xFFFFFFFFFFFFFFFF
            key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
            hi = seed >> 31
            if hi:
                key = jax.random.fold_in(key, hi & 0xFFFFFFFF)
                if hi >> 32:
                    key = jax.random.fold_in(key, hi >> 32)
            return jax.random.fold_in(key, lane)
        self._rng, sub = jax.random.split(self._rng)
        return sub

    async def _apply_kv_import(self, req: GenRequest, loop) -> None:
        """Seed fetched donor pages into the runner's prefix index right
        before this request's prefill (docs/KV_TRANSFER.md).  Failure is a
        perf event, not a correctness one — the request continues with a
        plain prefill of the same tokens."""
        import functools

        payload, req.kv_import = req.kv_import, None
        imp = getattr(self.runner, "import_pages", None)
        if payload is None or imp is None:
            return
        try:
            self.state, n = await loop.run_in_executor(
                self._exec, functools.partial(imp, self.state, payload))
            if n:
                log.info("kv import: seeded %d fetched pages", n)
        except Exception as e:
            log.warning("kv import failed (%s); falling back to plain "
                        "prefill", e)

    async def _admit_one(self, req: GenRequest, slot: int,
                         ahead: "_InFlightChunk | None") -> None:
        """Monolithic admission; ``ahead`` is the flight queued on the
        device before this request's prefill."""
        req.admitted_at = time.monotonic()
        loop = asyncio.get_running_loop()
        mark = self._prefix_mark()
        req.dispatch_watch = self._watch_ready(loop, ahead)
        req.exec_start_at = time.monotonic()

        def prefill():
            # The sampling key's small programs run on the dispatch thread
            # with the call they serve: one phase, one trace event.
            return self.runner.prefill(
                req.prompt_ids, req.temperature, req.top_p,
                self._req_key(req, 0), state=self.state, top_k=req.top_k,
                repeat_penalty=req.repeat_penalty)

        first, ks, vs, plen = await self._call(
            loop, "prefill", prefill,
            note={"prompt_tokens": len(req.prompt_ids)})
        self._count_admission(req, mark)
        await self._place(req, slot, ks, vs, plen, first)

    async def _place(self, req: GenRequest, slot: int, ks, vs, plen: int,
                     first) -> None:
        """Insert a prefilled request into its slot (shared by monolithic
        and chunked admission).  ``first`` is what the runner's prefill
        handed back.  A Python int (a chunked finish, the multi-host
        wrapper) is emitted here, behind the insert.  Anything else is the
        sampled scalar still on the device: the insert takes it unread, the
        slot is placed so the next flight carries the row, and
        ``_emit_firsts`` reads and emits the token once that flight is
        queued — the host waits for nothing in between, so the device
        never drains across an admission.  Runs the insert on the dispatch
        executor: under multi-host serving (parallel/replicated.py) every
        runner call is also a cross-host broadcast, which must never block
        the event loop."""
        loop = asyncio.get_running_loop()
        on_host = isinstance(first, (int, np.integer))
        ENGINE_TELEMETRY.admission_inc("host" if on_host else "device")

        def insert():
            return self.runner.insert(
                self.state, slot, ks, vs, plen, first, req.temperature,
                req.top_p, prompt_tokens=req.prompt_ids,
                slot_key=self._req_key(req, 1), top_k=req.top_k,
                repeat_penalty=req.repeat_penalty)

        self.state = await self._call(loop, "insert", insert)
        info = _SlotInfo(req=req, prompt_len=plen)
        if not on_host:
            info.first_dev = first
            self.slots[slot] = info
            return
        await self._stamp_first_token(req)
        self.slots[slot] = info
        self._emit_first(req, int(first), info)
        await self._flush_releases(loop)

    def _firsts(self) -> "list[tuple[int, _SlotInfo]]":
        """The slots whose first token is still on the device."""
        return [(i, s) for i, s in enumerate(self.slots)
                if isinstance(s, _SlotInfo) and s.first_dev is not None]

    async def _emit_firsts(self, loop,
                           firsts: "list[tuple[int, _SlotInfo]]") -> None:
        """Read and emit the first tokens ``_place`` left on the device.

        ``firsts`` are the slots placed before this turn's flight was
        dispatched (``_firsts()`` right behind the dispatch), and the call
        comes once this turn's own admissions are queued too: the flight
        carries their rows and the device has work for as long as the host
        waits.  The read runs on a pool thread (the loop stays free, the
        dispatch stream untouched) and waits for the prefill alone — the
        device runs its queue in order, so whatever was ahead of the
        prefill is done when it is.  No flight with such a row is retired
        before this point, so a stream's first token always precedes its
        flight tokens.  A slot that was swept meanwhile (cancelled,
        migrated, failed) has no owner left: its token is dropped, and the
        flight's row is discarded by the snapshot check like any overshoot
        — as is the row of a stream whose first token ends it (``_emit``
        releases the slot here, between dispatches)."""

        def read(token) -> int:
            with jax.profiler.TraceAnnotation(SCHED_READBACK, first_token=1):
                return int(token)

        for slot, info in firsts:
            if self.slots[slot] is not info:
                continue
            first = await loop.run_in_executor(None, read, info.first_dev)
            info.first_dev = None
            await self._stamp_first_token(info.req)
            if self.slots[slot] is info and not info.req.finished:
                self._emit_first(info.req, first, info)
        await self._flush_releases(loop)

    async def _flush_releases(self, loop) -> None:
        """Perform device releases queued by _emit (which runs in sync
        emit loops) on the dispatch executor."""
        while self._to_release:
            slot = self._to_release.pop(0)
            self.state = await self._call(
                loop, "release", self.runner.release, self.state, slot)

    def _emit(self, req: GenRequest, token: int, info: _SlotInfo) -> None:
        info.generated += 1
        self.tokens_generated += 1
        req.out.put_nowait((token, ""))
        # Retire on EOS, request budget, or context exhaustion (the KV slot is
        # full; decoding further would clamp-and-overwrite the last position).
        out_of_context = info.prompt_len + info.generated >= self.runner.max_seq - 1
        if token == req.eos_id or info.generated >= req.max_tokens or out_of_context:
            reason = "stop" if token == req.eos_id else "length"
            req.finish(reason)
            slot = self.slots.index(info)
            self.slots[slot] = None
            if getattr(self.runner, "defer_release", False):
                # Multi-host (parallel/replicated.py): a release is a
                # cross-host broadcast and must not run inside this sync
                # emit loop on the event loop — defer to _flush_releases.
                self._to_release.append(slot)
            else:
                # Single-host: release immediately, exactly the pre-
                # multi-host semantics (pages/slots reclaimed before the
                # client's done is even consumed).
                self.state = self.runner.release(self.state, slot)
            self.requests_served += 1

    def _chunk_size(self) -> int:
        """Steps per dispatch, chosen from slot occupancy: ``decode_chunk``
        while every slot is taken, 1 while a slot is free — two sizes, so
        two decode programs (warmup covers both).  The next dispatch is
        queued before this one is read back, so an arrival waits for the
        flight in the air AND the one behind it: a long flight is free only
        when nothing could be admitted during it.  Whether a request is
        waiting yet is not asked (it may arrive mid-flight); at saturation
        there is nothing to admit into and amortization wins, however long
        the queue.  EOS / budget overshoot within a chunk is discarded by
        _loop's snapshot.  Adaptive-spec PROBES also dispatch size 1: the
        probe exists to sample acceptance, and a full chunk of speculative
        steps against a draft that just proved useless would burn a
        chunk's worth of slowdown per sample."""
        if self._spec_probing or self._free_slot() is not None:
            return 1
        return self.decode_chunk

    def _spec_retune(self, accepted: int, offered: int) -> None:
        """Fold one retired chunk's acceptance into the window; retune
        draft_len when the window holds enough evidence (≥ 2k offered
        draft tokens — about one decode chunk at steady state).  Shrink is
        geometric (a useless draft reaches the k=0 pause in O(log k)
        chunks), growth is linear (one step toward spec_draft_max per
        fully-accepting window)."""
        self._accept_acc += accepted
        self._accept_off += offered
        k = getattr(self.runner, "draft_len", 0)
        if self._accept_off < 2 * max(1, k):
            return
        rate = self._accept_acc / max(1, self._accept_off)
        new_k = k
        if rate <= self.spec_shrink_rate:
            new_k = k // 2
        elif rate >= self.spec_grow_rate and k < self.spec_draft_max:
            new_k = k + 1
        self._accept_acc = self._accept_off = 0
        self._spec_probing = False
        if new_k != k:
            self.runner.set_draft_len(new_k)
            self.spec_retunes += 1
            if new_k == 0:
                self._plain_since_probe = 0
            log.info("spec retune: draft_len %d -> %d (window rate %.2f)",
                     k, new_k, rate)

    # ------------------------------------ gateway-drafted pipeline pacing

    def _paced_slots(self, rjob) -> list:
        """Live slots pacing their decode on remote-draft credits, after
        the release rules: a closed-and-drained feed, a mixed batch
        (unpaced live slots share the fixed-shape dispatch), or an active
        ragged prefill flips its stream to free_run.  Pacing is exact
        only when every live slot is paced — the remote-draft serving
        regime; anything else degrades to best-effort full speed."""
        paced = []
        live = 0
        for i, info in enumerate(self.slots):
            if not isinstance(info, _SlotInfo):
                continue
            live += 1
            feed = getattr(info.req, "feed", None)
            if feed is None or feed.free_run:
                continue
            if feed.closed and not feed.chunks:
                feed.free_run = True  # gateway hung up: finish at speed
                continue
            paced.append((i, info))
        if paced and (rjob is not None or len(paced) != live):
            for _i, info in paced:
                info.req.feed.free_run = True
                self.spec_pipeline_freeruns += 1
            return []
        return paced

    async def _dispatch_paced(self, loop, paced):
        """One pipeline round over paced slots: consume one credit per
        feed (flushing stale draft chunks with an immediate nack), then
        dispatch ONE verify round — the hosted program over the gateway's
        drafts when any credit carried tokens, the worker's own spec/plain
        step for pure-ack credits.  Creditless feeds park the loop on the
        wake event until credit arrives or the stall budget releases the
        stream to free_run.  Returns the in-flight chunk, or None when no
        dispatch happened this iteration."""
        if self._inflight is not None:
            # The previous round has not retired, so per-slot generated
            # counts are pre-retire — validating a pipelined credit here
            # (positioned assuming that round fully accepts) would flush
            # it as stale.  Skip; the loop retires the flight right after
            # this and the next iteration consumes credits against
            # current counts.  Paced rounds thus give up the dispatch/
            # readback overlap: the credit pipeline hides swarm RTT,
            # which dwarfs the readback latency the overlap hides.
            return None

        now = time.monotonic()
        ready = True
        park = self.spec_pipeline_stall_s
        for _i, info in paced:
            feed = info.req.feed
            if feed.chunks:
                feed.stalled_at = 0.0
                continue
            if not feed.stalled_at:
                feed.stalled_at = now
            waited = now - feed.stalled_at
            if waited >= self.spec_pipeline_stall_s:
                feed.free_run = True
                self.spec_pipeline_freeruns += 1
                log.warning("spec pipeline stall: releasing paced stream "
                            "to full speed after %.1fs without credit",
                            waited)
            else:
                ready = False
                park = min(park, self.spec_pipeline_stall_s - waited)
        if any(info.req.feed.free_run for _i, info in paced):
            return None  # released: the next iteration dispatches normally
        if not ready:
            # Park only when nothing else needs the loop (an undrained
            # flight, pending admissions, cancels and exclusive fns all
            # take priority and re-enter here next iteration).
            if (self._inflight is None and self.pending.empty()
                    and not self._deferred and not self._exclusive
                    and self._migrating is None and self._chunking is None):
                self._wake.clear()
                with jax.profiler.TraceAnnotation(SCHED_WAIT_FOR_WORK,
                                                  paced=1):
                    try:
                        await asyncio.wait_for(self._wake.wait(),
                                               timeout=max(0.01, park))
                    except asyncio.TimeoutError:
                        pass
            return None
        kmax = int(getattr(self.runner, "draft_len", 0))
        meta: list[tuple[int, int]] = []
        token_chunks: dict[int, list[int]] = {}
        for i, info in paced:
            feed = info.req.feed
            credit = None
            while feed.chunks:
                cid, pos, toks = feed.chunks.popleft()
                if toks and (kmax <= 0 or pos != info.generated):
                    # Stale (drafted from a superseded prefix — an earlier
                    # partial acceptance corrected past its base) or the
                    # runner paused drafting since the advertise: nack
                    # immediately so the gateway's window keeps moving
                    # without a wasted verify forward.
                    self.spec_stale_chunks += 1
                    self.spec_verifies += 1
                    info.req.out.put_nowait((_VERIFY, {
                        "chunk_id": cid, "position": info.generated,
                        "accepted": 0, "tokens": []}))
                    continue
                credit = (cid, pos, toks)
                break
            if credit is None:
                continue  # the stale flush ate every queued credit
            cid, _pos, toks = credit
            meta.append((i, cid))
            if toks:
                token_chunks[i] = toks
        if not meta:
            return None
        if token_chunks:
            kk = min(max(len(t) for t in token_chunks.values()), kmax)
            drafts = np.full((len(self.slots), kk), -1, np.int32)
            for i, toks in token_chunks.items():
                t = toks[:kk]
                drafts[i, :len(t)] = t
            tokens_dev, self.state = await self._call(
                loop, "decode", self.runner.decode_steps_hosted, self.state,
                drafts, note={"dispatch": "spec", "steps": 1})
        else:
            # Pure ack credits (worker-draft pacing): one round of the
            # worker's OWN program — a packed spec verify step while
            # drafting is on, a plain step while paused.
            tokens_dev, self.state = await self._call(
                loop, "decode", self.runner.decode_steps_device, self.state,
                1, note={"dispatch": "plain", "steps": 1})
        self.host_dispatches += 1
        return _InFlightChunk(
            tokens_dev=tokens_dev, snapshot=list(self.slots),
            dispatched_at=time.monotonic(), verify_meta=meta)

    async def _loop(self) -> None:
        while True:
            try:
                await self._loop_once()
            except asyncio.CancelledError:
                raise
            except Exception:
                # A failed dispatch must not silently kill serving: fail every
                # in-flight request, reset device state, keep the loop alive.
                log.exception("decode loop error; failing in-flight requests")
                self._inflight = None  # its slots are failed below anyway
                if self._chunking is not None:
                    # Mid-chunked-admission request is in neither pending
                    # nor slots — fail it here (unless its own chunk step
                    # already did, which clears _chunking before raising).
                    creq, _, _ = self._chunking
                    self._chunking = None
                    self._admitting -= 1
                    creq.finish("error: engine failure")
                for i, info in enumerate(self.slots):
                    if isinstance(info, _SlotInfo):
                        info.req.finish("error: engine failure")
                    self.slots[i] = None
                while self._deferred:
                    self._deferred.popleft().finish("error: engine failure")
                while not self.pending.empty():
                    self.pending.get_nowait().finish("error: engine failure")
                if self._migrating is not None:
                    # A pending migrate() must not hang on engine failure;
                    # everything above was failed, nothing left to move.
                    fut, self._migrating = self._migrating, None
                    if not fut.cancelled():
                        fut.set_result(0)
                self._to_release.clear()  # init_state replaces it all
                self.state = await asyncio.get_running_loop(
                ).run_in_executor(self._exec, self.runner.init_state)

    async def _loop_once(self) -> None:
        # Idle: wait for work (an undrained in-flight chunk or an
        # in-progress chunked admission is work).
        if (all(s is None for s in self.slots) and self.pending.empty()
                and self._inflight is None and self._chunking is None
                and not self._deferred and not self._exclusive
                and self._migrating is None):
            self._wake.clear()
            with jax.profiler.TraceAnnotation(SCHED_WAIT_FOR_WORK):
                await self._wake.wait()

        # Free cancelled slots — only the loop touches device state, so a
        # release can never donate buffers out from under a dispatch, and
        # the slot stays occupied (unreusable) until exactly here.
        loop_ = asyncio.get_running_loop()
        for i, info in enumerate(self.slots):
            if isinstance(info, _SlotInfo) and info.req.cancelled:
                self.slots[i] = None
                self.state = await self._call(
                    loop_, "release", self.runner.release, self.state, i)
                self.requests_served += 1

        # Live migration (migrate()): retire everything with "migrate" at
        # this safe point.  Slots clear BEFORE the in-flight chunk is read
        # back, so _retire_inflight's identity check drops its undelivered
        # tokens — the successor replays decode from the prompt anyway.
        # Release goes through the executor like every device call; freed
        # pages land in the runner's prefix cache for KV export.
        if self._migrating is not None:
            fut, self._migrating = self._migrating, None
            moved = 0
            if self._chunking is not None:
                req, slot, job = self._chunking
                self._chunking = None
                self._admitting -= 1
                self.slots[slot] = None  # release the _RESERVED slot
                abort = self._abort_fn(job)
                if abort is not None:
                    await loop_.run_in_executor(self._exec, abort, job)
                if req.finish("migrate"):
                    moved += 1
            for i, info in enumerate(self.slots):
                if isinstance(info, _SlotInfo):
                    self.slots[i] = None
                    self.state = await loop_.run_in_executor(
                        self._exec, self.runner.release, self.state, i)
                    self.requests_served += 1
                    # Claim-or-skip: a stream whose final chunk retired
                    # between migrate() and this safe point already holds
                    # its "stop" terminal — it was SERVED, not moved.
                    if info.req.finish("migrate"):
                        moved += 1
            while self._deferred:
                if self._deferred.popleft().finish("migrate"):
                    moved += 1
            while not self.pending.empty():
                if self.pending.get_nowait().finish("migrate"):
                    moved += 1
            if not fut.cancelled():
                fut.set_result(moved)

        # Exclusive runner access (run_exclusive): no dispatch is queued on
        # the executor right now, so fn reads a live, undonated state.  A
        # failing fn fails only its caller, never the loop.
        while self._exclusive:
            fn, fut = self._exclusive.pop(0)
            try:
                res = await loop_.run_in_executor(self._exec, fn, self.state)
            except BaseException as e:
                if not fut.cancelled():
                    fut.set_exception(e)
                if not isinstance(e, Exception):
                    raise
            else:
                if not fut.cancelled():
                    fut.set_result(res)

        # Admit pending requests into free slots — but at most one prefill
        # per iteration once any slot is decoding, so a burst of long prompts
        # interleaves with decode chunks instead of freezing token streaming
        # for every active request until the whole queue is prefilled.
        loop = asyncio.get_running_loop()

        # Dispatch the NEXT chunk before reading back the previous one: the
        # dispatch is async (device-side queue), so the previous chunk's
        # readback + emit below overlap this chunk's compute.  Dispatching
        # BEFORE admission also lets this chunk execute while a long
        # prefill runs — the dominant decode stall under prompt bursts.
        dispatched: _InFlightChunk | None = None
        # Unified ragged batch (docs/RAGGED_BATCH.md): a parked
        # RaggedPrefillJob advances INSIDE this decode dispatch — each
        # step decodes every active slot AND prefills one fixed-token
        # chunk of the long prompt over the same paged pool, so a long
        # prompt never stalls token streaming.  Cancellation is handled
        # before dispatch so an abandoned job never costs another chunk.
        rjob = (self._chunking
                if (self._chunking is not None
                    and getattr(self._chunking[2], "ragged", False))
                else None)
        late = None  # a first token this turn's ragged finish left on device
        if rjob is not None and rjob[0].cancelled:
            req, slot, job = rjob
            self._chunking = None
            rjob = None
            self._admitting -= 1
            self.slots[slot] = None  # release the reservation
            abort = self._abort_fn(job)
            if abort is not None:
                await loop.run_in_executor(self._exec, abort, job)
        if (rjob is not None
                or any(isinstance(s, _SlotInfo) for s in self.slots)):
            # Gateway-drafted pacing (ISSUE 20, docs/SPECULATIVE.md):
            # when EVERY live slot rides a remote-draft stream, decode
            # advances one verify round per wire credit instead of free-
            # running — the gateway's outstanding-chunk window becomes
            # the dispatch clock.  Mixed batches and ragged prefills
            # release paced streams to full speed (pacing is perf-only;
            # the token stream is byte-identical either way).
            paced = self._paced_slots(rjob)
            k = 1 if paced else self._chunk_size()
            # Paged-KV runners grow page tables before the chunk; slots an
            # overcommitted pool cannot grow finish with "length" (their
            # pages free on release) instead of failing the whole engine.
            # One slot is released at a time and the check re-run: the freed
            # pages often let the remaining starved slots continue.
            check = getattr(self.runner, "pre_decode_check", None)
            if check is not None:
                # Executor, not the loop: under multi-host serving the
                # check broadcasts a frame (page growth must replay on
                # followers in stream order) and must not block the loop.
                starved = await self._call(loop, "pre_decode_check",
                                           check, k)
                if starved and self._inflight is not None:
                    # Drain the in-flight chunk first: force-finishing a
                    # starved slot now would drop its already-generated
                    # tokens, and retirement can itself free pages (EOS).
                    await self._retire_inflight(loop)
                    starved = await self._call(
                        loop, "pre_decode_check", check, k)
                while starved:
                    slot = starved[0]
                    info = self.slots[slot]
                    if isinstance(info, _SlotInfo):
                        log.warning(
                            "kv pool exhausted: finishing slot %d early", slot)
                        info.req.finish("length")
                        self.slots[slot] = None
                        self.requests_served += 1
                    self.state = await self._call(
                        loop, "release", self.runner.release, self.state,
                        slot)
                    starved = await self._call(
                        loop, "pre_decode_check", check, k)
            live = sum(1 for s in self.slots if isinstance(s, _SlotInfo))
            if rjob is not None:
                req, slot, job = rjob
                c = getattr(self.runner, "ragged_chunk", 1)
                chunk_toks = min(k * c,
                                 len(job.prompt_ids) - job.done_tokens)
                n_chunks = -(-chunk_toks // max(1, c))
                try:
                    await faults.inject("scheduler.ragged_chunk",
                                        done=job.done_tokens,
                                        total=len(job.prompt_ids))
                except faults.DrainRequested:
                    # Chaos trigger for MID-CHUNKED-PREFILL migration: start
                    # the drain concurrently and keep chunking — migrate()
                    # aborts the job at the next safe point, the completed
                    # pages stay prefix-cached for the successor's KV fetch.
                    if self.drain_requested_cb is not None:
                        self.drain_requested_cb()
                    else:
                        loop.create_task(self.migrate())
                if not req.exec_start_at:
                    # The job's first flight: it starts on the device when
                    # the flight still in flight ends (dispatch_wait).
                    req.dispatch_watch = self._watch_ready(loop,
                                                           self._inflight)
                try:
                    tokens_dev, self.state = await self._call(
                        loop, "ragged", self.runner.ragged_step,
                        self.state, job, k,
                        note={"dispatch": "ragged", "steps": k})
                except ValueError as e:
                    # Pool cannot cover the job's next chunk pages
                    # (PagesExhausted is a ValueError): fail THIS request,
                    # engine stays up — mirrors the legacy chunked path.
                    self._chunking = None
                    self._admitting -= 1
                    self.slots[slot] = None
                    abort = self._abort_fn(job)
                    if abort is not None:
                        await loop.run_in_executor(self._exec, abort, job)
                    log.warning("ragged admit failed: %s", e)
                    req.finish(f"error: {e}")
                else:
                    # On BaseException _chunking stays set: _loop's
                    # recovery fails the request and resets state.
                    self.ragged_chunks += n_chunks
                    self.host_dispatches += 1
                    dispatched = _InFlightChunk(
                        tokens_dev=tokens_dev, snapshot=list(self.slots),
                        dispatched_at=time.monotonic(),
                        ragged_steps=n_chunks,
                        counters_dev=self._flight_counters())
                    if not req.exec_start_at:
                        req.exec_start_at = dispatched.dispatched_at
                    if job.finished:
                        # Whole prompt is in the pool: sample the first
                        # token and activate the slot (the ragged
                        # counterpart of prefill_finish + _place; no KV
                        # insert — the pages are already there).
                        self._chunking = None
                        self._admitting -= 1

                        def finish():
                            return self.runner.ragged_finish(
                                self.state, job, req.temperature,
                                req.top_p, self._req_key(req, 0),
                                slot_key=self._req_key(req, 1),
                                top_k=req.top_k,
                                repeat_penalty=req.repeat_penalty)

                        # As ``_place``: a Python int (the multi-host
                        # wrapper) is emitted here; anything else is the
                        # sampled scalar still on the device, which the
                        # activation took unread — the slot's rows ride
                        # the NEXT flight, and ``_emit_firsts`` reads the
                        # token once that one is queued (``late``: not
                        # behind this turn's flight, which is the job's
                        # own), so the device does not drain here.
                        try:
                            first, self.state = await self._call(
                                loop, "ragged_finish", finish)
                        except BaseException:
                            self.slots[slot] = None
                            req.finish("error: engine failure")
                            raise
                        info = _SlotInfo(req=req,
                                         prompt_len=len(req.prompt_ids))
                        on_host = isinstance(first, (int, np.integer))
                        ENGINE_TELEMETRY.admission_inc(
                            "host" if on_host else "device")
                        self.slots[slot] = info
                        if on_host:
                            await self._stamp_first_token(req)
                            self._emit_first(req, int(first), info)
                            await self._flush_releases(loop)
                        else:
                            info.first_dev = late = first
            elif paced:
                # Credits are positioned against emitted counts, and a
                # paced round gives up the overlap anyway: first tokens
                # go out before the round, not behind it.
                await self._emit_firsts(loop, self._firsts())
                dispatched = await self._dispatch_paced(loop, paced)
            elif live:
                tokens_dev, self.state = await self._call(
                    loop, "decode", self.runner.decode_steps_device,
                    self.state, k,  # [K,B] on device
                    note={"dispatch": "plain", "steps": k})
                self.host_dispatches += 1
                dispatched = _InFlightChunk(
                    tokens_dev=tokens_dev, snapshot=list(self.slots),
                    dispatched_at=time.monotonic(),
                    counters_dev=self._flight_counters())

        # Every slot placed so far rides the flight just queued: the host
        # may wait for their first tokens — once this turn's own admissions
        # are queued behind it too, so that the wait keeps nothing back.
        firsts = [f for f in self._firsts() if f[1].first_dev is not late]

        # Advance an in-progress LEGACY chunked admission by ONE prefill
        # chunk (ragged jobs already advanced inside the dispatch above).
        if (self._chunking is not None
                and not getattr(self._chunking[2], "ragged", False)):
            req, slot, job = self._chunking
            try:
                if req.cancelled:
                    self._chunking = None
                    self.slots[slot] = None  # release the reservation
                    # Multi-host: followers hold the abandoned job's KV
                    # accumulators until told to drop them (ADVICE r4).
                    abort = getattr(self.runner, "prefill_abort", None)
                    if abort is not None:
                        await loop.run_in_executor(self._exec, abort, job)
                elif await self._call(loop, "prefill_step",
                                      self.runner.prefill_step, job):
                    self._chunking = None

                    def finish():
                        return self.runner.prefill_finish(
                            job, req.temperature, req.top_p,
                            self._req_key(req, 0), top_k=req.top_k,
                            repeat_penalty=req.repeat_penalty)

                    first, ks, vs, plen = await self._call(
                        loop, "prefill_finish", finish)
                    await self._place(req, slot, ks, vs, plen, first)
            except ValueError as e:
                # Bad request / pool exhaustion at insert (PagesExhausted
                # is a ValueError): fail THIS request, engine stays up —
                # mirrors the monolithic admission path below.
                self._chunking = None
                self.slots[slot] = None
                log.warning("chunked admit failed: %s", e)
                req.finish(f"error: {e}")
            except BaseException:
                self._chunking = None
                self.slots[slot] = None
                req.finish("error: engine failure")
                raise
            finally:
                if self._chunking is None:
                    self._admitting -= 1

        while True:
            slot = self._free_slot()
            if slot is None:
                break
            if self._deferred and self._chunking is None:
                # Deferred long prompts only become admittable once the
                # running chunked admission finishes; while it runs, fall
                # through to pending so short requests keep admitting
                # (no head-of-line blocking, no deque rotation).
                req = self._deferred.popleft()
            elif not self.pending.empty():
                req = self.pending.get_nowait()
            else:
                break
            if req.cancelled:
                continue
            if req.kv_import is not None:
                # Before the monolithic-vs-chunked decision: imported pages
                # flip prefill_prefers_monolithic toward the suffix-only
                # path, exactly like a local cache hit would.
                await self._apply_kv_import(req, loop)
            chunk = getattr(self.runner, "prefill_chunk", 0)
            if self._ragged:
                # Unified ragged admission gates on what ONE dispatch may
                # carry: under the default budget ragged_chunk equals
                # prefill_chunk, but a tight step_token_budget shrinks it,
                # and prompts above it chunk instead of stalling decode
                # behind a monolithic prefill.
                chunk = getattr(self.runner, "ragged_chunk", chunk)
            # Paged runners keep the suffix-only (prefix-cache) path for
            # prompts the cache mostly covers — chunked admission would
            # re-prefill what cached pages already hold.
            hint = getattr(self.runner, "prefill_prefers_monolithic", None)
            with jax.profiler.TraceAnnotation(SCHED_ADMIT):
                incremental = bool(
                    chunk and len(req.prompt_ids) > chunk
                    and not (hint is not None
                             and hint(req.prompt_ids, chunk=chunk)))
            # The flight queued on the device ahead of this admission.
            ahead = dispatched or self._inflight
            if incremental:
                if self._chunking is not None:
                    # One chunked admission at a time; park it and keep
                    # admitting short requests from pending.
                    self._deferred.append(req)
                    continue
                # Long prompt: admit incrementally, one chunk per loop
                # iteration (decode keeps streaming in between).  The slot
                # is RESERVED so short requests can still fill the others.
                try:
                    # Executor, not the loop: prefix-cache seeding gathers
                    # cached pages on device (compile on first use) — the
                    # loop must keep streaming while that happens.  The
                    # loop parks on this await, so allocator/index state
                    # stays single-flight.
                    req.admitted_at = time.monotonic()
                    mark = self._prefix_mark()
                    note = {"prompt_tokens": len(req.prompt_ids)}
                    if self._ragged:
                        # Unified ragged admission: the job prefills inside
                        # subsequent decode dispatches (KV straight into
                        # the slot's pool pages, no accumulators); its
                        # first flight stamps exec_start_at.
                        job = await self._call(
                            loop, "ragged_begin", self.runner.ragged_begin,
                            req.prompt_ids, slot, state=self.state,
                            note=note)
                    else:
                        req.dispatch_watch = self._watch_ready(loop, ahead)
                        req.exec_start_at = time.monotonic()
                        job = await self._call(
                            loop, "prefill_begin", self.runner.prefill_begin,
                            req.prompt_ids, state=self.state, note=note)
                    self._count_admission(req, mark)
                except ValueError as e:
                    log.warning("admit failed: %s", e)
                    req.finish(f"error: {e}")
                    continue
                except BaseException:
                    # Engine failure in prefill_begin (e.g. the prefix-seed
                    # gather): the popped request is in neither slots nor
                    # pending — fail it before the loop's recovery resets
                    # state, or its client waits forever.
                    req.finish("error: engine failure")
                    raise
                self._admitting += 1
                self._chunking = (req, slot, job)
                self.slots[slot] = _RESERVED
                continue
            self._admitting += 1
            try:
                await self._admit_one(req, slot, ahead)
            except ValueError as e:  # bad request (too long, etc.)
                log.warning("admit failed: %s", e)
                req.finish(f"error: {e}")
                continue
            except BaseException:
                # Engine failure mid-admission: the popped request is in
                # neither slots nor pending, so _loop's recovery would miss
                # it — fail it here, then let the recovery reset state.
                req.finish("error: engine failure")
                raise  # the dispatched chunk is dropped; recovery resets state
            finally:
                self._admitting -= 1
            if sum(1 for s in self.slots if isinstance(s, _SlotInfo)) > 1:
                break

        await self._emit_firsts(loop, firsts)
        # Retire the PREVIOUS chunk (readback overlaps the new dispatch and
        # any prefill above).
        await self._retire_inflight(loop)
        self._inflight = dispatched
        # Yield so submitters/streamers run between chunks.
        with jax.profiler.TraceAnnotation(SCHED_YIELD):
            await asyncio.sleep(0)

    async def _retire_inflight(self, loop) -> None:
        """Read back and emit the in-flight chunk, if any."""
        if self._inflight is None:
            return
        fl, self._inflight = self._inflight, None
        # ONE host transfer per flight: tokens and counters come back
        # together — device_get over the pair is the whole readback,
        # there is no per-step host sync anywhere in the loop.
        cls = self._flight_class(fl)

        def readback():
            with jax.profiler.TraceAnnotation(SCHED_READBACK, dispatch=cls):
                tokens, counters = jax.device_get(
                    (fl.tokens_dev, fl.counters_dev))
                # [K,B] (or packed [K,2+J,B]) on the host
                return np.asarray(tokens), counters

        tokens, counters = await loop.run_in_executor(self._exec, readback)
        if counters is not None:
            ENGINE_TELEMETRY.moe_counts_inc(cls, counters)
        now = time.monotonic()
        with jax.profiler.TraceAnnotation(SCHED_EMIT, dispatch=cls):
            emitted, dt = self._account_and_emit(fl, cls, tokens, now)
        await self._flush_releases(loop)
        if emitted == 0:
            # Pure-overshoot chunk (dispatched before its slots' EOS was
            # discovered): not a throughput sample, don't drag the EMA down.
            return
        rate = emitted / dt
        self.throughput_ema = (
            rate if self.throughput_ema == 0.0
            else 0.9 * self.throughput_ema + 0.1 * rate
        )

    def _account_and_emit(self, fl: _InFlightChunk, cls: str,
                          tokens: np.ndarray, now: float
                          ) -> tuple[int, float]:
        """The host side of one retire, between readback and the release
        flush: duty-cycle and flight accounting, then every token of the
        flight to its request.  Returns (tokens emitted, wall seconds
        attributed to the flight)."""
        dt = max(now - max(self._last_retire_at, fl.dispatched_at), 1e-6)
        # Duty-cycle accounting (PR 13): the host gap is the stretch after
        # the previous flight retired with NOTHING queued on the device —
        # admission, emit, asyncio overhead.  When dispatch N happened
        # before retire N-1 finished (the pipelined steady state) the gap
        # is zero by construction; dt is the remaining wall time
        # attributed to waiting on this flight.  Host timestamps only —
        # the device_get above is the one sync this loop already pays.
        gap = (max(0.0, fl.dispatched_at - self._last_retire_at)
               if self._last_retire_at else 0.0)
        ENGINE_TELEMETRY.host_gap_seconds.labels(cls).observe(gap)
        duty = dt / max(dt + gap, 1e-9)
        prev = self._duty.get(cls)
        self._duty[cls] = duty if prev is None else 0.9 * prev + 0.1 * duty
        # Flight-duration EWMA per dispatch class: the self-watchdog's
        # baseline.  dt is the wall time attributed to waiting on THIS
        # flight, so a healthy class's EWMA tracks its real cadence and
        # wedge thresholds scale with the chunk size instead of being a
        # global constant.
        e = self._flight_ewma.get(cls)
        self._flight_ewma[cls] = dt if e is None else 0.9 * e + 0.1 * dt
        self._last_retire_at = now
        # Decode chunks run the full fixed batch shape: every slot that was
        # empty at dispatch computed throwaway rows for the whole chunk.
        live = sum(1 for s in fl.snapshot if isinstance(s, _SlotInfo))
        steps = tokens.shape[0]
        batch = tokens.shape[-1]
        ENGINE_TELEMETRY.flight_inc(
            cls, seconds=dt, steps=steps, useful=live * steps,
            waste=max(0, batch - live) * steps,
            short=steps < self.decode_chunk)
        emitted = 0
        chunk_acc = 0  # draft tokens accepted in this chunk (live slots)
        chunk_off = 0  # draft tokens offered in this chunk (live slots)
        # Paced flights answer each consumed DraftChunk credit with ONE
        # VerifyResult carrying the tokens this round actually emitted.
        verify_tok: dict[int, list[int]] = {}
        # k at DISPATCH time, recovered from the packed layout [K, 3+k, B]
        # — the live draft_len may already have been retuned since.
        k_dispatch = tokens.shape[1] - 3 if tokens.ndim == 3 else 0
        for step in range(tokens.shape[0]):
            for i, info in enumerate(fl.snapshot):
                # Identity check: emit only to slots still owned by the
                # request they were dispatched for — a slot retired
                # mid-chunk (EOS overshoot) or retired-and-readmitted
                # since dispatch is skipped.
                if not isinstance(info, _SlotInfo) or self.slots[i] is not info:
                    continue
                if tokens.ndim == 3:
                    # Speculative packed layout [K, 2+J, B] (engine/spec.py):
                    # row 0 = emit count, rows 1..J+1 = tokens for this
                    # step, row -1 = acceptance source.
                    step_emitted = 0
                    for jj in range(int(tokens[step, 0, i])):
                        if self.slots[i] is not info:  # retired mid-step
                            break
                        tok = int(tokens[step, 1 + jj, i])
                        self._emit(info.req, tok, info)
                        if fl.verify_meta is not None:
                            verify_tok.setdefault(i, []).append(tok)
                        emitted += 1
                        step_emitted += 1
                    # Split by source, counting only tokens actually
                    # emitted (consistent with spec_emitted) — the packed
                    # counts row includes post-retirement steps.
                    if step_emitted > 1:
                        if int(tokens[step, -1, i]) == 1:
                            self.spec_accept_echo += step_emitted - 1
                        else:
                            self.spec_accept_gen += step_emitted - 1
                    if step_emitted >= 1:
                        # Window sample: this live step offered k_dispatch
                        # draft tokens and accepted step_emitted-1 of them.
                        chunk_acc += step_emitted - 1
                        chunk_off += k_dispatch
                else:
                    tok = int(tokens[step, i])
                    self._emit(info.req, tok, info)
                    if fl.verify_meta is not None:
                        verify_tok.setdefault(i, []).append(tok)
                    emitted += 1
        if tokens.ndim == 3:
            # Acceptance telemetry: emitted / (verify steps × live slots)
            # ≈ tokens per dispatch the speculation is buying.  Updated
            # BEFORE the release flush's await point: a client observing
            # its _DONE (queued in the emit loop above) may read
            # describe() immediately.
            self.spec_steps += tokens.shape[0] * max(
                1, sum(1 for s in fl.snapshot if isinstance(s, _SlotInfo)))
            self.spec_emitted += emitted
            if self._spec_adaptive and chunk_off:
                self._spec_retune(chunk_acc, chunk_off)
        elif (self._spec_adaptive
              and getattr(self.runner, "draft_len", -1) == 0):
            # Speculation paused (plain 2-D chunks).  Workloads shift —
            # after spec_probe_interval plain steps, dispatch ONE k=1
            # verify step (chunk size 1 via _chunk_size) to re-sample
            # acceptance; _spec_retune then resumes or re-pauses.  Probe
            # overhead is a few small-model steps per interval: a paused
            # engine stays within a few % of a plain engine by design.
            self._plain_since_probe += tokens.shape[0]
            if (not self._spec_probing
                    and self._plain_since_probe >= self.spec_probe_interval):
                self._plain_since_probe = 0
                self._spec_probing = True
                self.spec_probes += 1
                self.runner.set_draft_len(1)
        self._tokens_per_dispatch = float(emitted)
        if fl.verify_meta:
            # One VerifyResult per consumed credit: position is the slot's
            # post-round generated count, accepted = emitted - 1 (the last
            # emit is always the model-chosen continuation, never a draft).
            # A slot retired mid-round still answers its credit (possibly
            # with done already queued) so the gateway's window drains.
            for slot_idx, chunk_id in fl.verify_meta:
                info = fl.snapshot[slot_idx]
                if not isinstance(info, _SlotInfo):
                    continue
                toks = verify_tok.get(slot_idx, [])
                self.spec_verifies += 1
                info.req.out.put_nowait((_VERIFY, {
                    "chunk_id": chunk_id, "position": info.generated,
                    "accepted": max(0, len(toks) - 1), "tokens": toks}))
        return emitted, dt


DONE = _DONE
VERIFY = _VERIFY
