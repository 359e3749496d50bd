"""Engine facade: the inference seam between the swarm and the model.

``Engine`` is the TPU-native replacement for the reference's
``UnifiedAPIHandler`` (/root/reference/pkg/crowdllama/api.go:19): everything
above it (worker stream handler, gateway, IPC) talks BaseMessage; everything
below is JAX.  ``JaxEngine`` serves real models with continuous batching and
token streaming; ``FakeEngine`` is the test double at the same seam the
reference mocks with an HTTP fake (test/integration_test.go:32-135).
"""

from __future__ import annotations

import asyncio
import logging
import os
import socket
import time
from dataclasses import dataclass
from typing import AsyncIterator

from crowdllama_tpu.config import Configuration
from crowdllama_tpu.core import pb
from crowdllama_tpu.core import wire
from crowdllama_tpu.core.messages import (
    create_embed_response,
    create_generate_response,
    extract_embed_request,
    extract_generate_request,
    flatten_chat,
    genresp_frame_bytes,
    migrate_frame_msg,
    verify_result_msg,
)
from crowdllama_tpu.obs.metrics import (
    DISPATCH_CLASSES, ENGINE_TELEMETRY, process_age_seconds,
)
from crowdllama_tpu.testing import faults

log = logging.getLogger("crowdllama.engine")


@dataclass
class Chunk:
    text: str
    done: bool = False
    done_reason: str = ""
    prompt_tokens: int = 0
    completion_tokens: int = 0
    # Tracing (crowdllama_tpu/obs): engines that know their real queue/
    # prefill split stamp it on the FINAL chunk (ns); zero means "unknown"
    # and the Engine seam falls back to first-chunk timing.
    queue_ns: int = 0
    prefill_ns: int = 0
    # With them, the stamps that make the spans a timeline: when the
    # request was submitted to the scheduler (absolute monotonic ns; the
    # queue and prefill intervals follow it back to back), and the split
    # of prefill into the wait for the dispatch in flight and the
    # request's own program(s).  Zero means "unknown".
    submitted_ns: int = 0
    dispatch_wait_ns: int = 0
    prefill_exec_ns: int = 0
    # KV shipping (docs/KV_TRANSFER.md): wall time the engine spent fetching
    # donor pages before prefill — becomes a kv_fetch span on the worker's
    # trace surface.  Zero = no fetch attempted.
    kv_fetch_ns: int = 0
    # True when a fetch was attempted but yielded no pages (donor error or
    # empty payload) and prefill ran plain — the flight recorder's
    # kv_ship_fallback trigger confirms against this span meta post-stitch.
    kv_fallback: bool = False
    # Remote-draft control plane (docs/SPECULATIVE.md): when set, this
    # chunk answers one consumed DraftChunk credit and handle_streaming
    # emits a VerifyResult frame for it (keys: chunk_id/position/accepted/
    # tokens, optionally prompt_ids on the chunk_id=0 handshake).  A pure
    # verify chunk carries no text and no done flag.
    verify: dict | None = None


class ProfileDisabled(RuntimeError):
    """No ``profile_dir`` is configured: the node cannot be traced."""


class ProfileBusy(RuntimeError):
    """A profiler trace is already running (or none is, on stop)."""


# The shortest trace profile_stop collects, and capture_profile's floor.  A
# decode flight is 40-100 ms, so a trace stopped as soon as it was started
# holds nothing for a reader; and collecting is no small thing to do for
# nothing: the session describes every PROGRAM that ran while it was on (the
# HLO protos, 11 MB for a decode program of ~4,000 ops, 31 MB with an
# admission's programs), which took 1.5-1.9 s mostly and 3.7-20 s inside an
# admission burst, whatever the trace held.  Dropping the session took
# 0.24-0.27 s, 14 of 14.  Half a second, because a stop asked for at once
# found the trace on for 0.00-0.01 s mostly and for 0.16, 0.19 and 0.35 s when
# the loop was late to it — under the same bursts (PERF.md §6, PR 37).
MIN_TRACE_S = 0.5


class StopMatcher:
    """Streaming stop-sequence scanner (Ollama options.stop semantics).

    ``feed(text)`` returns (emit_now, stopped): text that is safe to send —
    up to ``max(len(stop)) - 1`` chars are held back so a stop spanning two
    decoded chunks is still caught — and whether a stop fired (everything
    from the match onward is dropped).  ``flush()`` returns the held tail
    at end-of-stream.  ONE implementation, shared by every engine that
    streams text (a fix here cannot ship in one engine and miss another).
    """

    def __init__(self, stop: list[str] | None):
        self.stops = [s for s in (stop or []) if s]
        self._hold = max((len(s) for s in self.stops), default=1) - 1
        self._pending = ""

    def feed(self, text: str) -> tuple[str, bool]:
        if not self.stops:
            return text, False
        self._pending += text
        cut = min((i for i in (self._pending.find(s) for s in self.stops)
                   if i >= 0), default=-1)
        if cut >= 0:
            emit, self._pending = self._pending[:cut], ""
            return emit, True
        if len(self._pending) > self._hold:
            split = len(self._pending) - self._hold
            emit, self._pending = self._pending[:split], self._pending[split:]
            return emit, False
        return "", False

    def flush(self) -> str:
        out, self._pending = self._pending, ""
        return out


class Engine:
    """Abstract engine seam."""

    models: list[str] = []
    # NodeObs of the owning worker peer (set by Peer.start); None when the
    # engine runs without a peer (IPC-only, unit tests).
    obs = None
    # Engines that can act on a GenerateRequest.kv_donor hint (fetch cached
    # KV pages from a peer before prefill, docs/KV_TRANSFER.md) opt in; the
    # hint is dropped silently everywhere else so the wire field is always
    # safe to set.
    supports_kv_donor = False
    # Engines that can batch-verify gateway-drafted tokens (a runner with
    # the hosted spec verify program, docs/SPECULATIVE.md) opt in; on every
    # other engine GenerateRequest.remote_draft streams run unpaced and the
    # peer nacks DraftChunk credits so the gateway degrades to plain mode.
    supports_remote_draft = False
    # True for engines that run JAX programs in THIS process.  An
    # accelerator belongs to one process at a time, so only such a node may
    # ask the JAX runtime about devices (peer capabilities, device-memory
    # gauges): a gateway or DHT process that initialized a backend would
    # take the chip from the worker beside it.
    on_device = False

    async def start(self) -> None: ...
    async def stop(self) -> None: ...

    def obs_gauges(self) -> dict:
        """Engine/scheduler gauges for the /metrics exposition.

        Every engine exposes the same four keys so the series exist on
        every worker (FakeEngine included, at zero) — an absent series
        breaks absent()-style alerts across engine kinds.
        """
        g = {"pending_depth": 0.0, "active_slots": 0.0,
             "batch_occupancy": 0.0, "kv_cache_utilization": 0.0,
             "host_dispatches_total": 0.0, "tokens_per_dispatch": 0.0}
        # Duty-cycle gauges (PR 13): labeled children, one per dispatch
        # class, zero on engines without a scheduler for the same
        # absent()-alert reason.
        for cls in DISPATCH_CLASSES:
            g[f"duty_cycle|dispatch={cls}"] = 0.0
        return g

    def _verify_frame_fields(self) -> tuple[int, int]:
        """(draft_k, depth_hint) advertised on every VerifyResult frame —
        the worker's live draft length (gateway clamps its chunk size to
        it; 0 = drafting paused, send pure acks) and the pipeline depth
        the worker is willing to absorb."""
        return 0, 1

    async def drain(self, timeout: float = 30.0) -> bool:
        """Finish in-flight work before shutdown; True when drained."""
        return True

    async def migrate(self) -> int:
        """Hand off every in-flight request for live migration (graceful
        drain, docs/ROBUSTNESS.md): each active stream retires with a
        ``"migrate"`` terminal reason, which ``handle_streaming`` turns
        into a MigrateFrame so the gateway re-routes it.  Returns how many
        requests were moved; engines without a scheduler have nothing to
        move."""
        return 0

    def attach_peer(self, peer) -> None:
        """Called by Peer.start() so engines that talk to the swarm (e.g.
        ShardedEngine's group leader) can reach the host/DHT/peer manager."""

    def describe(self) -> dict:
        """Capability/telemetry snapshot for Resource advertisement."""
        return {"models": self.models, "throughput": 0.0, "load": 0.0}

    def model_dir(self, model: str) -> str | None:
        """Local checkpoint directory for ``model`` if this engine can
        SHARE it over the swarm (net/model_share.py); None otherwise."""
        return None

    async def export_kv_pages(self, model: str, chain_hashes: list[bytes],
                              page_size: int) -> dict | None:
        """Serve a peer's KvFetchRequest (docs/KV_TRANSFER.md): the KV
        pages of the longest locally indexed prefix of ``chain_hashes``,
        or None when this engine has nothing to offer (no paged prefix
        cache, unknown model, geometry mismatch)."""
        return None

    def generate(
        self,
        prompt: str,
        model: str = "",
        max_tokens: int = 128,
        temperature: float = 0.0,
        top_p: float = 1.0,
        seed: int = 0,
        stop: list[str] | None = None,
        top_k: int = 0,
        repeat_penalty: float = 1.0,
    ) -> AsyncIterator[Chunk]:
        raise NotImplementedError

    async def embed(self, texts: list[str], model: str = "",
                    truncate: bool = True) -> tuple[list[list[float]], int]:
        """Embed texts → (one vector per text, total prompt tokens).

        ``truncate=False`` must raise instead of silently clipping an input
        that exceeds the context window (Ollama semantics)."""
        raise NotImplementedError

    # ---- the UnifiedAPIHandler seam (api.go:19) --------------------------

    def _obs_generate(self, msg: pb.BaseMessage, model: str,
                      t0: int, first_ns: int, end_ns: int,
                      final: "Chunk | None") -> None:
        """Record worker-side spans + histograms for one generate exchange.

        The queue/prefill split comes from the engine's own stamps on the
        final chunk when available (JaxEngine: scheduler admission times);
        otherwise prefill defaults to the first-chunk latency — the same
        catalogue either way, so FakeEngine traces read like real ones.
        """
        if self.obs is None:
            return
        def stamp(name: str) -> int:
            return getattr(final, name, 0) if final else 0

        queue_ns, prefill_ns = stamp("queue_ns"), stamp("prefill_ns")
        kv_ns = stamp("kv_fetch_ns")
        if kv_ns:
            # The donor fetch ran before submit, so it is in neither the
            # queue nor the prefill stamp — give it its own span and keep
            # it out of the decode residual below.
            kv_meta = ({"fallback": True}
                       if getattr(final, "kv_fallback", False) else {})
            self.obs.trace.record(
                getattr(msg, "trace_id", ""), "kv_fetch", kv_ns,
                parent=getattr(msg, "parent_span", ""), **kv_meta)
        if not prefill_ns:
            prefill_ns = max(0, (first_ns or end_ns) - t0 - queue_ns - kv_ns)
        decode_ns = max(0, (end_ns - t0) - queue_ns - prefill_ns - kv_ns)
        steps = stamp("completion_tokens")
        if steps > 0 and decode_ns > 0:
            self.obs.metrics.decode_step_seconds.observe(
                decode_ns / steps / 1e9)
        self.obs.observe_generate(
            getattr(msg, "trace_id", ""), getattr(msg, "parent_span", ""),
            model, queue_ns, prefill_ns, decode_ns, steps, end_ns - t0,
            start_ns=stamp("submitted_ns") or t0 + kv_ns,
            dispatch_wait_ns=stamp("dispatch_wait_ns"),
            prefill_exec_ns=stamp("prefill_exec_ns"), node="worker")

    def _obs_begin(self, msg: pb.BaseMessage, model: str) -> int:
        """Open the worker's trace record as the request arrives, so its
        spans' ``start_us`` are offsets from the arrival; returns the
        arrival stamp (monotonic ns)."""
        if self.obs is not None:
            self.obs.trace.begin(getattr(msg, "trace_id", ""), model=model,
                                 node="worker")
        return time.monotonic_ns()

    async def handle(self, msg: pb.BaseMessage, worker_id: str = "") -> pb.BaseMessage:
        """Blocking BaseMessage → BaseMessage (reference semantics)."""
        if msg.WhichOneof("message") == "embed_request":
            ereq = extract_embed_request(msg)
            t0 = time.monotonic_ns()
            vectors, n_tokens = await self.embed(
                list(ereq.input), model=ereq.model, truncate=ereq.truncate)
            dt = time.monotonic_ns() - t0
            if self.obs is not None:
                self.obs.metrics.request_seconds.labels(
                    ereq.model).observe(dt / 1e9)
                tid = getattr(msg, "trace_id", "")
                if tid:
                    self.obs.trace.record(
                        tid, "embed", dt,
                        parent=getattr(msg, "parent_span", ""))
                    self.obs.trace.finish(tid, dt)
            return create_embed_response(
                model=ereq.model, embeddings=vectors, worker_id=worker_id,
                total_duration_ns=dt,
                prompt_tokens=n_tokens,
            )
        req = extract_generate_request(msg)
        await faults.inject("engine.request", worker=worker_id,
                            model=req.model)
        t0 = self._obs_begin(msg, req.model)
        first_ns = 0
        text_parts: list[str] = []
        final: Chunk | None = None
        async for chunk in self._gen_from_request(req, trace_id=msg.trace_id):
            if not first_ns:
                first_ns = time.monotonic_ns()
            text_parts.append(chunk.text)
            final = chunk
        assert final is not None
        end_ns = time.monotonic_ns()
        self._obs_generate(msg, req.model, t0, first_ns, end_ns, final)
        return create_generate_response(
            model=req.model,
            response="".join(text_parts),
            worker_id=worker_id,
            done=True,
            done_reason=final.done_reason or "stop",
            total_duration_ns=end_ns - t0,
            prompt_tokens=final.prompt_tokens,
            completion_tokens=final.completion_tokens,
        )

    async def handle_streaming(
        self, msg: pb.BaseMessage, worker_id: str = "",
        draft_feed=None,
    ) -> AsyncIterator[pb.BaseMessage]:
        """Streaming superset: one GenerateResponse frame per chunk, done
        marked on the last (SURVEY §7 hard part 5 — the reference carries a
        stream flag but never streams).

        Decode-wrapper over ``handle_streaming_frames`` — the wire hot
        path yields encoded frames directly; this keeps the pb-object
        surface for tests and non-wire consumers.
        """
        async for frame in self.handle_streaming_frames(
                msg, worker_id=worker_id, draft_feed=draft_feed):
            yield wire.decode_payload(frame[4:])

    async def handle_streaming_frames(
        self, msg: pb.BaseMessage, worker_id: str = "",
        draft_feed=None,
    ) -> AsyncIterator[bytes]:
        """Streaming hot path: yields complete encoded wire frames
        ([4B BE len][BaseMessage]) — one per chunk, trace_id embedded —
        built straight from engine scalars with zero intermediate pb
        objects when the native encoder is loaded."""
        req = extract_generate_request(msg)
        t0 = self._obs_begin(msg, req.model)
        first_ns = 0
        n_chunk = 0
        final: Chunk | None = None
        async for chunk in self._gen_from_request(req, trace_id=msg.trace_id,
                                                  draft_feed=draft_feed):
            if not first_ns:
                first_ns = time.monotonic_ns()
            if chunk.verify is not None:
                # Remote-draft control plane: answer a consumed DraftChunk
                # credit with a VerifyResult frame, interleaved with (and
                # invisible to) the client's GenerateResponse stream.
                v = chunk.verify
                await faults.inject("spec.verify", worker=worker_id,
                                    model=req.model,
                                    chunk_id=int(v.get("chunk_id", 0)))
                dk, dh = self._verify_frame_fields()
                vmsg = verify_result_msg(
                    chunk_id=int(v.get("chunk_id", 0)),
                    position=int(v.get("position", 0)),
                    accepted=int(v.get("accepted", 0)),
                    tokens=[int(t) for t in v.get("tokens", [])],
                    done=False,
                    draft_k=int(v.get("draft_k", dk)),
                    depth_hint=int(v.get("depth_hint", dh)),
                    prompt_ids=[int(t) for t in v.get("prompt_ids", [])],
                )
                if msg.trace_id:
                    vmsg.trace_id = msg.trace_id
                yield wire.encode_frame(vmsg)
                if not chunk.text and not chunk.done:
                    continue  # pure control chunk: no client frame
            try:
                await faults.inject("engine.stream_chunk", worker=worker_id,
                                    model=req.model, index=n_chunk)
            except faults.DrainRequested:
                # Chaos trigger for live migration (docs/ROBUSTNESS.md):
                # as if SIGTERM / POST /drain landed mid-stream.  Start the
                # drain concurrently and keep streaming — the scheduler
                # retires this request with "migrate" at its next safe
                # point, and the done branch below emits the MigrateFrame.
                peer = getattr(self, "_peer", None)
                if peer is not None and hasattr(peer, "drain"):
                    asyncio.get_running_loop().create_task(peer.drain())
                else:
                    asyncio.get_running_loop().create_task(self.migrate())
            n_chunk += 1
            if chunk.done and chunk.done_reason == "migrate":
                # Live migration: the terminal frame is a MigrateFrame, not
                # a GenerateResponse — generation state for the gateway to
                # re-route the stream with this worker as KV donor.  Any
                # held-back text (stop-matcher tail) is dropped: the
                # successor replays the whole generation and the gateway's
                # sent_text trim dedups what was already delivered.
                self._obs_generate(msg, req.model, t0, first_ns,
                                   time.monotonic_ns(), chunk)
                hashes, page_size = self._migrate_export_meta(req)
                mig = migrate_frame_msg(
                    model=req.model,
                    worker_id=worker_id,
                    delivered_tokens=chunk.completion_tokens,
                    prompt_tokens=chunk.prompt_tokens,
                    chain_hashes=hashes,
                    page_size=page_size,
                    reason="drain",
                )
                if msg.trace_id:
                    mig.trace_id = msg.trace_id
                yield wire.encode_frame(mig)
                return
            if chunk.done:
                final = chunk
                self._obs_generate(msg, req.model, t0, first_ns,
                                   time.monotonic_ns(), final)
            yield genresp_frame_bytes(
                model=req.model,
                response=chunk.text,
                worker_id=worker_id,
                done=chunk.done,
                done_reason=chunk.done_reason if chunk.done else "",
                total_duration_ns=(time.monotonic_ns() - t0) if chunk.done else 0,
                prompt_tokens=chunk.prompt_tokens if chunk.done else 0,
                completion_tokens=chunk.completion_tokens if chunk.done else 0,
                trace_id=msg.trace_id,
            )

    def _format_chat(self, messages: list[dict], model: str = "") -> str:
        """Chat → prompt string.  Engines with a templated tokenizer
        override this; the default is the generic role-tagged flattening
        (the reference concatenates contents, gateway.go:189-207)."""
        return flatten_chat(messages)

    def _prompt_of(self, req: pb.GenerateRequest) -> str:
        prompt = req.prompt
        if not prompt and req.messages:
            prompt = self._format_chat(
                [{"role": m.role, "content": m.content} for m in req.messages],
                model=req.model,
            )
        return prompt

    def _migrate_export_meta(self, req: pb.GenerateRequest
                             ) -> tuple[list[bytes], int]:
        """(chain hashes, page size) for a MigrateFrame — what this worker
        can serve the successor as a KV donor.  Informational: the
        successor recomputes the chain from the replayed prompt; engines
        without a paged prefix index advertise nothing."""
        return [], 0

    def _gen_from_request(self, req: pb.GenerateRequest,
                          trace_id: str = "",
                          draft_feed=None) -> AsyncIterator[Chunk]:
        prompt = self._prompt_of(req)
        kwargs = {}
        if (draft_feed is not None and getattr(req, "remote_draft", False)
                and self.supports_remote_draft):
            # Same opt-in shape as kv_donor below: only engines that can
            # pace on DraftChunk credits see the kwargs, so third-party
            # generate() signatures keep working and the stream silently
            # runs unpaced elsewhere (the peer nacks the credits).
            kwargs["remote_draft"] = True
            kwargs["draft_feed"] = draft_feed
        donor = getattr(req, "kv_donor", "")
        if donor and self.supports_kv_donor:
            # Only engines that opted in receive the kwargs — third-party
            # Engine subclasses with the pre-KV-ship generate() signature
            # keep working with the hint silently dropped.  The trace id
            # rides along so the donor's kv_export span lands in the SAME
            # cross-node trace as the fetcher's kv_fetch.
            kwargs["kv_donor"] = donor
            kwargs["kv_trace"] = trace_id
            if getattr(req, "migrate", False):
                # Migrated stream (docs/ROBUSTNESS.md): the fetch is the
                # point of the re-route — bypass the kv_ship opt-in and
                # break-even gates so the successor always tries the donor.
                kwargs["migrate"] = True
        return self.generate(
            prompt,
            model=req.model,
            max_tokens=req.max_tokens or 128,
            temperature=req.temperature,
            top_p=req.top_p or 1.0,
            seed=int(req.seed or 0),
            stop=list(req.stop),
            top_k=int(req.top_k or 0),
            repeat_penalty=float(req.repeat_penalty or 1.0),
            **kwargs,
        )


class JaxEngine(Engine):
    """The real engine: ModelRunner + continuous-batching Scheduler."""

    supports_kv_donor = True
    on_device = True

    def __init__(self, config: Configuration | None = None, **overrides):
        self.config = config or Configuration.from_environment()
        for k, v in overrides.items():
            setattr(self.config, k, v)
        self.models = [self.config.model]
        self.scheduler = None
        self.tokenizer = None
        self._runner = None
        self._peer = None  # set by attach_peer (KV fetch dials through it)
        self._kv_streams = None  # pooled donor streams (lazy StreamPool)
        # The profiler's single flight: the latch holds from the start of
        # profile_start to the end of profile_stop; _profile is the running
        # trace (profile_start's answer) while it can be stopped.
        self._profile_latch = False
        self._profile: dict | None = None
        self._profile_session = None
        self._profile_seq = 0

    def attach_peer(self, peer) -> None:
        self._peer = peer

    @property
    def supports_remote_draft(self) -> bool:
        """True once the runner carries the hosted spec verify program
        (SpecPagedModelRunner) — known only after start() builds it."""
        return bool(getattr(self._runner, "supports_remote_draft", False))

    def _verify_frame_fields(self) -> tuple[int, int]:
        r, s = self._runner, self.scheduler
        return (int(getattr(r, "draft_len", 0)),
                int(getattr(s, "spec_pipeline_depth", 1)))

    async def start(self) -> None:
        """Build tokenizer/params/runner (compiles on first use)."""
        from crowdllama_tpu.engine.scheduler import Scheduler
        from crowdllama_tpu.engine.tokenizer import get_tokenizer
        from crowdllama_tpu.engine.weights import (
            load_params_for,
            resolve_clamped_model_config,
        )

        cfg = resolve_clamped_model_config(self.config)
        self.tokenizer = get_tokenizer(self.config.model_path)
        loop = asyncio.get_running_loop()

        def _build():
            import jax

            from crowdllama_tpu.engine.factory import build_runner
            from crowdllama_tpu.engine.plan import resolve_serving_plan

            # The composition matrix's single decision point
            # (engine/plan.py; exhaustively swept by tests/test_matrix.py).
            plan = resolve_serving_plan(self.config, len(jax.devices()),
                                        n_processes=jax.process_count())
            for note in plan.notes:
                log.warning("%s", note)

            t_w = time.monotonic()
            # Waited for, so that the gauge is the weights' time and not
            # their dispatch's (the warm-up would wait for them anyway).
            params = jax.block_until_ready(
                load_params_for(self.config, cfg))
            ENGINE_TELEMETRY.startup_set("weights", time.monotonic() - t_w)
            # ONE builder shared with run_follower: leader and followers
            # must construct bit-identical runners (engine/factory.py).
            runner = build_runner(self.config, plan, cfg, params)
            if jax.process_count() > 1:
                # Multi-host pod-slice serving: wrap the runner so every
                # device-touching call is broadcast to the follower
                # processes before it dispatches (leader-replicated
                # dispatch, parallel/replicated.py); the frames cover
                # every runner surface the matrix serves, spec included.
                from crowdllama_tpu.parallel.replicated import (
                    ReplicatedRunner,
                )

                runner = ReplicatedRunner(runner)
            return runner

        self._runner = await loop.run_in_executor(None, _build)
        ENGINE_TELEMETRY.weight_layouts_set(
            getattr(self._runner, "weight_layouts", {}))
        ENGINE_TELEMETRY.moe_matmul_path_set(
            getattr(self._runner, "moe_matmul_path", ""))
        ENGINE_TELEMETRY.ssm_update_path_set(
            getattr(self._runner, "ssm_update_path", ""))
        ENGINE_TELEMETRY.kda_update_path_set(
            getattr(self._runner, "kda_update_path", ""))
        ENGINE_TELEMETRY.attn_decode_path_set(
            getattr(self._runner, "attn_decode_path", ""))
        t_w = time.monotonic()
        if self.config.warmup:
            await loop.run_in_executor(None, self._warmup)
        # set by every start: the gauges are the newest engine's, and an
        # engine that did not warm up did so in 0 s
        ENGINE_TELEMETRY.startup_set(
            "warmup", time.monotonic() - t_w if self.config.warmup else 0.0)
        self.scheduler = Scheduler(
            self._runner,
            decode_chunk=self.config.decode_chunk,
            admission_pending_max=self.config.admission_pending_max,
            spec_draft_max=self.config.spec_draft_max,
            ragged=self.config.ragged_prefill,
            wedge_multiplier=self.config.wedge_multiplier)
        self.scheduler.drain_requested_cb = self._chaos_drain
        self.scheduler.start()
        ENGINE_TELEMETRY.startup_set(
            "ready", time.monotonic() - ENGINE_TELEMETRY.t_import)
        # the same instant on the operating system's clock: what lies
        # before the first import (under the benchmark's launcher, JAX
        # reaching the chip) is ``process`` - ``ready``
        ENGINE_TELEMETRY.startup_set("process", process_age_seconds() or 0.0)
        log.info(
            "engine up: model=%s mesh=%s slots=%d max_seq=%d",
            cfg.name, dict(self._runner.mesh.shape), self._runner.max_slots,
            self._runner.max_seq,
        )

    def _warmup(self) -> None:
        """Compile the hot paths before serving (smallest prefill bucket,
        decode chunks of 1 and decode_chunk, the smallest-bucket ctx-prefill
        when the prefix cache is on, the embeddings forward) so the first
        request of each kind doesn't pay 30-40 s of XLA compilation in its
        latency."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        r = self._runner
        state = r.init_state()
        tok, ks, vs, plen = r.prefill([1, 2, 3], 0.0, 1.0, jax.random.PRNGKey(0))
        state = r.insert(state, 0, ks, vs, plen, tok, 0.0, 1.0)
        for k in {1, self.config.decode_chunk}:
            _, state = r.decode_steps(state, k)
        if getattr(r, "prefix_cache", False):
            r.warmup_ctx_prefill(state)
        if getattr(r, "prefill_chunk", 0) and r.max_seq > r.prefill_chunk + 1:
            # Chunked-admission programs (the long-prompt path): compile
            # one chunk step at the chunk bucket so the first long prompt
            # doesn't pay the forward's XLA compile in its TTFT.  Needs a
            # prompt longer than one chunk that still fits under max_seq
            # (max_seq == prefill_chunk + 1 has no such prompt, ADVICE r3).
            job = r.prefill_begin(list(range(1, r.prefill_chunk + 2)))
            while not r.prefill_step(job):
                pass
            # Finish the job (also compiles the finish-sampling program):
            # under multi-host replication an abandoned job would pin its
            # KV accumulators on every follower indefinitely.
            r.prefill_finish(job, 0.0, 1.0, jax.random.PRNGKey(0))
        r.embed_prompts([[1, 2, 3]])
        state = r.release(state, 0)
        if (self.config.ragged_prefill
                and getattr(r, "supports_ragged", False)
                and r.max_seq > r.ragged_chunk + 1):
            # Unified ragged batch (docs/RAGGED_BATCH.md): compile the
            # single-step unified program + finish activation so the first
            # long prompt admitted under load doesn't pay the compile in
            # its TTFT.  The decode_chunk-step variant compiles on first
            # use (only dispatched while the batch is saturated, where one
            # compile amortizes immediately).
            # A runner whose unified programs take ONE page-table width
            # (engine/hybrid.py: a model with window layers, whose every
            # long prompt comes this way) is compiled for both flight
            # lengths here: all its admissions will ever dispatch.
            for k in sorted({1, self.config.decode_chunk}
                            if r.ragged_width_fixed else {1}):
                job = r.ragged_begin(list(range(1, r.ragged_chunk + 2)), 0,
                                     state=state)
                while not job.finished:
                    _, state = r.ragged_step(state, job, k)
                _, state = r.ragged_finish(state, job, 0.0, 1.0,
                                           jax.random.PRNGKey(0))
                state = r.release(state, 0)
        log.info("warmup compile done")

    async def drain(self, timeout: float = 30.0) -> bool:
        """Finish in-flight requests before shutdown; False on timeout."""
        if self.scheduler is None:
            return True
        return await self.scheduler.drain(timeout)

    async def migrate(self) -> int:
        """Retire every in-flight request with "migrate" at the decode
        loop's next safe point (graceful drain); prefix pages stay cached
        so this worker keeps serving them as a KV donor."""
        if self.scheduler is None:
            return 0
        moved = await self.scheduler.migrate()
        if moved and self.obs is not None:
            self.obs.metrics.drain_inc("migrated_slots", moved)
        return moved

    def _chaos_drain(self) -> None:
        """The scheduler's "scheduler.ragged_chunk" drain hook: start a
        graceful drain exactly as the "engine.stream_chunk" site does —
        through the peer when attached (publishes draining to the swarm),
        else the engine's own migrate."""
        peer = getattr(self, "_peer", None)
        loop = asyncio.get_running_loop()
        if peer is not None and hasattr(peer, "drain"):
            loop.create_task(peer.drain())
        else:
            loop.create_task(self.migrate())

    def _migrate_export_meta(self, req: pb.GenerateRequest
                             ) -> tuple[list[bytes], int]:
        r = self._runner
        if (r is None or self.tokenizer is None
                or not getattr(r, "prefix_cache", False)
                or not hasattr(r, "chain_keys_for_prompt")):
            return [], 0
        ids = self.tokenizer.encode(self._prompt_of(req))
        return r.chain_keys_for_prompt(ids), int(r.page_size)

    async def stop(self) -> None:
        if self._kv_streams is not None:
            self._kv_streams.close()
        exec_ = getattr(self.scheduler, "_exec", None)
        if self.scheduler is not None:
            await self.scheduler.stop()
        if self._runner is not None and hasattr(self._runner, "shutdown"):
            # Multi-host: release the follower frame loops — AFTER any
            # in-flight dispatch on the scheduler's executor thread has
            # finished, or the STOP broadcast would interleave with that
            # dispatch's collectives mid-frame.
            if exec_ is not None:
                loop = asyncio.get_running_loop()
                await loop.run_in_executor(None, exec_.shutdown, True)
            self._runner.shutdown()

    def model_dir(self, model: str) -> str | None:
        from pathlib import Path

        mp = self.config.model_path
        if (model in self.models and mp
                and list(Path(mp).expanduser().glob("*.safetensors"))):
            return mp
        return None

    def obs_gauges(self) -> dict:
        if self.scheduler is None:
            return super().obs_gauges()
        return self.scheduler.telemetry_gauges()

    # ---------------------------- KV shipping (docs/KV_TRANSFER.md) -------

    def _kv_ship_ready(self) -> bool:
        r = self._runner
        return (bool(self.config.kv_ship) and self.scheduler is not None
                and r is not None and getattr(r, "prefix_cache", False)
                and hasattr(r, "import_pages"))

    async def export_kv_pages(self, model: str, chain_hashes: list[bytes],
                              page_size: int) -> dict | None:
        """Donor side: serve a peer's fetch from the prefix index.

        Runs through the scheduler's exclusive point so the device→host
        gather reads a live (undonated) pool between dispatches; the
        runner ref-pins the matched pages for the gather's duration."""
        r = self._runner
        if (self.scheduler is None or r is None
                or not getattr(r, "prefix_cache", False)
                or not hasattr(r, "export_pages")):
            return None
        if model and model not in self.models:
            return None
        hashes = [bytes(h) for h in chain_hashes]

        def _export(state):
            return r.export_pages(state, hashes, page_size=int(page_size))

        return await self.scheduler.run_exclusive(_export)

    async def _fetch_kv_payload(self, donor: str, model: str,
                                prompt_ids: list[int], trace_id: str = "",
                                migrate: bool = False
                                ) -> tuple[dict | None, int]:
        """Receiver side: dial the donor and pull the prefix's pages.

        Returns (payload-for-GenRequest.kv_import | None, fetch wall ns;
        0 ns = no fetch was even attempted).  Every failure mode — donor
        gone, stream killed, timeout, dtype mismatch discovered at import —
        degrades to plain prefill; this path can make a request faster,
        never break it.  One transient failure earns one retry inside the
        same kv_ship_timeout budget (decorrelated jitter), so a donor
        hiccup doesn't forfeit a large prefix over nothing.

        ``migrate`` marks a migrated stream (docs/ROBUSTNESS.md): the
        kv_ship opt-in and break-even gates are bypassed — the fetch IS
        the point of the re-route — and prompt pages the donor could have
        served but this worker recomputed are counted in
        ``crowdllama_replayed_prefill_tokens_total`` (0 == complete
        handoff)."""
        import random

        r = self._runner
        peer = self._peer
        ready = (self.scheduler is not None and r is not None
                 and getattr(r, "prefix_cache", False)
                 and hasattr(r, "import_pages")
                 and (bool(self.config.kv_ship) or migrate))
        if (not ready or peer is None or not donor
                or donor == getattr(peer, "peer_id", "")):
            return None, 0
        keys = r.chain_keys_for_prompt(prompt_ids)
        covered = r.local_prefix_coverage(keys)
        uncovered = (len(keys) - covered) * r.page_size
        mx = self.obs.metrics if self.obs is not None else None

        def _account_replay(covered_pages: int) -> None:
            if migrate and mx is not None:
                mx.replayed_prefill_tokens += (
                    max(0, len(keys) - covered_pages) * r.page_size)

        if uncovered <= 0:
            return None, 0  # local pages already cover the prompt
        if (not migrate
                and uncovered < max(1, int(self.config.kv_ship_min_tokens))):
            return None, 0  # short tail: the round trip costs more than it saves
        timeout = max(0.5, float(self.config.kv_ship_timeout))
        deadline = time.monotonic() + timeout
        t0 = time.monotonic_ns()
        payload, err = None, None
        for attempt in range(2):
            budget = deadline - time.monotonic()
            if budget <= 0:
                break
            try:
                payload = await asyncio.wait_for(
                    self._kv_fetch_once(peer, donor, model, keys, trace_id),
                    budget)
                err = None
                break
            except Exception as e:
                err = e
                if attempt:
                    break
                # Decorrelated jitter; skip the retry when the backoff
                # would eat what's left of the budget.
                backoff = random.uniform(0.05, 0.15)
                if deadline - time.monotonic() <= backoff:
                    break
                if mx is not None:
                    mx.kv_ship_inc("retries")
                log.warning("kv fetch from %s failed (%s); retrying in "
                            "%.0f ms", donor, e, backoff * 1e3)
                await asyncio.sleep(backoff)
        dt = time.monotonic_ns() - t0
        if err is not None:
            if mx is not None:
                mx.kv_ship_inc("fetches")
                mx.kv_ship_inc("fallbacks")
                mx.kv_fetch_seconds.observe(dt / 1e9)
            log.warning("kv fetch from %s failed (%s); plain prefill",
                        donor, err)
            _account_replay(covered)
            return None, dt
        if mx is not None:
            mx.kv_ship_inc("fetches")
            mx.kv_fetch_seconds.observe(dt / 1e9)
        if payload is None:
            if mx is not None:
                mx.kv_ship_inc("fallbacks")
            _account_replay(covered)
            return None, dt
        if mx is not None:
            mx.kv_ship_inc("bytes", payload.get("bytes", 0))
        # The donor's pages cover keys[:n] from the start of the chain —
        # a superset or subset of the local coverage, never disjoint.
        _account_replay(max(covered, len(payload.get("keys", ()))))
        return payload, dt

    async def _kv_fetch_once(self, peer, donor: str, model: str,
                             keys: list[bytes],
                             trace_id: str = "") -> dict | None:
        from crowdllama_tpu.core import wire
        from crowdllama_tpu.core.messages import (
            create_kv_fetch_request,
            extract_kv_pages,
        )
        from crowdllama_tpu.core.protocol import INFERENCE_PROTOCOL

        await faults.inject("kv.fetch", worker=getattr(peer, "peer_id", ""),
                            donor=donor)
        # Pool donor streams: the TCP + signed-hello handshake costs ~20 ms
        # on loopback — more than the page transfer itself — and the donor's
        # inference serve loop already handles many exchanges per stream.
        if self._kv_streams is None:
            from crowdllama_tpu.net.host import StreamPool

            self._kv_streams = StreamPool(max_per_key=2)
        stream = self._kv_streams.get(donor)
        if stream is None:
            contact = await peer.dht.find_peer(donor)
            if contact is None:
                raise LookupError(f"kv donor {donor} not found in DHT")
            stream = await peer.host.new_stream(contact, INFERENCE_PROTOCOL)
        done = False
        try:
            fetch = create_kv_fetch_request(model, keys,
                                            self._runner.page_size)
            fetch.trace_id = trace_id  # donor's kv_export joins this trace
            await wire.write_length_prefixed_pb(stream.writer, fetch)
            k_pages: list[bytes] = []
            v_pages: list[bytes] = []
            k_scales: list[bytes] = []
            v_scales: list[bytes] = []
            matched, dtype = 0, ""
            while True:
                frame = await wire.read_length_prefixed_pb(
                    stream.reader,
                    timeout=max(0.5, float(self.config.kv_ship_timeout)))
                kvp = extract_kv_pages(frame)
                if kvp.error:
                    raise RuntimeError(f"kv donor error: {kvp.error}")
                matched = int(kvp.matched) or matched
                dtype = kvp.kv_dtype or dtype
                k_pages.extend(kvp.k_pages)
                v_pages.extend(kvp.v_pages)
                k_scales.extend(kvp.k_scales)
                v_scales.extend(kvp.v_scales)
                if kvp.done:
                    done = True
                    break
        finally:
            # A completed exchange leaves the stream at a frame boundary —
            # reusable.  Anything else (error frame, timeout mid-stream)
            # may have frames in flight: close, never pool.
            if done:
                self._kv_streams.put(donor, stream)
            else:
                stream.close()
        n = min(len(k_pages), len(v_pages))
        if n == 0:
            return None  # donor matched nothing (or evicted everything)
        total = sum(len(b) for b in (*k_pages, *v_pages,
                                     *k_scales, *v_scales))
        return {
            "keys": keys[:n],
            "k_pages": k_pages[:n], "v_pages": v_pages[:n],
            "k_scales": k_scales[:n], "v_scales": v_scales[:n],
            "kv_dtype": dtype, "bytes": total,
        }

    def describe(self) -> dict:
        d = {"models": self.models, "throughput": 0.0, "load": 0.0}
        if self._runner is not None:
            # Every mesh kind has an embeddings forward (pp runs the
            # microbatch pipeline, sp the ring — runner.embed_prompts),
            # including multi-host leader-replicated serving since v2
            # (the EMBED frame replays the forward on every process).
            d["embeddings"] = True
        if self.scheduler is not None:
            d["throughput"] = round(self.scheduler.throughput_ema, 2)
            d["load"] = round(self.scheduler.load, 3)
        if self._runner is not None and hasattr(self._runner, "prefix_hits"):
            d["prefix_cache"] = {
                "hits": self._runner.prefix_hits,
                "misses": self._runner.prefix_misses,
                "tokens_reused": self._runner.prefix_tokens_reused,
            }
        if (self._runner is not None
                and hasattr(self._runner, "kv_pages_exported")):
            d["kv_ship"] = {
                "enabled": bool(self.config.kv_ship),
                "pages_exported": self._runner.kv_pages_exported,
                "pages_imported": self._runner.kv_pages_imported,
            }
        if self.scheduler is not None and self.scheduler.spec_steps:
            steps = self.scheduler.spec_steps
            emitted = self.scheduler.spec_emitted
            offered = steps * max(1, self.config.spec_draft)
            echo = self.scheduler.spec_accept_echo
            gen = self.scheduler.spec_accept_gen
            d["spec_decode"] = {
                "mode": self.config.spec_decode,
                "verify_steps": steps,
                "tokens_emitted": emitted,
                "tokens_per_step": round(emitted / steps, 2),
                # Fraction of offered draft tokens the verifier accepted,
                # split by proposal source: prompt-echo acceptance only
                # exists on templated/retrieval traffic that replays its
                # input — operators reading one blended rate would enable
                # spec expecting 2x and get 1.1x on generative chat.
                # Derived from the per-emission split (NOT emitted-steps,
                # which pure-overshoot chunks skew).
                "acceptance_rate": round((echo + gen) / offered, 3),
                "accepted_prompt_echo": echo,
                "accepted_generative": gen,
                "acceptance_rate_prompt_echo": round(echo / offered, 3),
                "acceptance_rate_generative": round(gen / offered, 3),
            }
            if self.config.spec_decode == "draft":
                d["spec_decode"]["draft_model"] = (
                    self.config.spec_draft_model
                    or self.config.spec_draft_path)
            if self.scheduler._spec_adaptive:
                d["spec_decode"]["adaptive"] = {
                    "draft_len": getattr(self.scheduler.runner,
                                         "draft_len", 0),
                    "draft_len_max": self.scheduler.spec_draft_max,
                    "retunes": self.scheduler.spec_retunes,
                    "probes": self.scheduler.spec_probes,
                }
        return d

    # ---- the profiler control (obs/http.py, the IPC "profile" op) --------

    async def profile_start(self) -> dict:
        """Begin a ``jax.profiler`` trace of this process — the one that
        holds the chip — into a fresh directory under ``profile_dir``.

        The profiler session is global across threads, so the trace spans
        whatever the scheduler dispatches until :meth:`profile_stop`.  The
        Python tracer is off (it slows the host it is meant to observe and
        makes the trace hundreds of MB); the runtime's own host events and
        the scheduler's ``sched.*`` annotations are on.  Single-flight:
        :class:`ProfileBusy` while a trace runs."""
        if not self.config.profile_dir:
            raise ProfileDisabled(
                "profiling disabled: set profile_dir "
                "(--profile-dir / CROWDLLAMA_TPU_PROFILE_DIR)")
        if self._profile_latch:
            raise ProfileBusy("a profiler trace is already running")
        self._profile_latch = True
        self._profile_seq += 1
        path = os.path.join(
            self.config.profile_dir,
            f"profile-{int(time.time())}-{self._profile_seq}")
        started = {"artifact": path}

        def _start() -> None:
            import jax
            from jax._src.lib import _profiler

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            os.makedirs(path, exist_ok=True)
            # the session jax.profiler.start_trace makes, held here so that
            # profile_stop can write the XSpace alone
            self._profile_session = _profiler.ProfilerSession(opts)
            started["started_monotonic"] = time.monotonic()
            started["started_unix"] = time.time()

        try:
            await asyncio.get_running_loop().run_in_executor(None, _start)
        except BaseException:
            self._profile_latch = False
            raise
        self._profile = started
        return dict(started)

    async def profile_stop(self) -> dict:
        """End the running trace; answers once the ``.xplane.pb`` is
        written, with the artifact directory and the host's clock at start
        and stop, monotonic and unix.  A trace stopped before it was on for
        :data:`MIN_TRACE_S` is ended without being collected: the artifact
        directory stays empty and ``xspace_bytes`` is 0."""
        if not self.config.profile_dir:
            raise ProfileDisabled("profiling disabled: set profile_dir")
        if self._profile is None:
            raise ProfileBusy("no running profiler trace to stop")
        done, self._profile = self._profile, None

        def _stop() -> None:
            done["stopped_monotonic"] = time.monotonic()
            done["stopped_unix"] = time.time()
            session, self._profile_session = self._profile_session, None
            if (done["stopped_monotonic"] - done["started_monotonic"]
                    < MIN_TRACE_S):
                # dropping the last reference stops the tracers; nothing is
                # gathered from them
                del session
                xspace = b""
            else:
                xspace = session.stop()
            done["collected_monotonic"] = time.monotonic()
            if xspace:
                # TensorBoard's layout.  jax.profiler.stop_trace would also
                # convert every event to a trace.json.gz that nothing here
                # reads: three quarters of a stop on the chip (PERF.md, PR 37)
                run = os.path.join(done["artifact"], "plugins", "profile",
                                   time.strftime("%Y_%m_%d_%H_%M_%S"))
                os.makedirs(run, exist_ok=True)
                with open(os.path.join(
                        run, f"{socket.gethostname()}.xplane.pb"), "wb") as f:
                    f.write(xspace)
            done["xspace_bytes"] = len(xspace)
            done["written_monotonic"] = time.monotonic()

        try:
            await asyncio.get_running_loop().run_in_executor(None, _stop)
        finally:
            self._profile_latch = False
        log.info("profiler trace %d: on for %.2fs, %s in %.2fs, "
                 "%.1f MB written in %.2fs", self._profile_seq,
                 done["stopped_monotonic"] - done["started_monotonic"],
                 "collected" if done["xspace_bytes"] else "dropped",
                 done["collected_monotonic"] - done["stopped_monotonic"],
                 done["xspace_bytes"] / 1e6,
                 done["written_monotonic"] - done["collected_monotonic"])
        return done

    async def capture_profile(self, seconds: float = 3.0) -> str:
        """A trace of a fixed window of live serving: start, sleep, stop.
        Returns the trace directory (TensorBoard-loadable)."""
        seconds = min(max(float(seconds), MIN_TRACE_S), 60.0)
        await self.profile_start()
        try:
            await asyncio.sleep(seconds)
        finally:
            done = await self.profile_stop()
        return done["artifact"]

    def _format_chat(self, messages: list[dict], model: str = "") -> str:
        """Prefer the checkpoint's own chat template (Llama-3 headers,
        Qwen im_start, ...) when the HF tokenizer ships one."""
        fmt = getattr(self.tokenizer, "format_chat", None)
        if fmt is not None:
            try:
                return fmt(messages)
            except ValueError:
                pass  # no template in this checkpoint: generic flattening
            except Exception:
                # A template that EXISTS but rejects this conversation
                # (e.g. Gemma's raises on system-role messages) — fall back,
                # but loudly: silently divergent prompt formats are a
                # miserable thing to debug.
                log.warning("chat template failed; using generic "
                            "flattening", exc_info=True)
        return flatten_chat(messages)

    async def generate(  # type: ignore[override]
        self,
        prompt: str,
        model: str = "",
        max_tokens: int = 128,
        temperature: float = 0.0,
        top_p: float = 1.0,
        seed: int = 0,
        stop: list[str] | None = None,
        top_k: int = 0,
        repeat_penalty: float = 1.0,
        kv_donor: str = "",
        kv_trace: str = "",
        migrate: bool = False,
        remote_draft: bool = False,
        draft_feed=None,
    ) -> AsyncIterator[Chunk]:
        from crowdllama_tpu.engine.scheduler import (
            DONE,
            VERIFY,
            GenRequest,
            WedgedError,
        )

        if self.scheduler is None:
            raise RuntimeError("engine not started")
        if model and model not in self.models:
            raise ValueError(f"model {model!r} not served (have {self.models})")

        prompt_ids = self.tokenizer.encode(prompt)
        kv_import, kv_ns = None, 0
        if kv_donor:
            kv_import, kv_ns = await self._fetch_kv_payload(
                kv_donor, model, prompt_ids, trace_id=kv_trace,
                migrate=migrate)
        kv_fallback = kv_import is None and kv_ns > 0
        req = GenRequest(
            prompt_ids=prompt_ids,
            max_tokens=max_tokens,
            temperature=temperature,
            top_p=top_p,
            top_k=max(0, int(top_k)),
            repeat_penalty=float(repeat_penalty or 1.0),
            eos_id=self.tokenizer.eos_id,
            seed=seed,
            kv_import=kv_import,
        )
        if remote_draft and draft_feed is not None:
            req.remote_draft = True
            req.feed = draft_feed
        await self.scheduler.submit(req)
        decoder = self.tokenizer.stream_decoder()
        matcher = StopMatcher(stop)
        completion = 0
        finished = False

        def _trace_stamps() -> dict:
            # Scheduler stamps → the final chunk's spans (obs plane):
            # worker_queue = submit→admission, prefill = admission→first
            # token, split at exec_start_at into dispatch_wait (the flight
            # queued ahead) and prefill_exec (the request's own programs).
            base = req.admitted_at or req.submitted_at
            q = max(0.0, base - req.submitted_at)
            p = (max(0.0, req.first_token_at - base)
                 if req.first_token_at else 0.0)
            wait = (min(p, max(0.0, req.exec_start_at - base))
                    if req.exec_start_at and p else 0.0)
            return {"queue_ns": int(q * 1e9), "prefill_ns": int(p * 1e9),
                    "submitted_ns": int(req.submitted_at * 1e9),
                    "dispatch_wait_ns": int(wait * 1e9),
                    "prefill_exec_ns": (int((p - wait) * 1e9)
                                        if req.exec_start_at else 0)}

        try:
            while True:
                token, reason = await req.out.get()
                if token is VERIFY:
                    # Remote-draft control plane: the scheduler answers
                    # each consumed DraftChunk credit with a verify payload
                    # — pure control chunk, no client-visible text.
                    yield Chunk(text="", verify=reason)
                    continue
                if token is DONE:
                    finished = True
                    if reason.startswith("error: wedged"):
                        # Typed: the dispatch self-watchdog failed this
                        # request (docs/ROBUSTNESS.md) — callers and the
                        # serve loop can tell a wedge from a generic
                        # engine failure.
                        raise WedgedError(reason[len("error: "):])
                    if reason.startswith("error"):
                        raise RuntimeError(reason)
                    yield Chunk(
                        text=matcher.flush(), done=True, done_reason=reason,
                        prompt_tokens=len(prompt_ids),
                        completion_tokens=completion,
                        kv_fetch_ns=kv_ns, kv_fallback=kv_fallback,
                        **_trace_stamps(),
                    )
                    return
                completion += 1
                if completion == 1 and req.remote_draft:
                    # Handshake (chunk_id 0, never a real credit): gives
                    # the gateway's drafter the tokenized prompt and the
                    # model's first token so it can seed its own KV before
                    # the first text frame even decodes.
                    yield Chunk(text="", verify={
                        "chunk_id": 0, "position": 1, "accepted": 0,
                        "tokens": [int(token)],
                        "prompt_ids": [int(t) for t in prompt_ids]})
                if token == req.eos_id:
                    continue  # silent; DONE follows
                text = decoder.feed(token)
                if not text:
                    continue
                emit, stopped = matcher.feed(text)
                if stopped:
                    finished = True
                    self.scheduler.cancel(req)
                    yield Chunk(
                        text=emit, done=True, done_reason="stop",
                        prompt_tokens=len(prompt_ids),
                        completion_tokens=completion,
                        kv_fetch_ns=kv_ns, kv_fallback=kv_fallback,
                        **_trace_stamps(),
                    )
                    return
                if emit:
                    yield Chunk(text=emit)
        finally:
            if not finished:
                # Consumer stopped early (client disconnect closes the
                # generator): free the decode slot instead of generating
                # into the void until max_tokens.
                self.scheduler.cancel(req)

    async def embed(self, texts: list[str], model: str = "",
                    truncate: bool = True) -> tuple[list[list[float]], int]:
        """Mean-pooled final-hidden-state embeddings (runner.embed_prompt).

        Dispatches on the scheduler's single-flight executor thread so
        embedding forwards serialize with decode chunks instead of racing
        them (and never block the event loop)."""
        if self.scheduler is None:
            raise RuntimeError("engine not started")
        if self.scheduler._draining:
            # Mirror submit(): reject so the gateway fails over instead of
            # racing the executor shutdown mid-drain (ADVICE r2).
            raise RuntimeError("worker is draining for shutdown")
        if model and model not in self.models:
            raise ValueError(f"model {model!r} not served (have {self.models})")
        max_len = self._runner.max_seq - 1
        loop = asyncio.get_running_loop()
        prompts, n_tokens = [], 0
        for text in texts:
            ids = self.tokenizer.encode(text)
            if len(ids) > max_len:
                if not truncate:
                    raise ValueError(
                        f"input of {len(ids)} tokens exceeds context length "
                        f"{max_len} and truncate=false")
                ids = ids[:max_len]
            ids = ids or [0]
            n_tokens += len(ids)
            prompts.append(ids)
        # One executor submission per padded batch (not per text, not the
        # whole list): same-bucket texts still share a forward, but decode
        # chunks get to interleave between batches instead of stalling
        # behind a bulk embed of hundreds of texts.
        out: list[list[float]] = []
        chunk_size = self._runner._EMBED_BATCH[-1]
        self.scheduler._embeds += 1  # drain() waits for in-flight embeds
        try:
            for i in range(0, len(prompts), chunk_size):
                vecs = await loop.run_in_executor(
                    self.scheduler._exec, self._runner.embed_prompts,
                    prompts[i:i + chunk_size])
                out.extend(vecs.tolist())
        finally:
            self.scheduler._embeds -= 1
        return out, n_tokens


class FakeEngine(Engine):
    """Echo engine for tests (the engine-seam mock, cf. MockOllamaServer)."""

    def __init__(self, models: list[str] | None = None, delay: float = 0.0):
        self.models = models or ["tiny-test"]
        self.delay = delay
        self.calls = 0
        # Live-migration test double: migrate() flips the flag and every
        # active generator retires with "migrate" at its next yield point
        # — the cheap path for exercising the gateway's migration handling
        # without a real scheduler.
        self._migrating = False
        self._active = 0

    async def start(self) -> None:
        return

    async def stop(self) -> None:
        return

    async def migrate(self) -> int:
        self._migrating = True
        return self._active

    def describe(self) -> dict:
        return {"models": self.models, "throughput": 100.0, "load": 0.1}

    async def generate(  # type: ignore[override]
        self, prompt: str, model: str = "", max_tokens: int = 128,
        temperature: float = 0.0, top_p: float = 1.0, seed: int = 0,
        stop: list[str] | None = None, top_k: int = 0,
        repeat_penalty: float = 1.0,
    ) -> AsyncIterator[Chunk]:
        self.calls += 1
        self._active += 1
        try:
            if self.delay:
                await asyncio.sleep(self.delay)
            matcher = StopMatcher(stop)
            words = f"echo: {prompt}".split(" ")
            emitted = 0
            stopped = False
            for i, w in enumerate(words):
                if self._migrating:
                    yield Chunk(text="", done=True, done_reason="migrate",
                                prompt_tokens=len(prompt.split()),
                                completion_tokens=max(emitted, 1))
                    return
                emit, stopped = matcher.feed(w + ("" if i == len(words) - 1
                                                  else " "))
                if emit:
                    yield Chunk(text=emit)
                    emitted += 1
                if stopped:
                    break
            yield Chunk(text="" if stopped else matcher.flush(), done=True,
                        done_reason="stop",
                        prompt_tokens=len(prompt.split()),
                        completion_tokens=max(emitted, 1))
        finally:
            self._active -= 1

    async def embed(self, texts: list[str], model: str = "",
                    truncate: bool = True) -> tuple[list[list[float]], int]:
        """Deterministic unit vectors keyed by text hash (test double)."""
        import hashlib
        import math

        self.calls += 1
        out = []
        for text in texts:
            h = hashlib.sha256(text.encode()).digest()
            vec = [b / 255.0 - 0.5 for b in h[:8]]
            norm = math.sqrt(sum(v * v for v in vec)) or 1.0
            out.append([v / norm for v in vec])
        return out, sum(len(t.split()) for t in texts)
