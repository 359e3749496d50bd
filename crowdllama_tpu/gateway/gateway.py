"""Consumer-facing HTTP gateway (Ollama-compatible API).

Counterpart of /root/reference/pkg/gateway/gateway.go: ``POST /api/chat``
accepts Ollama-style JSON {model, messages[], stream, options}
(gateway.go:31-41,168-231), routes to the best worker via the peer manager
(:191,346-348), forwards the request over an inference stream, and converts
the protobuf reply back to Ollama-shaped JSON (:209-230).  ``GET /api/health``
dumps the per-worker health map (:426-461).  Request logging middleware with
real durations (:107-135).

Supersets over the reference: ``stream: true`` actually streams — NDJSON
chunks exactly like Ollama's own API — and worker-side failures retry once on
the next-best worker (the reference surfaces them directly, gateway.go:210-217).
The stock ``ollama`` Python client works against this server
(examples/chat.py, cf. reference examples/chat/chat.py).
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import random
import time
from collections import OrderedDict

from aiohttp import web

from crowdllama_tpu.core import wire
from crowdllama_tpu.core.messages import (
    create_embed_request,
    create_generate_request,
    extract_embed_response,
    extract_generate_response,
)
from crowdllama_tpu.core.protocol import INFERENCE_PROTOCOL
from crowdllama_tpu.obs import (
    DEFAULT_TRACE_CAPACITY,
    GATEWAY_ROOT_SPAN,
    NodeObs,
    new_trace_id,
)
from crowdllama_tpu.obs.http import host_stat_lines, native_metric_lines
from crowdllama_tpu.obs.metrics import (
    ENGINE_TELEMETRY,
    LabelGuard,
    device_memory_lines,
    engine_gauge_lines,
)
from crowdllama_tpu.peer.peer import Peer

log = logging.getLogger("crowdllama.gateway")

# Gateway span phases recorded per request (docs/OBSERVABILITY.md): the
# always-present quartet + dial/stream_flush when the request paid them.
_GW_PHASES = ("route", "serde", "aead", "io_wait")
_GW_OPT_PHASES = ("dial", "stream_flush")


def _now_rfc3339() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%S", time.gmtime()) + "Z"


class _StreamStarted(Exception):
    """The CLIENT side of a streamed response failed (disconnect, write
    error) or the terminal frame already went out: not retryable, not
    failover-able — the response object is final as-is.

    Worker-side mid-stream failures deliberately do NOT raise this any
    more: they propagate as ordinary exceptions so _route can fail the
    stream over to the next-best worker and resume it (docs/ROBUSTNESS.md).
    """

    def __init__(self, response: "web.StreamResponse", cause: Exception):
        super().__init__(str(cause))
        self.response = response
        self.cause = cause


class _BudgetExhausted(Exception):
    """The request's wall-clock budget expired (pre- or mid-stream)."""


class _WorkerDraining(Exception):
    """The worker announced it is draining: either it rejected the request
    up front (typed ``draining`` terminal frame) or it handed off an
    in-flight stream with a MigrateFrame.  _route treats this as a
    MIGRATION, not a failure: the drained worker is quarantined from the
    routing snapshot but attached to the retry as a KV donor with
    ``migrate=True``, so the successor imports the prompt's pages instead
    of re-running prefill (docs/ROBUSTNESS.md)."""

    def __init__(self, worker_id: str, migrated: bool = False,
                 delivered_tokens: int = 0):
        super().__init__(
            f"worker {worker_id[:8]} draining"
            + (" (mid-stream handoff)" if migrated else ""))
        self.worker_id = worker_id
        self.migrated = migrated  # True: MigrateFrame, stream was in flight
        self.delivered_tokens = delivered_tokens


class _StreamStalled(Exception):
    """The worker stopped making token progress past the stall budget
    while holding the transport OPEN: the gray failure.  There is no EOF
    and no error frame to react to — only the per-stream progress
    watchdog (``--stream-stall-ms``, docs/ROBUSTNESS.md) notices.
    _route tears the stream down, quarantines the worker as ``wedged``
    (it may still answer health probes) and fails the stream over."""

    def __init__(self, worker_id: str, phase: str):
        super().__init__(
            f"worker {worker_id[:8]} stalled (no {phase} progress)")
        self.worker_id = worker_id
        self.phase = phase  # "ttft" | "decode"


class _StreamCtx:
    """Client-side state of ONE streamed response, surviving failover.

    Created per routed request; ``out``/``sent_text`` carry the prepared
    response and every char already delivered across worker attempts, and
    the OpenAI envelope state (rid/created/chunk ordinal) stays stable so
    a failover does not re-send the role delta or change the stream id."""

    __slots__ = ("out", "sent_text", "rid", "created", "nth", "winner")

    def __init__(self, shape: str):
        self.out: web.StreamResponse | None = None
        self.sent_text = ""
        self.rid = ("chatcmpl-" if shape == "openai-chat" else "cmpl-") \
            + os.urandom(12).hex()
        self.created = int(time.time())
        self.nth = 0
        # Hedged dispatch: the worker that actually served the stream
        # (may differ from the one _route picked when the hedge won).
        self.winner = ""


class Gateway:
    def __init__(self, peer: Peer, port: int = 9001, host: str = "0.0.0.0",
                 trace_buffer: int = DEFAULT_TRACE_CAPACITY, request_timeout: float = 600.0,
                 admission_max_inflight: int = 0,
                 retry_after_s: float = 1.0, kv_ship: bool = False,
                 gossip=None, tenant_quotas=None, flight_recorder: int = 32,
                 trace_ttl: float = 0.0, metrics_exemplars: bool = False,
                 slo_ttft_ms: float = 0.0, slo_decode_ms: float = 0.0,
                 stream_stall_ms: float = 0.0, hedge_ttft_ms: float = 0.0,
                 spec_pipeline: str = "off",
                 spec_draft_path: str = ""):
        self.peer = peer
        self.port = port
        self.host = host
        # Replicated gateway plane (docs/ROBUSTNESS.md): the swarm/gossip.py
        # GossipNode sharing affinity pins + quarantines with the other
        # replicas (None = single-gateway, everything stays process-local),
        # and the per-tenant token buckets replacing the global shed.
        self.gossip = gossip
        self.tenant_quotas = tenant_quotas
        # KV shipping (docs/KV_TRANSFER.md): on an affinity MISS, hint the
        # remembered worker as a page donor so the chosen worker fetches
        # the shared prefix instead of recomputing it.
        self.kv_ship = bool(kv_ship)
        # Robustness plane (docs/ROBUSTNESS.md): total wall-clock budget
        # per request, charged across retries and failovers (a client may
        # lower it per request via X-Request-Timeout); gateway-side
        # admission cap (0 = off); Retry-After hint on shed 503s.
        self.request_timeout = max(0.1, float(request_timeout))
        self.admission_max_inflight = max(0, int(admission_max_inflight))
        self.retry_after_s = max(0.0, float(retry_after_s))
        self._inflight = 0  # routed inference requests currently in flight
        self._runner: web.AppRunner | None = None
        self.app = web.Application(middlewares=[self._log_middleware])
        self.app.router.add_post("/api/chat", self.handle_chat)
        self.app.router.add_post("/api/generate", self.handle_generate)
        self.app.router.add_get("/api/health", self.handle_health)
        self.app.router.add_get("/api/tags", self.handle_tags)
        self.app.router.add_get("/api/version", self.handle_version)
        self.app.router.add_post("/api/show", self.handle_show)
        self.app.router.add_get("/api/ps", self.handle_ps)
        self.app.router.add_post("/api/embed", self.handle_embed)
        self.app.router.add_post("/api/embeddings", self.handle_embeddings)
        self.app.router.add_post("/api/pull", self.handle_pull)
        # OpenAI-compatible surface (Ollama serves the same aliases; stock
        # openai clients pointed at the gateway work unchanged).
        self.app.router.add_post("/v1/chat/completions",
                                 self.handle_openai_chat)
        self.app.router.add_post("/v1/completions",
                                 self.handle_openai_completions)
        self.app.router.add_get("/v1/models", self.handle_openai_models)
        self.app.router.add_post("/v1/embeddings",
                                 self.handle_openai_embeddings)
        self.app.router.add_get("/metrics", self.handle_metrics)
        self.app.router.add_get("/debug/trace", self.handle_trace)
        # Cross-node trace assembly + flight recorder (PR 8): the stitched
        # endpoint fans TraceFetch out over the p2p plane per hit, so it is
        # a debugging surface, not a hot path.
        self.app.router.add_get("/debug/trace/{trace_id}",
                                self.handle_trace_stitched)
        self.app.router.add_get("/debug/flightrecorder",
                                self.handle_flightrecorder)
        # Swarm observatory (PR 13, docs/OBSERVABILITY.md): cluster-wide
        # metric fan-in over the p2p plane — an operator surface hit per
        # request, never on the inference hot path.  (The profiler trigger
        # lives on the worker's ObsServer: only the process that holds the
        # chip can trace it.)
        self.app.router.add_get("/metrics/cluster",
                                self.handle_metrics_cluster)
        for route in ("/api/delete", "/api/create", "/api/copy", "/api/push"):
            self.app.router.add_route("*", route, self.handle_unsupported)
        # Prometheus-style counters fed by the logging middleware
        # ((path, status) -> count / summed seconds).  The reference has no
        # metrics surface at all (SURVEY §5: "No Prometheus/metrics
        # endpoint") — this is part of the TPU-native superset.
        self._req_count: dict[tuple[str, int], int] = {}
        self._req_seconds: dict[tuple[str, int], float] = {}
        # Streamed-inference time-to-first-frame histogram (Prometheus
        # buckets, seconds): the gateway-side TTFT the operator actually
        # controls — from admission to the worker's first token frame.
        self._ttfb_le = (0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)
        self._ttfb_buckets = [0] * (len(self._ttfb_le) + 1)
        self._ttfb_sum = 0.0
        self._ttfb_count = 0
        # Label hygiene: only registered routes become label values —
        # scanner probes of arbitrary paths must not grow the counter maps
        # without bound or inject quotes into the exposition format.  The
        # guard itself lives in obs/ (LabelGuard) so worker-side metrics
        # apply the same policy to their labels.
        self._known_paths = {r.resource.canonical
                             for r in self.app.router.routes()
                             if r.resource is not None}
        self._path_guard = LabelGuard(allowed=self._known_paths)
        # Tracing + histogram plane (obs/): trace ids minted per routed
        # request, spans recorded into the ring served at /debug/trace,
        # histograms rendered into /metrics alongside the PR 1 counters.
        self.obs = NodeObs(trace_capacity=trace_buffer, node="gateway",
                           trace_ttl=trace_ttl, exemplars=metrics_exemplars)
        # Swarm-stitched traces + flight recorder (PR 8): the collector
        # assembles this gateway's fragment with every remote node's via
        # TraceFetch fan-out; the recorder keeps complete stitched traces
        # for interesting requests (p99 tail, failover, migrate, shed,
        # kv-ship fallback) in its own ring so they outlive the general one.
        from crowdllama_tpu.obs.collector import FlightRecorder, TraceCollector

        self.collector = TraceCollector(peer, self.obs)
        self.flight = FlightRecorder(capacity=flight_recorder)
        # Rolling-p99 capture needs a floor of observations before the
        # quantile means anything; below it only event triggers capture.
        self._flight_min_count = 30
        # A 5xx storm (mass shedding) must not fan a stitch out per failed
        # request: captures beyond this many in flight are dropped — the
        # ring only keeps the newest N complete traces anyway.
        self._flight_inflight = 0
        self._flight_max_inflight = 4
        # Swarm observatory (PR 13): the /metrics/cluster scraper and the
        # SLO burn-rate engine (objectives in ms; 0 = disabled).
        from crowdllama_tpu.obs.cluster import ClusterScraper
        from crowdllama_tpu.obs.slo import SloEngine

        self.cluster = ClusterScraper(peer)
        self.slo = SloEngine(ttft_ms=float(slo_ttft_ms),
                             decode_ms=float(slo_decode_ms))
        # Inference-stream pool: a request to a worker reuses an idle
        # encrypted stream instead of paying TCP connect + signed-hello
        # handshake (Ed25519 sign/verify + X25519) per request — the
        # per-request analog of the reference's O(1) routing
        # (manager.go:338-387; libp2p reuses connections the same way).
        # Workers loop on the stream (peer._handle_inference_stream) with
        # an idle window outlasting the pool's, so one stream serves many
        # sequential requests; stale entries (worker restarted) are
        # detected by the first failed roundtrip and retried fresh.
        from crowdllama_tpu.net.host import StreamPool

        # max_per_key matches typical per-worker request concurrency:
        # with only 4 slots, a 1-worker swarm under 8-way concurrency
        # redials on half its requests and pays handshakes a larger
        # swarm doesn't.
        self._stream_pool = StreamPool(max_per_key=8)
        # Per-phase CPU attribution for the request hot path (monotonic
        # perf_counter_ns sums; exposed in /metrics and hotpath_snapshot):
        #   route_ns   — worker selection (affinity probe + snapshot scan)
        #   serde_ns   — protobuf encode/decode
        #   io_wait_ns — awaiting socket readiness/frames (includes the
        #                secure layer's inline seal/open, which is ALSO
        #                broken out process-wide as aead_us — subtract to
        #                isolate pure socket wait)
        # requests counts routed inference/embed requests (not every HTTP
        # hit), so per-request figures divide cleanly.
        self._perf = {"route_ns": 0, "serde_ns": 0, "io_wait_ns": 0,
                      "requests": 0}
        # Robustness counters (exposed in /metrics): mid-stream failovers,
        # replayed-and-trimmed chunks during them, shed requests (gateway
        # admission cap + worker "overloaded" rejections), and wall-clock
        # budget exhaustions.
        self._robust = {"failovers": 0, "replayed_chunks": 0, "shed": 0,
                        "budget_exhausted": 0,
                        # Gray-failure immunity (docs/ROBUSTNESS.md):
                        # streams torn down by the progress watchdog,
                        # workers quarantined as wedged for it, and the
                        # hedged-dispatch exactly-once ledger (launched ==
                        # won + cancelled, asserted by the chaos soak).
                        "stalled_streams": 0, "wedge_quarantines": 0,
                        "hedge_launched": 0, "hedge_won": 0,
                        "hedge_cancelled": 0}
        # Per-stream progress watchdog + hedged first-token dispatch
        # (docs/ROBUSTNESS.md): both default OFF; the live SLO objectives
        # raise the stall budget, the live TTFT p95 raises the hedge
        # threshold, so neither knob can fire tighter than the swarm's
        # actual promised/observed latency.
        self.stream_stall_ms = max(0.0, float(stream_stall_ms))
        self.hedge_ttft_ms = max(0.0, float(hedge_ttft_ms))
        # Prefix-affinity routing: multi-turn chats replay their history
        # verbatim, so turn N shares its leading tokens with turn 1 — the
        # engine's automatic prefix cache only pays if the continuation
        # lands on the SAME worker.  Conversation fingerprint (model +
        # first message head) -> (worker_id, ts); honored while the
        # worker is healthy and not near-saturated, otherwise scoring
        # wins (affinity is a tiebreak on top of manager.go:338-387's
        # throughput/(1+load), never a replacement for health).
        # Bounded LRU (same policy PeerManager.recently_removed got):
        # get/put move the key to the MRU end, inserts at capacity evict
        # the LRU entry — O(1), no sort-half stalls under churn.
        self._affinity: OrderedDict[str, tuple[str, float]] = OrderedDict()
        self._affinity_hits = 0
        self._affinity_evicted = 0
        self._affinity_repointed = 0
        self._kv_hints = 0
        # Cross-replica affinity: continuations whose pin came from the
        # gossip map rather than this process's own LRU
        # (crowdllama_gateway_gossip_affinity_hits_total).
        self._gossip_affinity_hits = 0
        # Per-tenant inflight (weighted-fair admission): tenant -> count.
        self._tenant_inflight: dict[str, int] = {}
        # Gateway-drafted speculative pipeline (docs/SPECULATIVE.md):
        # "off" routes plain streams; "gateway" drafts locally from
        # spec_draft_path and streams DraftChunk frames ahead of the
        # worker; "worker" sends pure ack credits (worker-paced remote
        # speculation — the RTT-linear baseline).  The drafter loads lazily on first use so a gateway
        # that never sees a remote-draft stream never touches jax.
        if spec_pipeline not in ("off", "gateway", "worker"):
            raise ValueError(
                f"spec_pipeline must be off|gateway|worker, "
                f"got {spec_pipeline!r}")
        self.spec_pipeline = spec_pipeline
        self.spec_draft_path = str(spec_draft_path or "")
        self._spec_drafter = None
        self._spec_drafter_tried = False
        # crowdllama_draft_chunk_* counter family (handle_metrics).
        self._spec_stats = {"chunks": 0, "acks": 0, "nacks": 0,
                            "accepted": 0, "offered": 0}
        # Warm-start cache for the depth controller: RTT and worker round
        # time are properties of the WIRE to a worker, not of one stream,
        # but the pump is per-stream — without this every short chat
        # spends its first RTTs re-learning the window from stop-and-wait.
        self._spec_wire: dict[str, tuple[float, float]] = {}

    # ----------------------------------------------------------- lifecycle

    async def start(self) -> None:
        pm = self.peer.peer_manager
        if pm is not None:
            # Affinity hygiene rides the manager's eviction hook — CHAINED,
            # not replaced: the DHT's provider-store eviction (net/dht.py)
            # may have registered first and must keep firing.
            prev = pm.on_peer_removed

            def _on_removed(peer_id: str) -> None:
                if prev is not None:
                    prev(peer_id)
                self._affinity_drop_worker(peer_id)

            pm.on_peer_removed = _on_removed
            if self.gossip is not None:
                # Quarantine publication: OUR observation of a drain
                # (mark_draining) enters the replicated map, so the other
                # replicas stop routing to the worker within one gossip
                # round instead of a probe interval later.
                prev_drain = pm.on_draining

                def _on_draining(peer_id: str) -> None:
                    if prev_drain is not None:
                        prev_drain(peer_id)
                    self.gossip.record_quarantine(peer_id)

                pm.on_draining = _on_draining
        if self.gossip is not None:
            # Remote entries applied by anti-entropy: another replica's
            # quarantine decision quarantines the worker HERE (split-brain
            # safe — mark_draining is idempotent and versioned entries
            # can't regress).  Affinity entries need no eager action: the
            # routing path consults the gossip map on local miss.
            from crowdllama_tpu.swarm.gossip import QUARANTINE_PREFIX

            def _on_entry(entry) -> None:
                if entry.tombstone \
                        or not entry.key.startswith(QUARANTINE_PREFIX):
                    return
                pm2 = self.peer.peer_manager
                if pm2 is not None:
                    pm2.mark_draining(entry.key[len(QUARANTINE_PREFIX):])

            self.gossip.on_entry = _on_entry
        self._runner = web.AppRunner(self.app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        log.info("gateway listening on %s:%d", self.host, self.port)

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None
        # The pool stays a null sink afterwards: an in-flight request
        # finishing post-stop closes its stream instead of repooling it.
        self._stream_pool.close()

    # ------------------------------------------------------- stream pool

    def _pool_get(self, worker_id: str):
        """Pop a live pooled stream for ``worker_id`` (None on miss)."""
        return self._stream_pool.get(worker_id)

    def _pool_put(self, worker_id: str, s) -> None:
        """Return a stream whose last request completed CLEANLY (a
        mid-response abort leaves unread frames — close those instead)."""
        self._stream_pool.put(worker_id, s)

    async def _dial(self, worker_id: str, acc: dict | None = None,
                    timeout: float | None = None, trace_id: str = ""):
        """``timeout`` caps the dial + handshake at the request's remaining
        budget (never above the protocol's own handshake timeout).
        ``trace_id`` rides a relay-splice fallback's connect frame so the
        relay node records a relay_splice span the collector can stitch."""
        from crowdllama_tpu.net.host import HANDSHAKE_TIMEOUT

        t0 = time.perf_counter_ns()
        contact = await self.peer.dht.find_peer(worker_id)
        if contact is None:
            raise LookupError(f"worker {worker_id[:8]} not resolvable")
        hs = (HANDSHAKE_TIMEOUT if timeout is None
              else max(0.05, min(HANDSHAKE_TIMEOUT, timeout)))
        s = await self.peer.host.new_stream(contact, INFERENCE_PROTOCOL,
                                            timeout=hs, trace_id=trace_id)
        if acc is not None:
            acc["dial_ns"] = acc.get("dial_ns", 0) \
                + time.perf_counter_ns() - t0
        return s

    # ------------------------------------------------- budgets and shedding

    def _budget(self, request: web.Request) -> float:
        """Per-request wall-clock budget in seconds: the configured ceiling,
        lowered by a valid ``X-Request-Timeout`` header."""
        hdr = request.headers.get("X-Request-Timeout", "")
        if hdr:
            try:
                v = float(hdr)
            except ValueError:
                v = 0.0
            if v > 0:
                return min(v, self.request_timeout)
        return self.request_timeout

    def _shed_headers(self) -> dict:
        # Jittered Retry-After in [base, 2*base]: a constant value tells
        # every shed client to come back at the SAME instant, so a
        # recovering gateway eats its own retry stampede.  Integer seconds
        # (the HTTP-date alternative is the only other legal form).
        base = self.retry_after_s
        return {"Retry-After": str(max(1, round(random.uniform(base,
                                                               2 * base))))}

    def _shed_response(self, shape: str, model: str,
                       message: str) -> web.Response:
        """503 + Retry-After: the uniform load-shedding response."""
        self._robust["shed"] += 1
        # Flight-recorder shed capture (PR 13): shedding happens before a
        # trace id is minted, so mint one here — the recorded trace is a
        # single gateway-side "shed" span, enough to see WHEN and WHY the
        # gateway refused (the message carries cap/quota context).
        tid = new_trace_id()
        self.obs.trace.record(tid, "shed", 0, parent=GATEWAY_ROOT_SPAN,
                              detail=message[:120], model=model)
        self.obs.trace.finish(tid, 1, status=503)
        self._flight_capture(tid, ["shed"])
        headers = self._shed_headers()
        if shape.startswith("openai"):
            return self._openai_error(message, 503, "server_error",
                                      headers=headers)
        return web.json_response({"error": message, "model": model},
                                 status=503, headers=headers)

    # ------------------------------------------------- hot-path attribution
    #
    # Each helper charges the SAME timing to the process-wide _perf counters
    # (PR 1 exposition, hotpath_snapshot) and — when the caller passes a
    # per-request accumulator ``acc`` — to that request's trace spans, so
    # the counters and /debug/trace spans are one instrumentation.

    def _encode_frame(self, msg, acc: dict | None = None) -> bytes:
        """Serialize a request ONCE per _route attempt; the same bytes are
        reused if the pooled stream turns out stale and the request redials
        (previously the protobuf was re-encoded per send)."""
        t0 = time.perf_counter_ns()
        frame = wire.encode_frame(msg)
        dt = time.perf_counter_ns() - t0
        self._perf["serde_ns"] += dt
        if acc is not None:
            acc["serde_ns"] = acc.get("serde_ns", 0) + dt
        return frame

    async def _send_frame(self, s, frame: bytes,
                          acc: dict | None = None) -> None:
        # write() is synchronous buffering (+ inline seal, counted by the
        # secure layer's aead counters); only the drain is socket wait.
        s.writer.write(frame)
        t0 = time.perf_counter_ns()
        await s.writer.drain()
        dt = time.perf_counter_ns() - t0
        self._perf["io_wait_ns"] += dt
        if acc is not None:
            acc["io_wait_ns"] = acc.get("io_wait_ns", 0) + dt

    async def _recv_pb(self, s, timeout: float = 600,
                       acc: dict | None = None):
        t0 = time.perf_counter_ns()
        payload = await wire.read_frame_payload(s.reader, timeout=timeout)
        t1 = time.perf_counter_ns()
        # Fast path: the native strict decoder handles the GenerateResponse
        # arm (the per-chunk hot case); anything else falls back to the
        # real parser inside decode_payload_fast with identical semantics.
        reply = wire.decode_payload_fast(payload)
        t2 = time.perf_counter_ns()
        self._perf["io_wait_ns"] += t1 - t0
        self._perf["serde_ns"] += t2 - t1
        if acc is not None:
            acc["io_wait_ns"] = acc.get("io_wait_ns", 0) + (t1 - t0)
            acc["serde_ns"] = acc.get("serde_ns", 0) + (t2 - t1)
        return reply

    def hotpath_snapshot(self) -> dict:
        """Point-in-time hot-path counters; benches diff two snapshots to
        attribute CPU per request phase (route/serde/aead/io_wait)."""
        from crowdllama_tpu.net import secure

        aead_ns, aead_ops = secure.aead_stats()
        pm = self.peer.peer_manager
        return {
            "requests": self._perf["requests"],
            "route_us": self._perf["route_ns"] / 1e3,
            "serde_us": self._perf["serde_ns"] / 1e3,
            "io_wait_us": self._perf["io_wait_ns"] / 1e3,
            "aead_us": aead_ns / 1e3,  # process-wide (see net/secure.py)
            "aead_ops": aead_ops,
            "pool_hits": self._stream_pool.hits,
            "pool_misses": self._stream_pool.misses,
            "route_snapshot_rebuilds": (
                pm.route_snapshot_rebuilds if pm is not None else 0),
        }

    # ---------------------------------------------------------- middleware

    @web.middleware
    async def _log_middleware(self, request: web.Request, handler):
        t0 = time.monotonic()
        status = 0
        try:
            resp = await handler(request)
            status = resp.status
            return resp
        except web.HTTPException as e:
            # aiohttp delivers router 404/405s (and handler short-circuits)
            # by raising — record their real status, not 0.
            status = e.status
            raise
        finally:
            dt = time.monotonic() - t0
            log.info("%s %s -> %.0fms", request.method, request.path,
                     dt * 1000)
            path = self._path_guard.value(request.path)
            key = (path, status)
            self._req_count[key] = self._req_count.get(key, 0) + 1
            self._req_seconds[key] = self._req_seconds.get(key, 0.0) + dt

    # ------------------------------------------------------------ handlers

    async def handle_chat(self, request: web.Request) -> web.StreamResponse:
        """POST /api/chat — Ollama chat API (gateway.go:168-231)."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON"}, status=400)
        model = body.get("model", "")
        messages = body.get("messages", [])
        if not model or not isinstance(messages, list) or not messages:
            return web.json_response(
                {"error": "model and messages are required"}, status=400)
        stream = bool(body.get("stream", False))
        options = body.get("options", {}) or {}
        return await self._route(
            request, model, stream, options, messages=messages,
            shape="chat")

    async def handle_generate(self, request: web.Request) -> web.StreamResponse:
        """POST /api/generate — Ollama completion API (prompt in, text out)."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON"}, status=400)
        model = body.get("model", "")
        prompt = body.get("prompt", "")
        if not model or not prompt:
            return web.json_response(
                {"error": "model and prompt are required"}, status=400)
        stream = bool(body.get("stream", False))
        options = body.get("options", {}) or {}
        return await self._route(
            request, model, stream, options, prompt=prompt,
            shape="generate")

    async def handle_health(self, request: web.Request) -> web.Response:
        """GET /api/health — per-worker health map (gateway.go:426-461)."""
        pm = self.peer.peer_manager
        workers = {}
        if pm is not None:
            for p in pm.get_workers():
                r = p.resource
                workers[p.peer_id] = {
                    "is_healthy": p.is_healthy,
                    "last_seen": time.time() - (time.monotonic() - p.last_seen),
                    "failed_attempts": p.failed_attempts,
                    "supported_models": r.supported_models,
                    "tokens_throughput": r.tokens_throughput,
                    "load": r.load,
                    "accelerator": r.accelerator,
                    "tpu_chip_count": r.tpu_chip_count,
                    "ici_topology": r.ici_topology,
                    "version": r.version,
                }
        return web.json_response({
            "status": "ok",
            "peer_id": self.peer.peer_id,
            "worker_count": len(workers),
            "workers": workers,
        })

    async def handle_tags(self, request: web.Request) -> web.Response:
        """GET /api/tags — available models (Ollama client handshake)."""
        pm = self.peer.peer_manager
        models: dict[str, dict] = {}
        if pm is not None:
            for p in pm.get_healthy_peers():
                if not p.is_worker:
                    continue
                for m in p.resource.supported_models:
                    models.setdefault(m, {"name": m, "model": m})
        return web.json_response({"models": list(models.values())})

    async def handle_version(self, request: web.Request) -> web.Response:
        """GET /api/version — Ollama client handshake."""
        from crowdllama_tpu.version import VERSION

        return web.json_response({"version": VERSION})

    async def handle_show(self, request: web.Request) -> web.Response:
        """POST /api/show — model details (registry config + swarm view)."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON"}, status=400)
        name = body.get("model") or body.get("name") or ""
        if not name:
            return web.json_response({"error": "model is required"}, status=400)
        pm = self.peer.peer_manager
        serving = [p.peer_id for p in (pm.get_healthy_peers() if pm else [])
                   if p.is_worker and name in p.resource.supported_models]
        details: dict = {"format": "safetensors"}
        model_info: dict = {}
        try:
            from crowdllama_tpu.models.config import get_config

            cfg = get_config(name)
            details.update({
                "family": cfg.family,
                "families": [cfg.family],
                "parameter_size": f"{cfg.param_count() / 1e9:.1f}B",
            })
            model_info = {
                "general.architecture": cfg.family,
                "general.parameter_count": cfg.param_count(),
                f"{cfg.family}.context_length": cfg.max_context_length,
                f"{cfg.family}.embedding_length": cfg.hidden_size,
                f"{cfg.family}.block_count": cfg.num_layers,
                f"{cfg.family}.attention.head_count": cfg.num_heads,
                f"{cfg.family}.attention.head_count_kv": cfg.num_kv_heads,
                f"{cfg.family}.vocab_size": cfg.vocab_size,
            }
            if cfg.is_moe:
                model_info[f"{cfg.family}.expert_count"] = cfg.num_experts
                model_info[f"{cfg.family}.expert_used_count"] = (
                    cfg.num_experts_per_tok)
        except KeyError:
            if not serving:
                return web.json_response(
                    {"error": f"model {name!r} not found"}, status=404)
        return web.json_response({
            "model": name,
            "details": details,
            "model_info": model_info,
            "workers_serving": serving,
        })

    async def handle_ps(self, request: web.Request) -> web.Response:
        """GET /api/ps — models currently loaded across the swarm."""
        pm = self.peer.peer_manager
        models: dict[str, dict] = {}
        if pm is not None:
            for p in pm.get_healthy_peers():
                if not p.is_worker:
                    continue
                for m in p.resource.supported_models:
                    entry = models.setdefault(m, {
                        "name": m, "model": m, "workers": 0,
                        "tokens_throughput": 0.0,
                    })
                    entry["workers"] += 1
                    entry["tokens_throughput"] += p.resource.tokens_throughput
        return web.json_response({"models": list(models.values())})

    async def handle_embed(self, request: web.Request) -> web.Response:
        """POST /api/embed — Ollama embeddings API: {model, input: str|[str]}
        → {model, embeddings: [[...]]}.  The reference delegates this surface
        to Ollama wholesale; here it routes over the swarm like chat."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON"}, status=400)
        model = body.get("model", "")
        inputs = body.get("input", "")
        if isinstance(inputs, str):
            inputs = [inputs]
        if not model or not isinstance(inputs, list) or not inputs \
                or not all(isinstance(t, str) for t in inputs):
            return web.json_response(
                {"error": "model and input are required"}, status=400)
        truncate = bool(body.get("truncate", True))
        resp, status = await self._route_embed(model, inputs, truncate)
        return web.json_response(resp, status=status)

    async def handle_embeddings(self, request: web.Request) -> web.Response:
        """POST /api/embeddings — legacy Ollama surface: {model, prompt}
        → {embedding: [...]}."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON"}, status=400)
        model = body.get("model", "")
        prompt = body.get("prompt", "")
        if not model or not prompt or not isinstance(prompt, str):
            return web.json_response(
                {"error": "model and prompt (a string) are required"},
                status=400)
        resp, status = await self._route_embed(
            model, [prompt], bool(body.get("truncate", True)))
        if status == 200:
            resp = {"embedding": resp["embeddings"][0]}
        return web.json_response(resp, status=status)

    async def _route_embed(self, model: str, inputs: list[str],
                           truncate: bool = True) -> tuple[dict, int]:
        msg = create_embed_request(model, inputs, truncate=truncate)
        from crowdllama_tpu.net import secure

        tid = new_trace_id()
        msg.trace_id = tid
        msg.parent_span = GATEWAY_ROOT_SPAN
        t0 = time.monotonic()
        self._perf["requests"] += 1
        acc: dict = {}
        self.obs.trace.begin(tid, node="gateway", model=model,
                             path="/api/embed")
        aead0 = secure.aead_stats()[0]
        status = 503
        served_by = ""
        try:
            tried: set[str] = set()
            last_err = "no workers available for model"
            for _attempt in range(2):  # retry once on next-best worker
                worker = self._find_worker(model, exclude=tried,
                                           require_embeddings=True, acc=acc)
                if worker is None:
                    break
                tried.add(worker.peer_id)
                try:
                    reply = await self._roundtrip(worker.peer_id, msg,
                                                  acc=acc)
                    resp = extract_embed_response(reply)
                    if resp.error.startswith("invalid:"):
                        # Deterministic client error (e.g. truncate=false
                        # input over the context window): 400, no retry.
                        status = 400
                        served_by = worker.peer_id
                        return {"error":
                                resp.error[len("invalid:"):].strip(),
                                "model": model}, 400
                    if resp.error:
                        raise RuntimeError(resp.error)
                    status = 200
                    served_by = worker.peer_id
                    return {
                        "model": model,
                        "embeddings": [list(e.values)
                                       for e in resp.embeddings],
                        "total_duration": resp.total_duration,
                        "prompt_eval_count": resp.prompt_tokens,
                        "worker_id": resp.worker_id,
                    }, 200
                except Exception as e:
                    last_err = str(e)
                    log.warning("embed via %s failed: %s",
                                worker.peer_id[:8], e)
            return {"error": f"embeddings failed: {last_err}",
                    "model": model}, 503
        finally:
            acc["aead_ns"] = max(0, secure.aead_stats()[0] - aead0)
            self._finish_trace(tid, acc, model, t0, status, served_by)

    async def _roundtrip(self, worker_id: str, msg, timeout: float = 600,
                         acc: dict | None = None,
                         frame: bytes | None = None):
        """Request/reply over a pooled (or fresh) inference stream.

        A pooled stream can be stale (worker idled it out or restarted):
        generation/embedding requests are stateless, so the failed attempt
        retries once on a fresh dial — reusing the ALREADY-ENCODED frame
        bytes — before surfacing the error.  ``frame`` lets _route pass
        natively pre-encoded request bytes (zero pb serialization here)."""
        if frame is None:
            frame = self._encode_frame(msg, acc=acc)
        s = self._pool_get(worker_id)
        if s is not None:
            try:
                await self._send_frame(s, frame, acc=acc)
                reply = await self._recv_pb(s, timeout=timeout, acc=acc)
                self._pool_put(worker_id, s)
                return reply
            except asyncio.CancelledError:
                s.close()
                raise
            except Exception as e:
                s.close()
                log.debug("pooled stream to %s stale (%s); redialing",
                          worker_id[:8], e)
        s = await self._dial(worker_id, acc=acc, trace_id=msg.trace_id)
        try:
            await self._send_frame(s, frame, acc=acc)
            reply = await self._recv_pb(s, timeout=timeout, acc=acc)
        except BaseException:
            s.close()
            raise
        self._pool_put(worker_id, s)
        return reply

    async def handle_pull(self, request: web.Request) -> web.Response:
        """POST /api/pull — Ollama clients call this when a model is absent.

        Resolution order: a healthy worker already serves the model →
        success immediately; otherwise the gateway PROXIES the pull to a
        worker (net/model_share.py "pull" op): that worker acquires the
        checkpoint peer-to-peer from whoever shares it and hot-registers
        it.  Only when no worker can acquire it does a clear error explain
        how models appear here."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON"}, status=400)
        name = body.get("model") or body.get("name") or ""
        if not name:
            return web.json_response({"error": "model is required"}, status=400)

        def _success(extra_lines=()):
            if not body.get("stream", True):
                # Non-streaming clients (ollama-python default) parse ONE
                # JSON body.
                return web.json_response({"status": "success"})
            lines = [{"status": "pulling manifest"}, *extra_lines,
                     {"status": "success"}]
            return web.Response(
                text="".join(json.dumps(line) + "\n" for line in lines),
                content_type="application/x-ndjson")

        # Same predicate routing uses: pull must not report success for a
        # model /api/chat would then 503 on.
        if self._find_worker(name) is not None:
            return _success()

        # Proxy to a worker that could acquire and serve it (best-scored
        # worker regardless of model; it pulls from whichever peer shares
        # the checkpoint — the swarm-native `ollama pull`).
        pull_err = "no workers available"
        pm = self.peer.peer_manager
        target = pm.find_best_worker("") if pm else None
        if target is not None:
            from crowdllama_tpu.net.model_share import request_pull

            try:
                contact = await self.peer.dht.find_peer(target.peer_id)
                if contact is None:
                    raise RuntimeError(
                        f"cannot resolve worker {target.peer_id[:8]}")
                path = await request_pull(self.peer.host, contact, name)
                return _success([{"status": f"pulled to {path} on worker "
                                            f"{target.peer_id[:8]}"}])
            except Exception as e:
                pull_err = str(e)
                log.warning("proxied pull of %s via %s failed: %s",
                            name, target.peer_id[:8], e)
        return web.json_response({
            "error": f"model {name!r} is not served by any worker and the "
                     f"swarm pull failed ({pull_err}); models are provided "
                     "by swarm workers (start one with "
                     f"--worker-mode --model {name})"}, status=404)

    async def handle_metrics(self, request: web.Request) -> web.Response:
        """GET /metrics — Prometheus text exposition of gateway + swarm
        state.  The machine-readable twin of /api/health (which mirrors the
        reference's JSON health map, gateway.go:426-461); the reference has
        no metrics endpoint."""
        lines = [
            "# TYPE crowdllama_gateway_requests_total counter",
        ]
        for (path, status), n in sorted(self._req_count.items()):
            lines.append(
                f'crowdllama_gateway_requests_total{{path="{path}",'
                f'status="{status}"}} {n}')
        lines.append("# TYPE crowdllama_gateway_request_seconds_total counter")
        for (path, status), s in sorted(self._req_seconds.items()):
            lines.append(
                f'crowdllama_gateway_request_seconds_total{{path="{path}",'
                f'status="{status}"}} {s:.6f}')
        pm = self.peer.peer_manager
        if pm is not None:
            workers = pm.get_workers()
            healthy = [p for p in workers if p.is_healthy]
            lines += [
                "# TYPE crowdllama_workers_total gauge",
                f"crowdllama_workers_total {len(workers)}",
                "# TYPE crowdllama_workers_healthy gauge",
                f"crowdllama_workers_healthy {len(healthy)}",
                "# TYPE crowdllama_worker_throughput_tokens_per_sec gauge",
                "# TYPE crowdllama_worker_load gauge",
                "# TYPE crowdllama_worker_healthy gauge",
            ]
            for p in workers:
                pid = p.peer_id[:16]
                r = p.resource
                lines.append(
                    f'crowdllama_worker_throughput_tokens_per_sec{{'
                    f'peer="{pid}"}} {r.tokens_throughput}')
                lines.append(
                    f'crowdllama_worker_load{{peer="{pid}"}} {r.load}')
                lines.append(
                    f'crowdllama_worker_healthy{{peer="{pid}"}} '
                    f'{1 if p.is_healthy else 0}')
        # Stream-path counters (host-level): how this node's streams
        # actually traveled — direct, relay-spliced, or reversed
        # (net/relay.py connection reversal).
        # Time-to-first-frame histogram for streamed inference, emitted
        # unconditionally (zeros before the first streamed request): an
        # absent series breaks absent()-style alerts and rate() windows
        # across restarts.
        lines.append("# TYPE crowdllama_gateway_ttfb_seconds histogram")
        acc = 0
        for le, n in zip(self._ttfb_le, self._ttfb_buckets):
            acc += n
            lines.append(
                f'crowdllama_gateway_ttfb_seconds_bucket{{le="{le}"}} '
                f"{acc}")
        lines.append(
            f'crowdllama_gateway_ttfb_seconds_bucket{{le="+Inf"}} '
            f"{self._ttfb_count}")
        lines.append(
            f"crowdllama_gateway_ttfb_seconds_sum {self._ttfb_sum:.6f}")
        lines.append(
            f"crowdllama_gateway_ttfb_seconds_count {self._ttfb_count}")
        lines.append("# TYPE crowdllama_gateway_stream_pool_hits_total counter")
        lines.append(
            f"crowdllama_gateway_stream_pool_hits_total "
            f"{self._stream_pool.hits}")
        lines.append(
            "# TYPE crowdllama_gateway_stream_pool_misses_total counter")
        lines.append(
            f"crowdllama_gateway_stream_pool_misses_total "
            f"{self._stream_pool.misses}")
        lines.append("# TYPE crowdllama_gateway_affinity_hits_total counter")
        lines.append(
            f"crowdllama_gateway_affinity_hits_total {self._affinity_hits}")
        lines.append(
            "# TYPE crowdllama_gateway_affinity_evicted_total counter")
        lines.append(
            f"crowdllama_gateway_affinity_evicted_total "
            f"{self._affinity_evicted}")
        lines.append(
            "# TYPE crowdllama_gateway_affinity_repointed_total counter")
        lines.append(
            f"crowdllama_gateway_affinity_repointed_total "
            f"{self._affinity_repointed}")
        lines.append("# TYPE crowdllama_gateway_kv_hints_total counter")
        lines.append(
            f"crowdllama_gateway_kv_hints_total {self._kv_hints}")
        lines.append(
            "# TYPE crowdllama_gateway_gossip_affinity_hits_total counter")
        lines.append(
            f"crowdllama_gateway_gossip_affinity_hits_total "
            f"{self._gossip_affinity_hits}")
        # Robustness plane (docs/ROBUSTNESS.md): failover/replay/shed/budget
        # counters plus dead-transport pool evictions.
        lines.append("# TYPE crowdllama_gateway_failovers_total counter")
        lines.append(
            f"crowdllama_gateway_failovers_total {self._robust['failovers']}")
        lines.append(
            "# TYPE crowdllama_gateway_replayed_chunks_total counter")
        lines.append(
            f"crowdllama_gateway_replayed_chunks_total "
            f"{self._robust['replayed_chunks']}")
        lines.append("# TYPE crowdllama_gateway_shed_total counter")
        lines.append(
            f"crowdllama_gateway_shed_total {self._robust['shed']}")
        lines.append(
            "# TYPE crowdllama_gateway_budget_exhausted_total counter")
        lines.append(
            f"crowdllama_gateway_budget_exhausted_total "
            f"{self._robust['budget_exhausted']}")
        lines.append(
            "# TYPE crowdllama_gateway_pool_evicted_dead_total counter")
        lines.append(
            f"crowdllama_gateway_pool_evicted_dead_total "
            f"{self._stream_pool.evicted_dead}")
        # Gray-failure immunity plane (docs/ROBUSTNESS.md): stalled-stream
        # watchdog teardowns, wedged-worker quarantines, and the hedged
        # first-token dispatch ledger (launched == won + cancelled is the
        # exactly-once conservation law the chaos soak asserts).
        lines.append(
            "# TYPE crowdllama_stall_aborted_streams_total counter")
        lines.append(
            f"crowdllama_stall_aborted_streams_total "
            f"{self._robust['stalled_streams']}")
        lines.append("# TYPE crowdllama_wedge_quarantines_total counter")
        lines.append(
            f"crowdllama_wedge_quarantines_total "
            f"{self._robust['wedge_quarantines']}")
        lines.append("# TYPE crowdllama_hedge_launched_total counter")
        lines.append(
            f"crowdllama_hedge_launched_total "
            f"{self._robust['hedge_launched']}")
        lines.append("# TYPE crowdllama_hedge_won_total counter")
        lines.append(
            f"crowdllama_hedge_won_total {self._robust['hedge_won']}")
        lines.append("# TYPE crowdllama_hedge_cancelled_total counter")
        lines.append(
            f"crowdllama_hedge_cancelled_total "
            f"{self._robust['hedge_cancelled']}")
        # Gateway-drafted speculative pipeline (docs/SPECULATIVE.md):
        # chunks/acks/nacks over the DraftChunk sub-protocol, plus the
        # offered-vs-accepted draft-token ledger (acceptance rate is
        # rate(accepted)/rate(offered)).
        lines.append("# TYPE crowdllama_draft_chunk_sent_total counter")
        lines.append(
            f"crowdllama_draft_chunk_sent_total "
            f"{self._spec_stats['chunks']}")
        lines.append("# TYPE crowdllama_draft_chunk_acks_total counter")
        lines.append(
            f"crowdllama_draft_chunk_acks_total "
            f"{self._spec_stats['acks']}")
        lines.append("# TYPE crowdllama_draft_chunk_nacked_total counter")
        lines.append(
            f"crowdllama_draft_chunk_nacked_total "
            f"{self._spec_stats['nacks']}")
        lines.append(
            "# TYPE crowdllama_draft_chunk_tokens_total counter")
        for outcome, key in (("offered", "offered"),
                             ("accepted", "accepted")):
            lines.append(
                f'crowdllama_draft_chunk_tokens_total{{outcome='
                f'"{outcome}"}} {self._spec_stats[key]}')
        # Request hot-path CPU attribution (ISSUE 1 tentpole d): cumulative
        # microseconds per phase; rate(phase)/rate(requests) is the
        # per-request cost.  aead_us is process-wide (net/secure.py).
        hp = self.hotpath_snapshot()
        lines.append(
            "# TYPE crowdllama_gateway_hotpath_us_total counter")
        for phase in ("route_us", "serde_us", "aead_us", "io_wait_us"):
            lines.append(
                f'crowdllama_gateway_hotpath_us_total{{phase='
                f'"{phase[:-3]}"}} {hp[phase]:.1f}')
        lines.append(
            "# TYPE crowdllama_gateway_hotpath_requests_total counter")
        lines.append(
            f"crowdllama_gateway_hotpath_requests_total {hp['requests']}")
        lines.append(
            "# TYPE crowdllama_route_snapshot_rebuilds_total counter")
        lines.append(
            f"crowdllama_route_snapshot_rebuilds_total "
            f"{hp['route_snapshot_rebuilds']}")
        # Swarm-uniform families (obs/): request/TTFT/decode-step
        # histograms + engine gauges — the same series a worker's
        # ObsServer exposes, so one dashboard reads every node.
        lines.extend(self.obs.metrics.expose())
        engine = getattr(self.peer, "engine", None)
        if engine is not None:
            try:
                lines.extend(engine_gauge_lines(engine.obs_gauges()))
            except Exception as e:
                log.debug("engine gauges unavailable: %s", e)
        # Engine compile/padding telemetry + device memory (PR 8): process
        # singletons, so a gateway co-located with an engine reports real
        # numbers and a pure consumer reports the zero series (present
        # families keep absent()-style alerts working).
        lines.extend(ENGINE_TELEMETRY.expose())
        lines.extend(device_memory_lines(
            getattr(engine, "on_device", False)))
        lines.extend(host_stat_lines(self.peer.host))
        lines.extend(native_metric_lines())
        # SLO burn-rate plane (PR 13): objective/burn-rate/fast-burn
        # gauges — the series swarm/autoscale.py parse_gauges consumes.
        lines.extend(self.slo.expose())
        return web.Response(text="\n".join(lines) + "\n",
                            content_type="text/plain")

    async def handle_metrics_cluster(self,
                                     request: web.Request) -> web.Response:
        """GET /metrics/cluster — the swarm-wide exposition (PR 13).

        Fans MetricsFetch out to every reachable worker over the
        authenticated p2p plane and re-exports each worker's families
        re-labeled with ``worker=``, plus pre-aggregated
        ``crowdllama_cluster_*`` rollups.  A dead or wedged worker costs a
        per-node timeout and one missing block — the snapshot is partial,
        never a 500.  ``?family=prefix`` (repeatable) narrows the scrape."""
        families = tuple(request.query.getall("family", []))
        text = await self.cluster.render(families)
        return web.Response(text=text, content_type="text/plain")

    async def handle_trace(self, request: web.Request) -> web.Response:
        """GET /debug/trace — JSON dump of the span ring buffer.

        ``?trace_id=`` filters to one trace, ``?limit=N`` keeps the N
        newest records (this node's fragment only — the stitched
        cross-node view lives at /debug/trace/<trace_id>)."""
        try:
            limit = max(0, int(request.query.get("limit", "0") or 0))
        except ValueError:
            limit = 0
        return web.json_response(self.obs.trace.snapshot(
            trace_id=request.query.get("trace_id", ""), limit=limit))

    async def handle_trace_stitched(self,
                                    request: web.Request) -> web.Response:
        """GET /debug/trace/<trace_id> — one clock-aligned cross-node span
        tree: this gateway's fragment as the root, plus every fragment a
        TraceFetch fan-out pulls from the swarm (workers, relay hosts)."""
        tid = request.match_info.get("trace_id", "")
        stitched = await self.collector.collect(tid)
        if stitched is None:
            return web.json_response(
                {"error": f"trace {tid!r} not found on any reachable node"},
                status=404)
        return web.json_response(stitched)

    async def handle_flightrecorder(self,
                                    request: web.Request) -> web.Response:
        """GET /debug/flightrecorder — the captured stitched traces of
        recent interesting requests, newest last."""
        return web.json_response(self.flight.snapshot())

    async def handle_unsupported(self, request: web.Request) -> web.Response:
        """Model management (delete/create/copy/push) has no meaning at the
        gateway: each worker owns its weights."""
        return web.json_response({
            "error": f"{request.path} is not supported: models are owned by "
                     "swarm workers, not the gateway"}, status=501)

    # -------------------------------------------------------------- routing

    def _find_worker(self, model: str, exclude: set[str] = frozenset(),
                     require_embeddings: bool = False,
                     acc: dict | None = None):
        pm = self.peer.peer_manager
        if pm is None:
            return None
        t0 = time.perf_counter_ns()
        try:
            return pm.find_best_worker(model, exclude=exclude,
                                       require_embeddings=require_embeddings)
        finally:
            dt = time.perf_counter_ns() - t0
            self._perf["route_ns"] += dt
            if acc is not None:
                acc["route_ns"] = acc.get("route_ns", 0) + dt

    # --------------------------------------------------- OpenAI-compat v1

    @staticmethod
    def _openai_error(message: str, status: int,
                      err_type: str = "invalid_request_error",
                      headers: dict | None = None):
        return web.json_response(
            {"error": {"message": message, "type": err_type,
                       "param": None, "code": None}}, status=status,
            headers=headers)

    @staticmethod
    def _openai_options(body: dict) -> dict:
        """OpenAI top-level params → Ollama-style options dict.

        Raises ``ValueError`` on wrong-typed params (handlers turn it into
        a 400 invalid_request_error, never an aiohttp 500).  Explicit
        ``null`` means "use the OpenAI default" — note `or`-folding would
        also clobber a legitimate temperature of 0."""
        def num(key, default, cast):
            v = body.get(key)
            return default if v is None else cast(v)

        stops = body.get("stop") or []
        if isinstance(stops, str):
            stops = [stops]
        elif not (isinstance(stops, list)
                  and all(isinstance(x, str) for x in stops)):
            raise ValueError("stop must be a string or list of strings")
        if num("n", 1, int) != 1:
            raise ValueError("only n=1 is supported")
        return {
            "num_predict": (num("max_completion_tokens", 0, int)
                            or num("max_tokens", 0, int)),
            # OpenAI's defaults (temperature 1, nucleus off).
            "temperature": num("temperature", 1.0, float),
            "top_p": num("top_p", 1.0, float),
            "seed": num("seed", 0, int),
            "stop": stops,
        }

    @staticmethod
    def _openai_message_text(content) -> str:
        """OpenAI message content may be a string OR a list of typed parts
        ([{"type": "text", "text": ...}, ...]) — flatten to text."""
        if isinstance(content, str):
            return content
        if isinstance(content, list):
            parts = []
            for p in content:
                if isinstance(p, dict) and p.get("type") == "text":
                    parts.append(str(p.get("text", "")))
                elif not isinstance(p, dict):
                    raise ValueError("invalid content part")
                else:
                    raise ValueError(
                        f"unsupported content part type "
                        f"{p.get('type')!r} (text only)")
            return "".join(parts)
        raise ValueError("message content must be a string or parts list")

    async def handle_openai_chat(self, request: web.Request):
        """POST /v1/chat/completions — the OpenAI chat API (Ollama serves
        the same alias; stock openai clients work against the gateway)."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return self._openai_error("invalid JSON body", 400)
        model = body.get("model", "")
        messages = body.get("messages", [])
        if not model or not isinstance(messages, list) or not messages:
            return self._openai_error("model and messages are required", 400)
        try:
            options = self._openai_options(body)
            messages = [
                {"role": str(m.get("role", "user")),
                 "content": self._openai_message_text(m.get("content", ""))}
                for m in messages if isinstance(m, dict)]
        except (ValueError, TypeError) as e:
            return self._openai_error(str(e), 400)
        if not messages:
            return self._openai_error("messages are required", 400)
        return await self._route(
            request, model, bool(body.get("stream", False)),
            options, messages=messages, shape="openai-chat")

    async def handle_openai_completions(self, request: web.Request):
        """POST /v1/completions — the legacy OpenAI completion API."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return self._openai_error("invalid JSON body", 400)
        model = body.get("model", "")
        prompt = body.get("prompt", "")
        if isinstance(prompt, list):
            if len(prompt) != 1 or not isinstance(prompt[0], str):
                return self._openai_error(
                    "only a single string prompt is supported", 400)
            prompt = prompt[0]
        if not model or not prompt:
            return self._openai_error("model and prompt are required", 400)
        try:
            options = self._openai_options(body)
        except (ValueError, TypeError) as e:
            return self._openai_error(str(e), 400)
        return await self._route(
            request, model, bool(body.get("stream", False)),
            options, prompt=prompt, shape="openai-completion")

    async def handle_openai_models(self, request: web.Request):
        """GET /v1/models — swarm-served models, OpenAI list shape."""
        pm = self.peer.peer_manager
        names: set[str] = set()
        if pm is not None:
            for p in pm.get_healthy_peers():
                if p.is_worker:
                    names.update(p.resource.supported_models)
        now = int(time.time())
        return web.json_response({
            "object": "list",
            "data": [{"id": m, "object": "model", "created": now,
                      "owned_by": "crowdllama"} for m in sorted(names)],
        })

    async def handle_openai_embeddings(self, request: web.Request):
        """POST /v1/embeddings — OpenAI embeddings shape over the swarm."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return self._openai_error("invalid JSON body", 400)
        model = body.get("model", "")
        inputs = body.get("input", "")
        if isinstance(inputs, str):
            inputs = [inputs]
        if not model or not isinstance(inputs, list) or not inputs \
                or not all(isinstance(t, str) for t in inputs):
            return self._openai_error("model and input are required", 400)
        resp, status = await self._route_embed(model, inputs)
        if status != 200:
            return self._openai_error(
                str(resp.get("error", "failed")), status,
                "invalid_request_error" if status < 500 else "server_error")
        return web.json_response({
            "object": "list",
            "model": model,
            "data": [{"object": "embedding", "index": i, "embedding": e}
                     for i, e in enumerate(resp["embeddings"])],
            "usage": {"prompt_tokens": resp.get("prompt_eval_count", 0),
                      "total_tokens": resp.get("prompt_eval_count", 0)},
        })

    # ------------------------------------------------------------- routing

    # --------------------------------------------------- prefix affinity

    _AFFINITY_TTL_S = 600.0  # engine prefix pages churn on LRU anyway
    _AFFINITY_MAX = 4096
    _AFFINITY_LOAD_CAP = 0.9

    @staticmethod
    def _affinity_key(model: str, messages, prompt: str):
        """Conversation fingerprint + whether this request is a
        CONTINUATION.

        The key hashes model + first message head + FIRST USER message
        head: a shared system prompt alone must not collapse every
        distinct conversation (and the scaling benchmark's identical
        single-message requests) onto one worker — different users of the
        same app differ in their first user turn, which every later turn
        of that conversation replays verbatim.  Affinity is only APPLIED
        to continuations (a second non-system turn exists): turn 1 has no
        cached prefix to reuse, so it routes by scoring and merely
        records where the conversation landed."""
        import hashlib

        if messages:
            m0 = messages[0]
            head = f"{m0.get('role', '')}:{str(m0.get('content', ''))[:256]}"
            users = [m for m in messages
                     if m.get("role", "") != "system"]
            if users:
                head += f"|u0:{str(users[0].get('content', ''))[:256]}"
            continuation = len(users) >= 2
        else:
            # /api/generate carries no turn structure: a key here would be
            # write-only (never consulted) and its churn would evict live
            # chat conversations from the bounded map.
            return None, False
        if not head:
            return None, False
        return (hashlib.sha1(f"{model}|{head}".encode()).hexdigest(),
                continuation)

    def _affinity_get(self, akey: str | None, model: str):
        """The remembered worker for this conversation, if it is still a
        routable (healthy, complete-group leader), non-saturated server
        of ``model``.  On a local miss the gossip map is consulted: a
        continuation whose first turns went through ANOTHER replica still
        routes to the worker holding its KV (the pin is seeded into the
        local LRU so later turns hit locally)."""
        if akey is None:
            return None
        entry = self._affinity.get(akey)
        if entry is None or time.monotonic() - entry[1] > self._AFFINITY_TTL_S:
            self._affinity.pop(akey, None)
            entry = None
            if self.gossip is not None:
                remote = self.gossip.lookup_affinity(
                    akey, max_age_s=self._AFFINITY_TTL_S)
                if remote is not None:
                    self._affinity_put(akey, remote[0])
                    self._gossip_affinity_hits += 1
                    entry = self._affinity.get(akey)
            if entry is None:
                return None
        self._affinity.move_to_end(akey)  # LRU touch: live conversation
        pm = self.peer.peer_manager
        cand = pm.is_routable(entry[0], model) if pm is not None else None
        if (cand is not None
                and getattr(cand.resource, "load", 0.0)
                < self._AFFINITY_LOAD_CAP):
            return cand
        return None

    def _affinity_put(self, akey: str | None, worker_id: str) -> None:
        if akey is None:
            return
        if akey not in self._affinity and \
                len(self._affinity) >= self._AFFINITY_MAX:
            self._affinity.popitem(last=False)
            self._affinity_evicted += 1
        self._affinity[akey] = (worker_id, time.monotonic())
        self._affinity.move_to_end(akey)
        if self.gossip is not None:
            # Mirror the pin into the replicated map so the OTHER
            # replicas route this conversation's continuations here too.
            self.gossip.record_affinity(akey, worker_id)

    def _affinity_drop_worker(self, worker_id: str,
                              successor: str = "") -> None:
        """Affinity hygiene on drain/removal: entries pinned to a worker
        that is leaving either re-point to its migration successor (whose
        cache holds the imported pages) or evict outright — a stale pin
        would burn a routing attempt per continuation until its TTL."""
        if not worker_id:
            return
        now = time.monotonic()
        for akey in [k for k, v in self._affinity.items()
                     if v[0] == worker_id]:
            if successor:
                self._affinity[akey] = (successor, now)
                self._affinity_repointed += 1
                if self.gossip is not None:
                    self.gossip.record_affinity(akey, successor)
            else:
                del self._affinity[akey]
                if self.gossip is not None:
                    self.gossip.drop_affinity(akey)

    def _kv_donor_for(self, akey: str | None, model: str,
                      chosen_worker: str) -> str:
        """Donor hint for a continuation that is NOT landing on its
        remembered worker: that worker's paged cache still holds the
        conversation's prefix, so the chosen worker can fetch the pages
        instead of recomputing them (docs/KV_TRANSFER.md).  Only a
        still-routable peer qualifies — hinting a dead donor would burn
        the fetch timeout on every request it's attached to."""
        if not self.kv_ship or akey is None:
            return ""
        entry = self._affinity.get(akey)
        if entry is None or time.monotonic() - entry[1] > self._AFFINITY_TTL_S:
            # Local miss: a donor hint remembered by ANOTHER replica is
            # just as good — its worker holds the conversation's pages.
            entry = None
            if self.gossip is not None:
                remote = self.gossip.lookup_affinity(
                    akey, max_age_s=self._AFFINITY_TTL_S)
                if remote is not None:
                    entry = (remote[0], time.monotonic())
            if entry is None:
                return ""
        if entry[0] == chosen_worker:
            return ""
        pm = self.peer.peer_manager
        if pm is None or pm.is_routable(entry[0], model) is None:
            return ""
        return entry[0]

    def _tenant_of(self, request: web.Request) -> str:
        """Tenant key for admission: the X-Tenant header, bounded through
        the same label hygiene as every exposition label (an attacker
        varying the header must not mint unbounded buckets/series)."""
        raw = request.headers.get("X-Tenant", "") or "default"
        return self.obs.metrics.tenant_guard.value(raw)

    async def _route(self, request, model, stream, options,
                     messages=None, prompt="",
                     shape="chat") -> web.StreamResponse:
        """Admission gate + inflight accounting around _route_admitted.

        Shedding happens BEFORE a trace id is minted or a worker touched:
        an overloaded gateway must answer 503 + Retry-After from pure
        in-memory state (docs/ROBUSTNESS.md).  With tenant quotas
        configured the global shed becomes per-tenant: a token bucket
        bounds each tenant's rate CLUSTER-WIDE (remote replicas' admits
        arrive as gossiped usage digests and drain the same buckets), and
        under inflight pressure a tenant at/above its weighted fair share
        of the cap is shed while lighter tenants keep being admitted —
        one hot tenant cannot starve the rest no matter which replica it
        hits."""
        tq = self.tenant_quotas
        tenant = self._tenant_of(request) if tq is not None else ""
        if self.admission_max_inflight \
                and self._inflight >= self.admission_max_inflight:
            if tq is not None:
                tq.shed_total += 1
                self.obs.metrics.tenant_inc(
                    self.obs.metrics.tenant_shed, tenant)
            return self._shed_response(
                shape, model,
                f"overloaded: {self._inflight} requests in flight "
                f"(admission cap {self.admission_max_inflight})")
        if tq is not None:
            if not tq.try_admit(tenant):
                self.obs.metrics.tenant_inc(
                    self.obs.metrics.tenant_shed, tenant)
                return self._shed_response(
                    shape, model,
                    f"tenant {tenant!r} over quota "
                    f"({tq.quotas.get(tenant, tq.quotas.get('default', 0))}"
                    f" req/s)")
            cap = self.admission_max_inflight
            if cap:
                active = {t for t, n in self._tenant_inflight.items()
                          if n > 0}
                share = tq.fair_share(tenant, cap, active)
                if self._tenant_inflight.get(tenant, 0) >= share:
                    self.obs.metrics.tenant_inc(
                        self.obs.metrics.tenant_shed, tenant)
                    return self._shed_response(
                        shape, model,
                        f"tenant {tenant!r} over fair share "
                        f"({share:.1f} of {cap} inflight)")
            self.obs.metrics.tenant_inc(
                self.obs.metrics.tenant_admitted, tenant)
        self._inflight += 1
        if tq is not None:
            self._tenant_inflight[tenant] = \
                self._tenant_inflight.get(tenant, 0) + 1
            self.obs.metrics.tenant_inflight[tenant] = \
                self._tenant_inflight[tenant]
        try:
            return await self._route_admitted(
                request, model, stream, options, messages=messages,
                prompt=prompt, shape=shape)
        finally:
            self._inflight -= 1
            if tq is not None:
                self._tenant_inflight[tenant] -= 1
                self.obs.metrics.tenant_inflight[tenant] = \
                    self._tenant_inflight[tenant]

    async def _route_admitted(self, request, model, stream, options,
                              messages=None, prompt="",
                              shape="chat") -> web.StreamResponse:
        req_kwargs = dict(
            model=model,
            prompt=prompt,
            stream=stream,
            messages=messages or (),
            max_tokens=int(options.get("num_predict", 0)),
            temperature=float(options.get("temperature", 0.0)),
            top_p=float(options.get("top_p", 1.0)),
            # Negative seeds are the conventional "random" sentinel
            # (clients commonly send -1) — map to 0 (unseeded) rather than
            # masking into a fixed reproducible value; oversize values clamp
            # into the proto's uint64 range instead of raising.
            seed=min(max(0, int(options.get("seed", 0))),
                     0xFFFFFFFFFFFFFFFF),
            # Ollama accepts a string or a list for options.stop.
            stop=([stops] if isinstance(
                stops := options.get("stop") or [], str) else
                [str(x) for x in stops]),
            # Clamp like seed: out-of-range/null client values must not
            # escape as proto setter errors.
            top_k=min(max(0, int(options.get("top_k", 0) or 0)), 2**31 - 1),
            repeat_penalty=max(0.0, float(
                options.get("repeat_penalty", 1.0) or 1.0)),
        )
        # pb-object construction is serde work: time it into serde_ns so
        # the native arm (scalar->frame, no pb build on the frame path)
        # and the pb arm attribute the same phase identically.
        t_build = time.perf_counter_ns()
        msg = create_generate_request(**req_kwargs)
        self._perf["serde_ns"] += time.perf_counter_ns() - t_build
        from crowdllama_tpu.net import secure

        # Mint the trace id here — the admission point every hop downstream
        # (stream pool, worker peer, engine, relay splice) inherits it from.
        tid = new_trace_id()
        msg.trace_id = tid
        msg.parent_span = GATEWAY_ROOT_SPAN
        # Speculative pipeline (docs/SPECULATIVE.md): flag streamed
        # generations as remote-draft so the worker opens the VerifyResult
        # sub-protocol.  Workers that don't support it (FakeEngine, old
        # builds) nack every chunk — the stream degrades to plain decode.
        if stream and self.spec_pipeline != "off":
            msg.generate_request.remote_draft = True

        # Size-aware dispatch (see wire.NATIVE_ENVELOPE_MIN_BYTES): short
        # prompts serialize faster through upb than through the ctypes
        # marshalling floor; both paths emit identical bytes.
        _req_payload_len = len(prompt) + sum(
            len(str(m.get("content", ""))) for m in (messages or ()))

        def _native_req_frame(kv_donor: str = "",
                              migrate: bool = False) -> bytes | None:
            """Pre-encode the request wire frame from the admission scalars
            (native fast path; byte-identical to _encode_frame(msg)).
            None → the per-attempt send falls back to pb serialization."""
            if _req_payload_len < wire.NATIVE_ENVELOPE_MIN_BYTES:
                return None
            t_enc = time.perf_counter_ns()
            try:
                f = wire.encode_genreq_frame(
                    **req_kwargs, kv_donor=kv_donor, migrate=migrate,
                    trace_id=tid, parent_span=GATEWAY_ROOT_SPAN)
            except wire.WireError:
                # Oversize raises at the same boundary on the pb path —
                # let _encode_frame produce the identical error there.
                return None
            dt = time.perf_counter_ns() - t_enc
            if f is not None:
                self._perf["serde_ns"] += dt
                acc["serde_ns"] = acc.get("serde_ns", 0) + dt
            return f
        t0 = time.monotonic()  # TTFB measures from ADMISSION, retries included
        # Total wall-clock budget, charged across every retry/failover this
        # request pays (docs/ROBUSTNESS.md): routing, dials, handshakes and
        # decode all race the same deadline.
        budget = self._budget(request)
        deadline = t0 + budget
        self._perf["requests"] += 1
        acc: dict = {}
        # Encode the request frame once at admission (native path); the
        # common attempt (no donor, no migrate) reuses it verbatim and
        # skips per-attempt pb serialization entirely.
        base_frame = _native_req_frame()
        self.obs.trace.begin(tid, node="gateway", model=model,
                             path=request.path, stream=stream)
        aead0 = secure.aead_stats()[0]
        status = 503
        served_by = ""
        sctx = _StreamCtx(shape)
        budget_out = False
        prev_worker = ""
        died_at = 0.0
        try:
            tr = time.perf_counter_ns()
            akey, continuation = self._affinity_key(model, messages, prompt)
            self._perf["route_ns"] += time.perf_counter_ns() - tr
            acc["route_ns"] = acc.get("route_ns", 0) \
                + time.perf_counter_ns() - tr
            tried: set[str] = set()
            last_err = "no workers available for model"
            attempt = 0
            max_attempts = 2  # retry once on next-best worker
            # Live migration (docs/ROBUSTNESS.md): a worker that announced
            # drain becomes the successor's KV donor, and the handoff is
            # granted ONE extra attempt beyond the ordinary retry budget.
            forced_donor = ""
            drained_worker = ""
            drain_extra_granted = False
            while attempt < max_attempts:
                attempt += 1
                now = time.monotonic()
                if now >= deadline:
                    budget_out = True
                    break
                worker = None
                used_affinity = False
                tr = time.perf_counter_ns()
                affine = (self._affinity_get(akey, model)
                          if continuation else None)
                dt_aff = time.perf_counter_ns() - tr
                self._perf["route_ns"] += dt_aff
                acc["route_ns"] = acc.get("route_ns", 0) + dt_aff
                if affine is not None and affine.peer_id not in tried:
                    worker = affine
                    used_affinity = True
                if worker is None:
                    worker = self._find_worker(model, exclude=tried, acc=acc)
                if worker is None:
                    break
                tried.add(worker.peer_id)
                # Affinity miss on a continuation: attach the remembered
                # worker as a KV donor so the chosen one fetches the shared
                # prefix's pages instead of recomputing them.  Reset per
                # attempt — a failover target may BE the donor.
                msg.generate_request.kv_donor = ""
                msg.generate_request.migrate = False
                if forced_donor and forced_donor != worker.peer_id:
                    # MIGRATION: the drained worker stays alive as a KV
                    # donor through its drain window, so the successor
                    # fetches the prompt's pages instead of re-running
                    # prefill (fetch-instead-of-recompute).
                    msg.generate_request.kv_donor = forced_donor
                    msg.generate_request.migrate = True
                elif continuation and not used_affinity:
                    donor = self._kv_donor_for(akey, model, worker.peer_id)
                    if donor:
                        msg.generate_request.kv_donor = donor
                        self._kv_hints += 1
                        self.obs.trace.record(
                            tid, "kv_hint", 0, parent=GATEWAY_ROOT_SPAN,
                            donor=donor[:8], worker=worker.peer_id[:8])
                gr = msg.generate_request
                if sctx.out is not None and getattr(gr, "remote_draft",
                                                    False):
                    # Failover replay runs plain: the in-flight draft
                    # window died with the worker, and the replay-trim
                    # contract only covers text frames.  Token replay
                    # resynchronizes the client; a fresh request would
                    # re-enter the pipeline from scratch.
                    gr.remote_draft = False
                req_frame = None
                if not getattr(gr, "remote_draft", False):
                    # The native encoder has no remote_draft field — a
                    # remote-draft request must take the pb path so the
                    # flag survives serialization.
                    req_frame = (base_frame
                                 if not gr.kv_donor and not gr.migrate
                                 else _native_req_frame(gr.kv_donor,
                                                        gr.migrate))
                if sctx.out is not None:
                    # MID-STREAM FAILOVER: headers (and sent_text chars)
                    # already reached the client from a worker that then
                    # died — replay on the next-best worker and resume the
                    # same response (docs/ROBUSTNESS.md).  The trace id is
                    # reused on purpose: one client request, one trace.
                    self._robust["failovers"] += 1
                    self.obs.trace.record(
                        tid, "failover",
                        int(max(0.0, now - died_at) * 1e9),
                        parent=GATEWAY_ROOT_SPAN,
                        from_worker=prev_worker[:8],
                        to_worker=worker.peer_id[:8])
                    log.warning(
                        "failing stream over %s -> %s (replaying %d "
                        "delivered chars)", prev_worker[:8],
                        worker.peer_id[:8], len(sctx.sent_text))
                try:
                    resp = await self._forward(request, worker.peer_id, msg,
                                               stream, shape, t0, acc=acc,
                                               ctx=sctx, deadline=deadline,
                                               req_frame=req_frame)
                    # Hedged dispatch may have delivered the stream from a
                    # different worker than the one routing picked — pin
                    # the affinity (and attribute the trace) to whoever
                    # actually produced the tokens.
                    winner_id = sctx.winner or worker.peer_id
                    self._affinity_put(akey, winner_id)
                    if drained_worker and drained_worker != winner_id:
                        # Every conversation pinned to the drained worker
                        # re-points to the successor that absorbed the
                        # handoff (satellite: affinity hygiene).
                        self._affinity_drop_worker(drained_worker,
                                                   successor=winner_id)
                    if used_affinity and winner_id == worker.peer_id:
                        # Counted only when the pinned route actually
                        # served: a failed forward falls back to scoring
                        # and must not inflate the hit counter.
                        self._affinity_hits += 1
                    served_by = winner_id
                    status = resp.status
                    return resp
                except _StreamStarted as e:
                    # The CLIENT side of the stream failed (disconnect,
                    # write error): no retry, no failover, no second
                    # response — nobody is listening.  The prefill still
                    # populated this worker's prefix cache, so the
                    # affinity record stays useful.
                    winner_id = sctx.winner or worker.peer_id
                    self._affinity_put(akey, winner_id)
                    if used_affinity and winner_id == worker.peer_id:
                        self._affinity_hits += 1
                    log.warning("stream to client aborted mid-flight: %s",
                                e.cause)
                    served_by = winner_id
                    status = e.response.status
                    return e.response
                except _BudgetExhausted as e:
                    last_err = str(e) or "request budget exhausted"
                    budget_out = True
                    break
                except _WorkerDraining as e:
                    # A drain is a deliberate handoff, not a failure:
                    # quarantine the worker from routing immediately (epoch
                    # bump derails other in-flight routing at the snapshot),
                    # grant the handoff one extra attempt, and carry the
                    # drained worker forward as the successor's KV donor.
                    last_err = str(e)
                    pm = self.peer.peer_manager
                    mark = getattr(pm, "mark_draining", None)
                    if mark is not None:
                        mark(e.worker_id)
                    forced_donor = e.worker_id
                    drained_worker = e.worker_id
                    if not drain_extra_granted:
                        drain_extra_granted = True
                        max_attempts += 1
                    if e.migrated:
                        self.obs.metrics.migrated_streams += 1
                    self.obs.trace.record(
                        tid, "migrate", 0, parent=GATEWAY_ROOT_SPAN,
                        from_worker=e.worker_id[:8],
                        mid_stream=e.migrated,
                        delivered_tokens=e.delivered_tokens)
                    prev_worker = e.worker_id
                    died_at = time.monotonic()
                    log.info(
                        "worker %s draining; re-routing with KV handoff "
                        "(mid_stream=%s, delivered_tokens=%d)",
                        e.worker_id[:8], e.migrated, e.delivered_tokens)
                except _StreamStalled as e:
                    # GRAY FAILURE: the worker holds the transport open
                    # but stopped producing frames past the stall budget.
                    # Unlike a crash there is no EOF — the watchdog turns
                    # silence into an actionable death: quarantine the
                    # worker as WEDGED (it may still answer health
                    # probes, so an ordinary probe would never evict it)
                    # and fail the stream over like any worker death.
                    last_err = str(e)
                    self._robust["stalled_streams"] += 1
                    pm = self.peer.peer_manager
                    mark = getattr(pm, "mark_draining", None)
                    if mark is not None and mark(e.worker_id,
                                                 reason="wedged"):
                        self._robust["wedge_quarantines"] += 1
                    self.obs.trace.record(
                        tid, "wedged", 0, parent=GATEWAY_ROOT_SPAN,
                        worker=e.worker_id[:8], phase=e.phase)
                    prev_worker = e.worker_id
                    died_at = time.monotonic()
                    log.warning(
                        "worker %s stalled (%s phase); quarantined as "
                        "wedged, failing stream over", e.worker_id[:8],
                        e.phase)
                except Exception as e:
                    # Worker-side failure (pre- OR mid-stream): eligible
                    # for retry/failover on the next-best worker.
                    last_err = str(e)
                    prev_worker = worker.peer_id
                    died_at = time.monotonic()
                    log.warning("worker %s failed: %s", worker.peer_id[:8], e)
            if budget_out:
                self._robust["budget_exhausted"] += 1
            if sctx.out is not None:
                # Headers already out and every attempt exhausted: finish
                # the started stream with a terminal error frame instead
                # of dropping the connection mid-body.
                status = sctx.out.status
                detail = (f"request budget exhausted after {budget:.1f}s"
                          if budget_out else f"inference failed: {last_err}")
                served_by = prev_worker
                return await self._terminal_error_frame(
                    sctx, shape, model, detail)
            if budget_out:
                status = 504
                detail = (f"deadline exceeded: request budget "
                          f"{budget:.1f}s exhausted ({last_err})")
                if shape.startswith("openai"):
                    return self._openai_error(detail, 504, "server_error")
                return web.json_response(
                    {"error": detail, "model": model}, status=504)
            if "overloaded" in last_err:
                # Worker-side admission rejection (scheduler pending depth
                # over threshold): shed with the same 503 + Retry-After
                # contract as the gateway's own cap.
                status = 503
                return self._shed_response(
                    shape, model, f"inference failed: {last_err}")
            if shape.startswith("openai"):
                return self._openai_error(
                    f"inference failed: {last_err}", 503, "server_error")
            return web.json_response(
                {"error": f"inference failed: {last_err}", "model": model},
                status=503)
        finally:
            acc["aead_ns"] = max(0, secure.aead_stats()[0] - aead0)
            self._finish_trace(tid, acc, model, t0, status, served_by)

    def _finish_trace(self, tid: str, acc: dict, model: str, t0: float,
                      status: int, worker_id: str = "") -> None:
        """Flush one routed request's accumulated phase timings into its
        trace record and the request_seconds histogram.  The aead figure is
        a process-wide delta over the request window (net/secure.py keeps
        module counters), so concurrent requests' seal/open time can bleed
        into each other's span — fine for attribution, not for billing."""
        total_ns = int((time.monotonic() - t0) * 1e9)
        tr = self.obs.trace
        for phase in _GW_PHASES:
            tr.record(tid, phase, acc.get(phase + "_ns", 0),
                      parent=GATEWAY_ROOT_SPAN)
        for phase in _GW_OPT_PHASES:
            if acc.get(phase + "_ns"):
                tr.record(tid, phase, acc[phase + "_ns"],
                          parent=GATEWAY_ROOT_SPAN)
        tr.finish(tid, total_ns, status=status,
                  worker=worker_id[:8] if worker_id else "")
        hist = self.obs.metrics.request_seconds.labels(model)
        total_s = total_ns / 1e9
        # Flight-recorder decision BEFORE observing this request: a tail
        # request must be compared against the p99 of everything before it,
        # not a distribution it already dragged upward.
        reasons = self._flight_reasons(tid, hist, total_s, status)
        hist.observe(total_s, exemplar=tid)
        if reasons:
            self._flight_capture(tid, reasons)

    def _flight_reasons(self, tid: str, hist, total_s: float,
                        status: int) -> list[str]:
        """Why this request is interesting enough for the flight recorder
        (empty = it is not).  Gateway-visible triggers only; worker-side
        kv-ship fallbacks are confirmed post-stitch in _flight_capture."""
        reasons: list[str] = []
        if hist.count >= self._flight_min_count \
                and total_s > hist.quantile(0.99):
            reasons.append("p99_latency")
        if status >= 500:
            reasons.append(f"status_{status}")
        if status == 504:
            # Budget exhaustion gets its own reason on top of status_504
            # so the recorder ring is filterable by failure mode.
            reasons.append("budget_exhausted")
        if self.slo.enabled:
            # Edge-triggered: only the request that TIPS the SLO into
            # fast burn is captured, not every request inside an episode.
            before = self.slo.fast_burn_episodes_total
            if self.slo.fast_burn() \
                    and self.slo.fast_burn_episodes_total > before:
                reasons.append("slo_fast_burn")
        rec = self.obs.trace.get(tid)
        if rec is not None:
            names = {s.get("name", "") for s in rec.get("spans", [])}
            if "failover" in names:
                reasons.append("failover")
            if "migrate" in names:
                reasons.append("migrate")
            if "wedged" in names:
                # A gray failure the progress watchdog converted into a
                # failover: the stitched trace shows WHERE the stream
                # stalled (ttft vs decode) and which worker was
                # quarantined (docs/ROBUSTNESS.md).
                reasons.append("wedged")
            if "kv_hint" in names:
                # Candidate only: kept iff the stitched worker fragment
                # shows the donor fetch actually fell back.
                reasons.append("kv_hint")
        return reasons

    def _flight_capture(self, tid: str, reasons: list[str]) -> None:
        """Stitch + capture asynchronously: the fan-out must never sit on
        the request path (we are inside _route's finally)."""
        if (self.flight.get(tid) is not None
                or self._flight_inflight >= self._flight_max_inflight):
            return
        self._flight_inflight += 1

        async def _go() -> None:
            try:
                stitched = await self.collector.collect(tid)
            except Exception as e:
                log.debug("flight-recorder stitch for %s failed: %s",
                          tid, e)
                return
            finally:
                self._flight_inflight -= 1
            if stitched is None:
                return
            final = list(reasons)
            if "kv_hint" in final:
                final.remove("kv_hint")
                if any(s.get("name") == "kv_fetch"
                       and (s.get("meta", {}).get("fallback")
                            or s.get("meta", {}).get("error"))
                       for s in stitched.get("spans", [])):
                    final.append("kv_ship_fallback")
            if final:
                self.flight.capture(tid, final, stitched)

        asyncio.ensure_future(_go())

    def _observe_ttfb(self, dt: float, tid: str = "") -> None:
        for i, le in enumerate(self._ttfb_le):
            if dt <= le:
                self._ttfb_buckets[i] += 1
                break
        else:
            self._ttfb_buckets[-1] += 1
        self._ttfb_sum += dt
        self._ttfb_count += 1
        self.obs.metrics.ttft_seconds.observe(dt, exemplar=tid)
        self.slo.observe_ttft(dt)

    async def _terminal_error_frame(self, ctx: _StreamCtx, shape: str,
                                    model: str,
                                    message: str) -> web.StreamResponse:
        """Every attempt exhausted AFTER headers went out: end the started
        stream with a well-formed terminal error frame (Ollama NDJSON error
        line / OpenAI SSE error event + [DONE]) instead of dropping the
        connection mid-body.  Client write failures here are moot — nobody
        is listening — hence the blanket suppress."""
        out = ctx.out
        try:
            if shape.startswith("openai"):
                line = json.dumps({"error": {
                    "message": message, "type": "server_error"}}).encode()
                await out.write(b"data: " + line + b"\n\n")
                await out.write(b"data: [DONE]\n\n")
            else:
                line = json.dumps({
                    "model": model,
                    "created_at": _now_rfc3339(),
                    "done": True, "done_reason": "error",
                    "error": message,
                }).encode()
                await out.write(line + b"\n")
            await out.write_eof()
        except Exception:
            pass
        return out

    # ------------------------------------- gray-failure immunity plane

    def _stall_budget(self, phase: str) -> float:
        """Seconds of token-progress silence tolerated in ``phase``
        ("ttft" | "decode") before the stream is declared stalled
        (0.0 = watchdog off).  The live SLO objective raises the floor:
        a stall deadline must never be tighter than the latency the
        operator promised clients for the same phase."""
        if self.stream_stall_ms <= 0:
            return 0.0
        ms = self.stream_stall_ms
        tr = self.slo.trackers.get(phase)
        if tr is not None and tr.objective_ms > ms:
            ms = tr.objective_ms
        return ms / 1000.0

    def _hedge_threshold(self) -> float:
        """Seconds of first-token silence before a hedge launches
        (0.0 = hedging off).  The LIVE TTFT p95 raises the configured
        floor once the histogram has enough mass (same observation floor
        the flight recorder uses), falling back to the SLO TTFT
        objective — so "slow" always means slow RELATIVE TO THE SWARM,
        and a uniformly slow model does not trigger a hedge storm."""
        if self.hedge_ttft_ms <= 0:
            return 0.0
        thr = self.hedge_ttft_ms / 1000.0
        hist = self.obs.metrics.ttft_seconds
        if hist.count >= self._flight_min_count:
            thr = max(thr, hist.quantile(0.95))
        else:
            tr = self.slo.trackers.get("ttft")
            if tr is not None:
                thr = max(thr, tr.objective_ms / 1000.0)
        return thr

    def _classify_frame(self, raw, worker_id: str):
        """Decode one inference-stream frame, surfacing drain/handoff
        frames as _WorkerDraining so _route re-routes with the drained
        worker attached as KV donor (checked BEFORE the generate
        extraction: a MigrateFrame is a different oneof arm)."""
        if raw.WhichOneof("message") == "migrate_frame":
            mf = raw.migrate_frame
            raise _WorkerDraining(worker_id, migrated=True,
                                  delivered_tokens=mf.delivered_tokens)
        resp = extract_generate_response(raw)
        if resp.done and resp.done_reason == "draining":
            raise _WorkerDraining(worker_id)
        return resp

    async def _open_stream(self, worker_id: str, msg, frame: bytes,
                           deadline: float | None, stall_ttft: float,
                           acc: dict, use_pool: bool = True,
                           vsink: list | None = None):
        """Open an inference stream to ``worker_id``, send the encoded
        ``frame`` and read the FIRST response frame; returns
        ``(stream, first_resp)`` with the caller owning the stream.

        Pooled stream first (a stale one — worker idled it out or
        restarted — gets ONE fresh redial), fresh dial otherwise.  Every
        receive is clamped to ``stall_ttft`` when the progress watchdog
        is armed: a worker that accepted the request and went silent
        surfaces as _StreamStalled rather than a redial — a second dial
        would burn another full stall budget on the same wedged worker.
        Cancellation (hedge race lost) closes the stream before any of
        its frames can reach a client."""
        def remaining() -> float:
            return (deadline - time.monotonic()) if deadline is not None \
                else 600.0

        def _recv_timeout() -> float:
            t = max(0.05, min(600.0, remaining()))
            return min(t, stall_ttft) if stall_ttft > 0 else t

        async def _first(s):
            """First NON-verify frame: a remote-draft worker yields the
            VerifyResult handshake before its first text frame — divert
            those into vsink for the pump instead of classifying them."""
            while True:
                raw = await self._recv_pb(s, timeout=_recv_timeout(),
                                          acc=acc)
                if (vsink is not None
                        and raw.WhichOneof("message") == "verify_result"):
                    vsink.append(raw.verify_result)
                    continue
                return self._classify_frame(raw, worker_id)

        s = self._pool_get(worker_id) if use_pool else None
        if s is not None:
            try:
                await self._send_frame(s, frame, acc=acc)
                return s, await _first(s)
            except (asyncio.CancelledError, _WorkerDraining):
                # A draining reject is a DELIBERATE answer, not a stale
                # pooled stream: no redial (it would get the same
                # reject).  A cancel means the hedge race was lost.
                s.close()
                raise
            except asyncio.TimeoutError as e:
                s.close()
                if remaining() <= 0:
                    raise _BudgetExhausted(
                        "budget exhausted on pooled attempt") from e
                if stall_ttft > 0:
                    raise _StreamStalled(worker_id, "ttft") from e
                raise
            except Exception as e:
                s.close()
                if remaining() <= 0:
                    raise _BudgetExhausted(
                        "budget exhausted on pooled attempt") from e
                log.debug("pooled stream to %s stale (%s); redialing",
                          worker_id[:8], e)
        s = await self._dial(worker_id, acc=acc,
                             timeout=(remaining()
                                      if deadline is not None else None),
                             trace_id=msg.trace_id)
        try:
            await self._send_frame(s, frame, acc=acc)
            return s, await _first(s)
        except BaseException as e:
            s.close()
            if (isinstance(e, (asyncio.TimeoutError, OSError))
                    and remaining() <= 0):
                raise _BudgetExhausted(
                    "budget exhausted during dial/first frame") from e
            if isinstance(e, asyncio.TimeoutError) and stall_ttft > 0:
                raise _StreamStalled(worker_id, "ttft") from e
            raise

    async def _hedge_race(self, primary_id: str, msg, frame: bytes,
                          deadline: float | None, stall_ttft: float,
                          acc: dict, hedge_thr: float):
        """Hedged first-token dispatch (docs/ROBUSTNESS.md): give the
        primary worker ``hedge_thr`` seconds to produce a first frame;
        past it, speculatively dispatch the SAME request to the
        second-best worker and deliver whichever stream wins the race.

        EXACTLY-ONCE: _open_stream returns at the first frame — nothing
        reaches the client until a single winner is chosen, and every
        loser is cancelled/closed before its first byte could be
        written.  Counter conservation (asserted by the chaos soak):
        hedge_launched == hedge_won + hedge_cancelled.

        Returns ``(stream, first_resp, winner_worker_id)``."""
        tid = msg.trace_id
        p_task = asyncio.ensure_future(self._open_stream(
            primary_id, msg, frame, deadline, stall_ttft, acc))
        tasks: dict[asyncio.Task, str] = {p_task: primary_id}
        launched = False
        try:
            done, _ = await asyncio.wait({p_task}, timeout=hedge_thr)
            if not done:
                # First token is late relative to the swarm: launch the
                # hedge on the next-best worker.  Never pooled — the
                # pool hands out per-worker streams, but this request
                # may be abandoned mid-frame by a cancel, which poisons
                # a reusable transport.
                alt = self._find_worker(msg.generate_request.model,
                                        exclude={primary_id}, acc=acc)
                if alt is not None:
                    launched = True
                    self._robust["hedge_launched"] += 1
                    self.obs.trace.record(
                        tid, "hedge", 0, parent=GATEWAY_ROOT_SPAN,
                        primary=primary_id[:8], hedge=alt.peer_id[:8])
                    tasks[asyncio.ensure_future(self._open_stream(
                        alt.peer_id, msg, frame, deadline, stall_ttft,
                        acc, use_pool=False))] = alt.peer_id
            winner = None
            primary_err: BaseException | None = None
            while tasks and winner is None:
                done, _ = await asyncio.wait(
                    set(tasks), return_when=asyncio.FIRST_COMPLETED)
                for t in done:
                    wid = tasks.pop(t)
                    err = t.exception()
                    if err is None:
                        if winner is None:
                            winner = (await t, wid)
                        else:
                            # Two first frames landed in the same wait
                            # round: the second is a loser like any
                            # other — close before any byte escapes.
                            (await t)[0].close()
                        continue
                    if isinstance(err, _WorkerDraining):
                        # A loser's drain announcement must still
                        # quarantine it — the observation is real even
                        # though the race discards the attempt.
                        pm = self.peer.peer_manager
                        mark = getattr(pm, "mark_draining", None)
                        if mark is not None:
                            mark(err.worker_id)
                    if wid == primary_id:
                        primary_err = err
            if winner is None:
                # Both sides failed: the hedge did not win, account it
                # as cancelled (conservation) and surface the PRIMARY's
                # error so _route's ladder sees the same failure mode an
                # unhedged attempt would have produced.
                if launched:
                    self._robust["hedge_cancelled"] += 1
                if primary_err is not None:
                    raise primary_err
                raise RuntimeError("hedged dispatch failed on every leg")
            (s, first_resp), wid = winner
            if launched:
                if wid == primary_id:
                    self._robust["hedge_cancelled"] += 1
                else:
                    self._robust["hedge_won"] += 1
            return s, first_resp, wid
        finally:
            # Tear down every leg still racing — the loser BEFORE its
            # first byte reaches the client — and reap a straggler that
            # completed between the winner landing and the cancel.
            for t in tasks:
                t.cancel()
            if tasks:
                reaped = await asyncio.gather(*tasks,
                                              return_exceptions=True)
                for r in reaped:
                    if isinstance(r, tuple):
                        r[0].close()

    def _drafter(self):
        """The gateway's local draft model, loaded lazily on the first
        remote-draft stream.  Returns None in "worker" mode or when the
        checkpoint is unusable — the pump then sends pure ack credits and
        the stream still paces the worker (worker-draft speculation)."""
        if self.spec_pipeline != "gateway":
            return None
        if self._spec_drafter is None and not self._spec_drafter_tried:
            self._spec_drafter_tried = True
            if not self.spec_draft_path:
                log.warning("spec_pipeline=gateway with no draft "
                            "checkpoint; degrading to ack pacing")
            else:
                try:
                    from crowdllama_tpu.gateway.draft import GatewayDrafter

                    self._spec_drafter = GatewayDrafter.from_checkpoint(
                        self.spec_draft_path)
                    log.info("gateway draft model loaded from %s",
                             self.spec_draft_path)
                except Exception as e:
                    log.warning("gateway draft load failed (%s); "
                                "degrading to ack pacing", e)
        return self._spec_drafter

    def _spec_pump(self, s, msg, acc: dict, worker_id: str = ""):
        """Build the per-stream draft pump wired to ``s``'s writer,
        warm-starting its depth controller from the last stream to the
        same worker (the wire doesn't change between streams)."""
        from crowdllama_tpu.gateway.draft import SpecPipelinePump

        async def _send(frame: bytes) -> None:
            await self._send_frame(s, frame, acc=acc)

        pump = SpecPipelinePump(model=msg.generate_request.model,
                                send=_send, drafter=self._drafter())
        wire = self._spec_wire.get(worker_id)
        if wire is not None:
            pump.ctrl.rtt_ewma, pump.ctrl.step_ewma = wire
        return pump

    async def _forward(self, request, worker_id: str, msg, stream: bool,
                       shape: str, t0: float,
                       acc: dict | None = None,
                       ctx: _StreamCtx | None = None,
                       deadline: float | None = None,
                       req_frame: bytes | None = None) -> web.StreamResponse:
        """Open an inference stream to the worker and relay the reply
        (gateway.go:243-298).  ``shape`` picks the client dialect:
        Ollama NDJSON ("chat"/"generate") or OpenAI SSE ("openai-*").
        ``t0`` is the _route admission time: the TTFB histogram must
        charge failed-worker retries to the request, not reset on them.
        ``acc`` is the per-request phase accumulator from _route.

        ``ctx`` carries the client-side stream state across worker
        attempts: on a FAILOVER call (ctx.out already prepared) the reply
        is replayed and trimmed against ctx.sent_text so the client never
        sees a duplicated or missing character.  ``deadline`` is the
        absolute monotonic cutoff from the request's wall-clock budget —
        every dial/handshake/recv below is clamped to what remains of it,
        and expiry surfaces as _BudgetExhausted."""
        if acc is None:
            acc = {}
        if ctx is None:
            ctx = _StreamCtx(shape)
        openai = shape.startswith("openai")

        def remaining() -> float:
            return (deadline - time.monotonic()) if deadline is not None \
                else 600.0

        def _recv_timeout(stall: float = 0.0) -> float:
            t = max(0.05, min(600.0, remaining()))
            return min(t, stall) if stall > 0 else t

        def render(resp, final: bool) -> dict:
            if openai:
                d = self._openai_json(resp, shape, final, stream, ctx.rid,
                                      ctx.created, first=ctx.nth == 0)
                ctx.nth += 1
                return d
            return self._ollama_json(resp, shape == "chat", final=final)

        def classify(raw):
            # Late-bound worker_id on purpose: a hedge win reassigns it
            # to the worker actually serving the decode loop.
            return self._classify_frame(raw, worker_id)

        if not stream:
            resp = classify(await self._roundtrip(
                worker_id, msg, timeout=_recv_timeout(), acc=acc,
                frame=req_frame))
            if resp.done_reason == "error":
                raise RuntimeError(resp.response)
            return web.json_response(render(resp, final=True))

        # Streamed: one NDJSON line (Ollama) or SSE data event (OpenAI)
        # per chunk.  The FIRST frame is read before sending headers
        # (_open_stream), so a worker that dies immediately is still
        # retryable by _route — and a STALE pooled stream is detected
        # while a fresh redial is still possible.  When the per-stream
        # progress watchdog is armed, every receive below is clamped to
        # the phase's stall budget: a worker holding the transport open
        # without producing frames surfaces as _StreamStalled instead of
        # hanging until the request budget dies (docs/ROBUSTNESS.md).
        stall_ttft = self._stall_budget("ttft")
        stall_decode = self._stall_budget("decode")
        if remaining() <= 0:
            raise _BudgetExhausted("budget exhausted before dial")
        frame = req_frame if req_frame is not None \
            else self._encode_frame(msg, acc=acc)
        # Speculative pipeline (docs/SPECULATIVE.md): a remote-draft
        # stream interleaves VerifyResult frames with the text frames.
        # Those feed the draft pump (which answers with DraftChunk
        # frames) and never reach the client; hedging is disabled —
        # a raced duplicate would double-consume the draft window — and
        # the stream is never pooled (the sub-protocol is one-shot on
        # the worker side too).
        rd = bool(getattr(msg.generate_request, "remote_draft", False))
        vsink: list | None = [] if rd else None
        # Hedged first-token dispatch: only on the FIRST attempt of a
        # stream — a failover replay already has client bytes out, and
        # failover itself covers that tail.
        hedge_thr = (self._hedge_threshold()
                     if (ctx.out is None and not rd) else 0.0)
        if hedge_thr > 0:
            s, first, worker_id = await self._hedge_race(
                worker_id, msg, frame, deadline, stall_ttft, acc,
                hedge_thr)
        else:
            s, first = await self._open_stream(
                worker_id, msg, frame, deadline, stall_ttft, acc,
                vsink=vsink)
        ctx.winner = worker_id
        pump = None
        if rd:
            pump = self._spec_pump(s, msg, acc, worker_id=worker_id)
            for vr in vsink:
                await pump.on_verify(vr)
            vsink.clear()
        # Pool the stream back only after the worker's terminal frame was
        # READ (a mid-response abort leaves frames in flight — closing is
        # the only safe disposal).
        clean = False
        try:
            if first.done_reason == "error":
                raise RuntimeError(first.response)
            if ctx.out is None:
                self._observe_ttfb(time.monotonic() - t0,
                                   tid=msg.trace_id)
                out = web.StreamResponse(
                    status=200,
                    headers={"Content-Type": ("text/event-stream" if openai
                                              else "application/x-ndjson")},
                )
                await out.prepare(request)
                ctx.out = out
            out = ctx.out

            async def write_frame(payload: dict) -> None:
                # A client-side write failure is final (_StreamStarted):
                # there is no one left to fail over for.
                line = json.dumps(payload).encode()
                tw = time.perf_counter_ns()
                try:
                    if openai:
                        await out.write(b"data: " + line + b"\n\n")
                    else:
                        await out.write(line + b"\n")
                except Exception as e:
                    raise _StreamStarted(out, e) from e
                acc["stream_flush_ns"] = acc.get("stream_flush_ns", 0) \
                    + time.perf_counter_ns() - tw

            # Replay trim (failover only): the re-sent request regenerates
            # from the prompt, so the first len(ctx.sent_text) chars of the
            # new reply were ALREADY delivered — skip them by count, and
            # log once if the replay text diverges from what the client
            # holds (non-greedy sampling without a seed can differ).
            skip = len(ctx.sent_text)
            replay_pos = 0
            diverged = False

            resp = first
            # Inter-frame receive gap ≈ worker decode step + wire, as seen
            # from the gateway — the consumer-side decode_step histogram.
            t_prev = time.perf_counter_ns()
            while True:
                if resp.done_reason == "error":
                    raise RuntimeError(resp.response)
                text = resp.response
                trimmed_empty = False
                if skip > 0 and text:
                    take = min(skip, len(text))
                    if (not diverged
                            and ctx.sent_text[replay_pos:replay_pos + take]
                            != text[:take]):
                        diverged = True
                        log.warning(
                            "failover replay diverged from delivered text "
                            "at char %d (request %s); resuming by count",
                            replay_pos, ctx.rid)
                    replay_pos += take
                    skip -= take
                    text = text[take:]
                    resp.response = text
                    self._robust["replayed_chunks"] += 1
                    trimmed_empty = not text
                if resp.done or not trimmed_empty:
                    ctx.sent_text += text
                    await write_frame(render(resp, final=resp.done))
                if resp.done:
                    clean = True  # terminal frame read: stream reusable
                    break
                if remaining() <= 0:
                    raise _BudgetExhausted("budget exhausted mid-stream")
                try:
                    while True:
                        raw = await self._recv_pb(
                            s, timeout=_recv_timeout(stall_decode),
                            acc=acc)
                        if (pump is not None
                                and raw.WhichOneof("message")
                                == "verify_result"):
                            await pump.on_verify(raw.verify_result)
                            continue
                        break
                    resp = classify(raw)
                except asyncio.TimeoutError as e:
                    if remaining() <= 0:
                        raise _BudgetExhausted(
                            "budget exhausted mid-stream") from e
                    if stall_decode > 0:
                        # Mid-decode stall: frames stopped arriving past
                        # the watchdog budget with the transport still
                        # open — tear down and fail over (the replay
                        # trim resumes the client byte-identically).
                        raise _StreamStalled(worker_id, "decode") from e
                    raise
                t_now = time.perf_counter_ns()
                self.obs.metrics.decode_step_seconds.observe(
                    (t_now - t_prev) / 1e9, exemplar=msg.trace_id)
                self.slo.observe_decode((t_now - t_prev) / 1e9)
                t_prev = t_now
            if openai:
                try:
                    await out.write(b"data: [DONE]\n\n")
                except Exception as e:
                    raise _StreamStarted(out, e) from e
            try:
                await out.write_eof()
            except Exception as e:
                raise _StreamStarted(out, e) from e
            return out
        finally:
            if pump is not None:
                self._spec_stats["chunks"] += pump.chunks_sent
                self._spec_stats["acks"] += pump.acks_sent
                self._spec_stats["nacks"] += pump.nacks
                self._spec_stats["accepted"] += pump.tokens_accepted
                self._spec_stats["offered"] += pump.tokens_offered
                if pump.ctrl.rtt_ewma > 0.0 and pump.ctrl.step_ewma > 0.0:
                    self._spec_wire[worker_id] = (pump.ctrl.rtt_ewma,
                                                  pump.ctrl.step_ewma)
            if clean and pump is None:
                self._pool_put(worker_id, s)
            else:
                # Remote-draft streams are one-shot on both sides: the
                # worker's reader task may still own half a frame.
                s.close()

    @staticmethod
    def _ollama_json(resp, chat: bool, final: bool) -> dict:
        """PB → Ollama-shaped JSON (gateway.go:220-230)."""
        d: dict = {
            "model": resp.model,
            "created_at": _now_rfc3339(),
            "done": resp.done,
        }
        if chat:
            d["message"] = {"role": "assistant", "content": resp.response}
        else:
            d["response"] = resp.response
        if final:
            d["done_reason"] = resp.done_reason or "stop"
            d["total_duration"] = resp.total_duration
            d["prompt_eval_count"] = resp.prompt_tokens
            d["eval_count"] = resp.completion_tokens
            d["worker_id"] = resp.worker_id
        return d

    @staticmethod
    def _openai_json(resp, shape: str, final: bool, stream: bool,
                     rid: str, created: int, first: bool = False) -> dict:
        """PB → OpenAI-shaped JSON (chat.completion[.chunk] /
        text_completion)."""
        chat = shape == "openai-chat"
        finish = ({"stop": "stop", "length": "length"}.get(
            resp.done_reason or "stop", "stop") if final else None)
        if chat:
            if stream:
                delta: dict = {}
                if first:
                    # OpenAI's first-chunk contract: the role arrives on
                    # the opening delta (clients accumulate it).
                    delta["role"] = "assistant"
                    delta["content"] = ""
                if resp.response:
                    delta["content"] = resp.response
                choice: dict = {"index": 0, "delta": delta,
                                "finish_reason": finish}
            else:
                choice = {"index": 0,
                          "message": {"role": "assistant",
                                      "content": resp.response},
                          "finish_reason": finish}
            obj = "chat.completion.chunk" if stream else "chat.completion"
        else:
            choice = {"index": 0, "text": resp.response,
                      "finish_reason": finish}
            obj = "text_completion"
        d = {"id": rid, "object": obj, "created": created,
             "model": resp.model, "choices": [choice]}
        if final:
            d["usage"] = {
                "prompt_tokens": resp.prompt_tokens,
                "completion_tokens": resp.completion_tokens,
                "total_tokens": resp.prompt_tokens + resp.completion_tokens,
            }
        return d
