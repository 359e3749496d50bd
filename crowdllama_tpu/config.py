"""Layered configuration.

Counterpart of /root/reference/pkg/config/config.go: a Configuration object
populated defaults → ``CROWDLLAMA_TPU_*`` environment (config.go:58-79 uses
viper with the ``CROWDLLAMA_`` prefix) → CLI flags (config.go:46-55), plus the
test-mode switch that compresses every background interval
(``CROWDLLAMA_TEST_MODE`` in the reference, checked in 6 places — here it is
read in exactly one).
"""

from __future__ import annotations

import argparse
import os
from dataclasses import dataclass, field

from crowdllama_tpu.obs.trace import DEFAULT_TRACE_CAPACITY


def is_test_mode() -> bool:
    return os.environ.get("CROWDLLAMA_TPU_TEST_MODE", "") == "1"


def _norm_quantize(value: str) -> str:
    """Normalize quantize spellings; reject unknown modes loudly (a typo
    must not silently serve bf16)."""
    v = (value or "").strip().lower()
    if v in ("", "none", "off", "0", "false"):
        return ""
    if v in ("int8", "int4"):
        return v
    raise ValueError(
        f"unknown quantize mode {value!r} (want '', 'int8' or 'int4')")


@dataclass
class Intervals:
    """Every background cadence in one place, test-mode aware.

    Defaults mirror the reference's constants: metadata publish 5 s
    (main.go:267-281), advertise 1 s (peer.go:450-504), local metadata refresh
    30 s (peer.go:361-389), discovery 10 s (manager.go:66-104), health check
    20 s, stale 60 s, quarantine 600 s; test mode compresses them the way
    CROWDLLAMA_TEST_MODE=1 does (peer.go:159-175, gateway.go:360).
    """

    discovery: float = 10.0
    advertise: float = 1.0
    metadata_publish: float = 5.0
    metadata_refresh: float = 30.0
    health_check: float = 20.0
    stale_after: float = 60.0
    cleanup: float = 20.0
    quarantine: float = 600.0
    metadata_max_age: float = 3600.0
    metadata_timeout: float = 5.0
    stream_read_timeout: float = 5.0
    backoff_base: float = 10.0
    max_failed_attempts: int = 3
    dht_provider_check: float = 60.0
    dht_bucket_refresh: float = 600.0
    # relay_mode=auto workers re-probe reachability on this cadence and
    # drop their relay when a direct dialback starts succeeding.
    relay_reprobe: float = 60.0
    # Minimum age before the advertise/publish tickers actually re-provide
    # their DHT records.  Membership/own-contact changes re-provide after
    # at most reprovide/20 (the churn floor in DHTNode.provide);
    # PROVIDER_TTL is 30 min, so 2 min keeps records fresh at ~1/100th of
    # the naive per-tick chatter.
    reprovide: float = 120.0

    @classmethod
    def default(cls) -> "Intervals":
        if is_test_mode():
            return cls(
                discovery=2.0,
                advertise=0.5,
                metadata_publish=1.0,
                metadata_refresh=5.0,
                health_check=5.0,
                stale_after=30.0,
                cleanup=5.0,
                quarantine=30.0,
                backoff_base=0.5,
                dht_provider_check=2.0,
                dht_bucket_refresh=5.0,
                relay_reprobe=2.0,
                # Change-driven re-provides (membership/contact
                # fingerprint) wait at most reprovide/20 = 0.5 s, so
                # tests stay fast; the periodic refresh only guards
                # against record loss (TTL is 30 min either way).
                reprovide=10.0,
            )
        return cls()


@dataclass
class Configuration:
    """Node configuration (cf. config.go:25-33, extended for the TPU engine)."""

    verbose: bool = False
    key_path: str = ""
    bootstrap_peers: list[str] = field(default_factory=list)  # "host:port" addrs
    listen_host: str = "0.0.0.0"
    listen_port: int = 0  # 0 = ephemeral
    gateway_port: int = 9001
    ipc_socket: str = ""

    # Engine configuration (replaces the reference's OllamaBaseURL).
    model: str = "tinyllama-1.1b"
    model_path: str = ""  # local HF checkpoint dir; empty = random-init weights
    # Destination for swarm-pulled checkpoints (net/model_share.py).
    models_dir: str = "~/.crowdllama-tpu/models"
    # Whether remote peers may trigger this worker to download a model
    # (MODEL_PROTOCOL "pull" op, proxied by the gateway's /api/pull).
    # Serving manifests/files for models we already have is always on.
    allow_swarm_pull: bool = True
    engine_backend: str = "jax"  # "jax" | "fake" (testing)
    max_batch_slots: int = 8
    max_context_length: int = 2048
    mesh_shape: str = ""  # e.g. "1x8" → (dp=1, tp=8); empty = all devices on tp
    decode_chunk: int = 8  # decode steps per dispatch while every slot is taken
    # Unified ragged batch (docs/RAGGED_BATCH.md): long prompts prefill
    # INSIDE the decode dispatch — each step decodes every active slot and
    # carries one prefill chunk of up to (step_token_budget -
    # max_batch_slots) prompt tokens over the same paged pool.  0 = auto
    # (runner prefill_chunk + max_batch_slots: a full 512-token chunk
    # rides every step).  ragged_prefill=False keeps the legacy
    # alternating chunked-prefill dispatch.
    step_token_budget: int = 0
    ragged_prefill: bool = True
    warmup: bool = True  # compile prefill/decode at engine start
    quantize: str = ""  # "" (bf16) | "int8" | "int4" weight-only (ops/quant.py)
    # KV cache layout: "paged" (engine/paged.py, the default: page pool +
    # slot page tables + prefix cache + fused pallas decode) or
    # "contiguous" [L,B,Hkv,S,Dh] per slot (required by spec_decode and
    # dp/sp/pp meshes); kv_pool_tokens 0 = full capacity (no overcommit),
    # else total pooled tokens.
    kv_layout: str = "paged"
    kv_page_size: int = 128
    kv_pool_tokens: int = 0
    kv_dtype: str = "bf16"  # "bf16" | "int8" quantized KV cache (contiguous)
    kv_prefix_cache: bool = True  # paged layout: share prompt-prefix pages
    # NAT traversal (net/relay.py): "auto" probes reachability via the
    # bootstrap node's dialback and relays only when unreachable; "always"
    # forces relaying (tests / known-NATed deployments); "off" disables.
    relay_mode: str = "auto"
    # "" | "ngram" (prompt-lookup drafts) | "draft" (a small draft MODEL
    # proposes tokens; paged layout only) — engine/spec.py.
    spec_decode: str = ""
    spec_draft: int = 4  # draft tokens per verify step
    spec_draft_model: str = ""  # draft model registry name (spec "draft")
    spec_draft_path: str = ""   # draft checkpoint dir (random-init if empty)
    # > 0 enables the acceptance-adaptive draft-length controller
    # (engine/scheduler.py): draft_len retunes between dispatches within
    # [0, spec_draft_max], pausing speculation entirely (k=0, plain-decode
    # cost) when drafts mostly miss.  0 = fixed spec_draft (seed behavior).
    spec_draft_max: int = 0
    # Gateway-drafted speculative pipeline (docs/SPECULATIVE.md):
    # "off" | "gateway" (draft locally at the gateway from
    # spec_draft_path, stream DraftChunk frames ahead of the worker) |
    # "worker" (pure ack credits: worker-paced remote speculation, the
    # RTT-linear baseline).  Streamed requests only.
    gateway_spec_pipeline: str = "off"
    drain_timeout: float = 30.0  # graceful-shutdown grace for in-flight reqs
    # Robustness plane (docs/ROBUSTNESS.md): per-request wall-clock budget
    # in seconds, charged across retries and mid-stream failovers; clients
    # may request LESS via the X-Request-Timeout header (this value is the
    # ceiling).  600 matches the pre-budget hard-coded frame timeouts.
    request_timeout: float = 600.0
    # Gateway load shedding: max concurrently routed inference requests
    # before new ones get an immediate 503 + Retry-After (0 = off).
    admission_max_inflight: int = 0
    # Worker-side shedding: scheduler pending depth at which submit()
    # rejects with "overloaded" (0 = off; the gateway translates the
    # rejection into 503 + Retry-After after failing over).
    admission_pending_max: int = 0
    # Retry-After hint (seconds) stamped on shed 503 responses.
    retry_after_s: float = 1.0
    # KV shipping (docs/KV_TRANSFER.md): on a prefix-affinity miss the
    # gateway hints the last worker that held the prefix, and the chosen
    # worker fetches its paged-KV pages peer-to-peer instead of
    # recomputing the prefill.  Strictly additive: any fetch failure falls
    # back to plain prefill.
    kv_ship: bool = False
    # Don't bother fetching when fewer than this many prefix tokens are
    # missing locally — below break-even the round trip costs more than
    # the recompute it saves (no chip measurement of where that is).
    kv_ship_min_tokens: int = 512
    # Wall-clock cap on one fetch (dial + frames); charged against the
    # request's deadline budget like any other phase.
    kv_ship_timeout: float = 5.0
    # Replicated gateway plane (docs/ROBUSTNESS.md "replicated gateway"):
    # p2p listener addresses ("host:port") of the OTHER gateway replicas
    # this gateway gossips routing state with.  Empty = single gateway,
    # everything stays process-local (the seed behavior).
    gateway_peers: list[str] = field(default_factory=list)
    # Per-tenant admission quotas, "name=requests_per_sec" comma-separated
    # (e.g. "default=20,acme=100"); tenant key = X-Tenant header, unknown
    # tenants charge "default".  Empty = the global shed only.
    tenant_quota: str = ""
    # Seconds between gossip anti-entropy rounds.
    gossip_interval: float = 2.0
    # Snapshot file for the gossip map (affinity pins + quarantines):
    # saved on SIGTERM, rehydrated on start so a gateway bounce keeps its
    # affinity hit-rate.  Empty = no persistence.
    gossip_snapshot_path: str = ""
    # Directory for jax.profiler traces of THIS node's engine (the worker:
    # POST /debug/profile/start|stop on --worker-metrics-port, the IPC
    # "profile" op); empty disables the profile surface.
    profile_dir: str = ""

    # Observability plane (obs/): per-node span ring-buffer capacity
    # (GET /debug/trace on gateway and worker) and the worker-side
    # /metrics + /debug/trace listener port (0 = disabled; workers have
    # no other HTTP surface).
    trace_buffer: int = DEFAULT_TRACE_CAPACITY
    worker_metrics_port: int = 0
    # Flight recorder (obs/collector.py): how many stitched traces of
    # "interesting" requests (p99 tail, failovers, migrations, sheds,
    # kv-ship fallbacks) the gateway retains for GET /debug/flightrecorder.
    flight_recorder: int = 32
    # Age-based span eviction: trace ring entries older than this many
    # seconds are dropped at snapshot/record time (0 = capacity-only).
    trace_ttl: float = 0.0
    # Attach OpenMetrics exemplars (`# {trace_id="..."} <v>`) to latency
    # histogram bucket lines so a tail bucket links straight to a trace.
    metrics_exemplars: bool = False
    # SLO burn-rate plane (obs/slo.py): gateway latency objectives in
    # milliseconds — TTFT (admission to first token frame) and per
    # decode-step gap.  0 disables the tracker and its gauges.
    slo_ttft_ms: float = 0.0
    slo_decode_ms: float = 0.0
    # Gray-failure immunity (docs/ROBUSTNESS.md): the gateway's
    # per-stream progress watchdog — maximum token inter-arrival gap in
    # ms (applied to TTFT and decode separately; the live SLO objective
    # raises it when higher) before a stalled stream is torn down and
    # failed over with the worker quarantined as "wedged".  0 = off.
    stream_stall_ms: float = 0.0
    # Hedged first-token dispatch: when a stream's first frame is slower
    # than this (or the live TTFT p95 once the histogram has data), the
    # gateway races the second-best worker and delivers exactly one
    # stream.  0 = off.
    hedge_ttft_ms: float = 0.0
    # Worker-side dispatch self-watchdog (engine/scheduler.py): a flight
    # older than this multiple of its dispatch-class flight-duration EWMA
    # marks the engine wedged and self-drains.  0 = off.
    wedge_multiplier: float = 0.0

    # Multi-worker sharded serving (BASELINE configs 4-5): a node with
    # shard_count > 1 serves one shard of an N-way split; shard_group names
    # the group (same string on every member; default
    # "<model>/<strategy><count>").  Index 0 is the group leader.
    # strategy "pp": member i serves layer slice i (pipeline stages).
    # strategy "ep": member i hosts experts e % count == i (MoE models);
    # the leader runs attention/router and dispatches expert batches.
    shard_group: str = ""
    shard_index: int = 0
    shard_count: int = 1
    shard_strategy: str = "pp"  # "pp" | "ep"

    # Multi-host single-worker serving (parallel/multihost.py): when a
    # logical worker spans several hosts of a TPU pod slice, every process
    # sets dist_coordinator to process 0's "host:port" and the mesh spans
    # the GLOBAL device set (collectives ride ICI within a host, DCN
    # between).  Empty = single-host (the common case).
    dist_coordinator: str = ""
    dist_num_processes: int = 0  # 0 = let jax.distributed infer
    dist_process_id: int = -1    # -1 = let jax.distributed infer

    intervals: Intervals = field(default_factory=Intervals.default)

    @classmethod
    def from_environment(cls, **overrides) -> "Configuration":
        """Defaults ← env ← explicit overrides (cf. config.go:58-79)."""
        cfg = cls()
        env = os.environ
        cfg.verbose = env.get("CROWDLLAMA_TPU_VERBOSE", "") in ("1", "true")
        cfg.key_path = env.get("CROWDLLAMA_TPU_KEY_PATH", cfg.key_path)
        if env.get("CROWDLLAMA_TPU_BOOTSTRAP_PEERS"):
            cfg.bootstrap_peers = [
                a.strip()
                for a in env["CROWDLLAMA_TPU_BOOTSTRAP_PEERS"].split(",")
                if a.strip()
            ]
        cfg.listen_host = env.get("CROWDLLAMA_TPU_LISTEN_HOST", cfg.listen_host)
        cfg.listen_port = int(env.get("CROWDLLAMA_TPU_LISTEN_PORT", cfg.listen_port))
        cfg.gateway_port = int(env.get("CROWDLLAMA_TPU_GATEWAY_PORT", cfg.gateway_port))
        cfg.ipc_socket = env.get("CROWDLLAMA_TPU_SOCKET", cfg.ipc_socket)
        cfg.model = env.get("CROWDLLAMA_TPU_MODEL", cfg.model)
        cfg.model_path = env.get("CROWDLLAMA_TPU_MODEL_PATH", cfg.model_path)
        cfg.models_dir = env.get("CROWDLLAMA_TPU_MODELS_DIR", cfg.models_dir)
        if "CROWDLLAMA_TPU_ALLOW_SWARM_PULL" in env:
            cfg.allow_swarm_pull = env["CROWDLLAMA_TPU_ALLOW_SWARM_PULL"] in (
                "1", "true")
        cfg.engine_backend = env.get("CROWDLLAMA_TPU_ENGINE", cfg.engine_backend)
        cfg.mesh_shape = env.get("CROWDLLAMA_TPU_MESH", cfg.mesh_shape)
        cfg.max_batch_slots = int(env.get(
            "CROWDLLAMA_TPU_MAX_BATCH_SLOTS", cfg.max_batch_slots))
        cfg.max_context_length = int(env.get(
            "CROWDLLAMA_TPU_MAX_CONTEXT_LENGTH", cfg.max_context_length))
        cfg.decode_chunk = int(env.get("CROWDLLAMA_TPU_DECODE_CHUNK", cfg.decode_chunk))
        cfg.step_token_budget = int(env.get(
            "CROWDLLAMA_TPU_STEP_TOKEN_BUDGET", cfg.step_token_budget))
        if env.get("CROWDLLAMA_TPU_RAGGED_PREFILL"):
            cfg.ragged_prefill = env["CROWDLLAMA_TPU_RAGGED_PREFILL"] in (
                "1", "true")
        cfg.shard_group = env.get("CROWDLLAMA_TPU_SHARD_GROUP", cfg.shard_group)
        cfg.shard_index = int(env.get("CROWDLLAMA_TPU_SHARD_INDEX", cfg.shard_index))
        cfg.shard_count = int(env.get("CROWDLLAMA_TPU_SHARD_COUNT", cfg.shard_count))
        cfg.shard_strategy = env.get("CROWDLLAMA_TPU_SHARD_STRATEGY", cfg.shard_strategy)
        cfg.dist_coordinator = env.get("CROWDLLAMA_TPU_DIST_COORDINATOR",
                                       cfg.dist_coordinator)
        cfg.dist_num_processes = int(env.get(
            "CROWDLLAMA_TPU_DIST_NUM_PROCESSES", cfg.dist_num_processes))
        cfg.dist_process_id = int(env.get(
            "CROWDLLAMA_TPU_DIST_PROCESS_ID", cfg.dist_process_id))
        cfg.quantize = env.get("CROWDLLAMA_TPU_QUANTIZE", cfg.quantize)
        cfg.kv_layout = env.get("CROWDLLAMA_TPU_KV_LAYOUT", cfg.kv_layout)
        cfg.kv_page_size = int(env.get("CROWDLLAMA_TPU_KV_PAGE_SIZE",
                                       cfg.kv_page_size))
        cfg.kv_pool_tokens = int(env.get("CROWDLLAMA_TPU_KV_POOL_TOKENS",
                                         cfg.kv_pool_tokens))
        cfg.kv_dtype = env.get("CROWDLLAMA_TPU_KV_DTYPE", cfg.kv_dtype)
        if env.get("CROWDLLAMA_TPU_KV_PREFIX_CACHE"):
            cfg.kv_prefix_cache = env["CROWDLLAMA_TPU_KV_PREFIX_CACHE"] in (
                "1", "true")
        cfg.relay_mode = env.get("CROWDLLAMA_TPU_RELAY_MODE", cfg.relay_mode)
        cfg.spec_decode = env.get("CROWDLLAMA_TPU_SPEC_DECODE",
                                  cfg.spec_decode)
        cfg.spec_draft = int(env.get("CROWDLLAMA_TPU_SPEC_DRAFT",
                                     cfg.spec_draft))
        cfg.spec_draft_model = env.get("CROWDLLAMA_TPU_SPEC_DRAFT_MODEL",
                                       cfg.spec_draft_model)
        cfg.spec_draft_path = env.get("CROWDLLAMA_TPU_SPEC_DRAFT_PATH",
                                      cfg.spec_draft_path)
        cfg.spec_draft_max = int(env.get("CROWDLLAMA_TPU_SPEC_DRAFT_MAX",
                                         cfg.spec_draft_max))
        cfg.gateway_spec_pipeline = env.get(
            "CROWDLLAMA_TPU_GATEWAY_SPEC_PIPELINE",
            cfg.gateway_spec_pipeline)
        cfg.drain_timeout = float(env.get("CROWDLLAMA_TPU_DRAIN_TIMEOUT",
                                          cfg.drain_timeout))
        cfg.request_timeout = float(env.get(
            "CROWDLLAMA_TPU_REQUEST_TIMEOUT", cfg.request_timeout))
        cfg.admission_max_inflight = int(env.get(
            "CROWDLLAMA_TPU_ADMISSION_MAX_INFLIGHT",
            cfg.admission_max_inflight))
        cfg.admission_pending_max = int(env.get(
            "CROWDLLAMA_TPU_ADMISSION_PENDING_MAX",
            cfg.admission_pending_max))
        cfg.retry_after_s = float(env.get(
            "CROWDLLAMA_TPU_RETRY_AFTER", cfg.retry_after_s))
        if env.get("CROWDLLAMA_TPU_KV_SHIP"):
            cfg.kv_ship = env["CROWDLLAMA_TPU_KV_SHIP"] in ("1", "true")
        cfg.kv_ship_min_tokens = int(env.get(
            "CROWDLLAMA_TPU_KV_SHIP_MIN_TOKENS", cfg.kv_ship_min_tokens))
        cfg.kv_ship_timeout = float(env.get(
            "CROWDLLAMA_TPU_KV_SHIP_TIMEOUT", cfg.kv_ship_timeout))
        if env.get("CROWDLLAMA_TPU_GATEWAY_PEERS"):
            cfg.gateway_peers = [
                a.strip()
                for a in env["CROWDLLAMA_TPU_GATEWAY_PEERS"].split(",")
                if a.strip()
            ]
        cfg.tenant_quota = env.get("CROWDLLAMA_TPU_TENANT_QUOTA",
                                   cfg.tenant_quota)
        cfg.gossip_interval = float(env.get(
            "CROWDLLAMA_TPU_GOSSIP_INTERVAL", cfg.gossip_interval))
        cfg.gossip_snapshot_path = env.get(
            "CROWDLLAMA_TPU_GOSSIP_SNAPSHOT", cfg.gossip_snapshot_path)
        cfg.profile_dir = env.get("CROWDLLAMA_TPU_PROFILE_DIR", cfg.profile_dir)
        cfg.trace_buffer = int(env.get("CROWDLLAMA_TPU_TRACE_BUFFER",
                                       cfg.trace_buffer))
        cfg.worker_metrics_port = int(env.get(
            "CROWDLLAMA_TPU_WORKER_METRICS_PORT", cfg.worker_metrics_port))
        cfg.flight_recorder = int(env.get(
            "CROWDLLAMA_TPU_FLIGHT_RECORDER", cfg.flight_recorder))
        cfg.trace_ttl = float(env.get(
            "CROWDLLAMA_TPU_TRACE_TTL", cfg.trace_ttl))
        if env.get("CROWDLLAMA_TPU_METRICS_EXEMPLARS"):
            cfg.metrics_exemplars = (
                env["CROWDLLAMA_TPU_METRICS_EXEMPLARS"] in ("1", "true"))
        cfg.slo_ttft_ms = float(env.get(
            "CROWDLLAMA_TPU_SLO_TTFT_MS", cfg.slo_ttft_ms))
        cfg.slo_decode_ms = float(env.get(
            "CROWDLLAMA_TPU_SLO_DECODE_MS", cfg.slo_decode_ms))
        cfg.stream_stall_ms = float(env.get(
            "CROWDLLAMA_TPU_STREAM_STALL_MS", cfg.stream_stall_ms))
        cfg.hedge_ttft_ms = float(env.get(
            "CROWDLLAMA_TPU_HEDGE_TTFT_MS", cfg.hedge_ttft_ms))
        cfg.wedge_multiplier = float(env.get(
            "CROWDLLAMA_TPU_WEDGE_MULTIPLIER", cfg.wedge_multiplier))
        if env.get("CROWDLLAMA_TPU_WARMUP"):
            cfg.warmup = env["CROWDLLAMA_TPU_WARMUP"] in ("1", "true")
        for k, v in overrides.items():
            if v is not None:
                setattr(cfg, k, v)
        # Validate AFTER overrides so programmatic/flag values are checked
        # too (and a valid override can correct a bad env value).
        cfg.quantize = _norm_quantize(cfg.quantize)
        cfg.kv_layout = (cfg.kv_layout or "contiguous").strip().lower()
        if cfg.kv_layout not in ("contiguous", "paged"):
            raise ValueError(f"unknown kv layout {cfg.kv_layout!r} "
                             "(want 'contiguous' or 'paged')")
        if cfg.kv_page_size <= 0:
            raise ValueError(f"kv_page_size must be positive, "
                             f"got {cfg.kv_page_size}")
        if cfg.kv_pool_tokens < 0:
            raise ValueError(f"kv_pool_tokens must be >= 0, "
                             f"got {cfg.kv_pool_tokens}")
        cfg.kv_dtype = (cfg.kv_dtype or "bf16").strip().lower()
        if cfg.kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"unknown kv dtype {cfg.kv_dtype!r} "
                             "(want 'bf16' or 'int8')")
        # int8 KV composes with both layouts (paged pools carry per-page
        # scales; ops/pallas/paged.py dequantizes in-kernel).
        if cfg.trace_buffer < 1:
            raise ValueError(f"trace_buffer must be >= 1, "
                             f"got {cfg.trace_buffer}")
        if cfg.request_timeout <= 0:
            raise ValueError(f"request_timeout must be positive, "
                             f"got {cfg.request_timeout}")
        if cfg.admission_max_inflight < 0:
            raise ValueError(f"admission_max_inflight must be >= 0, "
                             f"got {cfg.admission_max_inflight}")
        if cfg.admission_pending_max < 0:
            raise ValueError(f"admission_pending_max must be >= 0, "
                             f"got {cfg.admission_pending_max}")
        if cfg.retry_after_s < 0:
            raise ValueError(f"retry_after_s must be >= 0, "
                             f"got {cfg.retry_after_s}")
        if cfg.kv_ship_min_tokens < 0:
            raise ValueError(f"kv_ship_min_tokens must be >= 0, "
                             f"got {cfg.kv_ship_min_tokens}")
        if cfg.kv_ship_timeout <= 0:
            raise ValueError(f"kv_ship_timeout must be positive, "
                             f"got {cfg.kv_ship_timeout}")
        if cfg.gossip_interval <= 0:
            raise ValueError(f"gossip_interval must be positive, "
                             f"got {cfg.gossip_interval}")
        if cfg.tenant_quota:
            # Fail at startup, not on the first shed decision.
            from crowdllama_tpu.swarm.gossip import parse_tenant_quotas

            parse_tenant_quotas(cfg.tenant_quota)
        if cfg.drain_timeout <= 0:
            raise ValueError(f"drain_timeout must be positive, "
                             f"got {cfg.drain_timeout}")
        if cfg.worker_metrics_port < 0:
            raise ValueError(f"worker_metrics_port must be >= 0, "
                             f"got {cfg.worker_metrics_port}")
        if cfg.flight_recorder < 1:
            raise ValueError(f"flight_recorder must be >= 1, "
                             f"got {cfg.flight_recorder}")
        if cfg.trace_ttl < 0:
            raise ValueError(f"trace_ttl must be >= 0, "
                             f"got {cfg.trace_ttl}")
        if cfg.slo_ttft_ms < 0:
            raise ValueError(f"slo_ttft_ms must be >= 0, "
                             f"got {cfg.slo_ttft_ms}")
        if cfg.slo_decode_ms < 0:
            raise ValueError(f"slo_decode_ms must be >= 0, "
                             f"got {cfg.slo_decode_ms}")
        if cfg.stream_stall_ms < 0:
            raise ValueError(f"stream_stall_ms must be >= 0, "
                             f"got {cfg.stream_stall_ms}")
        if cfg.hedge_ttft_ms < 0:
            raise ValueError(f"hedge_ttft_ms must be >= 0, "
                             f"got {cfg.hedge_ttft_ms}")
        if cfg.wedge_multiplier < 0:
            raise ValueError(f"wedge_multiplier must be >= 0, "
                             f"got {cfg.wedge_multiplier}")
        cfg.relay_mode = (cfg.relay_mode or "auto").strip().lower()
        if cfg.relay_mode not in ("auto", "always", "off"):
            raise ValueError(f"unknown relay_mode {cfg.relay_mode!r} "
                             "(want 'auto', 'always' or 'off')")
        cfg.spec_decode = (cfg.spec_decode or "").strip().lower()
        if cfg.spec_decode not in ("", "ngram", "draft"):
            raise ValueError(f"unknown spec_decode {cfg.spec_decode!r} "
                             "(want '', 'ngram' or 'draft')")
        cfg.gateway_spec_pipeline = (
            cfg.gateway_spec_pipeline or "off").strip().lower()
        if cfg.gateway_spec_pipeline not in ("off", "gateway", "worker"):
            raise ValueError(
                f"unknown gateway_spec_pipeline "
                f"{cfg.gateway_spec_pipeline!r} "
                "(want 'off', 'gateway' or 'worker')")
        if cfg.spec_decode:
            # Spec composes with BOTH layouts (VERDICT r3 #4): paged runs
            # SpecPagedModelRunner (bf16 or int8 pools); contiguous still
            # needs the bf16 cache (its verify forward reads the cache
            # directly as bf16 attention context).
            if cfg.kv_layout == "contiguous" and cfg.kv_dtype != "bf16":
                raise ValueError(
                    "spec_decode on the contiguous layout requires the bf16 "
                    "KV cache — use --kv-dtype bf16 or --kv-layout paged "
                    "(paged spec verifies against int8 pools)")
            if cfg.spec_draft < 1:
                raise ValueError("spec_draft must be >= 1")
            if cfg.spec_draft_max < 0:
                raise ValueError("spec_draft_max must be >= 0")
            if cfg.spec_draft_max and cfg.spec_draft_max < cfg.spec_draft:
                raise ValueError(
                    f"spec_draft_max ({cfg.spec_draft_max}) must be >= "
                    f"spec_draft ({cfg.spec_draft}) — it is the adaptive "
                    "controller's growth ceiling")
        if cfg.spec_decode == "draft":
            if not cfg.spec_draft_model and not cfg.spec_draft_path:
                raise ValueError(
                    "spec_decode=draft needs --spec-draft-model (registry "
                    "name) or --spec-draft-path (a distill-draft checkpoint "
                    "dir, which carries its own config)")
            if cfg.kv_layout != "paged":
                raise ValueError(
                    "draft-model speculation runs on the paged layout only "
                    "(the serving default); drop --kv-layout contiguous or "
                    "use spec_decode=ngram")
        return cfg

    @staticmethod
    def add_flags(parser: argparse.ArgumentParser) -> None:
        """Register shared CLI flags (cf. config.go:46-55)."""
        parser.add_argument("--verbose", action="store_true", default=None)
        parser.add_argument("--key-path", dest="key_path")
        parser.add_argument(
            "--bootstrap-peers",
            dest="bootstrap_peers",
            help="comma-separated host:port bootstrap addresses",
        )
        parser.add_argument("--listen-port", dest="listen_port", type=int)
        parser.add_argument("--gateway-port", dest="gateway_port", type=int)
        parser.add_argument("--model", dest="model")
        parser.add_argument("--model-path", dest="model_path")
        parser.add_argument("--engine", dest="engine_backend")
        parser.add_argument("--mesh", dest="mesh_shape")
        parser.add_argument("--shard-group", dest="shard_group",
                            help="sharded-model group id (same on all members)")
        parser.add_argument("--shard-index", dest="shard_index", type=int,
                            help="this worker's pipeline stage (0 = leader)")
        parser.add_argument("--shard-count", dest="shard_count", type=int,
                            help="number of workers sharing the model")
        parser.add_argument("--shard-strategy", dest="shard_strategy",
                            choices=("pp", "ep"),
                            help="pp: layer slices; ep: MoE expert banks")
        parser.add_argument("--dist-coordinator", dest="dist_coordinator",
                            help="multi-host: process 0's host:port "
                                 "(parallel/multihost.py)")
        parser.add_argument("--dist-num-processes",
                            dest="dist_num_processes", type=int)
        parser.add_argument("--dist-process-id", dest="dist_process_id",
                            type=int)
        parser.add_argument("--quantize", dest="quantize",
                            choices=("", "int8", "int4"),
                            help="weight-only quantization for the engine")
        parser.add_argument("--kv-layout", dest="kv_layout",
                            choices=("contiguous", "paged"),
                            help="KV cache layout (paged: shared page pool)")
        parser.add_argument("--kv-page-size", dest="kv_page_size", type=int,
                            help="paged KV page size in tokens")
        parser.add_argument("--kv-pool-tokens", dest="kv_pool_tokens",
                            type=int,
                            help="paged pool size in tokens (0 = no overcommit)")
        parser.add_argument("--kv-dtype", dest="kv_dtype",
                            choices=("bf16", "int8"),
                            help="KV cache dtype (int8: quantized cache, "
                                 "contiguous or paged layout)")
        parser.add_argument("--relay-mode", dest="relay_mode",
                            choices=("auto", "always", "off"),
                            help="NAT relay through the bootstrap node "
                                 "(auto: only when unreachable)")
        parser.add_argument("--spec-decode", dest="spec_decode",
                            choices=("", "ngram", "draft"),
                            help="speculative decoding: ngram prompt lookup "
                                 "or a small draft model")
        parser.add_argument("--spec-draft", dest="spec_draft", type=int,
                            help="draft tokens per speculative verify step")
        parser.add_argument("--spec-draft-model", dest="spec_draft_model",
                            help="draft model name (spec_decode=draft)")
        parser.add_argument("--spec-draft-path", dest="spec_draft_path",
                            help="draft model checkpoint dir")
        parser.add_argument("--spec-draft-max", dest="spec_draft_max",
                            type=int,
                            help="enable acceptance-adaptive draft length: "
                                 "retune k in [0, max] between dispatches "
                                 "(0 = fixed --spec-draft)")
        parser.add_argument("--gateway-spec-pipeline",
                            dest="gateway_spec_pipeline",
                            choices=("off", "gateway", "worker"),
                            help="gateway-drafted speculative pipeline: "
                                 "draft at the gateway (--spec-draft-path) "
                                 "and batch-verify at the worker; 'worker' "
                                 "sends pure ack credits (RTT-linear "
                                 "baseline)")
        parser.add_argument("--step-token-budget", dest="step_token_budget",
                            type=int,
                            help="unified ragged batch: per-step token "
                                 "budget (decode slots + one prefill "
                                 "chunk; 0 = auto)")
        parser.add_argument("--no-ragged-prefill", dest="ragged_prefill",
                            action="store_const", const=False, default=None,
                            help="disable unified ragged prefill: long "
                                 "prompts use the legacy alternating "
                                 "chunked-prefill dispatch")
        parser.add_argument("--profile-dir", dest="profile_dir",
                            help="worker: enable jax.profiler captures "
                                 "(POST /debug/profile/start|stop) into "
                                 "this dir")
        parser.add_argument("--trace-buffer", dest="trace_buffer", type=int,
                            help="span ring-buffer capacity for "
                                 f"GET /debug/trace (default "
                                 f"{DEFAULT_TRACE_CAPACITY})")
        parser.add_argument("--worker-metrics-port",
                            dest="worker_metrics_port", type=int,
                            help="worker-side /metrics + /debug/trace "
                                 "listener port (0 = disabled)")
        parser.add_argument("--flight-recorder", dest="flight_recorder",
                            type=int,
                            help="stitched traces of interesting requests "
                                 "kept for GET /debug/flightrecorder "
                                 "(default 32)")
        parser.add_argument("--trace-ttl", dest="trace_ttl", type=float,
                            help="evict trace-ring spans older than this "
                                 "many seconds (0 = capacity-only)")
        parser.add_argument("--metrics-exemplars", dest="metrics_exemplars",
                            action="store_const", const=True, default=None,
                            help="attach trace_id exemplars to latency "
                                 "histogram buckets on /metrics")
        parser.add_argument("--slo-ttft-ms", dest="slo_ttft_ms", type=float,
                            help="TTFT objective in ms for the SLO "
                                 "burn-rate plane (0 = disabled)")
        parser.add_argument("--slo-decode-ms", dest="slo_decode_ms",
                            type=float,
                            help="per decode-step objective in ms for the "
                                 "SLO burn-rate plane (0 = disabled)")
        parser.add_argument("--stream-stall-ms", dest="stream_stall_ms",
                            type=float,
                            help="gateway per-stream progress watchdog: max "
                                 "token inter-arrival gap in ms before the "
                                 "stream is declared stalled and failed over "
                                 "with the worker quarantined as wedged "
                                 "(0 = off; live SLO objectives raise it)")
        parser.add_argument("--hedge-ttft-ms", dest="hedge_ttft_ms",
                            type=float,
                            help="race the second-best worker when the first "
                                 "token is slower than this many ms (or the "
                                 "live TTFT p95 once known); exactly one "
                                 "stream is delivered (0 = off)")
        parser.add_argument("--wedge-multiplier", dest="wedge_multiplier",
                            type=float,
                            help="worker self-watchdog: declare the engine "
                                 "wedged when a dispatch flight exceeds this "
                                 "multiple of its class EWMA and self-drain "
                                 "(0 = off)")
        parser.add_argument("--request-timeout", dest="request_timeout",
                            type=float,
                            help="per-request wall-clock budget in seconds, "
                                 "charged across retries/failovers "
                                 "(X-Request-Timeout may lower it)")
        parser.add_argument("--admission-max-inflight",
                            dest="admission_max_inflight", type=int,
                            help="gateway: max concurrent routed requests "
                                 "before shedding 503s (0 = off)")
        parser.add_argument("--admission-pending-max",
                            dest="admission_pending_max", type=int,
                            help="worker: scheduler pending depth that "
                                 "rejects new work as overloaded (0 = off)")
        parser.add_argument("--retry-after", dest="retry_after_s",
                            type=float,
                            help="Retry-After seconds hinted on shed 503s")
        parser.add_argument("--kv-ship", dest="kv_ship",
                            action="store_const", const=True, default=None,
                            help="fetch paged-KV pages from the peer that "
                                 "last held a shared prefix instead of "
                                 "recomputing the prefill (paged cache only)")
        parser.add_argument("--kv-ship-min-tokens", dest="kv_ship_min_tokens",
                            type=int,
                            help="skip the fetch when fewer prefix tokens "
                                 "than this are missing locally")
        parser.add_argument("--kv-ship-timeout", dest="kv_ship_timeout",
                            type=float,
                            help="seconds before a KV fetch gives up and "
                                 "falls back to plain prefill")
        parser.add_argument("--drain-timeout", dest="drain_timeout",
                            type=float,
                            help="graceful-drain window in seconds: how "
                                 "long a SIGTERM'd/drained worker stays up "
                                 "as a KV donor for its migrated streams")
        parser.add_argument("--gateway-peers", dest="gateway_peers",
                            help="comma-separated host:port p2p addresses "
                                 "of the other gateway replicas to gossip "
                                 "routing state with")
        parser.add_argument("--tenant-quota", dest="tenant_quota",
                            help="per-tenant admission quotas, "
                                 "name=req_per_sec comma-separated "
                                 "(tenant key: X-Tenant header; unknown "
                                 "tenants charge 'default')")
        parser.add_argument("--gossip-interval", dest="gossip_interval",
                            type=float,
                            help="seconds between gossip anti-entropy "
                                 "rounds between gateway replicas")
        parser.add_argument("--gossip-snapshot", dest="gossip_snapshot_path",
                            help="file the gossip map is saved to on "
                                 "SIGTERM and rehydrated from on start")

    @classmethod
    def from_flags(cls, args: argparse.Namespace) -> "Configuration":
        overrides = {
            k: getattr(args, k, None)
            for k in (
                "verbose", "key_path", "listen_port", "gateway_port",
                "model", "model_path", "engine_backend", "mesh_shape",
                "shard_group", "shard_index", "shard_count", "shard_strategy",
                "quantize", "kv_layout", "kv_page_size", "kv_pool_tokens",
                "kv_dtype", "relay_mode", "spec_decode", "spec_draft",
                "spec_draft_model", "spec_draft_path", "spec_draft_max",
                "gateway_spec_pipeline",
                "step_token_budget", "ragged_prefill",
                "profile_dir", "trace_buffer", "worker_metrics_port",
                "flight_recorder", "trace_ttl", "metrics_exemplars",
                "slo_ttft_ms", "slo_decode_ms",
                "stream_stall_ms", "hedge_ttft_ms", "wedge_multiplier",
                "request_timeout", "admission_max_inflight",
                "admission_pending_max", "retry_after_s",
                "kv_ship", "kv_ship_min_tokens", "kv_ship_timeout",
                "drain_timeout", "tenant_quota", "gossip_interval",
                "gossip_snapshot_path",
                "dist_coordinator", "dist_num_processes", "dist_process_id",
            )
        }
        bp = getattr(args, "bootstrap_peers", None)
        if isinstance(bp, str):
            overrides["bootstrap_peers"] = [a.strip() for a in bp.split(",") if a.strip()]
        gp = getattr(args, "gateway_peers", None)
        if isinstance(gp, str):
            overrides["gateway_peers"] = [a.strip() for a in gp.split(",")
                                          if a.strip()]
        return cls.from_environment(**overrides)
