"""Unified worker/consumer peer.

Counterpart of /root/reference/pkg/peer/peer.go: one node object owning the
stream host, DHT, capability metadata, peer manager and the engine seam.
Registers the inference stream handler (peer.go:177-256) and metadata handler
(peer.go:284-316); runs the metadata refresh / publish / advertise loops
(peer.go:361-504) with DHT reconnect-on-empty-routing-table (peer.go:513-525).

Where the reference hardcodes a fake RTX 4090 advertisement (peer.go:320-343),
metadata here is real: model list, measured throughput EMA and slot load from
the engine, TPU chip count / HBM / ICI topology from the JAX runtime.
"""

from __future__ import annotations

import asyncio
import logging
import math
import time

from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

from crowdllama_tpu.config import Configuration
from crowdllama_tpu.core import wire
from crowdllama_tpu.core.protocol import (
    INFERENCE_PROTOCOL,
    METADATA_PROTOCOL,
    SHARD_PROTOCOL,
    metadata_key,
    namespace_key,
)
from crowdllama_tpu.core.resource import Resource
from crowdllama_tpu.engine.engine import Engine
from crowdllama_tpu.net.discovery import discover_peers, new_host_and_dht, request_peer_metadata
from crowdllama_tpu.net.host import Stream
from crowdllama_tpu.obs import DEFAULT_TRACE_CAPACITY, NodeObs
from crowdllama_tpu.peermanager.manager import PeerHealthConfig, PeerManager
from crowdllama_tpu.utils.aio import run_every
from crowdllama_tpu.version import VERSION

log = logging.getLogger("crowdllama.peer")


def _single_process() -> bool:
    """Swarm pull hot-registers a second engine, which multi-host
    leader-replicated serving cannot represent (parallel/replicated.py)
    — the pull op is disabled on multi-process clusters at the SERVICE,
    so programmatic workers are covered, not just the CLI."""
    import jax

    return jax.process_count() == 1


def _tpu_capabilities() -> dict:
    """Real accelerator capabilities introspected from the JAX runtime.

    HBM comes from ``device.memory_stats()['bytes_limit']`` (the runtime's
    actual allocatable budget); the ICI topology from device coords when the
    platform exposes them.  Nothing is hardcoded — the reference advertises
    a fake RTX 4090 (peer.go:320-343); a capability the runtime cannot
    report is reported as 0/unknown, not invented.

    Initializes the JAX backend, so only a node whose engine runs on the
    device calls it (``Engine.on_device``); a backend that fails to
    initialize there raises — a worker must not advertise "unknown" over a
    chip it could not open.
    """
    import jax

    devs = jax.devices()
    d0 = devs[0]
    kind = getattr(d0, "device_kind", "cpu") or "cpu"
    n = len(devs)

    hbm_gb = 0.0
    stats = d0.memory_stats() or {}  # None on platforms without the API
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if limit:
        hbm_gb = round(limit / (1 << 30), 1)

    # Physical mesh extent per axis from device coordinates; a flat 1xN
    # when the platform has no coords (CPU) or the extents don't cover the
    # device count.
    topology = f"1x{n}"
    coords = [getattr(d, "coords", None) for d in devs]
    if all(c is not None for c in coords):
        dims = [max(c[i] for c in coords) - min(c[i] for c in coords) + 1
                for i in range(len(coords[0]))]
        dims = [d for d in dims if d > 1]
        if dims and math.prod(dims) == n:
            topology = ("x".join(str(d) for d in dims) if len(dims) > 1
                        else f"1x{dims[0]}")

    return {
        "accelerator": kind.lower().replace(" ", "-"),
        "tpu_chip_count": n,
        "hbm_gb_per_chip": hbm_gb,
        "ici_topology": topology,
    }


class Peer:
    """One swarm node (worker when ``engine`` serves real models)."""

    def __init__(
        self,
        key: Ed25519PrivateKey,
        config: Configuration,
        engine: Engine,
        worker_mode: bool,
    ):
        self.config = config
        self.key = key
        self.engine = engine
        self.worker_mode = worker_mode
        self.host = None
        self.dht = None
        self.resource = Resource(worker_mode=worker_mode, version=VERSION)
        self.peer_manager: PeerManager | None = None
        self._tasks: list[asyncio.Task] = []
        self.relay_client = None  # net/relay.py RelayClient when relaying
        self.relay_service = None  # RelayService when hosting one (public)
        self._draining = False  # graceful drain entered (docs/ROBUSTNESS.md)
        # Replicated gateway plane: consumers attach a swarm/gossip.py
        # GossipNode here; the inference serve loop hands it inbound
        # gossip_frame arms.  None on workers and single-gateway setups.
        self.gossip_node = None
        # Per-node observability plane (trace ring + histograms): served by
        # obs/http.ObsServer on workers, read directly by tests/benches.
        self.obs = NodeObs(
            trace_capacity=getattr(config, "trace_buffer",
                                   DEFAULT_TRACE_CAPACITY),
            node="worker" if worker_mode else "consumer",
            trace_ttl=getattr(config, "trace_ttl", 0.0) or 0.0,
            exemplars=bool(getattr(config, "metrics_exemplars", False)))

    # ----------------------------------------------------------- lifecycle

    async def start(self, listen_sock=None) -> None:
        """``listen_sock``: a socket already bound to the listen address
        (net/host.py ``bind_listener``) to listen on; None: bind there."""
        self.host, self.dht = await new_host_and_dht(
            self.key,
            listen_host=self.config.listen_host,
            listen_port=self.config.listen_port,
            listen_sock=listen_sock,
        )
        self.resource.peer_id = self.host.peer_id
        self.update_metadata()

        self.host.set_stream_handler(METADATA_PROTOCOL, self._handle_metadata_stream)
        # Health probes and discovery prefer the pooled KAD "metadata" op
        # (one frame each way over a reused stream) — the legacy
        # read-to-EOF stream above stays served for wire parity
        # (discovery.go:186-275) and as the fallback path.
        self.dht.metadata_provider = self._metadata_snapshot
        self.host.set_stream_handler(INFERENCE_PROTOCOL, self._handle_inference_stream)
        if self.worker_mode:
            # Swarm model distribution (net/model_share.py): share local
            # checkpoints and accept pull triggers (the `ollama pull`
            # surface the reference inherits, cmd/crowdllama/main.go:49-78).
            from crowdllama_tpu.core.protocol import MODEL_PROTOCOL
            from crowdllama_tpu.net.model_share import ModelShareService

            self._model_share = ModelShareService(
                model_dir=self.engine.model_dir, pull=self.pull_model,
                allow_pull=(
                    getattr(self.config, "allow_swarm_pull", True)
                    and (not self.engine.on_device or _single_process())))
            self.host.set_stream_handler(MODEL_PROTOCOL,
                                         self._model_share.handle)
        shard_service = getattr(self.engine, "shard_service", None)
        if shard_service is not None:
            # Sharded-model member: serve our pipeline stage to group leaders.
            self.host.set_stream_handler(SHARD_PROTOCOL, shard_service.handle)
        # The engine records worker_queue/prefill/decode_step spans and the
        # per-request histograms into this node's obs plane (engine.py
        # _obs_generate); attach BEFORE attach_peer so engine overrides see
        # a fully wired peer.
        self.engine.obs = self.obs
        self.engine.attach_peer(self)

        self.peer_manager = PeerManager(
            self_peer_id=self.host.peer_id,
            config=PeerHealthConfig(intervals=self.config.intervals),
            metadata_fetcher=self._fetch_peer_metadata,
            discovery=self._run_discovery,
            # Health-machine eviction also drops the dead peer's provider
            # records / routing entry from our DHT view immediately.
            on_peer_removed=self.dht.evict_peer,
        )
        # Served RPCs prove the caller alive (replaces the per-probe
        # metadata-stream mark_seen the RPC pool elides).
        self.dht.peer_seen = self.peer_manager.mark_seen

        if self.config.bootstrap_peers:
            n = await self.dht.bootstrap(self.config.bootstrap_peers)
            log.info("bootstrapped to %d/%d peers", n, len(self.config.bootstrap_peers))

        await self._setup_relay()

        self.peer_manager.start()
        iv = self.config.intervals
        self.dht.start_maintenance(provider_check=iv.dht_provider_check,
                                   bucket_refresh=iv.dht_bucket_refresh)
        self._tasks = [
            asyncio.create_task(
                run_every(iv.metadata_refresh, self._refresh_metadata, log, logging.DEBUG),
                name="peer-metadata-refresh"),
            asyncio.create_task(
                run_every(iv.metadata_publish, self._publish_metadata, log, logging.DEBUG),
                name="peer-publish"),
            asyncio.create_task(
                run_every(iv.advertise, self._advertise, log, logging.DEBUG),
                name="peer-advertise"),
        ]
        if self.worker_mode and self.config.relay_mode == "auto":
            self._tasks.append(asyncio.create_task(
                run_every(iv.relay_reprobe, self._reprobe_relay, log,
                          logging.DEBUG),
                name="peer-relay-reprobe"))
        log.info("peer %s up (%s) on %s",
                 self.host.peer_id[:8],
                 "worker" if self.worker_mode else "consumer",
                 self.host.contact.addr)

    async def stop_advertising(self) -> None:
        """Stop the publish/advertise/refresh loops without closing streams.

        The graceful-shutdown first step: the swarm stops learning about
        this peer (provider records TTL out, metadata goes stale, health
        probes fail over) while in-flight requests keep being served."""
        for t in self._tasks:
            t.cancel()
        for t in self._tasks:
            try:
                await t
            except asyncio.CancelledError:
                pass
        self._tasks = []

    async def _setup_relay(self) -> None:
        """NAT traversal (net/relay.py; libp2p relay/hole-punch parity,
        /root/reference/pkg/dht/dht.go:386-395, discovery.go:62): a worker
        the bootstrap node cannot dial back registers for reverse streams
        through it — with failover to any relay_capable swarm peer — and
        advertises the relay address instead of its own.  Directly
        reachable workers instead HOST a RelayService themselves and
        advertise relay_capable, so the swarm's relay capacity scales with
        its public membership instead of hanging off bootstrap_peers[0]."""
        if self.config.relay_mode == "off":
            return
        from crowdllama_tpu.net.relay import RelayClient, dialback_probe

        if not self.worker_mode:
            # Consumers never relay, but knowing whether OUR listen port
            # is publicly dialable enables connection reversal on dials
            # to relayed workers (host._new_stream_via_relay): the worker
            # dials us back and the data path skips the relay hairpin.
            if self.config.bootstrap_peers:
                try:
                    self.host.reverse_dialable = await dialback_probe(
                        self.host, self.config.bootstrap_peers[0])
                except Exception as e:
                    log.debug("consumer dialback probe unavailable (%s)", e)
            return
        if not self.config.bootstrap_peers:
            self._start_relay_service()
            return
        relay_addr = self.config.bootstrap_peers[0]
        if self.config.relay_mode == "auto":
            try:
                if await dialback_probe(self.host, relay_addr):
                    # Directly reachable: no relay needed — serve as one.
                    self.host.reverse_dialable = True
                    self._start_relay_service()
                    return
            except Exception as e:
                # No relay service at the bootstrap node (or probe error):
                # relaying through it is impossible either way — stay
                # direct rather than stall startup on doomed registration.
                log.debug("dialback probe unavailable (%s); staying "
                          "direct", e)
                return
        await self._register_relay(relay_addr)

    async def _register_relay(self, relay_addr: str) -> bool:
        """Register for reverse streams via ``relay_addr`` (with failover
        candidates); returns False when registration can't start."""
        from crowdllama_tpu.net.relay import RelayClient

        log.info("worker not directly reachable: relaying via %s", relay_addr)
        # Stop advertising the direct address BEFORE registering, so the
        # relay (and every later peer) never learns a bogus direct contact.
        self.host.hello_dialable = False
        client = RelayClient(self.host, relay_addr,
                             candidates=self._relay_candidates,
                             on_relay_change=self._on_relay_change)
        try:
            await client.start()
        except Exception:
            await client.stop()  # kill the reconnect loop too
            self.host.hello_dialable = True  # direct-only better than dead
            log.exception("relay registration failed; staying direct")
            return False
        self.relay_client = client
        self.host.reverse_dialable = False  # confirmed not dialable
        if self.relay_service is not None:
            # A NATed node can't relay for others — stop advertising it.
            self.relay_service.close()
            self.relay_service = None
            self.resource.relay_capable = False
        self._on_relay_change(client.relay_addr)
        return True

    def _start_relay_service(self) -> None:
        """Host a RelayService for NATed swarm members (public workers)."""
        from crowdllama_tpu.net.relay import RelayService

        if self.relay_service is None:
            self.relay_service = RelayService(self.host)
            # Traced relay splices record relay_splice spans into this
            # node's ring so the trace collector can fetch the relay hop.
            self.relay_service.obs = self.obs
            self.resource.relay_capable = True
            log.info("hosting relay service for NATed peers")

    def _relay_candidates(self) -> list[str]:
        """Failover relay addresses: bootstrap peers first, then every
        healthy swarm peer advertising relay_capable (resolved through the
        local DHT routing table — no network round trip)."""
        cands = list(self.config.bootstrap_peers)
        try:
            capable = {
                p.peer_id for p in self.peer_manager.get_healthy_peers()
                if getattr(p.resource, "relay_capable", False)}
            for c in self.dht.table.contacts():
                if c.peer_id in capable and not c.relay:
                    cands.append(f"{c.host}:{c.port}")
        except Exception as e:
            log.debug("relay candidate scan failed: %s", e)
        seen: set[str] = set()
        return [a for a in cands if not (a in seen or seen.add(a))]

    def _on_relay_change(self, relay_addr: str) -> None:
        """(Re-)advertise the current relay contact — fires on every
        successful registration, including failover to a new relay."""
        from crowdllama_tpu.net.host import Contact

        rhost, _, rport = relay_addr.rpartition(":")
        self.host.relay_contact = Contact(
            peer_id=self.host.peer_id, host=rhost or "127.0.0.1",
            port=int(rport), relay=True)
        self.resource.reachability = "relay"
        self.update_metadata()

    async def _reprobe_relay(self) -> None:
        """relay_mode=auto reachability tracking, BOTH directions: a
        relaying worker whose listen port became directly reachable (NAT
        opened, port-forward added) drops the relay; a direct worker whose
        port stopped being reachable (mapping expired) goes back to
        relaying — without this the upgrade would be one-way and a
        transiently-open NAT would strand the worker advertising a dead
        direct address."""
        if self.config.relay_mode != "auto":
            return
        from crowdllama_tpu.net.relay import dialback_probe

        if self.relay_client is not None:
            try:
                reachable = await dialback_probe(
                    self.host, self.relay_client.relay_addr)
            except Exception:
                return  # relay gone mid-probe: client failover handles it
            if not reachable:
                return
            log.info("direct dialback succeeded; dropping relay %s",
                     self.relay_client.relay_addr)
            await self.relay_client.stop()
            self.relay_client = None
            self.host.relay_contact = None
            self.host.hello_dialable = True
            self.host.reverse_dialable = True
            self.resource.reachability = "direct"
            self._start_relay_service()
            self.update_metadata()
            await self._publish_metadata()
            return

        # Direct worker: confirm we are still dialable via any known relay.
        cands = self._relay_candidates()
        if not cands:
            return
        try:
            reachable = await dialback_probe(self.host, cands[0])
        except Exception:
            return  # no relay service reachable to probe through
        if reachable:
            self.host.reverse_dialable = True
            return
        log.info("direct dialback stopped succeeding; returning to relay")
        if await self._register_relay(cands[0]):
            await self._publish_metadata()

    async def pull_model(self, model: str) -> str:
        """Acquire ``model`` from a swarm peer and serve it.

        Resolves a healthy worker advertising the model, streams its
        checkpoint with per-file hash verification (net/model_share.py),
        then hot-registers it on engines that support it
        (MultiEngine.add_model).  Returns the local checkpoint path."""
        from crowdllama_tpu.net.model_share import (
            fetch_model,
            safe_model_dirname,
        )

        safe_model_dirname(model)  # reject path-traversal names up front
        if model in (self.engine.models or []):
            d = self.engine.model_dir(model)
            return d or ""
        if self.peer_manager is None:
            raise RuntimeError("peer not started")
        candidates = [
            p for p in self.peer_manager.get_healthy_peers()
            if p.is_worker and model in p.resource.supported_models
            and p.peer_id != self.peer_id]
        if not candidates:
            raise RuntimeError(
                f"no swarm peer advertises model {model!r}")
        last_err: Exception | None = None
        for cand in candidates:
            try:
                contact = await self.dht.find_peer(cand.peer_id)
                if contact is None:
                    raise RuntimeError(f"cannot resolve {cand.peer_id[:8]}")
                dest = await fetch_model(self.host, contact, model,
                                         self.config.models_dir)
                break
            except Exception as e:  # source without a checkpoint, wire error
                log.warning("pull of %s from %s failed: %s", model,
                            cand.peer_id[:8], e)
                last_err = e
        else:
            raise RuntimeError(f"pull failed from every source: {last_err}")
        add = getattr(self.engine, "add_model", None)
        if add is None:
            # Succeeding here would let the gateway's /api/pull report
            # success for a model /api/chat still 503s on.
            raise RuntimeError(
                f"checkpoint downloaded to {dest} but this worker's engine "
                f"cannot hot-register models; restart with --model {model} "
                f"--model-path {dest}")
        await add(model, str(dest))
        return str(dest)

    async def drain(self) -> int:
        """Graceful drain (docs/ROBUSTNESS.md): flip this peer to the
        ``draining`` state and hand off in-flight generation.

        Idempotent (SIGTERM and POST /drain may race).  Order matters:

        1. the engine's migration is REQUESTED first — the scheduler
           flips to draining and claims every in-flight stream at its
           next safe point (within one decode dispatch).  Requesting it
           after the network advertising below raced stream completion:
           the forced DHT provide can take seconds, long enough for a
           short stream to finish with ``"stop"`` on this worker instead
           of migrating (the drain-vs-completion race the claim-or-skip
           safe point closes from the scheduler side);
        2. advertised metadata flips to ``draining: true`` and ONE forced
           metadata provide goes out while the migration completes, so
           gateways that re-probe quarantine us now rather than at the
           next reprovide tick;
        3. the migration result is awaited — each claimed stream got a
           MigrateFrame and the gateway re-routes it with this worker
           attached as KV donor.

        New GenerateRequests are rejected with a ``draining`` terminal
        frame from here on, but the serve loops STAY UP: this node keeps
        answering KvFetchRequests (the donor role) until the process
        exits at drain_timeout.  Returns how many requests were migrated.
        """
        if self._draining:
            return 0
        self._draining = True
        self.resource.draining = True
        self.resource.touch()
        if self.obs is not None:
            self.obs.metrics.drain_inc("initiated")
        t0 = time.perf_counter_ns()
        migrating = asyncio.ensure_future(self.engine.migrate())
        await self.stop_advertising()
        if self.dht is not None and self.host is not None:
            try:
                await self.dht.reconnect_if_needed()
                # min_interval=0 forces the network provide NOW — the
                # stale record from the serving era must not outlive the
                # streams it would route here.
                await asyncio.wait_for(
                    self.dht.provide(metadata_key(self.host.peer_id.encode()),
                                     min_interval=0), timeout=5.0)
            except Exception as e:
                log.warning("drain metadata publish failed: %s", e)
        migrated = await migrating
        if self.obs is not None:
            self.obs.trace.record(
                f"drain-{self.peer_id[:8]}", "drain",
                time.perf_counter_ns() - t0, migrated=migrated)
        log.info("peer %s draining: %d in-flight requests migrated",
                 self.peer_id[:8], migrated)
        return migrated

    async def stop(self) -> None:
        await self.stop_advertising()
        # Departure publish BEFORE tearing down relay + inference streams:
        # peers that re-probe metadata during the teardown window see
        # draining=true and deroute instead of racing dead streams
        # (regression-tested in tests/test_churn.py).
        if self.dht is not None and self.host is not None:
            self.resource.draining = True
            self.resource.touch()
            try:
                await asyncio.wait_for(
                    self.dht.provide(metadata_key(self.host.peer_id.encode()),
                                     min_interval=0), timeout=2.0)
            except Exception as e:
                log.debug("departure publish failed: %s", e)
        if self.relay_client is not None:
            await self.relay_client.stop()
            self.relay_client = None
        if self.relay_service is not None:
            self.relay_service.close()
            self.relay_service = None
        if self.peer_manager is not None:
            await self.peer_manager.stop()
        if self.dht is not None:
            await self.dht.stop_maintenance()
        if self.host is not None:
            await self.host.close()

    @property
    def peer_id(self) -> str:
        return self.host.peer_id if self.host else ""

    # ------------------------------------------------------------ metadata

    def update_metadata(self) -> None:
        """Refresh the advertised Resource from live engine telemetry
        (replaces the reference's hardcoded advertisement, peer.go:320-343)."""
        d = self.engine.describe()
        r = self.resource
        r.supported_models = list(d.get("models", []))
        r.tokens_throughput = float(d.get("throughput", 0.0))
        r.load = float(d.get("load", 0.0))
        r.version = VERSION
        r.worker_mode = self.worker_mode
        r.max_context_length = self.config.max_context_length
        r.embeddings = bool(d.get("embeddings", True))
        if self.engine.on_device:
            for k, v in _tpu_capabilities().items():
                setattr(r, k, v)
        sg = d.get("shard_group")
        if sg is not None:
            r.shard_group = sg
        r.touch()

    async def _refresh_metadata(self) -> None:
        self.update_metadata()

    async def _publish_metadata(self) -> None:
        """Provide the metadata reachability key (peer.go:409-447).

        Divergence from the reference: it derives the key from the metadata
        JSON (a brand-new CID every refresh — write-only churn, nothing ever
        looks content-addressed metadata up); we provide a stable per-peer
        key so the record refreshes in place instead of accumulating.
        """
        await self.dht.reconnect_if_needed()
        await self.dht.provide(metadata_key(self.host.peer_id.encode()),
                               min_interval=self.config.intervals.reprovide)

    async def _advertise(self) -> None:
        """Provide the namespace rendezvous key (peer.go:450-504).  The
        tick stays fast (reconnect watch + membership/contact-change
        detection inside provide()); the network re-provide is
        rate-limited to ``intervals.reprovide``."""
        await self.dht.reconnect_if_needed()
        await self.dht.provide(namespace_key(),
                               min_interval=self.config.intervals.reprovide)

    # ------------------------------------------------------------- streams

    def _metadata_snapshot(self) -> bytes:
        """CURRENT Resource JSON for the pooled KAD metadata op — same
        live refresh the legacy stream handler performs, or probes would
        serve load/throughput frozen at the last refresh tick and
        find_best_worker would rank saturated workers as idle."""
        self.update_metadata()
        return self.resource.to_json()

    async def _handle_metadata_stream(self, stream: Stream) -> None:
        """Serve Resource JSON and close (peer.go:284-316)."""
        stream.writer.write(self._metadata_snapshot())
        await stream.writer.drain()
        stream.writer.write_eof()
        if self.peer_manager is not None:
            self.peer_manager.mark_seen(stream.remote_peer_id)

    async def _handle_inference_stream(self, stream: Stream) -> None:
        """Serve inference requests on one stream until the client closes
        or idles out (peer.go:190-256 serves exactly one per stream; the
        loop is what lets the gateway's stream pool amortize the TCP +
        signed-hello handshake over many requests).

        Non-streaming: one request frame in, one response frame out.
        Streaming (req.stream=true): one frame per token chunk, done on last —
        the superset the reference never implements (its TTFT == total
        latency, SURVEY §3.3).
        """
        while True:
            if not await self._serve_one_inference(stream):
                return

    async def _serve_one_inference(self, stream: Stream) -> bool:
        """One request/reply exchange; False ends the stream's loop."""
        from crowdllama_tpu.net.host import STREAM_POOL_IDLE_S

        try:
            # Idle window must OUTLAST the gateway pool's (plus slack), or
            # every pooled stream the gateway still considers fresh would
            # already be dead on this side and each hit would pay a failed
            # roundtrip before the redial.
            msg = await wire.read_length_prefixed_pb(
                stream.reader,
                timeout=max(self.config.intervals.stream_read_timeout,
                            STREAM_POOL_IDLE_S + 5.0),
            )
        except (wire.WireError, asyncio.TimeoutError, OSError) as e:
            log.debug("inference stream read ended: %s", e)
            return False
        # Trace propagation: the gateway's id arrives on the envelope and is
        # echoed on every response frame, so a multi-hop consumer (relay
        # splice included) can correlate replies without holding state.
        tid = msg.trace_id
        try:
            which = msg.WhichOneof("message")
            if which == "embed_request":
                reply = await self.engine.handle(msg, worker_id=self.peer_id)
                reply.trace_id = tid
                await wire.write_length_prefixed_pb(stream.writer, reply)
                return True
            if which == "kv_fetch_request":
                await self._serve_kv_fetch(stream, msg)
                return True
            if which == "trace_fetch":
                await self._serve_trace_fetch(stream, msg)
                return True
            if which == "metrics_fetch":
                await self._serve_metrics_fetch(stream, msg)
                return True
            if which == "gossip_frame":
                # Replicated gateway anti-entropy (swarm/gossip.py): merge
                # the sender's LWW map + usage digests, reply with our own
                # full frame when sync is requested.  A node with no gossip
                # plane attached ignores the frame (back-compat: workers
                # and pre-gossip gateways just keep the stream alive).
                if self.gossip_node is not None:
                    reply = await self.gossip_node.handle_frame(msg)
                    if reply is not None:
                        reply.trace_id = tid
                        await wire.write_length_prefixed_pb(
                            stream.writer, reply)
                return True
            if which == "draft_chunk":
                # A DraftChunk outside a remote-draft stream (stale gateway
                # pump after failover, or a pre-remote-draft worker build
                # being probed): nack it terminally so the pump stops
                # instead of waiting out its RTT budget.  In-stream chunks
                # never reach here — the reader task owns the transport.
                from crowdllama_tpu.core.messages import (
                    extract_draft_chunk,
                    verify_result_msg,
                )

                dc = extract_draft_chunk(msg)
                nack = verify_result_msg(
                    chunk_id=dc.chunk_id, position=dc.position,
                    accepted=0, tokens=[], done=True,
                    draft_k=0, depth_hint=1)
                nack.trace_id = tid
                await wire.write_length_prefixed_pb(stream.writer, nack)
                return True
            req = msg.generate_request
            if which != "generate_request":
                raise ValueError("expected GenerateRequest")
            if self._draining:
                # Typed reject (docs/ROBUSTNESS.md): a draining worker
                # takes no NEW generation — the gateway fails over without
                # burning its failover budget on us — but the stream stays
                # open: we keep serving KvFetchRequests as a migration
                # donor until drain_timeout.
                from crowdllama_tpu.core.messages import genresp_frame_bytes

                if self.obs is not None:
                    self.obs.metrics.drain_inc("rejected_requests")
                reject = genresp_frame_bytes(
                    model=req.model, response="", worker_id=self.peer_id,
                    done=True, done_reason="draining", trace_id=tid)
                await wire.write_frame_bytes(stream.writer, reject)
                return True
            if req.stream:
                # Frames-first hot path: the engine yields encoded wire
                # frames (trace_id embedded); the batcher sends the first
                # frame inline (hard TTFT bound even for burst producers)
                # and coalesces every later frame produced within one
                # event-loop tick into a single sealed write
                # (wire.FrameBatcher — flushes via call_soon).
                feed = reader_task = None
                remote_draft = bool(getattr(req, "remote_draft", False))
                if remote_draft:
                    # Gateway-drafted pipeline (docs/SPECULATIVE.md): the
                    # gateway keeps sending DraftChunk frames on THIS
                    # stream while we stream responses back.  A reader
                    # task drains them into the scheduler's credit feed —
                    # or nacks each one when the engine can't verify
                    # (FakeEngine, plain runner) so the gateway degrades
                    # to an unpaced plain stream.
                    from crowdllama_tpu.core.spec_pipeline import DraftFeed

                    feed = DraftFeed()
                    consume = bool(getattr(
                        self.engine, "supports_remote_draft", False))
                    reader_task = asyncio.get_running_loop().create_task(
                        self._read_draft_chunks(stream, feed, tid, consume))
                flush_ns = 0
                batcher = wire.FrameBatcher(stream.writer)
                try:
                    async for frame in self.engine.handle_streaming_frames(
                            msg, worker_id=self.peer_id, draft_feed=feed):
                        t0 = time.perf_counter_ns()
                        batcher.write(frame)
                        await batcher.drain()
                        flush_ns += time.perf_counter_ns() - t0
                    t0 = time.perf_counter_ns()
                    await batcher.flush()
                    flush_ns += time.perf_counter_ns() - t0
                finally:
                    if reader_task is not None:
                        reader_task.cancel()
                        try:
                            await reader_task
                        except (asyncio.CancelledError, Exception):
                            pass
                        feed.close()
                if tid:
                    self.obs.trace.record(tid, "stream_flush", flush_ns,
                                          parent=msg.parent_span)
                if remote_draft:
                    # One-shot stream: the cancelled reader may have left a
                    # partial DraftChunk frame in the receive buffer — a
                    # pooled reuse would misparse it as the next request.
                    return False
            else:
                reply = await self.engine.handle(msg, worker_id=self.peer_id)
                reply.trace_id = tid
                t0 = time.perf_counter_ns()
                await wire.write_length_prefixed_pb(stream.writer, reply)
                if tid:
                    self.obs.trace.record(
                        tid, "stream_flush", time.perf_counter_ns() - t0,
                        parent=msg.parent_span)
            return True
        except Exception as e:
            from crowdllama_tpu.testing.faults import KillStream, StallStream

            if isinstance(e, StallStream):
                # Injected gray failure (testing/faults.py): the transport
                # stays OPEN but nothing is ever written again — no EOF, no
                # error frame.  From the gateway this is a worker that
                # wedged mid-stream; only its per-stream progress watchdog
                # (--stream-stall-ms) can notice.  Park until the gateway
                # gives up and closes its end (reader EOF), then drop out.
                log.warning("fault injection stalled inference stream: %s", e)
                try:
                    await asyncio.wait_for(stream.reader.read(),
                                           timeout=600.0)
                except Exception:
                    pass
                stream.close()
                return False
            if isinstance(e, KillStream):
                # Injected worker death (testing/faults.py): drop the
                # transport with NO error frame — from the gateway this is
                # indistinguishable from the worker process crashing
                # mid-stream, which is what chaos tests simulate.
                log.warning("fault injection killed inference stream: %s", e)
                stream.close()
                return False
            # Synthesize an error response (peer.go:233-243).
            log.warning("inference failed: %s", e)
            from crowdllama_tpu.core.messages import (
                create_embed_response,
                genresp_frame_bytes,
            )

            if msg.WhichOneof("message") == "embed_request":
                # "invalid:" marks deterministic client errors (bad input)
                # so the gateway returns 400 without burning a retry on
                # another worker that would fail identically.  Capability
                # gaps (NotImplementedError) stay retryable — another
                # worker may well embed — and routing avoids them anyway
                # via Resource.embeddings.
                prefix = "invalid: " if isinstance(e, ValueError) else ""
                detail = str(e) or (
                    "this worker's engine does not support embeddings"
                    if isinstance(e, NotImplementedError) else repr(e))
                err = create_embed_response(
                    model=msg.embed_request.model, embeddings=[],
                    worker_id=self.peer_id, error=prefix + detail,
                )
                err.trace_id = tid
                err_frame = wire.encode_frame(err)
            else:
                err_frame = genresp_frame_bytes(
                    model=msg.generate_request.model if msg.generate_request else "",
                    response=f"error: {e}",
                    worker_id=self.peer_id,
                    done=True,
                    done_reason="error",
                    trace_id=tid,
                )
            try:
                await wire.write_frame_bytes(stream.writer, err_frame)
            except Exception:
                return False  # writer dead: end the stream's serve loop
            return True  # error frame delivered; the exchange is complete

    async def _read_draft_chunks(self, stream: Stream, feed, tid: str,
                                 consume: bool) -> None:
        """Reader side of a remote-draft stream (docs/SPECULATIVE.md):
        drain incoming DraftChunk frames into the scheduler's credit feed
        while the engine streams responses the other way.  ``consume``
        False (engine can't verify) nacks every chunk immediately so the
        gateway's pump degrades to plain streaming instead of stalling.
        Any transport error just closes the feed — the scheduler releases
        the stream to free_run and the generation finishes on its own."""
        from crowdllama_tpu.testing import faults
        from crowdllama_tpu.testing.faults import KillStream

        try:
            while True:
                msg = await wire.read_length_prefixed_pb(
                    stream.reader, timeout=600.0)
                if msg.WhichOneof("message") != "draft_chunk":
                    log.debug("remote-draft reader: unexpected %s frame",
                              msg.WhichOneof("message"))
                    continue
                dc = msg.draft_chunk
                await faults.inject("spec.draft_chunk", worker=self.peer_id,
                                    chunk_id=int(dc.chunk_id))
                if consume:
                    feed.push(dc.chunk_id, dc.position, list(dc.tokens))
                    continue
                from crowdllama_tpu.core.messages import verify_result_msg

                await faults.inject("spec.verify", worker=self.peer_id,
                                    chunk_id=int(dc.chunk_id))
                nack = verify_result_msg(
                    chunk_id=dc.chunk_id, position=dc.position,
                    accepted=0, tokens=[], done=False,
                    draft_k=0, depth_hint=1)
                if tid:
                    nack.trace_id = tid
                # Whole-frame write: FrameBatcher seals complete frames, so
                # interleaving with the engine's response frames is safe at
                # frame granularity.
                await wire.write_length_prefixed_pb(stream.writer, nack)
        except asyncio.CancelledError:
            raise
        except KillStream as e:
            # Injected worker death mid-verify (chaos): drop the transport
            # with no error frame, exactly like the generation-path kill.
            log.warning("fault injection killed draft reader: %s", e)
            stream.close()
            feed.close()
        except (wire.WireError, asyncio.TimeoutError, OSError) as e:
            log.debug("draft chunk reader ended: %s", e)
            feed.close()
        except Exception as e:
            log.warning("draft chunk reader failed: %s", e)
            feed.close()

    _KV_FRAME_BYTES = 4 * 1024 * 1024  # page payload per KvPages frame

    async def _serve_trace_fetch(self, stream: Stream, msg) -> None:
        """Serve the trace collector's span-fragment fetch (PR 8).

        The payload is the SAME JSON record this node's own /debug/trace
        serves — schema-stable as span vocabularies evolve, and the
        collector never needs per-span proto churn.  A node that never
        saw the id answers ``found=false``: the collector's fan-out IS
        the index, so a miss is the common, cheap case."""
        import json as _json

        from crowdllama_tpu.core.messages import trace_spans_msg

        trace_id = msg.trace_fetch.trace_id
        node = f"{self.obs.trace.node or 'peer'}:{self.peer_id[:8]}"
        rec = self.obs.trace.get(trace_id) if trace_id else None
        if rec is None:
            out = trace_spans_msg(trace_id, node=node, found=False)
        else:
            out = trace_spans_msg(
                trace_id, node=node,
                payload=_json.dumps(rec).encode("utf-8"), found=True)
        out.trace_id = trace_id
        await wire.write_length_prefixed_pb(stream.writer, out)

    async def _serve_metrics_fetch(self, stream: Stream, msg) -> None:
        """Serve the gateway's cluster-scrape fetch (PR 13, swarm
        observatory).

        The payload is the SAME exposition text this node's own ObsServer
        /metrics serves — one composition (obs/http.node_metric_lines), so
        the p2p scrape and the HTTP scrape cannot drift.  ``families``
        prefix-filters the reply (TYPE headers follow their family), which
        keeps a rollup-only scrape cheap on big swarms."""
        from crowdllama_tpu.core.messages import metrics_snapshot_msg
        from crowdllama_tpu.obs.http import node_metric_lines

        node = f"{self.obs.trace.node or 'peer'}:{self.peer_id[:8]}"
        try:
            lines = node_metric_lines(self)
            prefixes = tuple(msg.metrics_fetch.families)
            if prefixes:
                lines = [ln for ln in lines
                         if ln.split()[-2 if ln.startswith("# TYPE") else 0]
                         .startswith(prefixes)]
            out = metrics_snapshot_msg(
                node=node, payload="\n".join(lines).encode("utf-8"),
                found=True)
        except Exception as e:  # a sick node still answers, flagged
            log.warning("metrics snapshot failed: %s", e)
            out = metrics_snapshot_msg(node=node, found=False, error=str(e))
        out.trace_id = msg.trace_id
        await wire.write_length_prefixed_pb(stream.writer, out)

    async def _serve_kv_fetch(self, stream: Stream, msg) -> None:
        """Serve a peer's paged-KV fetch (docs/KV_TRANSFER.md, donor side).

        Pages stream out in bounded frames well under wire.MAX_MESSAGE_SIZE;
        the exporter pins page refs only for the device→host gather, so a
        slow receiver never holds donor pool pages hostage.  All failures
        are reported in-band (KvPages.error) — the fetcher falls back to
        plain prefill, it never retries against us."""
        from crowdllama_tpu.core import pb
        from crowdllama_tpu.core.messages import kv_pages_msg
        from crowdllama_tpu.testing.faults import KillStream

        req = msg.kv_fetch_request
        tid = msg.trace_id
        # Chaos choke point (testing/faults.py): a donor hiccup here is
        # what the fetcher's retry/deadline handling defends against.
        from crowdllama_tpu.testing import faults

        await faults.inject("kv.serve", worker=self.peer_id, model=req.model)
        t0 = time.perf_counter_ns()
        try:
            payload = await asyncio.wait_for(
                self.engine.export_kv_pages(
                    req.model, list(req.chain_hashes), int(req.page_size)),
                timeout=max(1.0, self.config.kv_ship_timeout))
        except KillStream:
            raise
        except Exception as e:
            payload, err = None, f"kv export failed: {e}"
        else:
            err = "" if payload is not None else "kv export unavailable"
        if payload is None or payload["matched"] == 0:
            out = kv_pages_msg(pb.KvPages(
                model=req.model, matched=0, done=True,
                error=err or ""))
            out.trace_id = tid
            await wire.write_length_prefixed_pb(stream.writer, out)
            return
        k_pages, v_pages = payload["k_pages"], payload["v_pages"]
        k_scales, v_scales = payload["k_scales"], payload["v_scales"]
        matched = payload["matched"]
        sent_bytes = 0
        start = 0
        while start < matched:
            end, size = start, 0
            while end < matched and (size < self._KV_FRAME_BYTES
                                     or end == start):
                size += len(k_pages[end]) + len(v_pages[end])
                if k_scales:
                    size += len(k_scales[end]) + len(v_scales[end])
                end += 1
            frame = pb.KvPages(
                model=req.model, matched=matched, start=start,
                kv_dtype=payload["kv_dtype"], done=(end >= matched))
            frame.k_pages.extend(k_pages[start:end])
            frame.v_pages.extend(v_pages[start:end])
            if k_scales:
                frame.k_scales.extend(k_scales[start:end])
                frame.v_scales.extend(v_scales[start:end])
            out = kv_pages_msg(frame)
            out.trace_id = tid
            await wire.write_length_prefixed_pb(stream.writer, out)
            sent_bytes += size
            start = end
        self.obs.metrics.kv_ship_inc("bytes", sent_bytes)
        self.obs.metrics.kv_ship_inc("fetches")
        if tid:
            self.obs.trace.record(tid, "kv_export",
                                  time.perf_counter_ns() - t0,
                                  pages=matched, bytes=sent_bytes)

    # ----------------------------------------------------------- discovery

    async def _fetch_peer_metadata(self, peer_id: str) -> Resource:
        contact = await self.dht.find_peer(peer_id)
        if contact is None:
            raise LookupError(f"peer {peer_id[:8]} not resolvable")
        # Pooled KAD op first (health probes are the steady-state churn);
        # legacy metadata stream as the fallback for peers not serving it.
        raw = await self.dht.request_metadata(contact)
        if raw is not None:
            resource = Resource.from_json(raw.encode()
                                          if isinstance(raw, str) else raw)
            if resource.peer_id and resource.peer_id != contact.peer_id:
                raise ValueError(
                    f"metadata peer_id {resource.peer_id[:8]} does not "
                    f"match peer {contact.peer_id[:8]}")
            return resource
        return await request_peer_metadata(
            self.host, contact, timeout=self.config.intervals.metadata_timeout
        )

    async def _run_discovery(self, skip: set[str]) -> list[Resource]:
        return await discover_peers(
            self.host, self.dht, intervals=self.config.intervals,
            skip_peer_ids=skip,
        )
