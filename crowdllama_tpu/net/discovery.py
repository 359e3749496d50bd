"""Peer discovery over the DHT: rendezvous advertise + metadata fetch.

Counterpart of /root/reference/internal/discovery/discovery.go: construct
host+DHT (NewHostAndDHT :48), bootstrap (:87-141), namespace rendezvous key
(:176-183), fetch a peer's Resource JSON over the metadata stream with a
deadline (:186-275), and DiscoverPeers = find providers of the namespace key
then fetch + freshness-gate each one's metadata (:278-366).
"""

from __future__ import annotations

import asyncio
import logging

from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

from crowdllama_tpu.config import Intervals
from crowdllama_tpu.core.protocol import METADATA_PROTOCOL, namespace_key
from crowdllama_tpu.core.resource import Resource
from crowdllama_tpu.net.dht import DHTNode
from crowdllama_tpu.net.host import Contact, Host

log = logging.getLogger("crowdllama.net.discovery")

MAX_METADATA_SIZE = 1 * 1024 * 1024


async def new_host_and_dht(
    key: Ed25519PrivateKey,
    listen_host: str = "0.0.0.0",
    listen_port: int = 0,
    advertise_host: str | None = None,
    listen_sock=None,
) -> tuple[Host, DHTNode]:
    """Build and start a host plus DHT in server mode (discovery.go:48-84);
    ``listen_sock``: a socket already bound to the listen address."""
    host = Host(key, listen_host=listen_host, listen_port=listen_port,
                advertise_host=advertise_host)
    dht = DHTNode(host, server_mode=True)
    await host.start(sock=listen_sock)
    return host, dht


async def request_peer_metadata(
    host: Host,
    target: Contact,
    timeout: float | None = None,
) -> Resource:
    """Open a metadata stream and read the peer's Resource JSON to EOF.

    cf. discovery.go:186-275: the serving side writes its metadata JSON and
    closes the stream; a 5 s deadline bounds the exchange.
    """
    timeout = timeout if timeout is not None else Intervals.default().metadata_timeout

    async def _fetch() -> Resource:
        stream = await host.new_stream(target, METADATA_PROTOCOL)
        try:
            # Read to EOF (the serving side closes the stream), bounded.
            chunks: list[bytes] = []
            total = 0
            while total <= MAX_METADATA_SIZE:
                chunk = await stream.reader.read(64 * 1024)
                if not chunk:
                    break
                chunks.append(chunk)
                total += len(chunk)
            if total > MAX_METADATA_SIZE:
                raise ValueError("metadata exceeds size cap")
            resource = Resource.from_json(b"".join(chunks))
            if resource.peer_id and resource.peer_id != target.peer_id:
                raise ValueError(
                    f"metadata peer_id {resource.peer_id[:8]} does not match "
                    f"stream peer {target.peer_id[:8]}"
                )
            return resource
        finally:
            stream.close()

    return await asyncio.wait_for(_fetch(), timeout)


async def discover_peers(
    host: Host,
    dht: DHTNode,
    intervals: Intervals | None = None,
    limit: int = 32,
    skip_peer_ids: set[str] | None = None,
) -> list[Resource]:
    """Find namespace providers and fetch fresh metadata from each.

    cf. discovery.go:278-366: FindProvidersAsync(namespace CID, 10), then
    per provider fetch metadata and reject records older than 1 h.
    ``skip_peer_ids`` carries the manager's filter — since round 4 that is
    EVERY known peer (their metadata refreshes via health probes).  The
    skip set is applied INSIDE find_providers, before its limit, so the
    limit bounds NEW providers per round — a growing swarm's joiners are
    found immediately no matter how many peers are already known.
    """
    intervals = intervals or Intervals.default()
    skip = skip_peer_ids or set()
    providers = await dht.find_providers(namespace_key(), limit=limit,
                                         skip=skip)

    async def _one(contact: Contact) -> Resource | None:
        if contact.peer_id in skip or contact.peer_id == host.peer_id:
            return None
        try:
            resource = await request_peer_metadata(
                host, contact, timeout=intervals.metadata_timeout
            )
        except Exception as e:
            log.debug("metadata fetch from %s failed: %s", contact.peer_id[:8], e)
            return None
        if resource.age_seconds > intervals.metadata_max_age:
            log.debug("rejecting stale metadata from %s (age %.0fs)",
                      contact.peer_id[:8], resource.age_seconds)
            return None
        if not resource.peer_id:
            resource.peer_id = contact.peer_id
        return resource

    fetched = await asyncio.gather(*(_one(c) for c in providers))
    results = [r for r in fetched if r is not None]
    return results


class Advertiser:
    """Periodic namespace provider advertisement (discovery.go:143-166 +
    peer.go:450-504): re-Provide the rendezvous key on a ticker, re-bootstrap
    first if the routing table went empty."""

    def __init__(self, dht: DHTNode, intervals: Intervals | None = None):
        self.dht = dht
        self.intervals = intervals or Intervals.default()
        self._task: asyncio.Task | None = None

    def start(self) -> None:
        if self._task is None:
            self._task = asyncio.create_task(self._run(), name="advertiser")

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None

    async def _run(self) -> None:
        while True:
            try:
                await self.dht.reconnect_if_needed()
                await self.dht.provide(namespace_key())
            except asyncio.CancelledError:
                raise
            except Exception as e:
                log.debug("advertise failed: %s", e)
            await asyncio.sleep(self.intervals.advertise)
