"""Swarm churn: workers die and join; discovery must converge and dead
providers must be evicted promptly (VERDICT round-1 missing #6).

The reference bootstrap server evicts on raw TCP disconnect
(/root/reference/pkg/dht/dht.go:370-383); the per-RPC transport here gets
the same effect from three eviction paths exercised below: the DHT
server's active liveness probe, RPC-failure eviction, and the health
machine's on_peer_removed hook into the local DHT view.
"""

import asyncio
import random

from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

from crowdllama_tpu.config import Configuration, Intervals
from crowdllama_tpu.core.protocol import namespace_key
from crowdllama_tpu.engine.engine import FakeEngine
from crowdllama_tpu.net.discovery import new_host_and_dht
from crowdllama_tpu.peer.peer import Peer


def _cfg(bootstrap):
    return Configuration(
        listen_host="127.0.0.1",
        bootstrap_peers=[bootstrap],
        model="churn-model",
        intervals=Intervals.default(),
    )


async def _wait_for(cond, timeout=45.0, interval=0.2, what="condition"):
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while loop.time() < deadline:
        if cond():
            return
        await asyncio.sleep(interval)
    raise AssertionError(f"timed out waiting for {what}")


async def _worker(bootstrap):
    w = Peer(Ed25519PrivateKey.generate(), _cfg(bootstrap),
             engine=FakeEngine(models=["churn-model"]), worker_mode=True)
    await w.start()
    return w


async def test_churn_converges_and_dead_providers_evicted():
    rng = random.Random(42)
    boot_host, boot_dht = await new_host_and_dht(
        Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    iv = Intervals.default()
    boot_dht.start_maintenance(provider_check=iv.dht_provider_check,
                               bucket_refresh=iv.dht_bucket_refresh)
    bootstrap = f"127.0.0.1:{boot_host.listen_port}"

    workers = [await _worker(bootstrap) for _ in range(3)]
    consumer = Peer(Ed25519PrivateKey.generate(), _cfg(bootstrap),
                    engine=FakeEngine(models=[]), worker_mode=False)
    await consumer.start()
    alive = list(workers)
    try:
        def healthy_ids():
            return {p.peer_id for p in consumer.peer_manager.get_healthy_peers()
                    if p.is_worker}

        await _wait_for(lambda: healthy_ids() >= {w.peer_id for w in alive},
                        what="initial discovery of 3 workers")

        # Churn rounds: kill a random worker, start a replacement.
        for round_no in range(2):
            victim = alive.pop(rng.randrange(len(alive)))
            victim_id = victim.peer_id
            await victim.stop()
            replacement = await _worker(bootstrap)
            alive.append(replacement)

            await _wait_for(
                lambda: replacement.peer_id in healthy_ids(),
                what=f"round {round_no}: replacement discovered")
            await _wait_for(
                lambda: victim_id not in healthy_ids(),
                what=f"round {round_no}: victim evicted from consumer")
            # Consumer's DHT view dropped the victim's provider records via
            # the health machine's on_peer_removed hook.
            await _wait_for(
                lambda: all(
                    c.peer_id != victim_id
                    for c in consumer.dht.providers.get(namespace_key())),
                what=f"round {round_no}: victim providers gone from consumer")
            # The bootstrap DHT server's liveness probe evicts the victim
            # well before the 30-minute record TTL.
            await _wait_for(
                lambda: all(
                    c.peer_id != victim_id
                    for c in boot_dht.providers.get(namespace_key())),
                what=f"round {round_no}: victim providers gone from server")

        # Steady state after churn: exactly the living workers are healthy
        # and routable.
        await _wait_for(
            lambda: healthy_ids() == {w.peer_id for w in alive},
            what="post-churn steady state")
        best = consumer.peer_manager.find_best_worker("churn-model")
        assert best is not None and best.peer_id in {w.peer_id for w in alive}
    finally:
        await consumer.stop()
        for w in alive:
            await w.stop()
        await boot_dht.stop_maintenance()
        await boot_host.close()


async def test_stop_publishes_departure_before_stream_teardown():
    """Ordered shutdown (docs/ROBUSTNESS.md): Peer.stop() must publish the
    draining departure record BEFORE tearing down relay/host streams, so a
    peer that re-probes metadata during the teardown window sees
    draining=true and deroutes instead of racing dead streams."""
    boot_host, _ = await new_host_and_dht(
        Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    bootstrap = f"127.0.0.1:{boot_host.listen_port}"
    try:
        w = await _worker(bootstrap)
        order = []
        real_provide = w.dht.provide
        real_close = w.host.close

        async def provide(*a, **kw):
            order.append("provide")
            return await real_provide(*a, **kw)

        async def close(*a, **kw):
            order.append("host_close")
            return await real_close(*a, **kw)

        w.dht.provide = provide
        w.host.close = close
        await w.stop()
        assert "provide" in order, "no departure publish during stop()"
        assert "host_close" in order
        assert order.index("provide") < order.index("host_close")
        # And the record it published said draining.
        assert w.resource.draining is True
    finally:
        await boot_host.close()


async def test_a_steady_discovery_round_is_one_lookup_and_no_metadata_fetch(
        monkeypatch):
    """The benchmark's three nodes (a DHT server, a worker, a gateway):
    once the gateway knows the worker, a discovery round is ONE provider
    lookup — asked with every known peer in the skip set — and fetches
    nobody's metadata, however often it runs; a second worker that joins
    is fetched once, in the next round, and then skipped like the first."""
    from crowdllama_tpu.net import discovery

    boot_host, _ = await new_host_and_dht(
        Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    bootstrap = f"127.0.0.1:{boot_host.listen_port}"
    workers = [await _worker(bootstrap)]
    consumer = Peer(Ed25519PrivateKey.generate(), _cfg(bootstrap),
                    engine=FakeEngine(models=[]), worker_mode=False)
    await consumer.start()
    try:
        pm = consumer.peer_manager
        await _wait_for(lambda: workers[0].peer_id in pm.peers,
                        what="the worker behind the gateway")
        # the rounds below are this test's own
        for t in pm._tasks:
            if t.get_name() == "pm-discovery":
                t.cancel()
        lookups, fetched = [], []
        find, fetch = consumer.dht.find_providers, (
            discovery.request_peer_metadata)

        async def counted_find(key, **kw):
            lookups.append(set(kw["skip"]))
            return await find(key, **kw)

        async def counted_fetch(host, contact, **kw):
            if host is consumer.host:     # the workers look around too
                fetched.append(contact.peer_id)
            return await fetch(host, contact, **kw)

        monkeypatch.setattr(consumer.dht, "find_providers", counted_find)
        monkeypatch.setattr(discovery, "request_peer_metadata",
                            counted_fetch)
        for _ in range(5):
            await pm.run_discovery_once()
        assert len(lookups) == 5 and fetched == []
        assert all(workers[0].peer_id in skip for skip in lookups)

        workers.append(await _worker(bootstrap))
        for _ in range(50):
            await pm.run_discovery_once()
            if workers[1].peer_id in pm.peers:
                break
            await asyncio.sleep(0.2)
        assert fetched == [workers[1].peer_id]
        for _ in range(3):
            await pm.run_discovery_once()
        assert fetched == [workers[1].peer_id]
    finally:
        await consumer.stop()
        for w in workers:
            await w.stop()
        await boot_host.close()
