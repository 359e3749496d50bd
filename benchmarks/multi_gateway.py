"""Replicated gateway plane benchmark (ISSUE 7, docs/ROBUSTNESS.md).

Three phases over one shared FakeEngine worker swarm (control-plane
focus — mini_swarm.py owns real-engine e2e):

  scaling   req/s with 1 -> 4 gateway replicas round-robined by the
            client.  All replicas live in ONE process/event loop, so the
            curve measures the coordination overhead a replica adds
            (gossip rounds, shared swarm), NOT multi-core scaling.
  affinity  cross-replica affinity hit-rate: turn 1 of each conversation
            lands on a random replica, the continuation on a DIFFERENT
            one — a hit means the gossiped pin routed it to the worker
            that served turn 1 (hot KV), which random load-based routing
            would only do 1/workers of the time.
  tenants   per-tenant fair admission: a hot tenant floods past its
            token-bucket quota while a light tenant keeps its trickle.
            Reported: hot-tenant shed count and the light tenant's p95
            TTFT vs its solo baseline (the ~15% isolation bar).

Prints ONE JSON line; value is req/s at the largest replica count.

Env overrides:
  CROWDLLAMA_BENCH_MGW_SIZES     replica counts    (default "1,2,4")
  CROWDLLAMA_BENCH_MGW_REQUESTS  requests per size (default 48)
  CROWDLLAMA_BENCH_MGW_CONCURRENCY in-flight cap   (default 8)
  CROWDLLAMA_BENCH_MGW_TOKENS    tokens per request (default 8)
  CROWDLLAMA_BENCH_MGW_CONVOS    conversations in the affinity phase
                                 (default 12)
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import _common  # noqa: E402 - repo path + compile cache bootstrap

import asyncio
import json
import os
import random
import statistics
import time

MODEL = "tiny-test"
N_WORKERS = 4


def _cfg(**kw):
    from crowdllama_tpu.config import Configuration, Intervals

    c = Configuration(listen_host="127.0.0.1", model=MODEL,
                      intervals=Intervals.default())
    for k, v in kw.items():
        setattr(c, k, v)
    return c


async def _swarm(n_workers: int):
    """Boot host + FakeEngine workers; returns (bootstrap, teardown)."""
    from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

    from crowdllama_tpu.engine.engine import FakeEngine
    from crowdllama_tpu.net.discovery import new_host_and_dht
    from crowdllama_tpu.peer.peer import Peer

    boot_host, _ = await new_host_and_dht(
        Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    bootstrap = f"127.0.0.1:{boot_host.listen_port}"
    workers = []
    for _ in range(n_workers):
        w = Peer(Ed25519PrivateKey.generate(),
                 _cfg(bootstrap_peers=[bootstrap]),
                 engine=FakeEngine(models=[MODEL]), worker_mode=True)
        await w.start()
        workers.append(w)

    async def teardown():
        for w in workers:
            await w.stop()
        await boot_host.close()

    return bootstrap, teardown


async def _replicas(bootstrap: str, n: int, quotas_spec: str = ""):
    """N gateway replicas (consumer + gossip + gateway each), fully
    meshed; returns (gateways, gnodes, ports, teardown)."""
    from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

    from crowdllama_tpu.engine.engine import FakeEngine
    from crowdllama_tpu.gateway.gateway import Gateway
    from crowdllama_tpu.peer.peer import Peer
    from crowdllama_tpu.swarm.gossip import (
        GossipNode, TenantQuotas, parse_tenant_quotas)

    consumers = []
    for _ in range(n):
        c = Peer(Ed25519PrivateKey.generate(),
                 _cfg(bootstrap_peers=[bootstrap]),
                 engine=FakeEngine(models=[]), worker_mode=False)
        await c.start()
        consumers.append(c)

    gateways, gnodes = [], []
    for i, c in enumerate(consumers):
        mesh = [f"127.0.0.1:{o.host.listen_port}"
                for j, o in enumerate(consumers) if j != i]
        quotas = (TenantQuotas(parse_tenant_quotas(quotas_spec),
                               node_id=c.peer_id) if quotas_spec else None)
        node = GossipNode(c, peers=mesh, interval=0.3, quotas=quotas)
        gw = Gateway(c, port=0, host="127.0.0.1", gossip=node,
                     tenant_quotas=quotas)
        node.metrics = gw.obs.metrics
        await node.start()
        await gw.start()
        gnodes.append(node)
        gateways.append(gw)
    ports = [g._runner.addresses[0][1] for g in gateways]

    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        if all(len({p.peer_id for p in c.peer_manager.get_healthy_peers()
                    if p.is_worker}) >= N_WORKERS for c in consumers):
            break
        await asyncio.sleep(0.1)
    else:
        raise RuntimeError("discovery stalled")

    async def teardown():
        for node in gnodes:
            await node.stop(save=False)
        for gw in gateways:
            await gw.stop()
        for c in consumers:
            await c.stop()

    return gateways, gnodes, ports, teardown


async def _one(session, port: int, body: dict,
               headers: dict | None = None) -> tuple[float, dict]:
    """One streamed chat; returns (ttft_ms, final_frame)."""
    t0 = time.monotonic()
    ttft = None
    last = {}
    async with session.post(f"http://127.0.0.1:{port}/api/chat",
                            json=body, headers=headers or {}) as resp:
        if resp.status != 200:
            await resp.read()
            return -1.0, {"status": resp.status}
        async for line in resp.content:
            if not line.strip():
                continue
            if ttft is None:
                ttft = (time.monotonic() - t0) * 1000
            last = json.loads(line)
    return (ttft if ttft is not None else -1.0), last


def _chat(content: str, n: int, messages=None) -> dict:
    return {"model": MODEL, "stream": True,
            "options": {"num_predict": n},
            "messages": messages or [{"role": "user", "content": content}]}


async def _scaling_phase(bootstrap, sizes, n_requests, concurrency,
                         num_predict) -> list[dict]:
    import aiohttp

    curve = []
    for size in sizes:
        gateways, _gn, ports, teardown = await _replicas(bootstrap, size)
        try:
            sem = asyncio.Semaphore(concurrency)
            ttfts: list[float] = []

            async def one(i: int) -> None:
                async with sem:
                    ttft, last = await _one(
                        s, ports[i % size],
                        _chat(f"{i:04d} multi gateway load", num_predict))
                    assert last.get("done"), last
                    ttfts.append(ttft)

            async with aiohttp.ClientSession() as s:
                await asyncio.gather(*(one(-1 - k) for k in range(size)))
                ttfts.clear()
                t0 = time.monotonic()
                await asyncio.gather(*(one(i) for i in range(n_requests)))
                dt = time.monotonic() - t0
            ttfts.sort()
            point = {
                "replicas": size,
                "requests_per_sec": round(n_requests / dt, 1),
                "ttft_p50_ms": round(statistics.median(ttfts), 1),
                "ttft_p95_ms": round(
                    ttfts[max(0, int(len(ttfts) * 0.95) - 1)], 1),
            }
            curve.append(point)
            print(f"# scaling replicas={size}: "
                  f"{point['requests_per_sec']} req/s, "
                  f"ttft p50 {point['ttft_p50_ms']}ms", file=sys.stderr)
        finally:
            await teardown()
    return curve


async def _affinity_phase(bootstrap, n_replicas, n_convos,
                          num_predict) -> dict:
    import aiohttp

    from crowdllama_tpu.gateway.gateway import Gateway

    gateways, gnodes, ports, teardown = await _replicas(
        bootstrap, n_replicas)
    try:
        rng = random.Random(7)
        cross_hits = 0
        continuations = 0
        async with aiohttp.ClientSession() as s:
            for c in range(n_convos):
                content = f"conversation {c:03d} about replicated gateways"
                turn1 = [{"role": "user", "content": content}]
                first = rng.randrange(n_replicas)
                _, last = await _one(s, ports[first],
                                     _chat(content, num_predict))
                worker1 = last.get("worker_id", "")

                # Wait for the pin to gossip to every OTHER replica.
                akey, _ = Gateway._affinity_key(MODEL, turn1, "")
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    if all(n.lookup_affinity(akey) for i, n in
                           enumerate(gnodes) if i != first):
                        break
                    await asyncio.sleep(0.05)

                other = rng.choice(
                    [i for i in range(n_replicas) if i != first])
                cont = turn1 + [
                    {"role": "assistant",
                     "content": last.get("message", {}).get("content", "")},
                    {"role": "user", "content": "continue"}]
                _, last2 = await _one(
                    s, ports[other], _chat("", num_predict, messages=cont))
                continuations += 1
                cross_hits += last2.get("worker_id", "") == worker1
        gossip_hits = sum(g._gossip_affinity_hits for g in gateways)
        point = {
            "replicas": n_replicas,
            "conversations": n_convos,
            "continuations_cross_replica": continuations,
            "same_worker_hits": cross_hits,
            "cross_replica_hit_rate": round(cross_hits / continuations, 3),
            "gossip_affinity_lookups_hit": gossip_hits,
            "random_routing_expectation": round(1 / N_WORKERS, 3),
        }
        print(f"# affinity: {cross_hits}/{continuations} continuations "
              f"pinned cross-replica (random would be "
              f"~{point['random_routing_expectation']})", file=sys.stderr)
        return point
    finally:
        await teardown()


async def _tenant_phase(bootstrap, num_predict) -> dict:
    """Hot tenant floods 2 replicas past its quota; the light tenant's
    p95 TTFT must stay near its solo baseline (the isolation bar)."""
    import aiohttp

    n_light = 16
    quotas = "default=1000,hot=8"
    gateways, _gn, ports, teardown = await _replicas(
        bootstrap, 2, quotas_spec=quotas)
    try:
        async def light_run(s) -> list[float]:
            ttfts = []
            for i in range(n_light):
                ttft, last = await _one(
                    s, ports[i % 2], _chat(f"light {i:03d}", num_predict),
                    headers={"X-Tenant": "light"})
                if last.get("done"):
                    ttfts.append(ttft)
                await asyncio.sleep(0.02)
            ttfts.sort()
            return ttfts

        def p95(ttfts: list[float]) -> float:
            return ttfts[max(0, int(len(ttfts) * 0.95) - 1)]

        async with aiohttp.ClientSession() as s:
            solo = await light_run(s)

            stop = asyncio.Event()
            flood_sent = [0]

            async def flood(k: int) -> None:
                i = 0
                while not stop.is_set():
                    await _one(s, ports[(k + i) % 2],
                               _chat(f"hot {k}:{i}", num_predict),
                               headers={"X-Tenant": "hot"})
                    flood_sent[0] += 1
                    i += 1

            flooders = [asyncio.create_task(flood(k)) for k in range(8)]
            try:
                loaded = await light_run(s)
            finally:
                stop.set()
                for t in flooders:
                    t.cancel()
                await asyncio.gather(*flooders, return_exceptions=True)

        shed = sum(g.obs.metrics.tenant_shed.get("hot", 0)
                   for g in gateways)
        admitted = sum(g.obs.metrics.tenant_admitted.get("hot", 0)
                       for g in gateways)
        point = {
            "quotas": quotas,
            "hot_requests_sent": flood_sent[0],
            "hot_admitted": admitted,
            "hot_shed": shed,
            "light_requests": n_light,
            "light_completed_under_load": len(loaded),
            "light_ttft_p95_solo_ms": round(p95(solo), 1),
            "light_ttft_p95_loaded_ms": round(p95(loaded), 1),
            "light_p95_ratio": round(p95(loaded) / max(p95(solo), 1e-9), 2),
        }
        print(f"# tenants: hot shed {shed}/{flood_sent[0]}, light p95 "
              f"{point['light_ttft_p95_loaded_ms']}ms vs solo "
              f"{point['light_ttft_p95_solo_ms']}ms "
              f"(x{point['light_p95_ratio']})", file=sys.stderr)
        return point
    finally:
        await teardown()


async def run() -> dict:
    sizes = [int(x) for x in os.environ.get(
        "CROWDLLAMA_BENCH_MGW_SIZES", "1,2,4").split(",") if x.strip()]
    n_requests = int(os.environ.get("CROWDLLAMA_BENCH_MGW_REQUESTS", "48"))
    concurrency = int(
        os.environ.get("CROWDLLAMA_BENCH_MGW_CONCURRENCY", "8"))
    num_predict = int(os.environ.get("CROWDLLAMA_BENCH_MGW_TOKENS", "8"))
    n_convos = int(os.environ.get("CROWDLLAMA_BENCH_MGW_CONVOS", "12"))

    bootstrap, teardown = await _swarm(N_WORKERS)
    try:
        scaling = await _scaling_phase(bootstrap, sizes, n_requests,
                                       concurrency, num_predict)
        affinity = await _affinity_phase(bootstrap, max(sizes), n_convos,
                                         num_predict)
        tenants = await _tenant_phase(bootstrap, num_predict)
    finally:
        await teardown()

    head = scaling[-1]
    return {
        "metric": (f"multi-gateway req/s, {head['replicas']} replicas "
                   f"over {N_WORKERS} FakeEngine workers"),
        "value": head["requests_per_sec"],
        "unit": "requests/sec",
        "vs_baseline": None,  # reference has a single, unreplicated gateway
        "extra": {
            "scaling_curve": scaling,
            "affinity_phase": affinity,
            "tenant_phase": tenants,
            "requests_per_size": n_requests,
            "concurrency": concurrency,
            "num_predict": num_predict,
            "note": "replicas share one process/event loop: the scaling "
                    "curve bounds per-replica coordination overhead, not "
                    "multi-core speedup; tenant bar = light p95 within "
                    "~15% of solo while the hot tenant is shed",
        },
    }


def main() -> None:
    os.environ.setdefault("CROWDLLAMA_TPU_TEST_MODE", "1")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    result = asyncio.run(run())
    _common.emit(result)


if __name__ == "__main__":
    sys.exit(main())
