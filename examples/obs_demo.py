"""One-shot demo of the swarm observatory (docs/OBSERVABILITY.md).

Boots a loopback swarm IN PROCESS — a bootstrap peer, two workers and a
gateway with SLO objectives configured — pushes a few chat requests
through it, then renders exactly what an operator sees: the
`crowdllama-tpu top` per-worker table and an excerpt of the
`GET /metrics/cluster` fan-in (worker-labeled families + swarm rollups +
SLO burn gauges).  Run it via `make obs-demo`.
"""

import asyncio

import aiohttp

from crowdllama_tpu.cli.main import render_top
from crowdllama_tpu.config import Configuration, Intervals
from crowdllama_tpu.engine.engine import FakeEngine
from crowdllama_tpu.gateway.gateway import Gateway
from crowdllama_tpu.peer.peer import Peer
from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey


def _cfg(bootstrap=None):
    return Configuration(
        listen_host="127.0.0.1",
        bootstrap_peers=[bootstrap] if bootstrap else [],
        intervals=Intervals.default(),
    )


# The families worth eyeballing in a terminal; the full exposition is
# hundreds of lines of histogram buckets.
_EXCERPT_PREFIXES = (
    "crowdllama_cluster_",
    "crowdllama_worker_",
    "crowdllama_engine_pending_depth",
    "crowdllama_engine_active_slots",
    "crowdllama_engine_duty_cycle",
)


async def main() -> int:
    boot = Peer(Ed25519PrivateKey.generate(), _cfg(),
                engine=FakeEngine(models=["boot-noop"]), worker_mode=True)
    await boot.start()
    bootstrap = f"127.0.0.1:{boot.host.listen_port}"

    workers = [Peer(Ed25519PrivateKey.generate(), _cfg(bootstrap),
                    engine=FakeEngine(models=["tiny-test"]),
                    worker_mode=True)
               for _ in range(2)]
    for w in workers:
        await w.start()
    consumer = Peer(Ed25519PrivateKey.generate(), _cfg(bootstrap),
                    engine=FakeEngine(models=[]), worker_mode=False)
    await consumer.start()
    gateway = Gateway(consumer, port=0, host="127.0.0.1",
                      slo_ttft_ms=500.0, slo_decode_ms=200.0)
    await gateway.start()
    gw = f"http://127.0.0.1:{gateway._runner.addresses[0][1]}"

    try:
        print("waiting for the swarm to assemble ...")
        deadline = asyncio.get_running_loop().time() + 30.0
        while asyncio.get_running_loop().time() < deadline:
            ready = [p for p in consumer.peer_manager.get_workers()
                     if "tiny-test" in p.resource.supported_models]
            if len(ready) == 2:
                break
            await asyncio.sleep(0.1)
        else:
            print("swarm never assembled")
            return 1

        async with aiohttp.ClientSession() as s:
            body = {"model": "tiny-test", "stream": False,
                    "messages": [{"role": "user",
                                  "content": "warm up the observatory"}]}
            for _ in range(4):
                async with s.post(f"{gw}/api/chat", json=body) as resp:
                    resp.raise_for_status()
                    await resp.json()
            async with s.get(f"{gw}/metrics/cluster") as resp:
                resp.raise_for_status()
                text = await resp.text()

        print(f"\n$ crowdllama-tpu top --gateway {gw}\n")
        print(render_top(text))

        print(f"\n$ curl {gw}/metrics/cluster   (excerpt)\n")
        for line in text.splitlines():
            if line.startswith(_EXCERPT_PREFIXES):
                print(line)
        print("\n(full exposition also carries every worker histogram; "
              "drill into a slow worker with POST /debug/profile/start|stop on "
              "its --worker-metrics-port "
              "— see docs/OBSERVABILITY.md, 'Swarm observatory')")
        return 0
    finally:
        await gateway.stop()
        await consumer.stop()
        for w in workers:
            await w.stop()
        await boot.stop()


if __name__ == "__main__":
    raise SystemExit(asyncio.run(main()))
