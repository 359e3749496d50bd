"""Real-engine mini-swarm benchmark (ROADMAP VERDICT #5, config-5 shape).

swarm_scaling.py measures the control plane with FakeEngine workers;
this phase puts 2-4 REAL tiny-model JaxEngines behind the gateway on
CPU and measures what a client actually experiences end to end under
concurrent load: sustained generated tokens/sec across the swarm and
per-request TTFT (first streamed NDJSON frame), crossing HTTP ->
routing -> p2p stream -> scheduler/prefill -> decode -> stream protocol.

The SAME topology and load is then re-run with FakeEngine workers — the
control-plane control curve: the gap between the two isolates engine
time (prefill + decode) from routing/transport, per swarm size.

Prints ONE JSON line; value is end-to-end tokens/sec at the largest
real-engine swarm, extra holds both curves.

Env overrides:
  CROWDLLAMA_BENCH_MINI_SIZES    swarm sizes      (default "2,4")
  CROWDLLAMA_BENCH_MINI_REQUESTS requests per size (default 24)
  CROWDLLAMA_BENCH_MINI_CONCURRENCY in-flight cap  (default 4)
  CROWDLLAMA_BENCH_MINI_TOKENS   tokens per request (default 16)
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import _common  # noqa: E402 - repo path + compile cache bootstrap

import asyncio
import json
import os
import statistics
import time

MODEL = "tiny-test"


async def _measure(kind: str, sizes: list[int], n_requests: int,
                   concurrency: int, num_predict: int) -> list[dict]:
    import aiohttp
    from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

    from crowdllama_tpu.config import Configuration, Intervals
    from crowdllama_tpu.engine.engine import FakeEngine, JaxEngine
    from crowdllama_tpu.gateway.gateway import Gateway
    from crowdllama_tpu.net.discovery import new_host_and_dht
    from crowdllama_tpu.peer.peer import Peer

    def cfg(**kw):
        c = Configuration(listen_host="127.0.0.1", model=MODEL,
                          intervals=Intervals.default())
        for k, v in kw.items():
            setattr(c, k, v)
        return c

    boot_host, _ = await new_host_and_dht(
        Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    bootstrap = f"127.0.0.1:{boot_host.listen_port}"

    consumer = Peer(Ed25519PrivateKey.generate(),
                    cfg(bootstrap_peers=[bootstrap]),
                    engine=FakeEngine(models=[]), worker_mode=False)
    await consumer.start()
    gateway = Gateway(consumer, port=0, host="127.0.0.1")
    await gateway.start()
    gw_port = gateway._runner.addresses[0][1]
    url = f"http://127.0.0.1:{gw_port}/api/chat"

    workers: list[Peer] = []
    engines: list = []
    curve: list[dict] = []

    async def add_worker() -> None:
        if kind == "real":
            eng = JaxEngine(cfg(), max_context_length=256)
            await eng.start()
            engines.append(eng)
        else:
            eng = FakeEngine(models=[MODEL])
        w = Peer(Ed25519PrivateKey.generate(),
                 cfg(bootstrap_peers=[bootstrap]), engine=eng,
                 worker_mode=True)
        workers.append(w)  # before start: finally stops partial starts
        await w.start()

    try:
        async with aiohttp.ClientSession() as session:
            for size in sizes:
                t_grow = time.monotonic()
                # Sequential: real engines compile on the same device;
                # parallel starts interleave compilations for no win.
                while len(workers) < size:
                    await add_worker()
                deadline = time.monotonic() + 120
                while time.monotonic() < deadline:
                    healthy = {p.peer_id for p in
                               consumer.peer_manager.get_healthy_peers()
                               if p.is_worker}
                    if len(healthy) >= size:
                        break
                    await asyncio.sleep(0.1)
                else:
                    raise RuntimeError(f"discovery stalled at size {size}")
                discovery_s = time.monotonic() - t_grow

                sem = asyncio.Semaphore(concurrency)
                ttfts: list[float] = []
                tokens = [0]
                hits: dict[str, int] = {}

                async def one(i: int) -> None:
                    # Unique leading tag: with the paged engines' prefix
                    # cache on, a repeated prompt would measure cache hits.
                    body = {"model": MODEL, "stream": True,
                            "options": {"num_predict": num_predict},
                            "messages": [{"role": "user",
                                          "content": f"{i:04d} mini swarm "
                                                     "load test prompt"}]}
                    async with sem:
                        t0 = time.monotonic()
                        first = True
                        async with session.post(url, json=body) as resp:
                            assert resp.status == 200, await resp.text()
                            async for line in resp.content:
                                if not line.strip():
                                    continue
                                if first:
                                    ttfts.append(
                                        (time.monotonic() - t0) * 1000)
                                    first = False
                                d = json.loads(line)
                                if d.get("done"):
                                    tokens[0] += d.get(
                                        "eval_count",
                                        num_predict)
                                    wid = d.get("worker_id", "")
                                    hits[wid] = hits.get(wid, 0) + 1

                # Prime every worker once (compile paths, warm streams)
                # before the timed window.
                await asyncio.gather(*(one(-1 - k) for k in range(size)))
                ttfts.clear(); tokens[0] = 0; hits.clear()

                t0 = time.monotonic()
                await asyncio.gather(*(one(i) for i in range(n_requests)))
                dt = time.monotonic() - t0
                ttfts.sort()
                point = {
                    "workers": size,
                    "tokens_per_sec": round(tokens[0] / dt, 1),
                    "requests_per_sec": round(n_requests / dt, 1),
                    "ttft_p50_ms": round(statistics.median(ttfts), 1),
                    "ttft_p95_ms": round(
                        ttfts[max(0, int(len(ttfts) * 0.95) - 1)], 1),
                    "tokens_generated": tokens[0],
                    "distinct_workers_hit": len(hits),
                    "discovery_s": round(discovery_s, 2),
                }
                curve.append(point)
                print(f"# {kind} size={size}: {point['tokens_per_sec']} "
                      f"tok/s, ttft p50 {point['ttft_p50_ms']}ms, "
                      f"{len(hits)} workers hit", file=sys.stderr)
    finally:
        await gateway.stop()
        await consumer.stop()
        for w in workers:
            await w.stop()
        for e in engines:
            await e.stop()
        await boot_host.close()
    return curve


async def _drain_phase(n_requests: int, concurrency: int,
                       num_predict: int) -> dict:
    """Live-migration phase (docs/ROBUSTNESS.md): 4 real engines under
    streaming load, one of them drained mid-burst.  Every in-flight
    stream must complete (migrated to a survivor with KV handoff), and
    NEW requests keep landing on the survivors — zero failed streams is
    the acceptance bar."""
    import aiohttp
    from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

    from crowdllama_tpu.config import Configuration, Intervals
    from crowdllama_tpu.engine.engine import FakeEngine, JaxEngine
    from crowdllama_tpu.gateway.gateway import Gateway
    from crowdllama_tpu.net.discovery import new_host_and_dht
    from crowdllama_tpu.peer.peer import Peer

    size = 4

    def cfg(**kw):
        c = Configuration(listen_host="127.0.0.1", model=MODEL,
                          intervals=Intervals.default(),
                          kv_layout="paged", kv_page_size=16,
                          kv_ship=True, kv_ship_min_tokens=16)
        for k, v in kw.items():
            setattr(c, k, v)
        return c

    boot_host, _ = await new_host_and_dht(
        Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    bootstrap = f"127.0.0.1:{boot_host.listen_port}"
    consumer = Peer(Ed25519PrivateKey.generate(),
                    cfg(bootstrap_peers=[bootstrap]),
                    engine=FakeEngine(models=[]), worker_mode=False)
    await consumer.start()
    gateway = Gateway(consumer, port=0, host="127.0.0.1", kv_ship=True)
    await gateway.start()
    gw_port = gateway._runner.addresses[0][1]
    url = f"http://127.0.0.1:{gw_port}/api/chat"

    workers: list[Peer] = []
    engines: list = []
    try:
        for _ in range(size):
            eng = JaxEngine(cfg(), max_context_length=256)
            await eng.start()
            engines.append(eng)
            w = Peer(Ed25519PrivateKey.generate(),
                     cfg(bootstrap_peers=[bootstrap]), engine=eng,
                     worker_mode=True)
            workers.append(w)
            await w.start()
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            healthy = {p.peer_id for p in
                       consumer.peer_manager.get_healthy_peers()
                       if p.is_worker}
            if len(healthy) >= size:
                break
            await asyncio.sleep(0.1)
        else:
            raise RuntimeError("discovery stalled in drain phase")

        sem = asyncio.Semaphore(concurrency)
        completed = [0]
        failed = [0]

        async with aiohttp.ClientSession() as session:
            async def one(i: int) -> None:
                # Multi-page prompt (page_size 16): the drained worker's
                # prefill pages are worth fetching on migration.
                body = {"model": MODEL, "stream": True,
                        "options": {"num_predict": num_predict},
                        "messages": [{"role": "user",
                                      "content": f"{i:04d} drain phase "
                                      "stream that must survive a mid-"
                                      "burst worker drain with its KV "
                                      "handed to a surviving engine"}]}
                async with sem:
                    try:
                        async with session.post(url, json=body) as resp:
                            assert resp.status == 200, await resp.text()
                            last = None
                            async for line in resp.content:
                                if line.strip():
                                    last = json.loads(line)
                            ok = (last is not None and last.get("done")
                                  and last.get("done_reason") != "error"
                                  and "error" not in last)
                            completed[0] += ok
                            failed[0] += not ok
                    except Exception:
                        failed[0] += 1

            # Prime compile paths outside the measured burst.
            await asyncio.gather(*(one(-1 - k) for k in range(size)))
            completed[0] = 0
            failed[0] = 0

            t0 = time.monotonic()
            burst = [asyncio.create_task(one(i)) for i in range(n_requests)]

            async def drain_one() -> tuple[str, float, int]:
                await asyncio.sleep(0.3)   # let streams get in flight
                # Drain the worker actually serving the burst — routing
                # may concentrate load, and draining an idle worker
                # would never exercise the mid-stream MigrateFrame path.
                def load(k: int) -> tuple:
                    g = engines[k].obs_gauges()
                    return (g.get("active_slots", 0.0),
                            g.get("pending_depth", 0.0))
                idx = max(range(size), key=load)
                td = time.monotonic()
                migrated = await workers[idx].drain()
                return (workers[idx].peer_id, time.monotonic() - td,
                        migrated)

            (drained_id, drain_s, migrated), *_ = await asyncio.gather(
                drain_one(), *burst)
            dt = time.monotonic() - t0

        gw_m = gateway.obs.metrics
        replayed = sum(e.obs.metrics.replayed_prefill_tokens
                       for e in engines)
        point = {
            "workers": size,
            "streams_total": n_requests,
            "streams_completed": completed[0],
            "streams_failed": failed[0],
            "drained_worker": drained_id[:8],
            "drain_call_s": round(drain_s, 3),
            "inflight_migrated": migrated,
            "gateway_migrated_streams": gw_m.migrated_streams,
            "replayed_prefill_tokens": replayed,
            "wall_s": round(dt, 2),
        }
        print(f"# drain phase: {completed[0]}/{n_requests} streams ok, "
              f"{migrated} migrated off {drained_id[:8]} in "
              f"{drain_s * 1000:.0f}ms, replayed_prefill={replayed}",
              file=sys.stderr)
        return point
    finally:
        await gateway.stop()
        await consumer.stop()
        for w in workers:
            await w.stop()
        for e in engines:
            await e.stop()
        await boot_host.close()


async def run() -> dict:
    sizes = [int(x) for x in os.environ.get(
        "CROWDLLAMA_BENCH_MINI_SIZES", "2,4").split(",") if x.strip()]
    n_requests = int(os.environ.get("CROWDLLAMA_BENCH_MINI_REQUESTS", "24"))
    concurrency = int(
        os.environ.get("CROWDLLAMA_BENCH_MINI_CONCURRENCY", "4"))
    num_predict = int(os.environ.get("CROWDLLAMA_BENCH_MINI_TOKENS", "16"))

    real = await _measure("real", sizes, n_requests, concurrency,
                          num_predict)
    control = await _measure("fake", sizes, n_requests, concurrency,
                             num_predict)
    drain = await _drain_phase(n_requests, concurrency, num_predict)

    head = real[-1]
    ctrl = control[-1]
    return {
        "metric": (f"mini-swarm e2e {MODEL} tokens/sec, "
                   f"{sizes[-1]} real engines behind the gateway"),
        "value": head["tokens_per_sec"],
        "unit": "tokens/sec",
        "vs_baseline": None,  # reference publishes no e2e numbers
        "extra": {
            "real_curve": real,
            "control_curve_fake_engine": control,
            # Engine share of TTFT: real minus control at the largest
            # size — what prefill+decode add on top of the control plane.
            "engine_ttft_ms": round(
                head["ttft_p50_ms"] - ctrl["ttft_p50_ms"], 1),
            "drain_phase": drain,
            "requests_per_size": n_requests,
            "concurrency": concurrency,
            "num_predict": num_predict,
            "note": "control curve = identical topology and load with "
                    "FakeEngine workers (control-plane only)",
        },
    }


def main() -> None:
    os.environ.setdefault("CROWDLLAMA_TPU_TEST_MODE", "1")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    result = asyncio.run(run())
    _common.emit(result)


if __name__ == "__main__":
    sys.exit(main())
