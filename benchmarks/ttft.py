"""Gateway TTFT benchmark: p50/p95 time-to-first-token through the full
stack (BASELINE metric 2 of 3).

Topology on loopback, all real sockets: DHT bootstrap node + worker
(JaxEngine, streaming) + consumer peer + gateway.  Each request POSTs
/api/chat with stream=true and times the first NDJSON frame — the true TTFT
a client observes, crossing HTTP -> scheduler/prefill -> stream protocol ->
HTTP chunk.  The reference cannot measure this at all: its stream flag is a
no-op, so TTFT == total latency there (SURVEY §3.3).

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", "extra"}.
vs_baseline is null: the reference publishes no TTFT number (BASELINE.md).

Env overrides:
  CROWDLLAMA_BENCH_MODEL     engine model      (default tiny-test on cpu,
                             tinyllama-1.1b when a TPU is attached)
  CROWDLLAMA_BENCH_REQUESTS  timed requests    (default 20)
  CROWDLLAMA_BENCH_PROMPT    prompt length chars (default 128)
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


async def run() -> dict:
    import aiohttp
    import jax
    from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

    from crowdllama_tpu.config import Configuration, Intervals
    from crowdllama_tpu.engine.engine import FakeEngine, JaxEngine
    from crowdllama_tpu.gateway.gateway import Gateway
    from crowdllama_tpu.net.discovery import new_host_and_dht
    from crowdllama_tpu.peer.peer import Peer

    on_tpu = jax.devices()[0].platform == "tpu"
    model = os.environ.get(
        "CROWDLLAMA_BENCH_MODEL", "tinyllama-1.1b" if on_tpu else "tiny-test")
    n_requests = int(os.environ.get("CROWDLLAMA_BENCH_REQUESTS", "20"))
    prompt = "benchmark " * (int(os.environ.get("CROWDLLAMA_BENCH_PROMPT", "128")) // 10)

    def cfg(**kw):
        c = Configuration(listen_host="127.0.0.1", model=model,
                          intervals=Intervals.default())
        for k, v in kw.items():
            setattr(c, k, v)
        return c

    boot_host, _ = await new_host_and_dht(
        Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    bootstrap = f"127.0.0.1:{boot_host.listen_port}"

    # 4k context on the chip: the long-prefix phase needs a 2k-token
    # cached system prompt to demonstrate what prefix caching buys
    # (VERDICT r4 #7: at short shapes every forward is weight-stream
    # bound, so suffix-only prefill saved ~3% — the feature's value is at
    # prefill lengths where MXU time dominates the weight stream).
    engine = JaxEngine(cfg(), max_context_length=4096 if on_tpu else 256,
                       quantize="int8" if on_tpu else "",
                       kv_layout="paged", kv_page_size=32)
    await engine.start()
    worker = Peer(Ed25519PrivateKey.generate(), cfg(bootstrap_peers=[bootstrap]),
                  engine=engine, worker_mode=True)
    await worker.start()
    consumer = Peer(Ed25519PrivateKey.generate(), cfg(bootstrap_peers=[bootstrap]),
                    engine=FakeEngine(models=[]), worker_mode=False)
    await consumer.start()
    # trace_buffer sized to hold every request of the run so the span
    # aggregation below sees all phases, not the tail of the ring.
    gateway = Gateway(consumer, port=0, host="127.0.0.1", trace_buffer=256)
    await gateway.start()
    gw_port = gateway._runner.addresses[0][1]

    try:
        # Wait for discovery.
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if consumer.peer_manager.find_best_worker(model) is not None:
                break
            await asyncio.sleep(0.1)
        else:
            raise RuntimeError("worker never discovered")

        def cold_body(i: int) -> dict:
            # The index leads the prompt so its FIRST page differs per
            # request: with the paged engine's prefix cache on, a repeated
            # identical prompt would turn the cold phase into a cache-hit
            # benchmark.
            return {"model": model, "stream": True,
                    "options": {"num_predict": 4},
                    "messages": [{"role": "user",
                                  "content": f"{i:04d} {prompt}"}]}

        url = f"http://127.0.0.1:{gw_port}/api/chat"

        async def timed_loop(s, make_body) -> list[float]:
            out: list[float] = []
            async with s.post(url, json=make_body(-1)) as resp:  # prime
                await resp.read()
            for i in range(n_requests):
                t0 = time.monotonic()
                async with s.post(url, json=make_body(i)) as resp:
                    assert resp.status == 200, await resp.text()
                    async for _ in resp.content:  # first NDJSON frame
                        out.append((time.monotonic() - t0) * 1000)
                        break
                    await resp.read()
            return out

        async with aiohttp.ClientSession() as s:
            ttfts = await timed_loop(s, cold_body)

            # Warm phase: a fixed long system prompt + varying questions —
            # the priming request populates the prefix cache, then only the
            # suffix prefills (the chat-with-system-prompt shape this
            # optimization exists for).
            system = ("You are a careful, concise assistant. "
                      * (16 if on_tpu else 4))  # fit tiny-test's 256 ctx
            before = dict(engine.describe().get("prefix_cache", {}))

            def warm_body(i: int) -> dict:
                return {"model": model, "stream": True,
                        "options": {"num_predict": 4},
                        "messages": [
                            {"role": "system", "content": system},
                            {"role": "user", "content": f"question {i}?"}]}

            warm = await timed_loop(s, warm_body)
            after = engine.describe().get("prefix_cache", {})
            prefix_stats = {k: after.get(k, 0) - before.get(k, 0)
                            for k in after}

            # Long-prefix phase (VERDICT r4 #7): a ~2k-token shared system
            # prompt — the RAG / long-instruction shape prefix caching
            # exists for.  Cold = unique leading page per request (no
            # cache reuse possible); warm = the same system prompt with a
            # varying question, suffix-only prefill after the prime.
            # Sized by TOKENS through the engine's own tokenizer (2048
            # characters would be ~4x fewer tokens under a BPE vocab).
            target_tokens = 2048 if on_tpu else 160
            unit = "be careful and cite sources. "
            long_system = "Policy: "
            while len(engine.tokenizer.encode(long_system)) < target_tokens:
                long_system += unit
            long_tokens = len(engine.tokenizer.encode(long_system))

            def long_cold_body(i: int) -> dict:
                return {"model": model, "stream": True,
                        "options": {"num_predict": 4},
                        "messages": [
                            {"role": "system",
                             "content": f"{i:04d} {long_system}"},
                            {"role": "user", "content": "summarize."}]}

            def long_warm_body(i: int) -> dict:
                return {"model": model, "stream": True,
                        "options": {"num_predict": 4},
                        "messages": [
                            {"role": "system", "content": long_system},
                            {"role": "user", "content": f"question {i}?"}]}

            long_before = dict(engine.describe().get("prefix_cache", {}))
            long_cold = await timed_loop(s, long_cold_body)
            mid = dict(engine.describe().get("prefix_cache", {}))
            long_warm = await timed_loop(s, long_warm_body)
            la = engine.describe().get("prefix_cache", {})
            long_prefix_stats = {k: la.get(k, 0) - long_before.get(k, 0)
                                 for k in la}
            # Warm-phase-only cache delta: tokens_reused per hit is the
            # prefix length the engine ACTUALLY materialized and reused —
            # tokenizer-side counting can overstate it (context clipping,
            # page-granular reuse).
            warm_hits = la.get("hits", 0) - mid.get("hits", 0)
            warm_reused = (la.get("tokens_reused", 0)
                           - mid.get("tokens_reused", 0))
    finally:
        for stop in (gateway.stop, consumer.stop, worker.stop, engine.stop,
                     boot_host.close):
            try:
                await stop()
            except Exception:
                pass  # teardown must not mask the benchmark's real error

    # Observability cross-check (obs/): the SAME percentile a dashboard
    # would read from the scraped crowdllama_ttft_seconds series, plus
    # per-phase means and one full span tree from the trace ring buffer.
    # In-memory state survives gateway.stop(), so this reads post-teardown.
    ttft_hist = gateway.obs.metrics.ttft_seconds
    phase_tot: dict[str, float] = {}
    phase_n: dict[str, int] = {}
    trace_sample = None
    for t in gateway.obs.trace.snapshot()["traces"]:
        for sp in t["spans"]:
            phase_tot[sp["name"]] = phase_tot.get(sp["name"], 0.0) \
                + sp["dur_us"]
            phase_n[sp["name"]] = phase_n.get(sp["name"], 0) + 1
        if t["done"]:
            trace_sample = t
    obs_extra = {
        "ttft_hist_p50_ms": round(ttft_hist.quantile(0.5) * 1000, 1),
        "ttft_hist_p95_ms": round(ttft_hist.quantile(0.95) * 1000, 1),
        "ttft_hist_count": ttft_hist.count,
        "decode_step_hist_p50_ms": round(
            gateway.obs.metrics.decode_step_seconds.quantile(0.5) * 1000, 2),
        "phase_mean_us": {k: round(phase_tot[k] / phase_n[k], 1)
                          for k in sorted(phase_tot)},
        "trace_sample": trace_sample,
    }

    ttfts.sort()
    p50 = statistics.median(ttfts)
    p95 = ttfts[max(0, int(len(ttfts) * 0.95) - 1)]
    lc50 = statistics.median(long_cold)
    lw50 = statistics.median(long_warm)
    # The phase only counts as a LONG-prefix result when the engine
    # demonstrably reused >= 75% of the target prefix per warm hit; a
    # clipped context or a cache that reuses a fraction of the prompt
    # would otherwise report short-prefix numbers under a long-prefix
    # label (the VERDICT r4 #7 failure shape this phase exists to avoid).
    materialized = round(warm_reused / warm_hits) if warm_hits else 0
    long_label = ("long_prefix" if materialized >= 0.75 * target_tokens
                  else "short_prefix")
    return {
        "metric": f"{model} gateway TTFT p50",
        "value": round(p50, 1),
        "unit": "ms",
        "vs_baseline": None,  # reference publishes no TTFT (BASELINE.md)
        "extra": {"p95_ms": round(p95, 1), "requests": n_requests,
                  "warm_prefix_p50_ms": round(statistics.median(warm), 1),
                  "prefix_cache": prefix_stats,
                  long_label: {
                      "prefix_tokens": long_tokens,
                      "target_prefix_tokens": target_tokens,
                      "materialized_prefix_tokens": materialized,
                      "cold_p50_ms": round(lc50, 1),
                      "warm_p50_ms": round(lw50, 1),
                      "ttft_reduction_pct": round(100 * (1 - lw50 / lc50), 1),
                      "prefix_cache": long_prefix_stats,
                  },
                  "obs": obs_extra,
                  "platform": "tpu" if on_tpu else "cpu"},
    }


def main() -> None:
    os.environ.setdefault("CROWDLLAMA_TPU_TEST_MODE", "1")
    result = asyncio.run(run())
    _common.emit(result)


if __name__ == "__main__":
    sys.exit(main())
