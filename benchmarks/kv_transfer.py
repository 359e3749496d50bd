"""Swarm KV shipping benchmark: prefix fetch vs prefill recompute TTFT
(docs/KV_TRANSFER.md).

Topology on loopback, all real sockets: DHT bootstrap + donor worker
(paged JaxEngine whose prefix cache holds the shared prefix) + a cold
fetcher worker.  For each prefix length the bench times the cold
worker's non-streamed serve of the SAME prompt two ways:

  recompute  plain prefill, no donor hint (the pre-KV-ship behaviour)
  fetch      kv_donor set -> the worker dials the donor over the real
             authenticated inference stream, imports the prefix pages,
             and prefills only the suffix

Loopback RTT is ~0, which understates a real swarm, so the fetch side
also SWEEPS injected RTT through the same transparent delay relay
ep_dispatch.py uses (injected RTT = 2x the one-way delay): the relay
fronts the donor's listen port and the fetcher's DHT lookup is rewired
to the relay, so only the KV-fetch dial pays the injected latency.

Each timed trial uses a UNIQUE prompt (served on the donor first) so
the fetcher is genuinely cold every time — no prefix-cache carryover
between trials, no cache clearing.

Prints ONE JSON line; value is the TTFT reduction (%) at the longest
prefix on loopback, extra carries both curves per RTT plus
``break_even_prefix_tokens`` — the regressed prefix length where fetch
starts beating recompute (per RTT point).

Env overrides:
  CROWDLLAMA_BENCH_KV_MODEL     test-scale model (default "tiny-test-gemma")
  CROWDLLAMA_BENCH_KV_PREFIXES  prefix token targets (default "64,128,240")
  CROWDLLAMA_BENCH_KV_RTTS      injected RTT sweep, ms (default "0,5,20")
  CROWDLLAMA_BENCH_KV_TRIALS    timed trials per point (default 5)
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
from dataclasses import replace

from crowdllama_tpu.testing.netem import DelayProxy  # noqa: E402

# tiny-test-gemma is the DEEPEST test-scale model (4 layers): prefill
# compute per token is the thing a fetch avoids, and the 2-layer toys
# price it so low that transport overhead swamps the comparison.
MODEL = os.environ.get("CROWDLLAMA_BENCH_KV_MODEL", "tiny-test-gemma")
PAGE = 16
CTX = 256  # the test-scale model configs clamp context to 256


async def run() -> dict:
    from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

    from crowdllama_tpu.config import Configuration, Intervals
    from crowdllama_tpu.core.messages import (
        create_generate_request,
        extract_generate_response,
    )
    from crowdllama_tpu.engine.engine import JaxEngine
    from crowdllama_tpu.net.discovery import new_host_and_dht
    from crowdllama_tpu.peer.peer import Peer

    prefixes = [int(x) for x in os.environ.get(
        "CROWDLLAMA_BENCH_KV_PREFIXES", "64,128,240").split(",") if x.strip()]
    rtts = [float(x) for x in os.environ.get(
        "CROWDLLAMA_BENCH_KV_RTTS", "0,5,20").split(",") if x.strip()]
    trials = int(os.environ.get("CROWDLLAMA_BENCH_KV_TRIALS", "5"))

    def cfg(**kw):
        c = Configuration(listen_host="127.0.0.1", model=MODEL,
                          intervals=Intervals.default(),
                          kv_layout="paged", kv_page_size=PAGE,
                          kv_ship=True, kv_ship_min_tokens=PAGE)
        for k, v in kw.items():
            setattr(c, k, v)
        return c

    boot_host, _ = await new_host_and_dht(
        Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    bootstrap = f"127.0.0.1:{boot_host.listen_port}"

    eng_a = JaxEngine(cfg(), max_context_length=CTX)          # donor
    eng_b = JaxEngine(cfg(), max_context_length=CTX)          # fetcher
    await eng_a.start()
    await eng_b.start()
    peer_a = Peer(Ed25519PrivateKey.generate(),
                  cfg(bootstrap_peers=[bootstrap]), engine=eng_a,
                  worker_mode=True)
    peer_b = Peer(Ed25519PrivateKey.generate(),
                  cfg(bootstrap_peers=[bootstrap]), engine=eng_b,
                  worker_mode=True)
    await peer_a.start()
    await peer_b.start()

    # The fetcher's donor lookup, optionally rewired through the relay.
    real_find = peer_b.dht.find_peer
    proxy_port: list[int | None] = [None]

    async def find_peer(pid):
        contact = await real_find(pid)
        if contact is not None and pid == peer_a.peer_id \
                and proxy_port[0] is not None:
            contact = replace(contact, port=proxy_port[0])
        return contact

    peer_b.dht.find_peer = find_peer

    # Prompts sized in TOKENS through the engine's own tokenizer; a unique
    # leading tag makes every page of every trial's chain distinct.
    unit = "ship pages not prefills across the swarm. "
    base = ""
    need = max(prefixes)
    while len(eng_a.tokenizer.encode("0000 " + base)) < need:
        base += unit

    def prompt_for(target: int, tag: int) -> str:
        text = f"{tag:04d} "
        while len(eng_a.tokenizer.encode(text)) < target:
            text += unit
        # Trim to the exact token target (the tokenizer may be char-level,
        # so one appended unit can overshoot by dozens of tokens).
        return eng_a.tokenizer.decode(eng_a.tokenizer.encode(text)[:target])

    tag = [0]

    def next_tag() -> int:
        tag[0] += 1
        return tag[0]

    async def serve(engine, prompt: str, donor: str = "") -> float:
        """Non-streamed serve, 1 new token: wall time ~= TTFT."""
        msg = create_generate_request(MODEL, prompt, max_tokens=1)
        if donor:
            msg.generate_request.kv_donor = donor
        t0 = time.monotonic()
        reply = await engine.handle(msg, worker_id="bench")
        dt = (time.monotonic() - t0) * 1000
        resp = extract_generate_response(reply)
        assert resp.done_reason != "error", resp.response
        return dt

    sweep: list[dict] = []
    recompute: dict[int, float] = {}
    bad_fetches = 0
    try:
        # Wait until the fetcher can resolve the donor in the DHT.
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if await real_find(peer_a.peer_id) is not None:
                break
            await asyncio.sleep(0.1)
        else:
            raise RuntimeError("donor never became resolvable")

        # Warmup: pay prefill-bucket XLA compiles on both engines and the
        # import-scatter compile on the fetcher, per prefix length.
        for L in prefixes:
            p = prompt_for(L, next_tag())
            await serve(eng_a, p)
            await serve(eng_b, prompt_for(L, next_tag()))
            await serve(eng_b, p, donor=peer_a.peer_id)

        # Recompute curve: RTT-independent (no donor dial), once per L.
        for L in prefixes:
            lat = []
            for _ in range(trials):
                lat.append(await serve(eng_b, prompt_for(L, next_tag())))
            recompute[L] = statistics.median(lat)

        for rtt_ms in rtts:
            proxy = None
            if rtt_ms > 0:
                proxy = DelayProxy(peer_a.host.listen_port, rtt_ms / 2000.0)
                proxy_port[0] = await proxy.start()
            # Drop pooled donor streams from the previous point: every RTT
            # point must dial through ITS relay, then reuse that stream
            # (the steady state the fetch path runs in).
            if eng_b._kv_streams is not None:
                eng_b._kv_streams.close_key(peer_a.peer_id)
            p = prompt_for(prefixes[0], next_tag())
            await serve(eng_a, p)
            await serve(eng_b, p, donor=peer_a.peer_id)  # establish stream
            points = []
            try:
                for L in prefixes:
                    lat = []
                    for _ in range(trials):
                        p = prompt_for(L, next_tag())
                        await serve(eng_a, p)       # donor caches the prefix
                        imp0 = eng_b._runner.kv_pages_imported
                        fb0 = eng_b.obs.metrics.kv_ship["fallbacks"]
                        lat.append(await serve(eng_b, p,
                                               donor=peer_a.peer_id))
                        if (eng_b._runner.kv_pages_imported == imp0
                                or eng_b.obs.metrics.kv_ship["fallbacks"]
                                != fb0):
                            bad_fetches += 1  # fell back: not a fetch number
                    fetch_ms = statistics.median(lat)
                    points.append({
                        "prefix_tokens": L,
                        "fetch_ttft_ms": round(fetch_ms, 1),
                        "recompute_ttft_ms": round(recompute[L], 1),
                        "ttft_reduction_pct": round(
                            100 * (1 - fetch_ms / recompute[L]), 1),
                    })
                    print(f"# rtt {rtt_ms:g}ms prefix {L}: fetch "
                          f"{fetch_ms:.1f}ms vs recompute "
                          f"{recompute[L]:.1f}ms", file=sys.stderr)
            finally:
                proxy_port[0] = None
                if proxy is not None:
                    await proxy.close()

            # Break-even prefix length: least-squares lines through both
            # curves; fetch cost is ~flat in L (dial + transfer), recompute
            # grows with L, so the crossing is where shipping starts
            # winning.  None when fetch never catches up in the sweep.
            break_even = None
            if len(points) >= 2:
                xs = [p["prefix_tokens"] for p in points]
                yr = [p["recompute_ttft_ms"] for p in points]
                yf = [p["fetch_ttft_ms"] for p in points]
                mx = sum(xs) / len(xs)
                den = sum((x - mx) ** 2 for x in xs)
                br = sum((x - mx) * (y - sum(yr) / len(yr))
                         for x, y in zip(xs, yr)) / den
                bf = sum((x - mx) * (y - sum(yf) / len(yf))
                         for x, y in zip(xs, yf)) / den
                ar = sum(yr) / len(yr) - br * mx
                af = sum(yf) / len(yf) - bf * mx
                if br > bf:
                    break_even = round(max(0.0, (af - ar) / (br - bf)))
            sweep.append({"rtt_ms": rtt_ms, "points": points,
                          "break_even_prefix_tokens": break_even})
    finally:
        for stop in (peer_b.stop, peer_a.stop, eng_b.stop, eng_a.stop,
                     boot_host.close):
            try:
                await stop()
            except Exception:
                pass  # teardown must not mask the benchmark's real error

    loopback = min(sweep, key=lambda s: s["rtt_ms"])
    head = loopback["points"][-1]
    kv_hist = eng_b.obs.metrics.kv_fetch_seconds
    return {
        "metric": (f"{MODEL} KV fetch vs prefill recompute, TTFT reduction "
                   f"at {head['prefix_tokens']}-token prefix (loopback)"),
        "value": head["ttft_reduction_pct"],
        "unit": "%",
        "vs_baseline": None,  # the reference always recomputes
        "extra": {
            "page_tokens": PAGE,
            "trials": trials,
            "rtt_sweep": sweep,
            "break_even_prefix_tokens":
                loopback["break_even_prefix_tokens"],
            "fetch_hist_p50_ms": round(kv_hist.quantile(0.5) * 1000, 1),
            "fetch_hist_count": kv_hist.count,
            "bytes_shipped": eng_b.obs.metrics.kv_ship["bytes"],
            "pages_imported": eng_b._runner.kv_pages_imported,
            "fallbacks_during_timed_trials": bad_fetches,
            "note": "fetch dials the donor over the real authenticated "
                    "p2p stream; rtt>0 points run through a transparent "
                    "delay relay on the donor dial only",
        },
    }


def main() -> None:
    os.environ.setdefault("CROWDLLAMA_TPU_TEST_MODE", "1")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    result = asyncio.run(run())
    _common.emit(result)


if __name__ == "__main__":
    sys.exit(main())
