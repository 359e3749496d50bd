"""Gateway-drafted speculative pipeline vs injected swarm RTT
(docs/SPECULATIVE.md, gateway drafting section).

Full serving topology on loopback, all real sockets: DHT bootstrap + one
spec-draft JaxEngine worker + consumer peer + HTTP gateway.  The draft
checkpoint equals the main model (same init seed), so acceptance sits at
the self-draft ceiling and the sweep isolates the ONE variable under
test: where the draft model lives relative to the RTT.

Three arms, all serving the identical streamed /api/chat request:

  no_spec        spec_pipeline=off — no remote-draft sub-protocol; the
                 worker speculates locally (PR 4) and free-runs, so RTT
                 is paid once at dial time (flat control arm)
  worker_draft   spec_pipeline=worker — remote-draft wire with pure ack
                 credits: the worker drafts, every verify round waits one
                 RTT for its credit (stop-and-wait; linear in RTT)
  gateway_draft  spec_pipeline=gateway — the gateway drafts and keeps
                 depth-controller-many chunks in flight, so verify rounds
                 overlap the wire (sub-linear in RTT)

RTT is injected with the shared DelayProxy relay
(crowdllama_tpu/testing/netem.py): the relay fronts the worker's listen
port and the consumer's DHT lookup is rewired to it, so every gateway
dial pays the latency.  Client streams must be byte-identical across all
arms and RTT points (greedy verify is exact); the bench hard-fails
otherwise.

Prints ONE JSON line; value is the gateway-draft / worker-draft decode
tokens/s ratio at the LARGEST injected RTT (the acceptance bar is 1.5x
at 20 ms), extra carries the full sweep and per-arm RTT-degradation
slopes.  Also writes benchmarks/results/SPEC_RTT_cpu_<date>.json.

Env overrides:
  CROWDLLAMA_BENCH_SPEC_RTTS    injected RTT sweep, ms (default "0,5,10,20")
  CROWDLLAMA_BENCH_SPEC_TOKENS  tokens generated per request (default 96)
  CROWDLLAMA_BENCH_SPEC_TRIALS  timed trials per cell (default 3)
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import _common  # noqa: E402 - repo path + compile cache bootstrap

import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402
from dataclasses import replace  # noqa: E402

from crowdllama_tpu.testing.modelgen import permutation_params  # noqa: E402
from crowdllama_tpu.testing.netem import DelayProxy  # noqa: E402

MODEL = "tiny-test"
CTX = 256
ARMS = ("no_spec", "worker_draft", "gateway_draft")
_MODE = {"no_spec": "off", "worker_draft": "worker",
         "gateway_draft": "gateway"}


async def run() -> dict:
    import aiohttp

    from crowdllama_tpu.config import Configuration, Intervals
    from crowdllama_tpu.engine.engine import FakeEngine, JaxEngine
    from crowdllama_tpu.engine.weights import save_params
    from crowdllama_tpu.gateway.gateway import Gateway
    from crowdllama_tpu.models.config import get_config
    from crowdllama_tpu.net.discovery import new_host_and_dht
    from crowdllama_tpu.peer.peer import Peer
    from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

    rtts = [float(x) for x in os.environ.get(
        "CROWDLLAMA_BENCH_SPEC_RTTS", "0,5,10,20").split(",") if x.strip()]
    n_tokens = int(os.environ.get("CROWDLLAMA_BENCH_SPEC_TOKENS", "96"))
    trials = int(os.environ.get("CROWDLLAMA_BENCH_SPEC_TRIALS", "3"))

    def cfg(**kw):
        c = Configuration(listen_host="127.0.0.1",
                          intervals=Intervals.default())
        for k, v in kw.items():
            setattr(c, k, v)
        return c

    # Both engines (worker main+draft, gateway draft) load the SAME
    # checkpoint: a constructed token-permutation model.  Random-init
    # weights have near-tie logits, so the paged verify path and the
    # gateway's contiguous draft path flip argmax on ulp-level noise and
    # acceptance collapses into numeric lottery; this model's logit gaps
    # are O(1), so greedy decode is path-stable, acceptance sits at the
    # ceiling, EOS never fires, and arm deltas isolate the one variable
    # under test — RTT x pipelining.  (Draft-model QUALITY is priced by
    # benchmarks/spec_decode.py, not here.)
    mcfg = get_config(MODEL, max_context_length=CTX)
    params = permutation_params(mcfg)
    ckpt = tempfile.mkdtemp(prefix="spec-rtt-draft-")
    save_params(mcfg, params, ckpt, {"note": "spec_rtt permutation model"})

    boot_host, _ = await new_host_and_dht(
        Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    bootstrap = f"127.0.0.1:{boot_host.listen_port}"
    engine = JaxEngine(
        cfg(bootstrap_peers=[bootstrap], model=MODEL, model_path=ckpt,
            spec_decode="draft", spec_draft=3, spec_draft_model=MODEL,
            spec_draft_path=ckpt, max_batch_slots=2, warmup=False),
        max_context_length=CTX)
    await engine.start()
    worker = Peer(Ed25519PrivateKey.generate(),
                  cfg(bootstrap_peers=[bootstrap], model=MODEL),
                  engine=engine, worker_mode=True)
    await worker.start()
    consumer = Peer(Ed25519PrivateKey.generate(),
                    cfg(bootstrap_peers=[bootstrap]),
                    engine=FakeEngine(models=[]), worker_mode=False)
    await consumer.start()
    gateway = Gateway(consumer, port=0, host="127.0.0.1",
                      spec_pipeline="off", spec_draft_path=ckpt)
    await gateway.start()
    gw_port = gateway._runner.addresses[0][1]

    # The gateway's worker lookup, optionally rewired through the relay
    # (same idiom as kv_transfer.py) so every inference dial pays the
    # injected latency.
    real_find = consumer.dht.find_peer
    proxy_port: list[int | None] = [None]

    async def find_peer(pid):
        contact = await real_find(pid)
        if contact is not None and pid == worker.peer_id \
                and proxy_port[0] is not None:
            contact = replace(contact, port=proxy_port[0])
        return contact

    consumer.dht.find_peer = find_peer

    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if consumer.peer_manager.find_best_worker(MODEL) is not None:
            break
        await asyncio.sleep(0.1)
    else:
        raise RuntimeError("worker never became routable")

    body = {"model": MODEL, "stream": True,
            "options": {"num_predict": n_tokens},
            "messages": [{"role": "user",
                          "content": "tell me a story about the swarm"}]}
    url = f"http://127.0.0.1:{gw_port}/api/chat"

    async def ask(http) -> tuple[str, float, int]:
        """One streamed request -> (text, decode tokens/s, eval_count).
        Rate spans first content frame to the done frame, so dial +
        prefill + the injected handshake RTT (TTFT) stay out of the
        decode number; token count comes from the final frame's
        eval_count (frames batch multiple tokens under flush coalescing,
        so counting frames would undercount)."""
        t_first = t_done = None
        n_eval = 0
        parts: list[str] = []
        async with http.post(url, json=body) as resp:
            assert resp.status == 200, await resp.text()
            async for raw in resp.content:
                raw = raw.strip()
                if not raw:
                    continue
                d = json.loads(raw)
                if t_first is None:
                    t_first = time.monotonic()
                parts.append(d.get("message", {}).get("content", ""))
                if d.get("done"):
                    t_done = time.monotonic()
                    n_eval = int(d.get("eval_count", 0))
                    assert d.get("done_reason") == "length", d
        text = "".join(parts)
        span = (t_done - t_first) if (t_first and t_done) else 0.0
        tps = (n_eval - 1) / span if span > 0 and n_eval > 1 else 0.0
        return text, tps, n_eval

    sweep: list[dict] = []
    expected_text: str | None = None
    async with aiohttp.ClientSession() as http:
        # Warmup at RTT 0: XLA compiles (engine decode buckets, hosted
        # verify program, gateway drafter prefill/step) all paid here.
        for arm in ARMS:
            gateway.spec_pipeline = _MODE[arm]
            text, _, _ = await ask(http)
            if expected_text is None:
                expected_text = text
            assert text == expected_text, \
                f"warmup stream diverged in arm {arm}"

        for rtt_ms in rtts:
            proxy = None
            if rtt_ms > 0:
                proxy = DelayProxy(worker.host.listen_port,
                                   rtt_ms / 2000.0)
                proxy_port[0] = await proxy.start()
            try:
                for arm in ARMS:
                    gateway.spec_pipeline = _MODE[arm]
                    # Pooled plain streams from the previous point would
                    # bypass this point's relay; drop them so every arm
                    # dials through the current wire.
                    gateway._stream_pool.close_key(worker.peer_id)
                    rates = []
                    for _ in range(trials):
                        text, tps, n = await ask(http)
                        assert text == expected_text, (
                            f"stream NOT byte-identical: arm {arm} at "
                            f"rtt {rtt_ms}ms")
                        rates.append(tps)
                    point = {"arm": arm, "rtt_ms": rtt_ms,
                             "decode_tok_s": round(
                                 statistics.median(rates), 1),
                             "tokens": n, "trials": trials}
                    sweep.append(point)
                    print(f"# rtt {rtt_ms:g}ms {arm}: "
                          f"{point['decode_tok_s']} tok/s",
                          file=sys.stderr)
            finally:
                proxy_port[0] = None
                if proxy is not None:
                    await proxy.close()
        spec_stats = dict(gateway._spec_stats)
    await gateway.stop()
    await consumer.stop()
    await worker.stop()
    await engine.stop()
    await boot_host.close()

    def cells(arm):
        return {p["rtt_ms"]: p["decode_tok_s"]
                for p in sweep if p["arm"] == arm}

    # Per-arm RTT sensitivity: least-squares slope of seconds-per-token
    # vs injected RTT.  A stop-and-wait arm that pays the full RTT every
    # verify round lands near 1/(k+1) s of token latency per s of RTT;
    # a pipelined arm lands near 0.
    def slope(arm):
        pts = [(r / 1000.0, 1.0 / t) for r, t in cells(arm).items()
               if t > 0]
        if len(pts) < 2:
            return None
        mx = sum(x for x, _ in pts) / len(pts)
        my = sum(y for _, y in pts) / len(pts)
        den = sum((x - mx) ** 2 for x, _ in pts)
        if den <= 0:
            return None
        return round(sum((x - mx) * (y - my) for x, y in pts) / den, 3)

    max_rtt = max(rtts)
    gw_at_max = cells("gateway_draft").get(max_rtt, 0.0)
    wk_at_max = cells("worker_draft").get(max_rtt, 0.0)
    ratio = round(gw_at_max / wk_at_max, 2) if wk_at_max > 0 else None

    def degradation(arm):
        c = cells(arm)
        lo, hi = c.get(min(rtts), 0.0), c.get(max_rtt, 0.0)
        return round(100 * (1 - hi / lo), 1) if lo > 0 else None

    return {
        "metric": "gateway-draft / worker-draft decode tokens/s at "
                  f"{max_rtt:g}ms injected RTT",
        "value": ratio,
        "unit": "x",
        "vs_baseline": None,  # the reference has no speculative pipeline
        "extra": {
            "sweep": sweep,
            "tok_latency_slope_s_per_s_rtt": {
                arm: slope(arm) for arm in ARMS},
            "degradation_pct_0_to_max_rtt": {
                arm: degradation(arm) for arm in ARMS},
            "byte_identical_all_cells": True,  # hard-asserted above
            "draft_chunk_stats": spec_stats,
            "tokens_per_request": n_tokens,
            "trials_per_cell": trials,
            "model": MODEL,
            "note": "draft == main checkpoint (acceptance ceiling), so "
                    "arm deltas isolate RTT x pipelining; worker_draft "
                    "is credit stop-and-wait (linear in RTT), "
                    "gateway_draft keeps depth-controller-many chunks "
                    "in flight",
        },
    }


def main() -> None:
    os.environ.setdefault("CROWDLLAMA_TPU_TEST_MODE", "1")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    result = asyncio.run(run())
    result.setdefault("device", _common.device_info())
    out = json.dumps(result)
    print(out)
    res_dir = Path(__file__).resolve().parent / "results"
    res_dir.mkdir(parents=True, exist_ok=True)
    stamp = time.strftime("%Y-%m-%d")
    (res_dir / f"SPEC_RTT_cpu_{stamp}.json").write_text(out + "\n")


if __name__ == "__main__":
    sys.exit(main())
