"""Cross-worker expert-parallel dispatch benchmark (BASELINE config 4).

A 2-member MoE expert group on real loopback streams: the leader runs
attention/router and dispatches per-layer (token, expert) batches to a
remote expert bank over SHARD_PROTOCOL — one DCN round trip per MoE
layer per decode step, the intrinsic cost of cross-worker EP.  This
measures the CONTROL-PLANE price of that hop (framing, AEAD, asyncio)
with a tiny model so compute does not mask it; the dominant term on a
real deployment is the same per-layer round trip over real DCN RTTs.

Loopback RTT is ~0, which understates a real deployment, so the bench
also SWEEPS injected RTT: a transparent TCP delay relay sits between
leader and expert bank and delivers each chunk one-way-delay late
(injected RTT = 2x the one-way delay).  The sweep reports steps/sec vs
RTT and the break-even RTT against the local-only pipeline — the
injected RTT at which dispatch overhead equals the whole local-only
step cost (i.e. cross-worker EP halves decode throughput).

Prints ONE JSON line; value is decode steps/sec through the 2-worker
pipeline at RTT 0, extra carries the RTT sweep, per-step latency and
the single-worker (local banks only) comparison.

Env overrides:
  CROWDLLAMA_BENCH_EP_STEPS   timed decode steps per point (default 64)
  CROWDLLAMA_BENCH_EP_RTTS    injected RTT sweep, ms (default "0,1,5,10,20")
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import _common  # noqa: E402 - repo path + compile cache bootstrap

import asyncio  # noqa: F401 - used by the bench body below
import json
import os
import time
from dataclasses import replace

# Shared injected-latency relay (factored out of this file once the
# spec-pipeline bench became its third consumer).
from crowdllama_tpu.testing.netem import DelayProxy  # noqa: E402,F401


async def run() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

    from crowdllama_tpu.core.protocol import SHARD_PROTOCOL
    from crowdllama_tpu.engine.expert_service import (
        EPLeaderRunner,
        EPPipeline,
        ExpertBankRunner,
        ExpertBankService,
        LocalExpertBank,
        RemoteExpertBank,
        assign_experts,
    )
    from crowdllama_tpu.models import transformer as T
    from crowdllama_tpu.models.config import get_config
    from crowdllama_tpu.net.host import Host

    steps = int(os.environ.get("CROWDLLAMA_BENCH_EP_STEPS", "64"))
    rtts = [float(x) for x in os.environ.get(
        "CROWDLLAMA_BENCH_EP_RTTS", "0,1,5,10,20").split(",") if x.strip()]
    cfg = get_config("tiny-test-moe", max_context_length=256)
    params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]

    async def decode_run(pipe, sid: str) -> tuple[float, list[float]]:
        logits = await pipe.prefill(sid, prompt, bucket=16)
        tok = int(np.argmax(logits))
        n = len(prompt)
        # Warmup (compile) steps, then timed.
        for _ in range(4):
            logits = await pipe.decode(sid, tok, n, n + 1)
            tok = int(np.argmax(logits))
            n += 1
        lat: list[float] = []
        t0 = time.monotonic()
        for _ in range(steps):
            t1 = time.monotonic()
            logits = await pipe.decode(sid, tok, n, n + 1)
            tok = int(np.argmax(logits))
            n += 1
            lat.append((time.monotonic() - t1) * 1000)
        dt = time.monotonic() - t0
        await pipe.release(sid)
        return dt, lat

    # Cross-worker: remote bank behind a REAL authenticated stream, once
    # per injected RTT.  Leader runner, local bank, hosts and the remote
    # bank runner are shared across sweep points (compiled fns are reused,
    # so only the first point pays XLA compilation); each point dials a
    # fresh stream — through a DelayProxy when rtt > 0.
    remote_runner = ExpertBankRunner(cfg, params, assign_experts(4, 2, 1),
                                     dtype=jnp.float32)
    worker_host = Host(Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    worker_host.set_stream_handler(
        SHARD_PROTOCOL, ExpertBankService(remote_runner).handle)
    await worker_host.start()
    leader_host = Host(Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    await leader_host.start()
    leader = EPLeaderRunner(cfg, params, max_seq=256, dtype=jnp.float32)
    local = LocalExpertBank(
        ExpertBankRunner(cfg, params, assign_experts(4, 2, 0),
                         dtype=jnp.float32))
    sweep: list[dict] = []
    lat: list[float] = []
    dt = 1.0
    try:
        for rtt_ms in rtts:
            proxy = None
            target = worker_host.contact
            if rtt_ms > 0:
                proxy = DelayProxy(worker_host.listen_port, rtt_ms / 2000.0)
                target = replace(target, port=await proxy.start())
            pipe = None
            try:
                stream = await leader_host.new_stream(target, SHARD_PROTOCOL)
                pipe = EPPipeline(cfg, leader, [
                    local,
                    RemoteExpertBank(stream, remote_runner.expert_ids)])
                dt_i, lat_i = await decode_run(pipe, f"bench-ep-rtt{rtt_ms:g}")
            finally:
                if pipe is not None:
                    pipe.close()
                if proxy is not None:
                    await proxy.close()
            lat_i.sort()
            point = {"rtt_ms": rtt_ms,
                     "steps_per_sec": round(steps / dt_i, 1),
                     "step_p50_ms": round(lat_i[len(lat_i) // 2], 2)}
            sweep.append(point)
            print(f"# rtt {rtt_ms:g}ms: {point['steps_per_sec']} steps/s, "
                  f"p50 {point['step_p50_ms']}ms", file=sys.stderr)
            if rtt_ms == 0:
                dt, lat = dt_i, lat_i  # headline = no injected RTT
        if not lat:  # sweep didn't include 0: headline = first point
            dt, lat = steps / sweep[0]["steps_per_sec"], [
                sweep[0]["step_p50_ms"]]
    finally:
        await leader_host.close()
        await worker_host.close()

    # Single-worker comparison: both banks local (no DCN hop) — the
    # delta per step IS the cross-worker dispatch price.
    leader2 = EPLeaderRunner(cfg, params, max_seq=256, dtype=jnp.float32)
    pipe2 = EPPipeline(cfg, leader2, [
        LocalExpertBank(ExpertBankRunner(cfg, params,
                                         assign_experts(4, 2, 0),
                                         dtype=jnp.float32)),
        LocalExpertBank(ExpertBankRunner(cfg, params,
                                         assign_experts(4, 2, 1),
                                         dtype=jnp.float32)),
    ])
    try:
        dt_local, lat_local = await decode_run(pipe2, "bench-ep-local")
    finally:
        pipe2.close()

    lat.sort()
    lat_local.sort()
    p50 = lat[len(lat) // 2]
    p50_local = lat_local[len(lat_local) // 2]
    n_moe = cfg.num_layers  # every tiny-test-moe layer is MoE

    # Least-squares slope of step p50 vs injected RTT: measured ms of step
    # latency added per ms of RTT (should approach the MoE hop count).
    # Break-even vs local-only: the injected RTT at which dispatch overhead
    # equals the entire local-only step cost — cross-worker EP then halves
    # decode throughput, p50_0 + slope*rtt = 2*p50_local.
    slope_ms_per_rtt_ms = None
    break_even_rtt_ms = None
    if len(sweep) >= 2:
        xs = [p["rtt_ms"] for p in sweep]
        ys = [p["step_p50_ms"] for p in sweep]
        mx, my = sum(xs) / len(xs), sum(ys) / len(ys)
        denom = sum((x - mx) ** 2 for x in xs)
        if denom > 0:
            slope = sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / denom
            slope_ms_per_rtt_ms = round(slope, 3)
            if slope > 0:
                break_even_rtt_ms = round(
                    max(0.0, 2 * p50_local - p50) / slope, 2)

    return {
        "metric": "cross-worker EP decode (2 expert banks over loopback "
                  "streams), steps/sec",
        "value": round(steps / dt, 1),
        "unit": "steps/sec",
        "vs_baseline": None,  # the reference has no model parallelism
        "extra": {
            "step_p50_ms": round(p50, 2),
            "local_only_step_p50_ms": round(p50_local, 2),
            "dispatch_overhead_ms_per_step": round(p50 - p50_local, 2),
            "moe_layers_per_step": n_moe,
            "dispatch_overhead_ms_per_layer_hop": round(
                (p50 - p50_local) / max(1, n_moe), 3),
            "rtt_sweep": sweep,
            "slope_ms_per_rtt_ms": slope_ms_per_rtt_ms,
            "break_even_rtt_ms": break_even_rtt_ms,
            "timed_steps": steps,
            "model": cfg.name,
            "note": "value is the RTT-0 loopback point; rtt_sweep injects "
                    "DCN-like RTT via a delay relay, break_even_rtt_ms is "
                    "where dispatch overhead halves throughput vs "
                    "local-only",
        },
    }


def main() -> None:
    os.environ.setdefault("CROWDLLAMA_TPU_TEST_MODE", "1")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    result = asyncio.run(run())
    _common.emit(result)


if __name__ == "__main__":
    sys.exit(main())
