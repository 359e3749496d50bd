"""Seeded chaos soak (docs/ROBUSTNESS.md, `make chaos-soak`).

Boots a REAL loopback swarm — bootstrap DHT node, N echo workers, one
consumer gateway — runs every prompt once fault-free (the control run),
then re-runs the exact same prompts under a seeded :class:`FaultPlan`
mixing every failure shape the request plane claims to survive:

- ``kill_stream`` — worker crash mid-stream (EOF, no error frame)
- ``stall_stream`` — gray failure: transport open, silence (only the
  per-stream progress watchdog can see it)
- ``slow_stream`` — a worker decoding at a fraction of its speed
- ``delay`` at first token — late TTFT, the hedged-dispatch trigger
- ``drain`` — live migration mid-stream
- ``error`` at ``host.new_stream`` — dial-plane partition flaps

and asserts the end-to-end invariants on EVERY stream:

1. byte-identical to its control run (implies zero lost tokens),
2. exactly one terminal frame, ``done_reason == "stop"`` (implies zero
   duplicated streams / no error surfaced to the client),
3. stalled-stream recovery bounded by stall budget + failover slack,
4. counter conservation: ``hedge_launched == hedge_won +
   hedge_cancelled``, internal counters == /metrics exposition,
5. the flight recorder captured a ``reason=wedged`` trace.

The schedule is SEEDED: the plan's rules fire at fixed pass indices and
the jitter RNG is seeded, so a red soak replays with the same seed.
Which concurrent stream absorbs a given fault depends on interleaving,
but every invariant above is interleaving-independent by construction.

Artifact: ``SOAK_seed<seed>.json`` under ``--out-dir`` (default: a fresh
temporary directory; the last line printed names the file).

Run: ``make chaos-soak`` (wired into ``make test``) or::

    JAX_PLATFORMS=cpu python -m crowdllama_tpu.testing.soak \
        --seed 42 --streams 200 --workers 4
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import tempfile
import time
from pathlib import Path

import aiohttp

from crowdllama_tpu.config import Configuration, Intervals
from crowdllama_tpu.core.protocol import INFERENCE_PROTOCOL
from crowdllama_tpu.engine.engine import FakeEngine
from crowdllama_tpu.gateway.gateway import Gateway
from crowdllama_tpu.net.discovery import new_host_and_dht
from crowdllama_tpu.peer.peer import Peer
from crowdllama_tpu.testing import faults
from crowdllama_tpu.testing.faults import FaultPlan, FaultRule
from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

MODEL = "tiny-test"
STALL_MS = 500.0  # progress-watchdog budget (both phases)
HEDGE_TTFT_MS = 150.0  # hedge launch threshold
# A stalled stream must recover within the stall budget plus this much
# failover work (teardown + replay dial + re-stream + run-queue jitter).
# Generous against CI noise but far below any client-visible hang.
FAILOVER_SLACK_S = 10.0


class SoakFailure(AssertionError):
    """An invariant did not hold; the JSON artifact records which."""


def _check(report: dict, name: str, ok: bool, detail: str) -> None:
    report["invariants"].append(
        {"name": name, "ok": bool(ok), "detail": detail})
    print(f"  [{'ok' if ok else 'FAIL'}] {name}: {detail}")


def build_plan(seed: int) -> FaultPlan:
    """The mixed fault schedule, phrased as pass indices through the
    instrumented sites.  A ~10-word echo prompt crosses
    ``engine.stream_chunk`` ~11 times, so 200 streams give >2000 passes
    — every rule below is guaranteed to exhaust its ``times``."""
    return FaultPlan(seed=seed, rules=[
        # Late first tokens: delay > hedge threshold but < stall budget,
        # so the hedge plane (not the stall watchdog) absorbs them.
        FaultRule(site="engine.stream_chunk", action="delay",
                  match={"index": 0}, delay_s=0.25, after=0, times=4),
        # Dial-plane partition flaps, absorbed by the pre-stream retry.
        FaultRule(site="host.new_stream",
                  match={"protocol": INFERENCE_PROTOCOL},
                  action="error", after=10, times=3),
        # Worker crashes mid-stream.  Pinned to chunk 4 so every firing
        # is guaranteed MID-stream (tokens already delivered → the
        # token-replay failover path, not a cheap pre-stream retry), and
        # SPACED as single-shot rules: a failover replay re-crosses
        # chunk 4, so one `times=5` rule would cascade all five kills
        # onto a single stream until it ran out of workers.
        *[FaultRule(site="engine.stream_chunk", action="kill_stream",
                    match={"index": 4}, after=20 + 40 * i, times=1)
          for i in range(5)],
        # A degraded worker pacing every chunk it serves for a while.
        FaultRule(site="engine.stream_chunk", action="slow_stream",
                  delay_s=0.002, jitter_s=0.003, after=300, times=40),
        # Gray failures: silence mid-DECODE (chunk 6: the first frame is
        # long gone, so only the decode-phase watchdog can see it).
        # Spaced for the same replay-cascade reason as the kills.
        FaultRule(site="engine.stream_chunk", action="stall_stream",
                  match={"index": 6}, after=100, times=1),
        FaultRule(site="engine.stream_chunk", action="stall_stream",
                  match={"index": 6}, after=140, times=1),
        # One live migration (graceful drain mid-stream).
        FaultRule(site="engine.stream_chunk", action="drain",
                  match={"index": 2}, after=170, times=1),
    ])


async def _wait_for(cond, timeout=30.0, interval=0.1, what="condition"):
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while loop.time() < deadline:
        if cond():
            return
        await asyncio.sleep(interval)
    raise SoakFailure(f"timed out waiting for {what}")


async def _swarm(n_workers: int):
    """Bootstrap + N echo workers + consumer gateway on real loopback
    sockets (same shape as tests/test_chaos.py, package-local so the
    soak is runnable outside pytest)."""
    boot_host, _ = await new_host_and_dht(
        Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    bootstrap = f"127.0.0.1:{boot_host.listen_port}"

    def cfg():
        return Configuration(listen_host="127.0.0.1",
                             bootstrap_peers=[bootstrap],
                             intervals=Intervals.default())

    workers = [Peer(Ed25519PrivateKey.generate(), cfg(),
                    engine=FakeEngine(models=[MODEL]), worker_mode=True)
               for _ in range(n_workers)]
    for w in workers:
        await w.start()
    consumer = Peer(Ed25519PrivateKey.generate(), cfg(),
                    engine=FakeEngine(models=[]), worker_mode=False)
    await consumer.start()
    gateway = Gateway(consumer, port=0, host="127.0.0.1",
                      stream_stall_ms=STALL_MS, hedge_ttft_ms=HEDGE_TTFT_MS)
    await gateway.start()
    gw_port = gateway._runner.addresses[0][1]

    await _wait_for(
        lambda: len({p.peer_id for p in
                     consumer.peer_manager.get_healthy_peers()
                     if p.is_worker}) == n_workers,
        what=f"all {n_workers} workers discovered")

    async def teardown():
        faults.clear()
        await gateway.stop()
        await consumer.stop()
        for w in workers:
            try:
                await w.stop()
            except Exception:
                pass
        await boot_host.close()

    return workers, consumer, gateway, gw_port, teardown


async def _one_stream(session: aiohttp.ClientSession, url: str,
                      idx: int) -> dict:
    """Drive one streamed chat; return its byte content and terminal
    shape.  Never raises — a transport-level surprise is itself an
    invariant violation the phase check reports."""
    body = {"model": MODEL, "stream": True,
            "messages": [{"role": "user",
                          "content": f"soak stream {idx:03d} tell the "
                                     "swarm a story about its peers "
                                     "and pages"}]}
    t0 = time.monotonic()
    try:
        async with session.post(url, json=body) as resp:
            status = resp.status
            raw = await resp.text()
    except Exception as e:  # noqa: BLE001 — recorded, judged later
        return {"idx": idx, "status": -1, "content": "", "terminals": 0,
                "done_reason": f"transport: {e}",
                "elapsed_s": time.monotonic() - t0}
    lines = [json.loads(l) for l in raw.splitlines() if l.strip()]
    return {
        "idx": idx,
        "status": status,
        "content": "".join(l.get("message", {}).get("content", "")
                           for l in lines),
        "terminals": sum(1 for l in lines if l.get("done")),
        "done_reason": lines[-1].get("done_reason") if lines else "empty",
        "error": next((l["error"] for l in lines if "error" in l), None),
        "elapsed_s": time.monotonic() - t0,
    }


async def _phase(url: str, n_streams: int, concurrency: int) -> list[dict]:
    sem = asyncio.Semaphore(concurrency)
    conn = aiohttp.TCPConnector(limit=concurrency)
    async with aiohttp.ClientSession(connector=conn) as session:

        async def bounded(i):
            async with sem:
                return await _one_stream(session, url, i)

        return list(await asyncio.gather(
            *(bounded(i) for i in range(n_streams))))


def _judge(report: dict, control: list[dict], chaos: list[dict],
           plan: FaultPlan, gateway) -> None:
    """Apply every soak invariant; append to report['invariants']."""
    fired = {}
    for _site, _attrs, action in plan.log:
        fired[action] = fired.get(action, 0) + 1
    report["faults_fired"] = fired
    _check(report, "schedule_exhausted",
           fired.get("kill_stream") == 5 and fired.get("stall_stream") == 2
           and fired.get("drain") == 1 and fired.get("error") == 3,
           f"fired={fired}")

    bad_control = [r for r in control
                   if r["status"] != 200 or r["terminals"] != 1
                   or r["done_reason"] != "stop"]
    _check(report, "control_clean", not bad_control,
           f"{len(control) - len(bad_control)}/{len(control)} clean"
           + (f"; first bad: {bad_control[0]}" if bad_control else ""))

    bad_terminal = [r for r in chaos
                    if r["status"] != 200 or r["terminals"] != 1
                    or r["done_reason"] != "stop" or r.get("error")]
    _check(report, "exactly_one_clean_terminal_per_stream", not bad_terminal,
           f"{len(chaos) - len(bad_terminal)}/{len(chaos)} clean"
           + (f"; first bad: {bad_terminal[0]}" if bad_terminal else ""))

    by_idx = {r["idx"]: r for r in control}
    mismatched = [r["idx"] for r in chaos
                  if r["content"] != by_idx[r["idx"]]["content"]]
    _check(report, "byte_identical_zero_lost_or_dup_tokens", not mismatched,
           f"{len(chaos) - len(mismatched)}/{len(chaos)} byte-identical"
           + (f"; mismatched idx {mismatched[:5]}" if mismatched else ""))

    # Stalled-stream recovery bound: the watchdog fires at the stall
    # budget and failover replays from there — no stream, stalled or
    # not, may take longer than budget + slack.
    bound = STALL_MS / 1000.0 + FAILOVER_SLACK_S
    slowest = max(r["elapsed_s"] for r in chaos)
    report["chaos_slowest_s"] = round(slowest, 3)
    report["recovery_bound_s"] = bound
    _check(report, "stalled_recovery_bounded",
           slowest <= bound,
           f"slowest stream {slowest:.2f}s <= {bound:.2f}s "
           f"(stall {STALL_MS:.0f}ms + failover slack)")

    r = gateway._robust
    report["gateway_counters"] = {k: r[k] for k in (
        "failovers", "replayed_chunks", "stalled_streams",
        "wedge_quarantines", "hedge_launched", "hedge_won",
        "hedge_cancelled")}
    _check(report, "hedge_conservation",
           r["hedge_launched"] == r["hedge_won"] + r["hedge_cancelled"]
           and r["hedge_launched"] >= 1,
           f"launched {r['hedge_launched']} == won {r['hedge_won']} + "
           f"cancelled {r['hedge_cancelled']}")
    _check(report, "stall_watchdog_counters",
           r["stalled_streams"] == 2 and 1 <= r["wedge_quarantines"] <= 2
           and r["failovers"] >= 7,
           f"stalled {r['stalled_streams']}, quarantined "
           f"{r['wedge_quarantines']}, failovers {r['failovers']} "
           "(>= 5 kills + 2 stalls)")

    wedged_traces = [e for e in gateway.flight.snapshot()["traces"]
                     if "wedged" in e["reasons"]]
    _check(report, "flight_recorder_captured_wedged",
           len(wedged_traces) >= 1,
           f"{len(wedged_traces)} trace(s) with reason=wedged")


async def _conservation_check(report: dict, gateway, gw_port: int) -> None:
    """Internal counters must equal the /metrics exposition (a divergence
    means a counter was bumped off the render path or vice versa)."""
    async with aiohttp.ClientSession() as s:
        async with s.get(f"http://127.0.0.1:{gw_port}/metrics") as resp:
            text = await resp.text()
    exposed = {}
    for line in text.splitlines():
        if line.startswith("#") or " " not in line:
            continue
        name, _, val = line.partition(" ")
        exposed[name] = val
    r = gateway._robust
    pairs = [
        ("crowdllama_gateway_failovers_total", r["failovers"]),
        ("crowdllama_stall_aborted_streams_total", r["stalled_streams"]),
        ("crowdllama_wedge_quarantines_total", r["wedge_quarantines"]),
        ("crowdllama_hedge_launched_total", r["hedge_launched"]),
        ("crowdllama_hedge_won_total", r["hedge_won"]),
        ("crowdllama_hedge_cancelled_total", r["hedge_cancelled"]),
    ]
    diverged = [(n, exposed.get(n), v) for n, v in pairs
                if exposed.get(n) != str(v)]
    _check(report, "metrics_exposition_conserved", not diverged,
           "internal counters == /metrics" if not diverged
           else f"diverged: {diverged}")


async def run_soak(seed: int, n_streams: int, n_workers: int,
                   concurrency: int, out_dir: Path) -> dict:
    t_start = time.monotonic()
    report: dict = {"seed": seed, "streams": n_streams,
                    "workers": n_workers, "concurrency": concurrency,
                    "stall_ms": STALL_MS, "hedge_ttft_ms": HEDGE_TTFT_MS,
                    "invariants": []}
    print(f"chaos soak: seed={seed} streams={n_streams} "
          f"workers={n_workers} concurrency={concurrency}")
    workers, consumer, gateway, gw_port, teardown = await _swarm(n_workers)
    try:
        url = f"http://127.0.0.1:{gw_port}/api/chat"

        print("phase 1/2: control (fault-free baseline)...")
        t0 = time.monotonic()
        control = await _phase(url, n_streams, concurrency)
        report["control_s"] = round(time.monotonic() - t0, 2)

        print("phase 2/2: chaos (seeded mixed-fault schedule)...")
        plan = build_plan(seed)
        t0 = time.monotonic()
        with faults.installed(plan):
            chaos = await _phase(url, n_streams, concurrency)
        report["chaos_s"] = round(time.monotonic() - t0, 2)

        # The flight recorder stitches its captures asynchronously —
        # give it a bounded window before judging (the invariant check
        # below still fails hard if nothing ever lands).
        try:
            await _wait_for(
                lambda: any("wedged" in e["reasons"]
                            for e in gateway.flight.snapshot()["traces"]),
                timeout=10.0, what="flight-recorder wedged capture")
        except SoakFailure:
            pass

        print("invariants:")
        _judge(report, control, chaos, plan, gateway)
        await _conservation_check(report, gateway, gw_port)
    finally:
        await teardown()

    report["elapsed_s"] = round(time.monotonic() - t_start, 2)
    report["pass"] = all(c["ok"] for c in report["invariants"])
    out_dir.mkdir(parents=True, exist_ok=True)
    out = out_dir / f"SOAK_seed{seed}.json"
    out.write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")
    print(f"{'PASS' if report['pass'] else 'FAIL'} in "
          f"{report['elapsed_s']}s — artifact: {out}")
    if not report["pass"]:
        failed = [c["name"] for c in report["invariants"] if not c["ok"]]
        raise SoakFailure(f"soak seed={seed} violated: {', '.join(failed)}")
    return report


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--streams", type=int, default=200)
    # 5: two wedge quarantines + one drained worker still leave TWO
    # healthy targets, so a kill replay always has somewhere to go.
    ap.add_argument("--workers", type=int, default=5)
    ap.add_argument("--concurrency", type=int, default=16)
    ap.add_argument("--out-dir", type=Path, default=None,
                    help="where the report goes (default: a fresh "
                         "temporary directory)")
    args = ap.parse_args(argv)
    if args.workers < 3:
        ap.error("--workers must be >= 3 (two stalls quarantine two)")
    out_dir = args.out_dir or Path(tempfile.mkdtemp(prefix="soak_"))
    try:
        asyncio.run(run_soak(args.seed, args.streams, args.workers,
                             args.concurrency, out_dir))
    except SoakFailure as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
