"""Live request migration (docs/ROBUSTNESS.md): graceful worker drain with
KV handoff.

Worker side: drain() flips the peer to ``draining`` (typed reject for new
requests, forced metadata publish) and the scheduler retires in-flight
streams with a MigrateFrame at its next safe point, keeping the node
alive as a KV donor.  Gateway side: a MigrateFrame (or draining reject)
re-routes the stream through the failover/replay machinery with the
drained worker attached as ``kv_donor`` + ``migrate=True``, so the
successor imports the prompt's pages instead of re-running prefill — the
client sees one uninterrupted, byte-identical stream.
"""

import asyncio
import json
import time

import aiohttp
import pytest
from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

from crowdllama_tpu.config import Configuration, Intervals
from crowdllama_tpu.core import wire
from crowdllama_tpu.core.messages import (
    create_generate_request,
    extract_migrate_frame,
    migrate_frame_msg,
)
from crowdllama_tpu.engine.engine import FakeEngine
from crowdllama_tpu.engine.scheduler import _DONE, GenRequest, Scheduler
from crowdllama_tpu.gateway.gateway import Gateway
from crowdllama_tpu.net.discovery import new_host_and_dht
from crowdllama_tpu.obs.http import ObsServer
from crowdllama_tpu.peer.peer import Peer
from crowdllama_tpu.testing import faults
from crowdllama_tpu.testing.faults import FaultPlan, FaultRule

MODEL = "tiny-test"


# ------------------------------------------------------------------- units


async def test_scheduler_migrate_retires_pending_with_migrate():
    """migrate() hands back every queued request with the "migrate" done
    reason (the loop-less unit path; the loop path is covered end to end
    below) and leaves the scheduler usable as a drain donor."""

    class _StubRunner:
        max_slots = 2
        max_seq = 128

        def init_state(self):
            return None

    sched = Scheduler(_StubRunner())
    try:
        reqs = [GenRequest(prompt_ids=[1, 2, 3]),
                GenRequest(prompt_ids=[4, 5])]
        for r in reqs:
            await sched.submit(r)
        moved = await sched.migrate()
        assert moved == 2
        for r in reqs:
            tok, reason = r.out.get_nowait()
            assert tok is _DONE and reason == "migrate"
        # Idempotent: nothing left to move.
        assert await sched.migrate() == 0
    finally:
        await sched.stop()


async def test_fake_engine_migrate_emits_migrate_frame():
    """Mid-stream migrate() turns the terminal frame into a MigrateFrame
    carrying delivered/prompt token counts (the gateway consumes it as the
    re-route trigger)."""
    eng = FakeEngine(models=[MODEL])
    msg = create_generate_request(
        MODEL, "one two three four five six seven eight", stream=True)
    stream = eng.handle_streaming(msg, worker_id="w-drain")
    frames = []
    async for frame in stream:
        frames.append(frame)
        if len(frames) == 2:
            assert await eng.migrate() == 1
    assert frames[-1].WhichOneof("message") == "migrate_frame"
    mf = extract_migrate_frame(frames[-1])
    assert mf.worker_id == "w-drain"
    assert mf.reason == "drain"
    assert mf.delivered_tokens >= 1
    assert mf.prompt_tokens == 8
    # Every earlier frame was an ordinary streamed GenerateResponse.
    assert all(f.WhichOneof("message") == "generate_response"
               for f in frames[:-1])


def test_migrate_frame_wire_roundtrip():
    """MigrateFrame and GenerateRequest.migrate survive the length-prefixed
    wire encoding — and a frame without them decodes as before (the field
    numbers extend the proto, nothing was renumbered)."""
    msg = migrate_frame_msg(
        model=MODEL, worker_id="w1", delivered_tokens=7, prompt_tokens=42,
        chain_hashes=[b"\x01" * 32, b"\x02" * 32], page_size=16,
        reason="drain")
    out = wire.decode_payload(wire.encode_frame(msg)[4:])
    assert out.WhichOneof("message") == "migrate_frame"
    mf = extract_migrate_frame(out)
    assert (mf.delivered_tokens, mf.prompt_tokens, mf.page_size) == (7, 42, 16)
    assert list(mf.chain_hashes) == [b"\x01" * 32, b"\x02" * 32]

    req = create_generate_request(MODEL, "p", stream=True)
    req.generate_request.migrate = True
    req.generate_request.kv_donor = "w1"
    back = wire.decode_payload(wire.encode_frame(req)[4:])
    assert back.generate_request.migrate is True
    # Default stays False: old senders never set the field.
    plain = create_generate_request(MODEL, "p")
    assert plain.generate_request.migrate is False


def test_affinity_drop_worker_repoints_and_evicts():
    """Affinity hygiene (drain/removal): entries pinned to the leaving
    worker re-point to the migration successor when one is known,
    otherwise evict — and the repoint counter moves."""
    from types import SimpleNamespace

    gw = Gateway(SimpleNamespace(peer_manager=None), port=0)
    gw._affinity_put("conv-a", "w-old")
    gw._affinity_put("conv-b", "w-old")
    gw._affinity_put("conv-c", "w-other")
    gw._affinity_drop_worker("w-old", successor="w-new")
    assert gw._affinity["conv-a"][0] == "w-new"
    assert gw._affinity["conv-b"][0] == "w-new"
    assert gw._affinity["conv-c"][0] == "w-other"
    assert gw._affinity_repointed == 2
    # Removal with no successor: evict.
    gw._affinity_drop_worker("w-other")
    assert "conv-c" not in gw._affinity
    assert gw._affinity_repointed == 2


def test_peermanager_mark_draining_quarantines_routing():
    from crowdllama_tpu.core.resource import Resource
    from crowdllama_tpu.peermanager.manager import PeerManager

    pm = PeerManager(self_peer_id="self")
    r = Resource(worker_mode=True, peer_id="w1", supported_models=[MODEL],
                 tokens_throughput=10.0)
    r.touch()
    pm.add_or_update_peer(r)
    assert pm.find_best_worker(MODEL) is not None
    epoch = pm.routing_epoch
    assert pm.mark_draining("w1") is True
    assert pm.routing_epoch == epoch + 1          # snapshot invalidated
    assert pm.find_best_worker(MODEL) is None       # quarantined
    assert pm.is_routable("w1", MODEL) is None
    assert pm.mark_draining("w1") is False          # idempotent
    assert pm.mark_draining("missing") is False


# ----------------------------------------------------- fake-engine topology


class _SlowEngine(FakeEngine):
    """Word-paced echo engine: slow enough that an HTTP POST /drain lands
    while the stream is verifiably in flight."""

    async def generate(self, prompt, **kw):  # type: ignore[override]
        async for chunk in super().generate(prompt, **kw):
            yield chunk
            if not chunk.done:
                await asyncio.sleep(0.05)


def _cfg(bootstrap, **kw):
    cfg = Configuration(
        listen_host="127.0.0.1",
        bootstrap_peers=[bootstrap],
        intervals=Intervals.default(),
    )
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


async def _wait_for(cond, timeout=30.0, interval=0.1, what="condition"):
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while loop.time() < deadline:
        if cond():
            return
        await asyncio.sleep(interval)
    raise AssertionError(f"timed out waiting for {what}")


def _ndjson_lines(raw: str) -> list[dict]:
    return [json.loads(line) for line in raw.splitlines() if line.strip()]


def _content(lines: list[dict]) -> str:
    return "".join(l.get("message", {}).get("content", "") for l in lines)


async def _topology(engine_factory, n_workers=2, obs=False, cfg_kw=None,
                    **gw_kwargs):
    boot_host, _ = await new_host_and_dht(
        Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    bootstrap = f"127.0.0.1:{boot_host.listen_port}"
    cfg_kw = cfg_kw or {}

    engines = [engine_factory(_cfg(bootstrap, **cfg_kw))
               for _ in range(n_workers)]
    for e in engines:
        await e.start()
    workers = [Peer(Ed25519PrivateKey.generate(), _cfg(bootstrap, **cfg_kw),
                    engine=e, worker_mode=True) for e in engines]
    for w in workers:
        await w.start()
    obs_servers = []
    if obs:
        for w in workers:
            srv = ObsServer(w, port=0)
            await srv.start()
            obs_servers.append(srv)
    consumer = Peer(Ed25519PrivateKey.generate(), _cfg(bootstrap, **cfg_kw),
                    engine=FakeEngine(models=[]), worker_mode=False)
    await consumer.start()
    gateway = Gateway(consumer, port=0, host="127.0.0.1", **gw_kwargs)
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
        for srv in obs_servers:
            await srv.stop()
        for w in workers:
            try:
                await w.stop()
            except Exception:
                pass
        for e in engines:
            await e.stop()
        await boot_host.close()

    return workers, engines, obs_servers, consumer, gateway, gw_port, teardown


def _chat_body(content, stream=True, **options):
    return {"model": MODEL, "stream": stream,
            "messages": [{"role": "user", "content": content}],
            "options": options}


@pytest.mark.chaos
async def test_http_drain_midstream_migrates_fake_engines():
    """Acceptance: POST /drain on the serving worker of a 2-worker swarm
    mid-stream — the client's stream completes byte-identically on the
    successor, the draining worker leaves the routing snapshot, and a
    follow-up request still lands 200."""
    workers, engines, obs_servers, consumer, gateway, gw_port, teardown = \
        await _topology(lambda cfg: _SlowEngine(models=[MODEL]), obs=True)
    try:
        url = f"http://127.0.0.1:{gw_port}/api/chat"
        content = ("drain me gracefully please, one word at a time, "
                   "so the handoff has a stream to move")
        async with aiohttp.ClientSession() as s:
            # Baseline from a fault-free run (echo engines are identical).
            async with s.post(url, json=_chat_body(content)) as resp:
                assert resp.status == 200
                base_text = _content(_ndjson_lines(await resp.text()))

            drain_reply = {}
            buf = b""
            lines: list[dict] = []
            async with s.post(url, json=_chat_body(content)) as resp:
                assert resp.status == 200
                drained = False
                async for chunk in resp.content.iter_any():
                    buf += chunk
                    while b"\n" in buf:
                        raw, buf = buf.split(b"\n", 1)
                        if raw.strip():
                            lines.append(json.loads(raw))
                    if len(lines) >= 2 and not drained:
                        drained = True
                        # Find the serving worker and drain it over HTTP.
                        idx = next(i for i, e in enumerate(engines)
                                   if e._active > 0)
                        async with s.post(
                                f"http://127.0.0.1:{obs_servers[idx].port}"
                                f"/drain") as dresp:
                            assert dresp.status == 200
                            drain_reply = await dresp.json()
            assert drained, "stream finished before /drain could land"
            assert drain_reply["draining"] is True
            assert drain_reply["migrated_streams"] == 1

            # One uninterrupted, byte-identical stream.
            assert lines[-1]["done"] is True
            assert lines[-1].get("done_reason") == "stop"
            assert _content(lines) == base_text

            drained_peer = workers[idx]
            other = workers[1 - idx]
            # Gateway counted the migration and quarantined the worker.
            assert gateway.obs.metrics.migrated_streams == 1
            assert consumer.peer_manager.is_routable(
                drained_peer.peer_id, MODEL) is None
            best = consumer.peer_manager.find_best_worker(MODEL)
            assert best is not None and best.peer_id == other.peer_id

            # Draining worker rejects NEW requests with the typed frame,
            # so a fresh request still lands 200 on the survivor.
            async with s.post(url, json=_chat_body(content,
                                                   stream=False)) as resp:
                assert resp.status == 200
                d = await resp.json()
            assert d["worker_id"] == other.peer_id
            assert workers[idx].obs.metrics.drain["initiated"] == 1

            # /drain is idempotent.
            async with s.post(f"http://127.0.0.1:{obs_servers[idx].port}"
                              f"/drain") as dresp:
                d2 = await dresp.json()
            assert d2["already_draining"] is True
            assert d2["migrated_streams"] == 0

            # The migrate span landed under the gateway root.
            traces = gateway.obs.trace.snapshot()["traces"]
            spans = [sp for t in traces for sp in t["spans"]
                     if sp["name"] == "migrate"]
            assert len(spans) == 1
            assert spans[0]["meta"]["from_worker"] == \
                drained_peer.peer_id[:8]

            # Exposition surfaces: gateway counts the migrated stream, the
            # drained worker its initiated drain + migrated slot.
            async with s.get(
                    f"http://127.0.0.1:{gw_port}/metrics") as resp:
                gw_text = await resp.text()
            assert "crowdllama_migrated_streams_total 1" in gw_text
            async with s.get(f"http://127.0.0.1:{obs_servers[idx].port}"
                             f"/metrics") as resp:
                wk_text = await resp.text()
            assert 'crowdllama_drain_initiated_total 1' in wk_text
    finally:
        await teardown()


# ------------------------------------------------- real-engine KV handoff


# Byte-level tokenizer: ~1 token per char.  Flattened chat adds ~18
# tokens of role tags; keep content + 32 decode tokens under the 256
# context while still spanning many 16-token pages.
LONG_CONTENT = (
    "Live migration moves an in-flight stream to a successor without "
    "redoing prefill: the drained worker stays up as a KV donor and "
    "the successor imports the paged prefix instead of recomputing it.")


@pytest.mark.chaos
async def test_drain_midstream_kv_handoff_end_to_end():
    """Acceptance: a drain landing mid-stream (the 'drain' chaos action —
    the exact code path SIGTERM / POST /drain take) on 1 of 2 REAL engines
    migrates the stream with fetch-instead-of-recompute: byte-identical
    output, kv pages imported on the successor, and
    replayed_prefill_tokens == 0 for the migrated stream.  Tail section:
    a deadline budget expiring mid-KV-fetch still yields the standard 504
    contract (satellite: budget coverage across kv-ship)."""
    from crowdllama_tpu.engine.engine import JaxEngine

    kv_cfg = dict(model=MODEL, kv_layout="paged", kv_page_size=16,
                  kv_ship=True, kv_ship_min_tokens=16, kv_ship_timeout=2.0)
    workers, engines, _obs, consumer, gateway, gw_port, teardown = \
        await _topology(
            lambda cfg: JaxEngine(cfg, max_context_length=256, warmup=False),
            cfg_kw=kv_cfg, kv_ship=True)
    try:
        by_id = {w.peer_id: (w, e) for w, e in zip(workers, engines)}
        url = f"http://127.0.0.1:{gw_port}/api/chat"
        body = _chat_body(LONG_CONTENT, num_predict=32)
        # Drain lands on the FIRST streamed chunk: the scheduler still has
        # ~31 decode steps ahead of it, so the migrate safe point is
        # reached with the request verifiably in flight.
        plan = FaultPlan(seed=11, rules=[
            FaultRule(site="engine.stream_chunk", action="drain",
                      after=1, times=1)])
        async with aiohttp.ClientSession() as s:
            with faults.installed(plan):
                async with s.post(url, json=body) as resp:
                    assert resp.status == 200
                    lines = _ndjson_lines(await resp.text())
            assert plan.log and plan.log[0][2] == "drain"
            donor_id = plan.log[0][1]["worker"]
            donor_peer, donor_eng = by_id[donor_id]
            succ_id = next(p for p in by_id if p != donor_id)
            succ_peer, succ_eng = by_id[succ_id]

            # The stream completed cleanly on the successor...
            assert lines[-1]["done"] is True
            assert lines[-1].get("done_reason") in ("stop", "length")
            assert lines[-1]["worker_id"] == succ_id
            migrated_text = _content(lines)
            assert migrated_text

            # ...and byte-identically: a post-drain rerun of the same
            # request (same weights, greedy decode) is the reference.
            async with s.post(url, json=body) as resp:
                assert resp.status == 200
                reference = _content(_ndjson_lines(await resp.text()))
            assert migrated_text == reference

            # Fetch-instead-of-recompute: the successor imported the
            # donor's pages and counted ZERO replayed prefill tokens.
            assert succ_eng._runner.kv_pages_imported > 0
            assert donor_eng._runner.kv_pages_exported > 0
            assert succ_eng.obs.metrics.replayed_prefill_tokens == 0
            assert succ_eng.obs.metrics.kv_ship["fetches"] == 1

            # Worker-side drain accounting + gateway-side migration.
            assert donor_peer.obs.metrics.drain["initiated"] == 1
            assert donor_peer.obs.metrics.drain["migrated_slots"] >= 1
            assert gateway.obs.metrics.migrated_streams == 1
            assert consumer.peer_manager.is_routable(donor_id, MODEL) is None

            # --------- budget expiring MID-KV-FETCH: standard 504 contract
            gateway._kv_donor_for = lambda akey, model, chosen: donor_id
            slow = FaultPlan(rules=[
                FaultRule(site="kv.serve", action="delay", delay_s=3.0,
                          match={"worker": donor_id}, times=0)])
            budget_body = {
                "model": MODEL, "stream": False,
                "messages": [
                    {"role": "user", "content": "fetch the pages for this "
                     "brand new prompt nobody has cached yet, via a donor "
                     "whose serve path is artificially slow"},
                    {"role": "assistant", "content": "understood"},
                    {"role": "user", "content": "decode now"}],
                "options": {"num_predict": 8}}
            t0 = time.monotonic()
            with faults.installed(slow):
                async with s.post(url, json=budget_body,
                                  headers={"X-Request-Timeout": "1"}) as resp:
                    assert resp.status == 504
                    d = await resp.json()
            elapsed = time.monotonic() - t0
            assert slow.log, "kv.serve delay never fired"
            assert elapsed < 2.5, f"504 took {elapsed:.1f}s on a 1s budget"
            assert "deadline exceeded" in d["error"]
    finally:
        await teardown()


# A prompt LONGER than the budget-shrunk ragged admission chunk
# (byte-level tokenizer: ~1 token per char), so the worker takes the
# unified ragged chunked-prefill path and a drain can land with the
# prompt half-built inside tiny-test's 256-token context.
RAGGED_CONTENT = (
    "A drain landing mid-chunked-prefill must not forfeit the work: the "
    "donor keeps every completed page in its prefix index and the "
    "successor resumes chunking from where the donor stopped.")


@pytest.mark.chaos
async def test_drain_mid_chunked_prefill_resumes_on_successor():
    """Acceptance (ISSUE 9): a drain landing MID-CHUNKED-PREFILL (the
    "scheduler.ragged_chunk" chaos site) migrates the request before a
    single token streamed — the MigrateFrame carries the prompt's chain
    hashes, the successor fetches the pages the donor already computed
    and resumes chunking the tail, and replayed_prefill_tokens counts
    ONLY the unshipped tail (0 < replayed < prompt)."""
    from crowdllama_tpu.engine.engine import JaxEngine

    # step_token_budget 48 on 16-token pages → 32-token ragged chunks;
    # decode_chunk 1 → 32 prompt tokens per dispatch, so the ~200-token
    # prompt needs ~7 dispatches and the after=1 drain rule fires with
    # most of the prompt still unbuilt.
    # mesh 1x1: the byte-identity contract between the cold ragged path and
    # the prefix-hit ctx-prefill rerun is per device program.  On the auto
    # tp=2 mesh of the eight virtual devices, jax 0.9.0's XLA:CPU lands the
    # layer output of the two GSPMD-partitioned programs one bf16 ulp apart
    # (layer-1 K of the suffix tokens differs by 2^-6; identical at tp=1),
    # and random weights turn that into a different greedy stream.
    kv_cfg = dict(model=MODEL, kv_layout="paged", kv_page_size=16,
                  kv_ship=True, kv_ship_min_tokens=16, kv_ship_timeout=2.0,
                  step_token_budget=48, decode_chunk=1, mesh_shape="1x1")
    workers, engines, _obs, consumer, gateway, gw_port, teardown = \
        await _topology(
            lambda cfg: JaxEngine(cfg, max_context_length=256,
                                  warmup=False),
            cfg_kw=kv_cfg, kv_ship=True)
    try:
        by_id = {w.peer_id: (w, e) for w, e in zip(workers, engines)}
        url = f"http://127.0.0.1:{gw_port}/api/chat"
        body = _chat_body(RAGGED_CONTENT, num_predict=16)
        # The delay rule (listed first so the drain's raise cannot skip
        # its pass counts) parks the scheduler loop between the next two
        # chunk dispatches, guaranteeing the drain task reaches its
        # migrate safe point while the job is still mid-prefill.
        plan = FaultPlan(seed=13, rules=[
            FaultRule(site="scheduler.ragged_chunk", action="delay",
                      delay_s=0.3, after=2, times=2),
            FaultRule(site="scheduler.ragged_chunk", action="drain",
                      after=1, times=1)])
        async with aiohttp.ClientSession() as s:
            with faults.installed(plan):
                async with s.post(url, json=body) as resp:
                    assert resp.status == 200
                    lines = _ndjson_lines(await resp.text())
            # The drain fired mid-prefill: some tokens built, most not.
            assert plan.log and plan.log[0][2] == "drain"
            attrs = plan.log[0][1]
            assert 0 < attrs["done"] < attrs["total"], attrs

            donor_id = next(w.peer_id for w in workers
                            if w.obs.metrics.drain["initiated"])
            donor_peer, donor_eng = by_id[donor_id]
            succ_id = next(p for p in by_id if p != donor_id)
            succ_peer, succ_eng = by_id[succ_id]

            # The stream completed cleanly on the successor — no token had
            # streamed yet, so the client sees one uninterrupted stream.
            assert lines[-1]["done"] is True
            assert lines[-1].get("done_reason") in ("stop", "length")
            assert lines[-1]["worker_id"] == succ_id
            migrated_text = _content(lines)
            assert migrated_text

            # Partial handoff: pages moved donor → successor, and the
            # replay counter holds ONLY the unshipped tail — more than
            # zero (the drain interrupted the prefill) but strictly less
            # than the prompt (the shipped prefix was NOT recomputed).
            assert donor_eng._runner.kv_pages_exported > 0
            assert succ_eng._runner.kv_pages_imported > 0
            replayed = succ_eng.obs.metrics.replayed_prefill_tokens
            assert 0 < replayed < attrs["total"], (replayed, attrs)

            # Both sides chunked: the donor before the drain, the
            # successor resuming the tail (the unshipped remainder is
            # longer than one admission chunk, so it re-enters the ragged
            # path rather than the monolithic fallback).
            assert donor_eng.scheduler.ragged_chunks > 0
            assert succ_eng.scheduler.ragged_chunks > 0

            # Worker-side drain accounting + gateway-side migration.
            assert donor_peer.obs.metrics.drain["initiated"] == 1
            assert donor_peer.obs.metrics.drain["migrated_slots"] >= 1
            assert gateway.obs.metrics.migrated_streams == 1
            assert consumer.peer_manager.is_routable(donor_id, MODEL) is None

            # Byte-identity: a rerun of the same request (greedy, same
            # weights) on the surviving worker is the reference.
            async with s.post(url, json=body) as resp:
                assert resp.status == 200
                reference = _content(_ndjson_lines(await resp.text()))
            assert migrated_text == reference
    finally:
        await teardown()
