"""Full-topology integration test, the analog of the reference's
TestFullIntegration (/root/reference/test/integration_test.go): DHT bootstrap
node + worker peer (FakeEngine at the engine seam) + consumer peer + gateway,
all real sockets on loopback with compressed intervals; drive through HTTP
and validate the Ollama-shaped reply."""

import asyncio
import json

import aiohttp
from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

from crowdllama_tpu.config import Configuration, Intervals
from crowdllama_tpu.engine.engine import FakeEngine
from crowdllama_tpu.gateway.gateway import Gateway
from crowdllama_tpu.net.discovery import new_host_and_dht
from crowdllama_tpu.peer.peer import Peer


def _cfg(bootstrap, **kw):
    cfg = Configuration(
        listen_host="127.0.0.1",
        bootstrap_peers=[bootstrap],
        intervals=Intervals.default(),  # test mode: compressed
    )
    for k, v in kw.items():
        setattr(cfg, k, v)
    return cfg


async def _wait_for(cond, timeout=20.0, interval=0.1, what="condition"):
    """Poll-with-deadline, the reference's synchronization style
    (integration_test.go:421-488)."""
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while loop.time() < deadline:
        if cond():
            return
        await asyncio.sleep(interval)
    raise AssertionError(f"timed out waiting for {what}")


async def _topology():
    boot_host, boot_dht = await new_host_and_dht(
        Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    bootstrap = f"127.0.0.1:{boot_host.listen_port}"

    worker = Peer(Ed25519PrivateKey.generate(), _cfg(bootstrap),
                  engine=FakeEngine(models=["tiny-test"]), worker_mode=True)
    await worker.start()

    consumer = Peer(Ed25519PrivateKey.generate(), _cfg(bootstrap),
                    engine=FakeEngine(models=[]), worker_mode=False)
    await consumer.start()

    gateway = Gateway(consumer, port=0, host="127.0.0.1")
    await gateway.start()
    gw_port = gateway._runner.addresses[0][1]

    async def teardown():
        await gateway.stop()
        await consumer.stop()
        await worker.stop()
        await boot_host.close()

    return worker, consumer, gateway, gw_port, teardown


async def test_full_integration_chat():
    worker, consumer, gateway, gw_port, teardown = await _topology()
    try:
        # Mutual discovery: consumer's manager must see the worker as healthy.
        await _wait_for(
            lambda: any(
                p.peer_id == worker.peer_id
                for p in consumer.peer_manager.get_healthy_peers()
            ),
            what="consumer discovering worker",
        )

        base = f"http://127.0.0.1:{gw_port}"
        async with aiohttp.ClientSession() as s:
            # Non-streaming chat (the reference's only mode).
            body = {"model": "tiny-test",
                    "messages": [{"role": "user", "content": "hello swarm"}]}
            async with s.post(f"{base}/api/chat", json=body) as resp:
                assert resp.status == 200, await resp.text()
                d = await resp.json()
            assert d["model"] == "tiny-test"
            assert d["done"] is True
            assert d["message"]["role"] == "assistant"
            assert "hello swarm" in d["message"]["content"]
            assert d["worker_id"] == worker.peer_id
            assert d["total_duration"] >= 0

            # Streaming chat (NDJSON superset).
            body["stream"] = True
            async with s.post(f"{base}/api/chat", json=body) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith("application/x-ndjson")
                lines = [json.loads(l) for l in (await resp.text()).splitlines()]
            assert lines[-1]["done"] is True
            assert all(not l["done"] for l in lines[:-1])
            text = "".join(l["message"]["content"] for l in lines)
            assert "hello swarm" in text

            # /api/generate
            async with s.post(f"{base}/api/generate",
                              json={"model": "tiny-test", "prompt": "ping"}) as resp:
                assert resp.status == 200
                d = await resp.json()
            assert "ping" in d["response"]

            # /api/health shows the worker with TPU-era fields
            async with s.get(f"{base}/api/health") as resp:
                h = await resp.json()
            assert h["status"] == "ok"
            assert worker.peer_id in h["workers"]
            w = h["workers"][worker.peer_id]
            assert w["is_healthy"] is True
            assert w["supported_models"] == ["tiny-test"]

            # /api/tags lists the model
            async with s.get(f"{base}/api/tags") as resp:
                tags = await resp.json()
            assert any(m["name"] == "tiny-test" for m in tags["models"])

            # Unknown model -> 503 with error body
            async with s.post(f"{base}/api/chat", json={
                "model": "nope", "messages": [{"role": "user", "content": "x"}]
            }) as resp:
                assert resp.status == 503

            # Malformed bodies -> 400
            async with s.post(f"{base}/api/chat", data=b"{not json") as resp:
                assert resp.status == 400
            async with s.post(f"{base}/api/chat", json={"model": "m"}) as resp:
                assert resp.status == 400
    finally:
        await teardown()


async def test_worker_death_detected():
    worker, consumer, gateway, gw_port, teardown = await _topology()
    try:
        await _wait_for(
            lambda: any(
                p.peer_id == worker.peer_id
                for p in consumer.peer_manager.get_healthy_peers()
            ),
            what="consumer discovering worker",
        )
        wid = worker.peer_id
        await worker.stop()
        # Health machine (3 strikes / stale eviction) must drop the worker.
        await _wait_for(
            lambda: not any(
                p.peer_id == wid for p in consumer.peer_manager.get_healthy_peers()
            ),
            timeout=40.0,
            what="worker eviction after death",
        )
        # Routing now fails cleanly.
        async with aiohttp.ClientSession() as s:
            async with s.post(f"http://127.0.0.1:{gw_port}/api/chat", json={
                "model": "tiny-test",
                "messages": [{"role": "user", "content": "x"}],
            }) as resp:
                assert resp.status == 503
    finally:
        await teardown()


async def test_ollama_surface_endpoints():
    """/api/version, /api/show, /api/ps complete the Ollama client surface."""
    worker, consumer, gateway, gw_port, teardown = await _topology()
    try:
        await _wait_for(
            lambda: any(
                p.peer_id == worker.peer_id
                for p in consumer.peer_manager.get_healthy_peers()
            ),
            what="consumer discovering worker",
        )
        base = f"http://127.0.0.1:{gw_port}"
        async with aiohttp.ClientSession() as s:
            async with s.get(f"{base}/api/version") as resp:
                assert resp.status == 200
                assert (await resp.json())["version"]

            async with s.get(f"{base}/api/ps") as resp:
                ps = await resp.json()
            assert any(m["model"] == "tiny-test" and m["workers"] == 1
                       for m in ps["models"])

            # Registry model: full details.
            async with s.post(f"{base}/api/show",
                              json={"model": "tiny-test"}) as resp:
                assert resp.status == 200
                d = await resp.json()
            assert d["details"]["family"] == "llama"
            assert d["model_info"]["general.parameter_count"] > 0
            assert worker.peer_id in d["workers_serving"]

            # Unknown model -> 404.
            async with s.post(f"{base}/api/show",
                              json={"model": "nope"}) as resp:
                assert resp.status == 404
    finally:
        await teardown()


async def test_openai_compat_surface():
    """The /v1 OpenAI-compatible endpoints (Ollama serves the same
    aliases): chat completions (non-stream + SSE stream), legacy
    completions, model list, embeddings — stock openai clients work."""
    worker, consumer, gateway, gw_port, teardown = await _topology()
    try:
        await _wait_for(
            lambda: any(p.peer_id == worker.peer_id
                        for p in consumer.peer_manager.get_healthy_peers()),
            what="consumer discovering worker")
        base = f"http://127.0.0.1:{gw_port}"
        async with aiohttp.ClientSession() as s:
            # Non-streaming chat completion.
            body = {"model": "tiny-test",
                    "messages": [{"role": "user", "content": "hello v1"}]}
            async with s.post(f"{base}/v1/chat/completions",
                              json=body) as resp:
                assert resp.status == 200, await resp.text()
                d = await resp.json()
            assert d["object"] == "chat.completion"
            assert d["id"].startswith("chatcmpl-")
            ch = d["choices"][0]
            assert ch["message"]["role"] == "assistant"
            assert "hello v1" in ch["message"]["content"]
            assert ch["finish_reason"] in ("stop", "length")
            assert d["usage"]["total_tokens"] == (
                d["usage"]["prompt_tokens"] + d["usage"]["completion_tokens"])

            # Streaming chat completion (SSE + [DONE] terminator).
            body["stream"] = True
            async with s.post(f"{base}/v1/chat/completions",
                              json=body) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith(
                    "text/event-stream")
                raw = await resp.text()
            events = [line[len("data: "):] for line in raw.splitlines()
                      if line.startswith("data: ")]
            assert events[-1] == "[DONE]"
            chunks = [json.loads(e) for e in events[:-1]]
            assert all(c["object"] == "chat.completion.chunk"
                       for c in chunks)
            assert len({c["id"] for c in chunks}) == 1  # stable id
            # First-chunk contract: role arrives on the opening delta.
            assert chunks[0]["choices"][0]["delta"]["role"] == "assistant"
            text = "".join(c["choices"][0]["delta"].get("content", "")
                           for c in chunks)
            assert "hello v1" in text
            assert chunks[-1]["choices"][0]["finish_reason"] in (
                "stop", "length")

            # Content-parts messages (framework-emitted shape) and null
            # params must work, not 500.
            async with s.post(f"{base}/v1/chat/completions", json={
                "model": "tiny-test", "temperature": None,
                "messages": [{"role": "user", "content": [
                    {"type": "text", "text": "parts "},
                    {"type": "text", "text": "work"}]}]}) as resp:
                assert resp.status == 200, await resp.text()
                d = await resp.json()
            assert "parts work" in d["choices"][0]["message"]["content"]

            # Wrong-typed params: OpenAI-shaped 400, not an aiohttp 500.
            async with s.post(f"{base}/v1/chat/completions", json={
                "model": "tiny-test", "n": "two",
                "messages": [{"role": "user",
                              "content": "x"}]}) as resp:
                assert resp.status == 400
                assert (await resp.json())["error"]["type"] == (
                    "invalid_request_error")

            # Legacy completions.
            async with s.post(f"{base}/v1/completions",
                              json={"model": "tiny-test",
                                    "prompt": "ping"}) as resp:
                assert resp.status == 200
                d = await resp.json()
            assert d["object"] == "text_completion"
            assert "ping" in d["choices"][0]["text"]

            # Model list.
            async with s.get(f"{base}/v1/models") as resp:
                assert resp.status == 200
                d = await resp.json()
            assert d["object"] == "list"
            assert any(m["id"] == "tiny-test" for m in d["data"])

            # Embeddings.
            async with s.post(f"{base}/v1/embeddings",
                              json={"model": "tiny-test",
                                    "input": ["a", "b"]}) as resp:
                assert resp.status == 200
                d = await resp.json()
            assert d["object"] == "list" and len(d["data"]) == 2
            assert d["data"][1]["index"] == 1
            assert isinstance(d["data"][0]["embedding"], list)

            # OpenAI-shaped errors.
            async with s.post(f"{base}/v1/chat/completions",
                              json={"model": "no-such",
                                    "messages": [
                                        {"role": "user",
                                         "content": "x"}]}) as resp:
                assert resp.status == 503
                d = await resp.json()
            assert d["error"]["type"] == "server_error"
            async with s.post(f"{base}/v1/chat/completions",
                              json={"model": "tiny-test", "n": 2,
                                    "messages": [
                                        {"role": "user",
                                         "content": "x"}]}) as resp:
                assert resp.status == 400
    finally:
        await teardown()


async def test_seeded_generation_reproducible_through_gateway():
    """Request ``seed`` is honored end-to-end (VERDICT r2 missing #5):
    identical seeded SAMPLED requests through the full HTTP → gateway →
    stream → JaxEngine path return identical text; a different seed
    diverges.  The reference inherits this from Ollama's seed option;
    proto/llama_v1.proto carries the field, gateway.py:379 parses it, and
    the scheduler folds it into the slot's private sampling stream."""
    from crowdllama_tpu.engine.engine import JaxEngine

    boot_host, _ = await new_host_and_dht(
        Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    bootstrap = f"127.0.0.1:{boot_host.listen_port}"

    engine = JaxEngine(_cfg(bootstrap, model="tiny-test"),
                       max_context_length=256, warmup=False)
    await engine.start()
    worker = Peer(Ed25519PrivateKey.generate(),
                  _cfg(bootstrap, model="tiny-test"),
                  engine=engine, worker_mode=True)
    await worker.start()
    consumer = Peer(Ed25519PrivateKey.generate(), _cfg(bootstrap),
                    engine=FakeEngine(models=[]), worker_mode=False)
    await consumer.start()
    gateway = Gateway(consumer, port=0, host="127.0.0.1")
    await gateway.start()
    gw_port = gateway._runner.addresses[0][1]

    try:
        await _wait_for(
            lambda: consumer.peer_manager.find_best_worker("tiny-test")
            is not None,
            what="consumer discovering JaxEngine worker",
        )

        async def ask(seed):
            body = {
                "model": "tiny-test", "stream": False,
                "options": {"temperature": 1.0, "num_predict": 12,
                            "seed": seed},
                "messages": [{"role": "user", "content": "tell me a story"}],
            }
            async with aiohttp.ClientSession() as s:
                async with s.post(f"http://127.0.0.1:{gw_port}/api/chat",
                                  json=body) as resp:
                    assert resp.status == 200, await resp.text()
                    d = await resp.json()
                    return d["message"]["content"]

        a = await ask(1234)
        b = await ask(1234)
        c = await ask(99)
        assert a == b, f"same seed diverged: {a!r} vs {b!r}"
        # Random-init tiny model at temperature 1.0: different seeds
        # agreeing on all 12 tokens would be astronomically unlikely.
        assert a != c, "different seeds produced identical output"
    finally:
        await gateway.stop()
        await consumer.stop()
        await worker.stop()
        await engine.stop()
        await boot_host.close()


async def test_metrics_endpoint():
    """GET /metrics: Prometheus text exposition with request counters and
    swarm worker gauges (no reference counterpart — SURVEY §5 notes the
    reference has no metrics endpoint)."""
    worker, consumer, gateway, gw_port, teardown = await _topology()
    try:
        await _wait_for(
            lambda: any(p.peer_id == worker.peer_id
                        for p in consumer.peer_manager.get_healthy_peers()),
            what="discovery",
        )
        async with aiohttp.ClientSession() as s:
            body = {"model": "tiny-test", "stream": False,
                    "messages": [{"role": "user", "content": "hi"}]}
            async with s.post(f"http://127.0.0.1:{gw_port}/api/chat",
                              json=body) as resp:
                assert resp.status == 200
            # One STREAMED request feeds the time-to-first-frame histogram.
            async with s.post(f"http://127.0.0.1:{gw_port}/api/chat",
                              json={**body, "stream": True}) as resp:
                assert resp.status == 200
                await resp.read()
            async with s.get(f"http://127.0.0.1:{gw_port}/metrics") as resp:
                assert resp.status == 200
                text = await resp.text()
        assert ('crowdllama_gateway_requests_total{path="/api/chat",'
                'status="200"} 2') in text
        assert "crowdllama_workers_healthy 1" in text
        assert "crowdllama_worker_load{" in text
        assert "crowdllama_gateway_request_seconds_total{" in text
        assert "crowdllama_gateway_ttfb_seconds_count 1" in text
        assert 'crowdllama_gateway_ttfb_seconds_bucket{le="+Inf"} 1' in text
        # Round-5 series: stream-pool reuse, affinity, per-path host
        # counters, and the rejected counter split out of streams_total.
        assert "crowdllama_gateway_stream_pool_hits_total" in text
        assert "crowdllama_gateway_stream_pool_misses_total" in text
        assert "crowdllama_gateway_affinity_hits_total" in text
        assert "crowdllama_host_rejected_total" in text
        assert 'crowdllama_host_streams_total{kind="rejected"}' not in text
    finally:
        await teardown()


async def test_gateway_options_stop_parsed():
    """options.stop reaches the worker through the REAL gateway parse
    path, in both Ollama spellings (string and list): FakeEngine echoes
    the prompt, so a stop sequence drawn from the prompt truncates the
    echo."""
    worker, consumer, gateway, gw_port, teardown = await _topology()
    try:
        await _wait_for(
            lambda: any(p.peer_id == worker.peer_id
                        for p in consumer.peer_manager.get_healthy_peers()),
            what="discovery",
        )
        async with aiohttp.ClientSession() as s:
            for stop_val in ("wor", ["wor"]):
                body = {"model": "tiny-test", "stream": False,
                        "options": {"stop": stop_val},
                        "messages": [{"role": "user",
                                      "content": "hello world"}]}
                async with s.post(f"http://127.0.0.1:{gw_port}/api/chat",
                                  json=body) as resp:
                    assert resp.status == 200, await resp.text()
                    d = await resp.json()
                # The chat flattens to "user: hello world\nassistant:";
                # the echo must truncate just before "wor".
                full = "echo: user: hello world\nassistant:"
                assert d["message"]["content"] == full[:full.find("wor")]
                assert d["done_reason"] == "stop"
    finally:
        await teardown()


async def test_pooled_inference_stream_reuse_and_stale_redial():
    """Sequential chats reuse ONE pooled inference stream (no per-request
    handshake), and a stale pooled entry (worker closed it) is detected
    and redialed transparently instead of failing the request."""
    worker, consumer, gateway, gw_port, teardown = await _topology()
    try:
        await _wait_for(
            lambda: consumer.peer_manager.find_best_worker("tiny-test")
            is not None, what="worker discovery")
        from crowdllama_tpu.core.protocol import INFERENCE_PROTOCOL

        url = f"http://127.0.0.1:{gw_port}/api/chat"
        body = {"model": "tiny-test",
                "messages": [{"role": "user", "content": "hi"}]}

        def inference_streams_in() -> int:
            # Worker-side inbound count for the inference protocol only:
            # host-wide streams_out on the consumer would race with its
            # background control-plane dials.
            return worker.host.stats_by_protocol.get(INFERENCE_PROTOCOL, 0)

        async with aiohttp.ClientSession() as s:
            async with s.post(url, json=body) as resp:
                assert resp.status == 200
            in0 = inference_streams_in()
            hits0 = gateway._stream_pool.hits
            for _ in range(3):
                async with s.post(url, json=body) as resp:
                    assert resp.status == 200
            assert gateway._stream_pool.hits - hits0 == 3
            assert inference_streams_in() == in0, (
                "pooled requests must not open new inference streams")

            # Stale-redial path: feed EOF into the pooled streams' READER
            # side so the pool's is_closing() pre-check still passes, the
            # write succeeds, and the subsequent read fails — exactly the
            # worker-went-away shape the redial branch exists for (a
            # local transport abort would be caught by the pre-check and
            # never exercise it).  pause_reading first: the worker's
            # reply to the stale write would otherwise hit asyncio's
            # feed_data-after-feed_eof assertion on the live transport.
            severed = []
            for pool in list(gateway._stream_pool._pools.values()):
                for st, _ts in pool:
                    st.writer._w.transport.pause_reading()
                    st.reader._r.feed_eof()
                    severed.append(st)
            async with s.post(url, json=body) as resp:
                assert resp.status == 200
                d = await resp.json()
                assert d["done"] is True
            assert inference_streams_in() > in0, (
                "the stale roundtrip must have redialed a fresh stream")
            for st in severed:
                st.writer._w.transport.abort()
    finally:
        await teardown()


async def test_prefix_affinity_routes_conversation_to_same_worker():
    """Multi-turn conversations (same leading message, growing tail) must
    land on ONE worker so its prefix cache pays; a dead affinity worker
    falls back to scoring."""
    boot_host, _ = await new_host_and_dht(
        Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    bootstrap = f"127.0.0.1:{boot_host.listen_port}"
    workers = [Peer(Ed25519PrivateKey.generate(), _cfg(bootstrap),
                    engine=FakeEngine(models=["tiny-test"]),
                    worker_mode=True) for _ in range(2)]
    for w in workers:
        await w.start()
    consumer = Peer(Ed25519PrivateKey.generate(), _cfg(bootstrap),
                    engine=FakeEngine(models=[]), worker_mode=False)
    await consumer.start()
    gateway = Gateway(consumer, port=0, host="127.0.0.1")
    await gateway.start()
    gw_port = gateway._runner.addresses[0][1]
    try:
        await _wait_for(
            lambda: len({p.peer_id for p in
                         consumer.peer_manager.get_healthy_peers()
                         if p.is_worker}) == 2,
            what="both workers discovered")

        def body(turn: int) -> dict:
            msgs = [{"role": "system", "content": "You are a helpful bot."}]
            for t in range(turn + 1):
                msgs.append({"role": "user", "content": f"question {t}"})
            return {"model": "tiny-test", "messages": msgs, "stream": False}

        async with aiohttp.ClientSession() as s:
            url = f"http://127.0.0.1:{gw_port}/api/chat"
            hit: list[str] = []
            for turn in range(6):
                async with s.post(url, json=body(turn)) as resp:
                    assert resp.status == 200
                    hit.append((await resp.json())["worker_id"])
            assert len(set(hit)) == 1, (
                f"conversation turns scattered across workers: {hit}")
            assert gateway._affinity_hits >= 5

            # The affinity worker dies: the conversation fails over.
            dead = hit[0]
            for w in workers:
                if w.peer_id == dead:
                    await w.stop()
            await _wait_for(
                lambda: all(p.peer_id != dead for p in
                            consumer.peer_manager.get_healthy_peers()),
                timeout=40.0, what="dead worker evicted")
            async with s.post(url, json=body(6)) as resp:
                assert resp.status == 200
                assert (await resp.json())["worker_id"] != dead
    finally:
        await gateway.stop()
        await consumer.stop()
        for w in workers:
            try:
                await w.stop()
            except Exception:
                pass
        await boot_host.close()


async def test_trace_propagates_across_two_worker_swarm():
    """Tentpole acceptance: a routed request through a 2-worker swarm
    shows up with the SAME trace id in the gateway's and the serving
    worker's /debug/trace, worker spans are children of the gateway root
    span, and the gateway's phase spans account for the request wall
    clock to within 20%."""
    from crowdllama_tpu.obs.http import ObsServer

    boot_host, _ = await new_host_and_dht(
        Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    bootstrap = f"127.0.0.1:{boot_host.listen_port}"

    # delay makes engine compute dominate HTTP/loopback overhead, so the
    # io_wait span (which envelopes the worker's work) carries the wall
    # clock and the 20% bound is insensitive to scheduler jitter.
    workers, obs_servers = [], []
    for _ in range(2):
        w = Peer(Ed25519PrivateKey.generate(), _cfg(bootstrap),
                 engine=FakeEngine(models=["tiny-test"], delay=0.25),
                 worker_mode=True)
        await w.start()
        workers.append(w)
        srv = ObsServer(w, port=0)
        await srv.start()
        obs_servers.append(srv)

    consumer = Peer(Ed25519PrivateKey.generate(), _cfg(bootstrap),
                    engine=FakeEngine(models=[]), worker_mode=False)
    await consumer.start()
    gateway = Gateway(consumer, port=0, host="127.0.0.1", trace_buffer=16)
    await gateway.start()
    gw_port = gateway._runner.addresses[0][1]

    try:
        await _wait_for(
            lambda: len(consumer.peer_manager.get_workers()) == 2,
            what="consumer discovering both workers")

        async with aiohttp.ClientSession() as s:
            body = {"model": "tiny-test", "stream": False,
                    "messages": [{"role": "user", "content": "trace me"}]}
            async with s.post(f"http://127.0.0.1:{gw_port}/api/chat",
                              json=body) as resp:
                assert resp.status == 200, await resp.text()
                served_by = (await resp.json())["worker_id"]

            async with s.get(
                    f"http://127.0.0.1:{gw_port}/debug/trace") as resp:
                assert resp.status == 200
                gw_dump = await resp.json()
        assert gw_dump["node"] == "gateway"
        assert gw_dump["capacity"] == 16
        gw_trace = gw_dump["traces"][-1]
        tid = gw_trace["trace_id"]
        assert len(tid) == 16 and gw_trace["done"]

        # The serving worker holds the same trace; the idle one does not.
        idx = next(i for i, w in enumerate(workers)
                   if w.peer_id == served_by)
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{obs_servers[idx].port}"
                             f"/debug/trace") as resp:
                assert resp.status == 200
                wk_dump = await resp.json()
        wk_trace = next((t for t in wk_dump["traces"]
                         if t["trace_id"] == tid), None)
        assert wk_trace is not None, (
            f"trace {tid} missing from serving worker's ring buffer")
        other = obs_servers[1 - idx].peer.obs.trace
        assert other.get(tid) is None, "idle worker recorded the trace"

        # Span catalogue + parentage.
        gw_spans = {sp["name"]: sp for sp in gw_trace["spans"]}
        assert {"route", "serde", "aead", "io_wait"} <= set(gw_spans)
        wk_spans = {sp["name"]: sp for sp in wk_trace["spans"]}
        assert {"worker_queue", "prefill", "decode_step",
                "stream_flush"} <= set(wk_spans)
        assert all(sp.get("parent") == "gateway"
                   for sp in wk_spans.values())

        # Phase accounting: gateway spans sum to the request wall clock
        # (trace total) within 20%; the worker's compute fits inside it.
        wall_us = gw_trace["total_us"]
        gw_sum = sum(sp["dur_us"] for sp in gw_trace["spans"])
        assert 0.8 * wall_us <= gw_sum <= 1.2 * wall_us, (
            f"gateway spans {gw_sum:.0f}us vs wall {wall_us:.0f}us")
        wk_sum = sum(sp["dur_us"] for sp in wk_trace["spans"])
        assert wk_sum <= 1.2 * wall_us, (
            f"worker spans {wk_sum:.0f}us exceed wall {wall_us:.0f}us")
    finally:
        await gateway.stop()
        await consumer.stop()
        for srv in obs_servers:
            await srv.stop()
        for w in workers:
            await w.stop()
        await boot_host.close()
