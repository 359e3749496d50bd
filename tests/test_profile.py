"""The tracing the worker carries (ISSUE 23): the profiler control on the
process that holds the device (engine start/stop, the ObsServer routes, the
IPC op), the scheduler's phases on the profiler's clock, request spans that
are a timeline, and the counters the ratios are made of."""

import asyncio
import json
import types
from pathlib import Path

import aiohttp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from crowdllama_tpu.config import Configuration, Intervals
from crowdllama_tpu.core.messages import create_generate_request
from crowdllama_tpu.engine.engine import MIN_TRACE_S, FakeEngine, JaxEngine
from crowdllama_tpu.ipc.server import IPCServer
from crowdllama_tpu.obs import NodeObs
from crowdllama_tpu.obs import trace as obs_trace
from crowdllama_tpu.obs.http import ObsServer
from crowdllama_tpu.obs.metrics import ENGINE_TELEMETRY


def _config(tmp_path=None, **kw) -> Configuration:
    return Configuration(
        model="tiny-test", max_context_length=128, max_batch_slots=2,
        warmup=False, kv_page_size=16, intervals=Intervals.default(),
        profile_dir=str(tmp_path / "traces") if tmp_path else "", **kw)


async def _generate(engine, prompt="profile me", max_tokens=24):
    async for _ in engine.generate(prompt, max_tokens=max_tokens):
        pass


def _host_events(trace_dir: str) -> dict[str, list[dict]]:
    """name -> the arguments of each event of that name on a host plane."""
    from jax.profiler import ProfileData

    files = list(Path(trace_dir).rglob("*.xplane.pb"))
    assert len(files) == 1, files
    out: dict[str, list[dict]] = {}
    for plane in ProfileData.from_file(str(files[0])).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(dict(e.stats))
    return out


async def test_capture_profile_writes_trace_with_the_schedulers_phases(
        tmp_path):
    engine = JaxEngine(_config(tmp_path))
    await engine.start()
    try:
        # a fixed window of live serving (the IPC op's form) writes a trace
        gen = asyncio.create_task(_generate(engine))
        trace_dir = await engine.capture_profile(seconds=0.2)
        await gen
        assert list(Path(trace_dir).rglob("*.xplane.pb"))
        # start; serve a request, let the loop park (and the trace grow
        # long enough to be collected), wake it with another (a phase is
        # recorded when it ends); stop
        await engine.profile_start()
        await _generate(engine)
        await asyncio.sleep(MIN_TRACE_S)
        await _generate(engine, max_tokens=4)
        trace_dir = (await engine.profile_stop())["artifact"]
        # host events on the profiler's clock, as host_tracer_level 2 (what
        # the control sets) records them: one per phase of a loop turn
        events = _host_events(trace_dir)
        assert {obs_trace.SCHED_ADMIT, obs_trace.SCHED_READBACK,
                obs_trace.SCHED_EMIT, obs_trace.SCHED_WAIT_FOR_WORK,
                f"{obs_trace.SCHED_DISPATCH}.prefill",
                f"{obs_trace.SCHED_DISPATCH}.insert",
                f"{obs_trace.SCHED_DISPATCH}.decode"} <= set(events), sorted(
                    n for n in events if n.startswith("sched"))
        # the dispatch class and the step count ride as arguments (a lone
        # request leaves slots free: one step a flight)
        assert {"dispatch": "plain", "steps": 1} in events[
            f"{obs_trace.SCHED_DISPATCH}.decode"]
        assert {"dispatch": "plain"} in events[obs_trace.SCHED_READBACK]
    finally:
        await engine.stop()


async def test_capture_profile_requires_config():
    engine = JaxEngine(_config())  # not started; capture checks config first
    with pytest.raises(RuntimeError, match="profiling disabled"):
        await engine.capture_profile()


async def test_ipc_profile_op(tmp_path):
    engine = JaxEngine(_config(tmp_path))
    await engine.start()
    sock = str(tmp_path / "ipc.sock")
    server = IPCServer(sock, engine)
    await server.start()
    try:
        reader, writer = await asyncio.open_unix_connection(sock)
        writer.write(json.dumps({"type": "profile", "seconds": 0.2}).encode() + b"\n")
        await writer.drain()
        reply = json.loads(await asyncio.wait_for(reader.readline(), 30))
        assert reply["type"] == "profile", reply
        assert Path(reply["trace_dir"]).exists()
        writer.close()
    finally:
        await server.stop()
        await engine.stop()


async def _post(session, port, action):
    async with session.post(
            f"http://127.0.0.1:{port}/debug/profile/{action}") as resp:
        return resp.status, await resp.json()


async def test_worker_http_control_starts_and_stops_one_trace(tmp_path):
    """POST /debug/profile/start|stop on the worker's ObsServer: stop
    answers with the written artifact and both clocks; single-flight."""
    engine = JaxEngine(_config(tmp_path))
    await engine.start()
    srv = ObsServer(types.SimpleNamespace(engine=engine, obs=NodeObs()),
                    port=0)
    await srv.start()
    try:
        async with aiohttp.ClientSession() as s:
            assert (await _post(s, srv.port, "stop"))[0] == 409  # none runs
            status, started = await _post(s, srv.port, "start")
            assert status == 200 and started["artifact"].startswith(
                str(tmp_path / "traces"))
            assert (await _post(s, srv.port, "start"))[0] == 409
            await _generate(engine, max_tokens=8)
            await asyncio.sleep(MIN_TRACE_S)
            status, done = await _post(s, srv.port, "stop")
            assert status == 200 and done["artifact"] == started["artifact"]
            # the XSpace alone, in TensorBoard's layout: no trace.json.gz,
            # whose conversion was most of a stop's time on the chip
            files = [f for f in Path(done["artifact"]).rglob("*") if f.is_file()]
            assert len(files) == 1 and files[0].name.endswith(".xplane.pb")
            assert files[0].parent.parent == Path(
                done["artifact"]) / "plugins" / "profile"
            assert files[0].stat().st_size == done["xspace_bytes"] > 0
            assert (done["started_monotonic"] < done["stopped_monotonic"]
                    <= done["collected_monotonic"]
                    <= done["written_monotonic"])
            assert 0 < done["stopped_unix"] - done["started_unix"] < 60
            # a second trace goes to a directory of its own; stopped at
            # once (under MIN_TRACE_S) it is dropped, not collected: the
            # directory stays empty, and a third trace is whole after it
            status, again = await _post(s, srv.port, "start")
            assert status == 200 and again["artifact"] != done["artifact"]
            status, short = await _post(s, srv.port, "stop")
            assert status == 200 and short["xspace_bytes"] == 0
            assert (short["stopped_monotonic"] - short["started_monotonic"]
                    < MIN_TRACE_S)
            assert list(Path(short["artifact"]).iterdir()) == []
            assert (await _post(s, srv.port, "start"))[0] == 200
            await asyncio.sleep(MIN_TRACE_S)
            status, third = await _post(s, srv.port, "stop")
            assert status == 200 and third["xspace_bytes"] > 0
            assert list(Path(third["artifact"]).rglob("*.xplane.pb"))
    finally:
        await srv.stop()
        await engine.stop()


@pytest.mark.parametrize("engine", ["no_profile_dir", "not_on_device"])
async def test_worker_http_control_is_501_where_nothing_can_be_traced(engine):
    eng = (JaxEngine(_config()) if engine == "no_profile_dir"
           else FakeEngine(models=["tiny-test"]))
    srv = ObsServer(types.SimpleNamespace(engine=eng, obs=NodeObs()), port=0)
    await srv.start()
    try:
        async with aiohttp.ClientSession() as s:
            for action in ("start", "stop"):
                status, body = await _post(s, srv.port, action)
                assert status == 501 and body["error"]
    finally:
        await srv.stop()


async def test_gateway_has_no_profile_endpoint():
    """The gateway maps no jaxlib; tracing is the worker's."""
    from crowdllama_tpu.gateway.gateway import Gateway
    from crowdllama_tpu.peer.peer import Peer
    from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

    peer = Peer(Ed25519PrivateKey.generate(),
                Configuration(listen_host="127.0.0.1",
                              intervals=Intervals.default()),
                engine=FakeEngine(models=[]), worker_mode=False)
    gateway = Gateway(peer, port=0, host="127.0.0.1")
    assert not hasattr(gateway, "profile_dir")
    async with TestClient(TestServer(gateway.app)) as client:
        for path in ("/debug/profile", "/debug/profile/start"):
            for method in ("GET", "POST"):
                resp = await client.request(method, path)
                assert resp.status in (404, 405), (method, path, resp.status)
        assert (await client.get("/debug/profile?seconds=1")).status == 404


async def _served_trace(engine, prompt: str, max_tokens: int = 12) -> dict:
    engine.obs = engine.obs or NodeObs(node="worker")
    msg = create_generate_request("tiny-test", prompt=prompt, stream=True,
                                  max_tokens=max_tokens)
    msg.trace_id = obs_trace.new_trace_id()

    async def serve() -> None:
        async for _ in engine.handle_streaming(msg, worker_id="w"):
            pass

    serving = asyncio.create_task(serve())
    while (first_seen := engine.obs.trace.get(msg.trace_id)) is None:
        await asyncio.sleep(0.001)
    await serving
    # the record was opened when the request arrived, not when it ended
    assert first_seen["done"] is False and not first_seen["spans"]
    return engine.obs.trace.get(msg.trace_id)


@pytest.mark.parametrize("admission", ["monolithic", "ragged", "chunked"])
async def test_worker_spans_are_a_timeline_and_prefill_splits(admission):
    """worker_queue → prefill → decode_step carry real start_us, in order;
    dispatch_wait + prefill_exec are prefill's children and fill it."""
    kw = {"monolithic": {},
          "ragged": {"step_token_budget": 10},
          "chunked": {"ragged_prefill": False}}[admission]
    engine = JaxEngine(_config(**kw))
    await engine.start()
    if admission == "chunked":
        engine._runner.prefill_chunk = 16    # the legacy chunked admission
    try:
        other = asyncio.create_task(_generate(engine, "keep the batch busy",
                                              max_tokens=40))
        await asyncio.sleep(0.3)
        prompt = "a b c" if admission == "monolithic" else "word " * 12
        tr = await _served_trace(engine, prompt)
        await other
    finally:
        await engine.stop()
    assert tr["done"] and tr["meta"]["node"] == "worker"
    spans = {s["name"]: s for s in tr["spans"]}
    order = ["worker_queue", "prefill", "decode_step"]
    starts = [spans[n]["start_us"] for n in order]
    assert starts == sorted(starts) and starts[0] > 0 and starts[2] > starts[0]
    for a, b in zip(order, order[1:]):
        assert spans[a]["start_us"] + spans[a]["dur_us"] == pytest.approx(
            spans[b]["start_us"], abs=5)
    wait, exe, pre = (spans[n] for n in ("dispatch_wait", "prefill_exec",
                                         "prefill"))
    assert wait["parent"] == exe["parent"] == "prefill"
    assert wait["start_us"] == pytest.approx(pre["start_us"], abs=5)
    assert exe["start_us"] == pytest.approx(
        wait["start_us"] + wait["dur_us"], abs=5)
    assert exe["dur_us"] > 0
    assert wait["dur_us"] + exe["dur_us"] <= pre["dur_us"] + 5
    assert wait["dur_us"] + exe["dur_us"] == pytest.approx(pre["dur_us"],
                                                           abs=5)
    assert tr["total_us"] >= starts[2] + spans["decode_step"]["dur_us"] - 5e3


def _series(text: str, name: str) -> float:
    return sum(float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
               if ln.startswith(name) and not ln.startswith("#"))


async def test_counters_move_as_the_request_mix_says():
    """Prefix reuse on a repeated prompt; one flight's worth of seconds
    and steps per retire; the start-up gauges set once."""
    engine = JaxEngine(_config())
    await engine.start()

    def scrape() -> str:
        return "\n".join(ENGINE_TELEMETRY.expose())

    def grew(before: str, after: str, name: str) -> float:
        return _series(after, name) - _series(before, name)

    try:
        t0 = scrape()
        for phase in ("weights", "ready"):
            assert _series(
                t0, f'crowdllama_startup_seconds{{phase="{phase}"}}') > 0
        assert 'crowdllama_startup_seconds{phase="warmup"} 0' in t0
        sched, runner = engine.scheduler, engine._runner
        prompt = "one two three four five six seven eight nine ten " * 2
        n = len(engine.tokenizer.encode(prompt))
        assert n > 2 * runner.page_size
        dispatches = sched.host_dispatches
        await _generate(engine, prompt, max_tokens=20)
        t1 = scrape()
        assert grew(t0, t1, "crowdllama_prompt_tokens_total") == n
        assert grew(t0, t1, "crowdllama_prefix_tokens_reused_total") == 0
        assert grew(t0, t1, "crowdllama_prefix_hits_total") == 0
        # every retired flight added its steps and its wall time once
        flights = sched.host_dispatches - dispatches
        assert flights >= 3
        steps = grew(t0, t1, "crowdllama_engine_flight_steps_total")
        # (the flight queued behind the one that ended the stream may
        # still be in the air)
        assert flights - 1 <= steps <= flights * sched.decode_chunk
        assert steps == grew(
            t0, t1,
            'crowdllama_engine_flight_steps_total{dispatch="plain"}')
        secs = grew(t0, t1, "crowdllama_engine_flight_seconds_total")
        assert 0 < secs < 60
        # one slot of two alive: each step one useful, one wasted token
        assert grew(t0, t1, "crowdllama_useful_tokens_total") >= steps
        # the same prompt again: its whole pages come from the cache
        await _generate(engine, prompt, max_tokens=4)
        t2 = scrape()
        reused = grew(t1, t2, "crowdllama_prefix_tokens_reused_total")
        assert grew(t1, t2, "crowdllama_prompt_tokens_total") == n
        assert grew(t1, t2, "crowdllama_prefix_hits_total") == 1
        assert reused == runner.prefix_tokens_reused > 0
        assert reused % runner.page_size == 0 and reused <= n
    finally:
        await engine.stop()
