"""Gray-failure immunity tests (docs/ROBUSTNESS.md, PR 18): the
per-stream progress watchdog turning silence into failover with a
``wedged`` quarantine, hedged first-token dispatch with exactly-once
delivery, and the scheduler's dispatch self-watchdog on a fake clock.

E2E scenarios run against the same REAL loopback swarm the chaos suite
uses (tests/test_chaos.py _topology); watchdog arithmetic is unit-tested
against an injected clock so thresholds are asserted exactly, not by
sleeping."""

import types

import aiohttp
import pytest

from crowdllama_tpu.engine.engine import FakeEngine
from crowdllama_tpu.engine.scheduler import (
    DONE,
    GenRequest,
    Scheduler,
    WedgedError,
)
from crowdllama_tpu.testing import faults
from crowdllama_tpu.testing.faults import FaultPlan, FaultRule
from tests.test_chaos import (
    _chat_body,
    _content,
    _ndjson_lines,
    _topology,
    _wait_for,
)

pytestmark = pytest.mark.chaos


# ------------------------------------------------- stall-stream watchdog


async def test_stall_mid_decode_fails_over_byte_identical_wedged():
    """Acceptance (ISSUE 18): a stream that STALLS mid-decode (transport
    open, no frames, no EOF — the gray failure kill_stream cannot model)
    is torn down by the progress watchdog, the stalled worker is
    quarantined under the new ``wedged`` reason, and the client receives
    the COMPLETE stream byte-identical to a fault-free run."""
    workers, consumer, gateway, gw_port, teardown = await _topology(
        2, stream_stall_ms=350)
    try:
        url = f"http://127.0.0.1:{gw_port}/api/chat"
        async with aiohttp.ClientSession() as s:
            # Fault-free baseline: the byte-identity reference.
            async with s.post(url, json=_chat_body()) as resp:
                assert resp.status == 200
                baseline = _ndjson_lines(await resp.text())
            base_text = _content(baseline)
            assert len(baseline) > 5, "prompt too short to stall mid-decode"

            plan = FaultPlan(seed=11, rules=[
                FaultRule(site="engine.stream_chunk",
                          action="stall_stream", after=3, times=1)])
            with faults.installed(plan):
                async with s.post(url, json=_chat_body()) as resp:
                    assert resp.status == 200
                    lines = _ndjson_lines(await resp.text())

        # The stall fired, and the client could not tell: complete,
        # clean, byte-identical stream.
        assert plan.log and plan.log[0][2] == "stall_stream"
        assert lines[-1]["done"] is True
        assert lines[-1].get("done_reason") == "stop"
        assert "error" not in lines[-1]
        assert _content(lines) == base_text
        assert gateway._robust["stalled_streams"] == 1
        assert gateway._robust["failovers"] == 1
        assert gateway._robust["wedge_quarantines"] == 1

        # The stalled worker is quarantined under the NEW reason — a
        # wedged worker still answers health probes, so the ordinary
        # probe plane would never have evicted it — and the stream was
        # finished by the OTHER worker.
        stalled = [p for p in consumer.peer_manager.peers.values()
                   if getattr(p.resource, "draining", False)]
        assert len(stalled) == 1
        assert stalled[0].resource.draining_reason == "wedged"
        assert lines[-1]["worker_id"] != stalled[0].peer_id

        # One "wedged" span under the gateway root names the phase...
        traces = gateway.obs.trace.snapshot()["traces"]
        spans = [sp for t in traces for sp in t["spans"]
                 if sp["name"] == "wedged"]
        assert len(spans) == 1
        assert spans[0]["parent"] == "gateway"
        assert spans[0]["meta"]["phase"] == "decode"
        # ...and the flight recorder captures the stitched trace with
        # the wedged reason (capture stitches asynchronously).
        await _wait_for(
            lambda: any("wedged" in e["reasons"]
                        for e in gateway.flight.snapshot()["traces"]),
            timeout=10.0, what="flight-recorder wedged capture")

        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{gw_port}/metrics") as resp:
                text = await resp.text()
        assert "crowdllama_stall_aborted_streams_total 1" in text
        assert "crowdllama_wedge_quarantines_total 1" in text
    finally:
        await teardown()


# ---------------------------------------------- hedged first-token race


async def test_hedge_race_original_wins_exactly_once():
    """Acceptance (ISSUE 18): with every worker's TTFT above the hedge
    threshold, the gateway launches a hedge; the ORIGINAL produces its
    first token first and wins — the client sees exactly one stream, the
    loser is cancelled before its first byte, and the conservation law
    hedge_launched == hedge_won + hedge_cancelled holds."""
    workers, consumer, gateway, gw_port, teardown = await _topology(
        2, engine_factory=lambda: FakeEngine(models=["tiny-test"],
                                             delay=1.0),
        hedge_ttft_ms=150)
    try:
        url = f"http://127.0.0.1:{gw_port}/api/chat"
        async with aiohttp.ClientSession() as s:
            async with s.post(url, json=_chat_body()) as resp:
                assert resp.status == 200
                lines = _ndjson_lines(await resp.text())

        # Exactly ONE complete stream reached the client: one terminal
        # frame, no interleaved duplicate of the hedged leg.
        assert [l["done"] for l in lines].count(True) == 1
        assert lines[-1]["done"] is True
        assert lines[-1]["done_reason"] == "stop"
        text = _content(lines)
        assert text.startswith("echo:")
        assert text.count("echo:") == 1

        r = gateway._robust
        assert r["hedge_launched"] == 1
        assert r["hedge_won"] == 0
        assert r["hedge_cancelled"] == 1
        assert r["hedge_launched"] == r["hedge_won"] + r["hedge_cancelled"]
        # No failover, no stall: the hedge plane is separate bookkeeping.
        assert r["failovers"] == 0 and r["stalled_streams"] == 0

        # The hedge span names both legs.
        traces = gateway.obs.trace.snapshot()["traces"]
        spans = [sp for t in traces for sp in t["spans"]
                 if sp["name"] == "hedge"]
        assert len(spans) == 1
        assert spans[0]["meta"]["primary"] != spans[0]["meta"]["hedge"]

        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{gw_port}/metrics") as resp:
                mtext = await resp.text()
        assert "crowdllama_hedge_launched_total 1" in mtext
        assert "crowdllama_hedge_won_total 0" in mtext
        assert "crowdllama_hedge_cancelled_total 1" in mtext
    finally:
        await teardown()


# ------------------------------------- scheduler dispatch self-watchdog


class _StubRunner:
    max_slots = 2
    max_seq = 128

    def init_state(self):
        return None


def _flight(dispatched_at: float, ragged: bool = False):
    """Host-side metadata of an in-flight chunk — exactly the fields
    Scheduler._flight_class inspects (the watchdog never touches the
    device, so a stand-in object is a faithful double)."""
    return types.SimpleNamespace(
        tokens_dev=types.SimpleNamespace(ndim=2),
        ragged_steps=2 if ragged else 0,
        dispatched_at=dispatched_at)


async def test_self_watchdog_threshold_arithmetic_on_fake_clock():
    """The wedge threshold is max(floor, multiplier × class EWMA), judged
    per dispatch class, and a class with no retired flight is NEVER
    judged (its first flight may legitimately be XLA compilation)."""
    now = [0.0]
    sched = Scheduler(_StubRunner(), wedge_multiplier=4.0,
                      clock=lambda: now[0])
    sched2 = Scheduler(_StubRunner(), wedge_multiplier=3.0,
                       clock=lambda: now[0])
    try:
        # No in-flight chunk: nothing to judge.
        assert sched.check_wedged() is False
        # In-flight but the class has no retired-flight history.
        sched._inflight = _flight(dispatched_at=0.0)
        now[0] = 1e6
        assert sched.check_wedged() is False
        # With history below the floor, the FLOOR is the threshold:
        # 4 × 0.5s = 2s, floored at wedge_floor_s = 5s.
        sched._flight_ewma["plain"] = 0.5
        assert sched.check_wedged(now=4.9) is False
        assert sched.check_wedged(now=5.1) is True
        assert sched.wedged is True
        assert sched.wedged_events == 1

        # A class whose EWMA puts the threshold ABOVE the floor is
        # judged against its own history: 3 × 10s = 30s.  A ragged
        # flight is judged as "ragged", not "plain".
        sched2._flight_ewma["ragged"] = 10.0
        sched2._flight_ewma["plain"] = 0.1
        sched2._inflight = _flight(dispatched_at=0.0, ragged=True)
        assert sched2.check_wedged(now=29.0) is False
        assert sched2.check_wedged(now=31.0) is True
    finally:
        await sched.stop()
        await sched2.stop()


async def test_self_watchdog_fails_requests_typed_and_drains_once():
    """A tripped watchdog fails every reachable request with the typed
    ``error: wedged`` reason (exactly one terminal each — the claim-or-
    skip contract), fires the self-drain callback EXACTLY once even
    across repeated probes, and short-circuits migrate() so a drain
    racing the wedge cannot hang on a safe point that will never run."""
    now = [0.0]
    sched = Scheduler(_StubRunner(), wedge_multiplier=2.0,
                      clock=lambda: now[0])
    fired = []
    sched.drain_requested_cb = lambda: fired.append(1)
    try:
        r1 = GenRequest(prompt_ids=[1, 2])
        r2 = GenRequest(prompt_ids=[3])
        await sched.submit(r1)
        await sched.submit(r2)
        sched._flight_ewma["plain"] = 1.0
        sched._inflight = _flight(dispatched_at=0.0)

        assert sched.check_wedged(now=6.0) is True

        # Both pending requests got EXACTLY one typed terminal.
        for r in (r1, r2):
            tok, reason = r.out.get_nowait()
            assert tok is DONE
            assert reason.startswith("error: wedged")
            assert "2x class EWMA" in reason
            assert r.out.qsize() == 0
            # Claim-or-skip: a later path cannot double-terminal it.
            assert r.finish("stop") is False
            assert r.out.qsize() == 0

        # Self-drain fired exactly once; repeated probes are idempotent.
        assert fired == [1]
        assert sched.check_wedged(now=100.0) is True
        assert fired == [1]
        assert sched.wedged_events == 1

        # migrate() must not wait on the stuck loop's safe point.
        assert await sched.migrate() == 0

        g = sched.telemetry_gauges()
        assert g["wedged"] == 1.0
        assert g["wedged_events_total"] == 1.0

        # The engine seam raises the TYPED error from this reason prefix
        # (engine/engine.py generate): a gateway distinguishes a wedge
        # from a generic engine failure without string-matching.
        assert issubclass(WedgedError, RuntimeError)
        err = WedgedError("wedged: plain flight stuck for 6.0s")
        assert str(err).startswith("wedged")
    finally:
        await sched.stop()


async def test_self_watchdog_off_by_default_and_submit_rejected_after():
    """wedge_multiplier=0 (the default) never judges a flight no matter
    how old; once wedged, _draining rejects new submissions so no new
    request can land on the dead engine."""
    sched = Scheduler(_StubRunner())  # watchdog off
    try:
        sched._flight_ewma["plain"] = 0.001
        sched._inflight = _flight(dispatched_at=0.0)
        assert sched.check_wedged(now=1e6) is False
    finally:
        await sched.stop()

    now = [0.0]
    sched2 = Scheduler(_StubRunner(), wedge_multiplier=2.0,
                       clock=lambda: now[0])
    try:
        sched2._flight_ewma["plain"] = 1.0
        sched2._inflight = _flight(dispatched_at=0.0)
        assert sched2.check_wedged(now=10.0) is True
        with pytest.raises(RuntimeError, match="draining"):
            await sched2.submit(GenRequest(prompt_ids=[1]))
    finally:
        await sched2.stop()
