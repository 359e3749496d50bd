"""Swarm observatory (PR 13, docs/OBSERVABILITY.md): cluster metric
fan-in over a real 2-worker loopback swarm (partial snapshot when a
worker dies mid-scrape — never a 500), SLO burn-rate window math on a
fake clock, duty-cycle gauges under a real scheduler run, shed
requests landing in the flight recorder, and the `top` table renderer.
"""

import asyncio
import re

import aiohttp
import pytest
from crowdllama_tpu.utils.crypto_compat import Ed25519PrivateKey

from crowdllama_tpu.config import Configuration, Intervals
from crowdllama_tpu.engine.engine import FakeEngine
from crowdllama_tpu.gateway.gateway import Gateway
from crowdllama_tpu.net.discovery import new_host_and_dht
from crowdllama_tpu.obs.slo import (
    FAST_BURN,
    BurnRateTracker,
    SloEngine,
)
from crowdllama_tpu.peer.peer import Peer
from crowdllama_tpu.testing import faults
from crowdllama_tpu.testing.faults import FaultPlan, FaultRule


def _cfg(bootstrap):
    return Configuration(listen_host="127.0.0.1",
                         bootstrap_peers=[bootstrap],
                         intervals=Intervals.default())


async def _wait_for(cond, timeout=20.0, what="condition"):
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while loop.time() < deadline:
        if cond():
            return
        await asyncio.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}")


# ------------------------------------------------- SLO burn-rate math


class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def test_burn_rate_good_traffic_is_zero():
    clk = _Clock()
    t = BurnRateTracker("ttft", objective_ms=100.0, clock=clk)
    for _ in range(50):
        assert t.observe(0.05) is False  # 50ms < 100ms objective
        clk.t += 1.0
    assert t.burn_rates() == {"5m": 0.0, "1h": 0.0}
    assert not t.in_fast_burn()
    assert t.good_total == 50 and t.bad_total == 0


def test_burn_rate_is_bad_fraction_over_budget():
    clk = _Clock()
    t = BurnRateTracker("ttft", objective_ms=100.0, budget=0.05, clock=clk)
    for i in range(20):
        t.observe(0.5 if i < 2 else 0.05)  # 2 bad of 20
        clk.t += 1.0
    # bad_fraction 0.1 / budget 0.05 = 2x burn, identical on both
    # windows while everything fits inside the short one.
    rates = t.burn_rates()
    assert rates["5m"] == pytest.approx(2.0)
    assert rates["1h"] == pytest.approx(2.0)
    assert not t.in_fast_burn()  # 2x is a leak, not an incident


def test_burn_rate_windows_roll_independently():
    clk = _Clock()
    t = BurnRateTracker("ttft", objective_ms=100.0, clock=clk)
    for _ in range(10):
        t.observe(1.0)  # all bad
        clk.t += 1.0
    # Step past the short window: the 5m rate empties, the 1h window
    # still remembers the burst.
    clk.t += 301.0
    rates = t.burn_rates()
    assert rates["5m"] == 0.0
    assert rates["1h"] > 0.0
    # Step past the long window too (observe() prunes dead cells).
    clk.t += 3600.0
    t.observe(0.05)
    assert t.burn_rates() == {"5m": 0.0, "1h": pytest.approx(0.0)}
    assert len(t._cells) == 1  # the old burst's cells were pruned


def test_fast_burn_requires_both_windows():
    clk = _Clock()
    t = BurnRateTracker("ttft", objective_ms=100.0, budget=0.05, clock=clk)
    for _ in range(10):
        t.observe(1.0)  # 100% bad -> 20x burn on both windows
        clk.t += 1.0
    assert t.burn_rates()["5m"] >= FAST_BURN
    assert t.in_fast_burn()
    # The 5m window recovering ends the fast burn even though the 1h
    # window still carries the burst.
    clk.t += 301.0
    for _ in range(200):
        t.observe(0.05)
        clk.t += 1.0
    assert not t.in_fast_burn()


def test_slo_engine_edge_triggered_episodes():
    clk = _Clock()
    eng = SloEngine(ttft_ms=100.0, clock=clk)
    assert eng.enabled
    for _ in range(10):
        eng.observe_ttft(1.0)
        clk.t += 1.0
    assert eng.fast_burn() is True
    assert eng.fast_burn_episodes_total == 1
    assert eng.fast_burn() is True  # level stays up...
    assert eng.fast_burn_episodes_total == 1  # ...the edge counted once
    clk.t += 4000.0  # everything ages out of both windows
    eng.observe_ttft(0.05)
    assert eng.fast_burn() is False
    for _ in range(10):
        eng.observe_ttft(1.0)
        clk.t += 1.0
    eng.fast_burn()
    assert eng.fast_burn_episodes_total == 2  # second rising edge


def test_slo_engine_disabled_is_inert():
    eng = SloEngine()  # both objectives 0
    assert not eng.enabled
    assert eng.observe_ttft(99.0) is False
    assert eng.observe_decode(99.0) is False
    assert eng.expose() == []
    assert eng.fast_burn() is False


def test_autoscale_parses_worst_burn_rate():
    from crowdllama_tpu.swarm.autoscale import parse_gauges

    text = (
        'crowdllama_engine_pending_depth 4\n'
        'crowdllama_slo_burn_rate{objective="ttft",window="5m"} 15.5\n'
        'crowdllama_slo_burn_rate{objective="ttft",window="1h"} 2.25\n'
        'crowdllama_slo_burn_rate{objective="decode",window="5m"} 1.0\n')
    g = parse_gauges(text)
    assert g["slo_burn_rate"] == pytest.approx(15.5)
    assert g["pending_depth"] == 4.0
    # SLO plane off -> no key; the controller reads it with .get().
    assert "slo_burn_rate" not in parse_gauges(
        "crowdllama_engine_pending_depth 1\n")


# ------------------------------------------------- duty-cycle profiler


async def test_duty_cycle_gauges_under_plain_run():
    """A real scheduler run of plain flights, one step a dispatch or
    eight, moves ONLY the `plain` duty-cycle gauge, which stays in (0, 1],
    and the host-gap histogram collects samples of that class alone."""
    import jax
    import jax.numpy as jnp

    from crowdllama_tpu.engine.paged import PagedModelRunner
    from crowdllama_tpu.engine.scheduler import DONE, Scheduler
    from crowdllama_tpu.models import transformer as T
    from crowdllama_tpu.models.config import get_config
    from crowdllama_tpu.obs.metrics import DISPATCH_CLASSES, ENGINE_TELEMETRY

    cfg = get_config("tiny-test", max_context_length=256)
    params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    runner = PagedModelRunner(cfg, params=params, max_slots=2, max_seq=256,
                              page_size=32, mesh_spec="1")

    async def _run(decode_chunk):
        from crowdllama_tpu.engine.scheduler import GenRequest

        sched = Scheduler(runner, decode_chunk=decode_chunk)
        sched.start()
        try:
            reqs = [GenRequest(prompt_ids=[3, 1, 4], max_tokens=12, seed=7),
                    GenRequest(prompt_ids=[2, 7], max_tokens=9, seed=5)]
            for r in reqs:
                await sched.submit(r)
            for r in reqs:
                while True:
                    tok, _ = await asyncio.wait_for(r.out.get(), 120)
                    if tok is DONE:
                        break
            return sched.telemetry_gauges()
        finally:
            await sched.stop()

    def gaps():
        return {cls: ENGINE_TELEMETRY.host_gap_seconds.labels(cls).count
                for cls in DISPATCH_CLASSES}

    before = gaps()
    for g in (await _run(8), await _run(1)):
        # every class always present, and no other
        assert {k.partition("=")[2] for k in g
                if k.startswith("duty_cycle|")} == set(DISPATCH_CLASSES)
        assert 0.0 < g["duty_cycle|dispatch=plain"] <= 1.0
        assert g["duty_cycle|dispatch=ragged"] == 0.0
        assert g["duty_cycle|dispatch=spec"] == 0.0
    # The host-gap histogram collected samples of the one class that flew.
    after = gaps()
    assert after["plain"] > before["plain"]
    assert after["ragged"] == before["ragged"]
    assert after["spec"] == before["spec"]


@pytest.mark.parametrize("slots,length", [(4, "short"), (1, "full")],
                         ids=["half-empty", "saturated"])
async def test_flight_length_counter_follows_occupancy(slots, length):
    """crowdllama_engine_flights_total: a lone stream among free slots
    flies one step at a time and counts ``short``; the same stream on a
    worker it fills flies ``decode_chunk`` steps and counts ``full``."""
    from test_admission_pipeline import _drain
    from test_engine import _step_recorder

    from crowdllama_tpu.engine.scheduler import GenRequest, Scheduler
    from crowdllama_tpu.obs.metrics import ENGINE_TELEMETRY

    before = dict(ENGINE_TELEMETRY._flights)
    runner = _step_recorder(slots)
    sched = Scheduler(runner, decode_chunk=8)
    sched.start()
    try:
        req = GenRequest(prompt_ids=[11, 2], max_tokens=30, eos_id=-1)
        await sched.submit(req)
        await _drain(req)
    finally:
        await sched.stop()
    grew = {k: ENGINE_TELEMETRY._flights[k] - before[k] for k in before}
    other = "full" if length == "short" else "short"
    # (the flight queued behind the one that ended the stream may not have
    # been retired)
    assert grew[other] == 0 and grew[length] >= 3, grew
    assert runner.flights - 1 <= grew[length] <= runner.flights, grew
    assert (f'crowdllama_engine_flights_total{{length="{length}"}} '
            f'{ENGINE_TELEMETRY._flights[length]}'
            in ENGINE_TELEMETRY.expose())


def test_multi_engine_max_merges_duty_cycle():
    """Duty cycle is a ratio: MultiEngine must max-merge it across
    children, not sum it past 1.0."""
    from crowdllama_tpu.engine.multi import MultiEngine

    class _Child:
        def __init__(self, duty):
            self._g = {"pending_depth": 1.0,
                       "duty_cycle|dispatch=plain": duty}

        def obs_gauges(self):
            return dict(self._g)

    me = MultiEngine.__new__(MultiEngine)
    me._engines = {"a": _Child(0.9), "b": _Child(0.4)}
    g = me.obs_gauges()
    assert g["duty_cycle|dispatch=plain"] == pytest.approx(0.9)
    assert g["pending_depth"] == pytest.approx(2.0)  # depths still sum


# --------------------------------------------- cluster metric fan-in e2e


async def _swarm(n_workers=2, **gw_kw):
    boot_host, _ = await new_host_and_dht(
        Ed25519PrivateKey.generate(), listen_host="127.0.0.1")
    bootstrap = f"127.0.0.1:{boot_host.listen_port}"
    workers = [Peer(Ed25519PrivateKey.generate(), _cfg(bootstrap),
                    engine=FakeEngine(models=["tiny-test"]),
                    worker_mode=True)
               for _ in range(n_workers)]
    for w in workers:
        await w.start()
    consumer = Peer(Ed25519PrivateKey.generate(), _cfg(bootstrap),
                    engine=FakeEngine(models=[]), worker_mode=False)
    await consumer.start()
    gateway = Gateway(consumer, port=0, host="127.0.0.1", **gw_kw)
    await gateway.start()
    gw_port = gateway._runner.addresses[0][1]
    await _wait_for(
        lambda: len(consumer.peer_manager.get_workers()) == n_workers,
        what=f"{n_workers} workers discovered")
    return boot_host, workers, consumer, gateway, gw_port


async def _teardown(boot_host, workers, consumer, gateway):
    await gateway.stop()
    await consumer.stop()
    for w in workers:
        try:
            await w.stop()
        except Exception:
            pass
    await boot_host.close()


async def test_cluster_scrape_two_workers():
    """/metrics/cluster returns worker-labeled families for BOTH workers
    plus the swarm rollups, and the family filter narrows the payload."""
    boot_host, workers, consumer, gateway, gw_port = await _swarm()
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{gw_port}"
                             f"/metrics/cluster") as resp:
                assert resp.status == 200
                text = await resp.text()

        for w in workers:
            label = w.peer_id[:16]
            assert (f'crowdllama_engine_pending_depth{{worker="{label}"}}'
                    in text), f"no engine block for worker {label}"
            # The gateway's routing view joins on the same id head.
            assert f'crowdllama_worker_healthy{{peer="{label}"}} 1' in text
        assert "crowdllama_cluster_workers_total 2" in text
        assert "crowdllama_cluster_workers_scraped 2" in text
        assert re.search(r"crowdllama_cluster_tokens_per_second \S+", text)
        assert re.search(r"crowdllama_cluster_inflight \S+", text)
        # Worker histograms merged with exactly one TYPE per family.
        assert text.count(
            "# TYPE crowdllama_decode_step_seconds histogram") == 1
        assert " # {" not in text  # exemplars stripped from the merge

        # Family filter: only crowdllama_engine_* survives per worker.
        async with aiohttp.ClientSession() as s:
            async with s.get(
                    f"http://127.0.0.1:{gw_port}/metrics/cluster"
                    f"?family=crowdllama_engine_") as resp:
                assert resp.status == 200
                narrowed = await resp.text()
        assert 'crowdllama_engine_pending_depth{worker="' in narrowed
        assert 'crowdllama_request_seconds' not in narrowed
    finally:
        await _teardown(boot_host, workers, consumer, gateway)


async def test_cluster_scrape_partial_on_worker_death():
    """A worker dying mid-scrape (obs.scrape fault + a stopped peer)
    degrades /metrics/cluster to a partial snapshot — 200, the live
    worker's block intact, misses counted.  Never a 500."""
    boot_host, workers, consumer, gateway, gw_port = await _swarm()
    try:
        dead, alive = workers[0], workers[1]
        plan = FaultPlan(seed=7, rules=[
            FaultRule(site="obs.scrape", action="error",
                      match={"worker": dead.peer_id}, times=0),
        ])
        with faults.installed(plan):
            async with aiohttp.ClientSession() as s:
                async with s.get(f"http://127.0.0.1:{gw_port}"
                                 f"/metrics/cluster") as resp:
                    assert resp.status == 200
                    text = await resp.text()
        assert plan.log, "obs.scrape fault never fired"
        alive_label = alive.peer_id[:16]
        dead_label = dead.peer_id[:16]
        assert (f'crowdllama_engine_pending_depth{{worker="{alive_label}"}}'
                in text)
        assert (f'crowdllama_engine_pending_depth{{worker="{dead_label}"}}'
                not in text)
        assert "crowdllama_cluster_workers_scraped 1" in text
        assert re.search(
            r"crowdllama_cluster_scrape_misses_total [1-9]", text)

        # Harder death: the worker process is GONE (socket closed).  The
        # p2p fetch times out / errors; the surface still answers 200.
        await dead.stop()
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{gw_port}"
                             f"/metrics/cluster") as resp:
                assert resp.status == 200
                text = await resp.text()
        assert (f'crowdllama_engine_pending_depth{{worker="{alive_label}"}}'
                in text)
    finally:
        await _teardown(boot_host, workers, consumer, gateway)


async def test_shed_request_lands_in_flight_recorder():
    """A shed 503 mints a gateway-only trace and the flight recorder
    captures it with reason `shed` (ISSUE 13 satellite)."""
    boot_host, workers, consumer, gateway, gw_port = await _swarm(
        n_workers=1, admission_max_inflight=1)
    try:
        gateway._inflight = 1  # the cap is reached
        body = {"model": "tiny-test", "stream": False,
                "messages": [{"role": "user", "content": "shed me"}]}
        async with aiohttp.ClientSession() as s:
            async with s.post(f"http://127.0.0.1:{gw_port}/api/chat",
                              json=body) as resp:
                assert resp.status == 503
                assert "Retry-After" in resp.headers
        gateway._inflight = 0
        await _wait_for(
            lambda: any("shed" in t["reasons"]
                        for t in gateway.flight.snapshot()["traces"]),
            timeout=10.0, what="shed capture in the flight recorder")
        cap = [t for t in gateway.flight.snapshot()["traces"]
               if "shed" in t["reasons"]][0]
        names = {sp.get("name") for sp in cap["trace"].get("spans", [])}
        assert "shed" in names
    finally:
        await _teardown(boot_host, workers, consumer, gateway)


# --------------------------------------------------------- top renderer


def test_render_top_joins_routing_and_engine_views():
    from crowdllama_tpu.cli.main import render_top

    text = "\n".join([
        "# TYPE crowdllama_cluster_workers_total gauge",
        "crowdllama_cluster_workers_total 2",
        "crowdllama_cluster_workers_scraped 2",
        "crowdllama_cluster_tokens_per_second 123.5",
        "crowdllama_cluster_batch_occupancy 0.5",
        "crowdllama_cluster_kv_cache_utilization 0.25",
        "crowdllama_cluster_inflight 3",
        'crowdllama_worker_load{peer="aaaa"} 0.4',
        'crowdllama_worker_healthy{peer="aaaa"} 1',
        'crowdllama_worker_throughput_tokens_per_sec{peer="aaaa"} 100',
        'crowdllama_worker_healthy{peer="bbbb"} 0',
        'crowdllama_engine_batch_occupancy{worker="aaaa"} 0.75',
        'crowdllama_engine_pending_depth{worker="aaaa"} 2',
        'crowdllama_engine_duty_cycle{worker="aaaa",dispatch="ragged"}'
        ' 0.93',
        'crowdllama_engine_duty_cycle{worker="aaaa",dispatch="plain"} 0.1',
    ])
    out = render_top(text)
    lines = out.splitlines()
    assert "workers 2 (scraped 2)" in lines[0]
    assert "tok/s 123.5" in lines[0]
    row_a = next(ln for ln in lines if ln.startswith("aaaa"))
    assert " y " in row_a or row_a.split()[1] == "y"
    assert "0.93" in row_a  # max duty across classes
    assert "0.75" in row_a
    row_b = next(ln for ln in lines if ln.startswith("bbbb"))
    assert row_b.split()[1] == "n"


def test_render_top_empty_swarm():
    from crowdllama_tpu.cli.main import render_top

    out = render_top("crowdllama_cluster_workers_total 0\n")
    assert "(no workers visible)" in out
