"""Peer manager unit tests mirroring the manager.go state machine:
scoring, health strikes/backoff, quarantine, stale cleanup, shard groups."""

import asyncio
import time

from crowdllama_tpu.config import Intervals
from crowdllama_tpu.core.resource import Resource, ShardGroup
from crowdllama_tpu.peermanager.manager import PeerHealthConfig, PeerManager


def _res(pid, models=("m",), tput=100.0, load=0.0, worker=True, sg=None):
    r = Resource(
        peer_id=pid, supported_models=list(models), tokens_throughput=tput,
        load=load, worker_mode=worker, shard_group=sg,
    )
    r.touch()
    return r


def _pm(**kw):
    return PeerManager(self_peer_id="self", config=PeerHealthConfig(Intervals()), **kw)


def test_find_best_worker_scoring():
    pm = _pm()
    pm.add_or_update_peer(_res("slow", tput=50, load=0.0))
    pm.add_or_update_peer(_res("fast-loaded", tput=200, load=1.0))   # 100
    pm.add_or_update_peer(_res("fast-idle", tput=150, load=0.1))     # ~136
    pm.add_or_update_peer(_res("wrong-model", models=("other",), tput=999))
    pm.add_or_update_peer(_res("consumer", worker=False, tput=999))
    best = pm.find_best_worker("m")
    assert best.peer_id == "fast-idle"
    assert pm.find_best_worker("missing") is None


def test_self_and_empty_ignored():
    pm = _pm()
    pm.add_or_update_peer(_res("self"))
    pm.add_or_update_peer(_res(""))
    assert pm.peers == {}


def test_health_three_strikes_and_recovery():
    fail = True

    async def fetch(pid):
        if fail:
            raise ConnectionError("down")
        return _res(pid)

    pm = _pm(metadata_fetcher=fetch)
    pm.add_or_update_peer(_res("w1"))
    info = pm.get_peer("w1")

    async def run():
        nonlocal fail
        for i in range(3):
            info.next_check_at = 0
            await pm.perform_health_checks()
        assert not info.is_healthy
        assert info.failed_attempts == 3
        assert "w1" in pm.skip_set()
        # recovery on a successful probe
        fail = False
        info.next_check_at = 0
        await pm.perform_health_checks()
        assert info.is_healthy and info.failed_attempts == 0

    asyncio.run(run())


def test_backoff_schedules_next_check():
    async def fetch(pid):
        raise ConnectionError("down")

    pm = _pm(metadata_fetcher=fetch)
    pm.add_or_update_peer(_res("w1"))
    info = pm.get_peer("w1")

    async def run():
        await pm.perform_health_checks()
        first = info.next_check_at
        assert first > time.monotonic()
        # not due yet → second round skips it
        await pm.perform_health_checks()
        assert info.failed_attempts == 1
        assert info.next_check_at == first

    asyncio.run(run())


def test_stale_cleanup_and_quarantine():
    iv = Intervals(stale_after=0.01, quarantine=0.05)
    pm = PeerManager(config=PeerHealthConfig(iv))
    pm.add_or_update_peer(_res("w1"))
    time.sleep(0.02)
    pm.perform_cleanup()
    assert pm.get_peer("w1") is None
    assert "w1" in pm.recently_removed
    # quarantined: stale metadata can't re-add... (fresh can)
    stale = _res("w1")
    stale.last_updated -= 7200
    pm.add_or_update_peer(stale)
    assert pm.get_peer("w1") is None
    fresh = _res("w1")
    pm.add_or_update_peer(fresh)
    assert pm.get_peer("w1") is not None
    # quarantine purges after its window
    pm.remove_peer("w1")
    time.sleep(0.06)
    pm.perform_cleanup()
    assert "w1" not in pm.recently_removed


def test_shard_group_routing():
    pm = _pm()
    # complete 2-shard EP group
    for i in range(2):
        pm.add_or_update_peer(_res(
            f"g1-{i}", models=("mix",), tput=100,
            sg=ShardGroup(group_id="g1", model="mix", strategy="ep",
                          shard_index=i, shard_count=2),
        ))
    # incomplete group
    pm.add_or_update_peer(_res(
        "g2-0", models=("mix",), tput=999,
        sg=ShardGroup(group_id="g2", model="mix", strategy="ep",
                      shard_index=0, shard_count=4),
    ))
    best = pm.find_best_worker("mix")
    assert best is not None and best.peer_id == "g1-0"  # leader of complete group
    members = pm.group_members("g1")
    assert [m.peer_id for m in members] == ["g1-0", "g1-1"]


def test_route_snapshot_epoch_invalidation():
    pm = _pm()
    pm.add_or_update_peer(_res("w1", tput=100))
    pm.add_or_update_peer(_res("w2", tput=50))
    assert pm.find_best_worker("m").peer_id == "w1"
    built = pm.route_snapshot_rebuilds
    for _ in range(20):
        pm.find_best_worker("m")
    assert pm.route_snapshot_rebuilds == built  # cached between events

    # A metadata update is a routing event: the next lookup rebuilds and
    # scores the fresh numbers.
    pm.add_or_update_peer(_res("w2", tput=500))
    assert pm.find_best_worker("m").peer_id == "w2"
    assert pm.route_snapshot_rebuilds == built + 1

    # So is a removal.
    pm.remove_peer("w2")
    assert pm.find_best_worker("m").peer_id == "w1"
    assert pm.route_snapshot_rebuilds == built + 2


def test_route_snapshot_stale_fallback_dead_worker():
    pm = _pm()
    pm.add_or_update_peer(_res("strong", tput=500))
    pm.add_or_update_peer(_res("weak", tput=100))
    assert pm.find_best_worker("m").peer_id == "strong"
    epoch = pm.routing_epoch
    # Best worker dies with NO routing event landed yet (the health loop
    # hasn't observed the flip): the genuinely-stale snapshot must skip it
    # via the live PeerInfo health flag instead of returning a dead pick.
    pm.get_peer("strong").is_healthy = False
    assert pm.routing_epoch == epoch
    assert pm.find_best_worker("m").peer_id == "weak"
    pm.get_peer("weak").is_healthy = False
    assert pm.find_best_worker("m") is None


def test_route_snapshot_no_unhealthy_rescan_at_scale():
    pm = _pm()
    for i in range(32):
        pm.add_or_update_peer(_res(f"w{i}", tput=100 + i))
    for i in range(0, 32, 2):  # half the swarm goes unhealthy
        pm.get_peer(f"w{i}").is_healthy = False
    pm._bump_routing_epoch()  # as health_check_peer would on the flip
    assert pm.find_best_worker("m").peer_id == "w31"
    built = pm.route_snapshot_rebuilds
    snap = pm._routing_snapshot("m")
    assert ({p.peer_id for p, _ in snap.entries}
            == {f"w{i}" for i in range(1, 32, 2)})
    for _ in range(200):
        assert pm.find_best_worker("m") is not None
    # Steady state: zero rebuilds across 200 requests — the hot path
    # touches only the precomputed eligible entries, never the unhealthy
    # half of the table.
    assert pm.route_snapshot_rebuilds == built


def test_discovery_applies_results():
    async def disc(skip):
        assert isinstance(skip, set)
        return [_res("found-1"), _res("found-2")]

    pm = _pm(discovery=disc)
    asyncio.run(pm.run_discovery_once())
    assert set(pm.peers) == {"found-1", "found-2"}


def _discovery_on_a_fake_clock(monkeypatch, disc, until, interval=10.0):
    """Run a manager's background loops with every ``asyncio.sleep`` taken
    for slept at once, until ``until()`` holds; the seconds the discovery
    loop's task asked to sleep, in order."""
    real_sleep = asyncio.sleep
    asked: list[float] = []

    async def sleep(seconds, *a):
        if asyncio.current_task().get_name() == "pm-discovery":
            asked.append(seconds)
        await real_sleep(0)

    async def run():
        pm = PeerManager(self_peer_id="self", discovery=disc,
                         config=PeerHealthConfig(Intervals(
                             discovery=interval, health_check=1e6,
                             cleanup=1e6)))
        monkeypatch.setattr(asyncio, "sleep", sleep)
        pm.start()
        try:
            for _ in range(10_000):
                if until(pm):
                    break
                await real_sleep(0)
            assert until(pm)
        finally:
            await pm.stop()

    asyncio.run(run())
    return asked


def test_empty_rounds_leave_discovery_at_its_one_cadence(monkeypatch):
    """However many rounds in a row find nobody, the next is
    ``intervals.discovery`` away (run_every's jitter aside), and every
    round asks with the skip set of that moment."""
    rounds = []

    async def disc(skip):
        rounds.append(set(skip))
        return []

    asked = _discovery_on_a_fake_clock(monkeypatch, disc,
                                       lambda pm: len(rounds) >= 40)
    first, *periods = asked
    assert 0.0 <= first <= 2.5            # the first tick's phase jitter
    assert len(periods) >= 39
    assert all(7.5 <= s <= 12.5 for s in periods), periods
    assert rounds == [set()] * len(rounds)


def test_a_worker_that_appears_after_a_long_idle_is_seen_in_one_interval(
        monkeypatch):
    """A gateway that has been alone for forty rounds sees a worker that
    joins within one discovery interval (and jitter) of its appearing, and
    from then on the worker is in the skip set: discovery asks for NEW
    providers only."""
    rounds = []
    appeared_at = []

    async def disc(skip):
        rounds.append(set(skip))
        if len(rounds) <= 40 or "w" in skip:
            return []
        return [_res("w")]

    def until(pm):
        if len(rounds) == 40 and not appeared_at:
            appeared_at.append(True)     # the worker comes up in this sleep
        return len(rounds) >= 44

    asked = _discovery_on_a_fake_clock(monkeypatch, disc, until)
    # round 41 is the first after the worker appeared: one sleep away
    assert rounds[40] == set() and 7.5 <= asked[40] <= 12.5
    assert all("w" in skip for skip in rounds[41:]), rounds[41:]
