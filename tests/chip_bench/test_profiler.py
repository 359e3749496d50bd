"""The gauge sampler covers the traced seconds and no more (PR 26): on the
chip the profiler's ``stop`` answers ~40 s after it is asked, and a sampler
that ran until the answer read an idle worker for most of its samples."""

import asyncio
import time

import pytest

from harness import profiler


class SlowStop:
    """The worker's profiler control as the engine answers it: ``start``
    stamps ``started_monotonic``; ``stop`` stamps ``stopped_monotonic`` at
    once and answers only after ``write_s`` (the trace being written)."""

    def __init__(self, write_s: float) -> None:
        self.write_s, self.asked = write_s, []
        self.answer: dict = {}

    def __call__(self, action: str) -> dict:
        self.asked.append((action, time.monotonic()))
        if action == "start":
            self.answer = {"artifact": "/nowhere",
                           "started_monotonic": time.monotonic()}
            return dict(self.answer)
        self.answer["stopped_monotonic"] = time.monotonic()
        time.sleep(self.write_s)
        self.answer["written_monotonic"] = time.monotonic()
        return dict(self.answer)


@pytest.mark.parametrize("scrape_s", [0.0, 0.12])
def test_every_sample_is_inside_the_traced_interval_when_stop_is_slow(
        monkeypatch, scrape_s):
    monkeypatch.setattr(profiler, "PERIOD_S", 0.05)
    post, length = SlowStop(write_s=0.8), 0.4
    busy_until: list[float] = []

    async def scrape() -> str:
        await asyncio.sleep(scrape_s)
        # what the worker would say: busy while traced traffic runs, idle
        # once the tail of the traffic is over
        return "busy" if not busy_until or time.monotonic() < busy_until[0] \
            else "idle"

    async def go():
        t = time.monotonic()
        busy_until.append(t + length + 0.05)
        return await profiler.profile_for(post, length, scrape)

    t0 = time.monotonic()
    stopped, samples = asyncio.run(go())
    took = time.monotonic() - t0
    assert took >= length + 0.8                       # stop was slow
    a, b = stopped["started_monotonic"], stopped["stopped_monotonic"]
    assert b - a == pytest.approx(length, abs=0.1)    # asked on time
    assert samples and all(a <= asked <= answered <= b
                           for asked, answered, _ in samples)
    assert all(text == "busy" for _, _, text in samples)
    # as many as the traced seconds hold at this period, not as many as
    # the wait for the answer would
    per = 0.05 + scrape_s
    assert length / per - 2 <= len(samples) <= length / per + 1
    assert [x for x, _ in post.asked] == ["start", "stop"]


def test_no_sampler_no_samples_and_a_failing_scrape_is_not_swallowed():
    async def boom() -> str:
        raise OSError("the worker's /metrics is gone")

    stopped, samples = asyncio.run(
        profiler.profile_for(SlowStop(0.0), 0.0, None))
    assert samples == [] and "stopped_monotonic" in stopped
    with pytest.raises(OSError, match="is gone"):
        asyncio.run(profiler.profile_for(SlowStop(0.0), 0.2, boom))
