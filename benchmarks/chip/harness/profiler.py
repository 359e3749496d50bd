"""One traced stretch of the worker's chip, through the worker's own
control (``POST /debug/profile/start|stop``), with the worker's gauges
sampled over the traced seconds and no others.

The profiler's ``stop`` answers once the trace is written, which can take
many times the traced seconds (~40 s for 3 s of ``mistral7b.decode_sat``:
PERF.md, PR 25).  A sampler that ran until the answer read an idle worker
for most of its samples; this one is bound by the profiler's own clock:
from ``started_monotonic`` for ``length`` seconds, whatever ``stop`` takes.
"""

from __future__ import annotations

import asyncio
import time
from typing import Awaitable, Callable

PERIOD_S = 0.5      # 2 Hz, as the gauges were sampled before


async def profile_for(post: Callable[[str], dict], length: float,
                      scrape: Callable[[], Awaitable[str]] | None = None
                      ) -> tuple[dict, list[tuple[float, float, str]]]:
    """Trace for ``length`` seconds.  ``post(action)`` is the blocking call
    of the worker's control ("start" | "stop") and returns its answer;
    ``scrape()`` one reading of the worker's /metrics.

    Returns the answer to "stop" (artifact directory, host clocks) and the
    samples ``(asked, answered, text)`` on this machine's monotonic clock —
    the worker's is the same clock — every one of them asked and answered
    inside the traced interval."""
    loop = asyncio.get_running_loop()
    started = await loop.run_in_executor(None, post, "start")
    t0 = started["started_monotonic"]
    t_end = t0 + length
    samples: list[tuple[float, float, str]] = []

    async def sample() -> None:
        while time.monotonic() < t_end:
            asked = time.monotonic()
            text = await scrape()
            samples.append((asked, time.monotonic(), text))
            await asyncio.sleep(PERIOD_S)

    sampler = asyncio.create_task(sample()) if scrape and length > 0 else None
    ended: BaseException | None = None
    try:
        await asyncio.sleep(max(0.0, t_end - time.monotonic()))
        stopped = await loop.run_in_executor(None, post, "stop")
    finally:
        if sampler is not None:       # it has ended by itself, at t_end
            sampler.cancel()
            ended = (await asyncio.gather(sampler, return_exceptions=True))[0]
    if isinstance(ended, Exception):  # a scrape that failed, not the cancel
        raise ended
    inside = [s for s in samples
              if t0 <= s[0] and s[1] <= stopped["stopped_monotonic"]]
    return stopped, inside
