"""SLO burn-rate engine (PR 13 swarm observatory, docs/OBSERVABILITY.md).

Classic multi-window burn-rate tracking (the SRE-workbook shape) over the
gateway's two latency objectives:

- **TTFT** (``--slo-ttft-ms``): time from admission to the worker's first
  token frame — observed where the gateway's TTFB histogram is fed.
- **decode p95** (``--slo-decode-ms``): per decode-step gap on streamed
  responses — observed in the gateway's stream-forward loop.

Each observation is classified good/bad against the objective; the burn
rate over a window is ``bad_fraction / error_budget`` — 1.0 means the
budget is being spent exactly as provisioned, N means N× too fast.  Two
rolling windows (5m fast / 1h slow) catch both a sharp regression and a
slow leak; *fast burn* (both windows over the threshold, the
page-worthy condition) flips an edge-triggered episode flag the flight
recorder uses to auto-capture the requests that breached.

Pure host-side math over bucketed rolling counters — bounded memory, a
monotonic clock injected for unit tests, no JAX, no asyncio.
"""

from __future__ import annotations

import threading
import time

# Rolling windows: (label, seconds).  The short window is the fast-burn
# trigger; the long one confirms it is not a blip (SRE workbook's
# multiwindow, multi-burn-rate alert shape).
WINDOWS = (("5m", 300.0), ("1h", 3600.0))
# Counter bucketing: one (good, bad) cell per this many seconds — 10s
# cells keep the 1h window at 360 cells per objective.
BUCKET_S = 10.0
# Error budget: fraction of requests allowed to breach the objective.
# burn = bad_fraction / budget, so with 5% budget a 100%-bad outage burns
# at 20×.
DEFAULT_BUDGET = 0.05
# Both windows at/above this burn rate = fast burn (with a 5% budget this
# is ~70% of requests breaching — an incident, not noise).
FAST_BURN = 14.0


class BurnRateTracker:
    """Good/bad classification + multi-window burn rates for ONE
    objective.  Thread-safe: the gateway observes from request handlers
    while /metrics renders from another task."""

    def __init__(self, name: str, objective_ms: float,
                 budget: float = DEFAULT_BUDGET,
                 clock=time.monotonic) -> None:
        self.name = name
        self.objective_ms = float(objective_ms)
        self.budget = min(1.0, max(1e-6, float(budget)))
        self._clock = clock
        self._lock = threading.Lock()
        # Rolling cells: bucket start -> [good, bad], pruned past the
        # longest window on every observe.
        self._cells: dict[float, list[int]] = {}
        self.good_total = 0
        self.bad_total = 0

    def observe(self, seconds: float) -> bool:
        """Record one request; returns True when it breached."""
        bad = seconds * 1000.0 > self.objective_ms
        now = self._clock()
        bucket = now - (now % BUCKET_S)
        horizon = now - max(w for _, w in WINDOWS) - BUCKET_S
        with self._lock:
            cell = self._cells.setdefault(bucket, [0, 0])
            cell[1 if bad else 0] += 1
            if bad:
                self.bad_total += 1
            else:
                self.good_total += 1
            for b in [b for b in self._cells if b < horizon]:
                del self._cells[b]
        return bad

    def burn_rates(self) -> dict[str, float]:
        """{window label: burn rate} — 0.0 for an idle window."""
        now = self._clock()
        out: dict[str, float] = {}
        with self._lock:
            for label, span in WINDOWS:
                good = bad = 0
                for b, (g, n) in self._cells.items():
                    if b >= now - span:
                        good += g
                        bad += n
                total = good + bad
                out[label] = (bad / total / self.budget) if total else 0.0
        return out

    def in_fast_burn(self) -> bool:
        rates = self.burn_rates()
        return all(r >= FAST_BURN for r in rates.values())


class SloEngine:
    """The gateway's objectives + the edge-triggered fast-burn episode
    flag.  An objective set to 0 is disabled (no tracker, no gauges)."""

    def __init__(self, ttft_ms: float = 0.0, decode_ms: float = 0.0,
                 budget: float = DEFAULT_BUDGET,
                 clock=time.monotonic) -> None:
        self.trackers: dict[str, BurnRateTracker] = {}
        if ttft_ms > 0:
            self.trackers["ttft"] = BurnRateTracker(
                "ttft", ttft_ms, budget, clock)
        if decode_ms > 0:
            self.trackers["decode"] = BurnRateTracker(
                "decode", decode_ms, budget, clock)
        self._in_episode = False
        self.fast_burn_episodes_total = 0

    @property
    def enabled(self) -> bool:
        return bool(self.trackers)

    def observe_ttft(self, seconds: float) -> bool:
        t = self.trackers.get("ttft")
        return t.observe(seconds) if t is not None else False

    def observe_decode(self, seconds: float) -> bool:
        t = self.trackers.get("decode")
        return t.observe(seconds) if t is not None else False

    def fast_burn(self) -> bool:
        """Level signal: ANY enabled objective is fast-burning on both
        windows.  Also advances the edge-triggered episode counter."""
        burning = any(t.in_fast_burn() for t in self.trackers.values())
        if burning and not self._in_episode:
            self.fast_burn_episodes_total += 1
        self._in_episode = burning
        return burning

    def expose(self) -> list[str]:
        """``crowdllama_slo_*`` families for the gateway /metrics.  The
        burn-rate gauge is the series the PR 6 autoscaler's parse_gauges
        consumes (swarm/autoscale.py)."""
        if not self.enabled:
            return []
        lines = [
            "# TYPE crowdllama_slo_objective_ms gauge",
        ]
        for name, t in sorted(self.trackers.items()):
            lines.append(
                f'crowdllama_slo_objective_ms{{objective="{name}"}} '
                f"{t.objective_ms:g}")
        lines.append("# TYPE crowdllama_slo_requests_total counter")
        for name, t in sorted(self.trackers.items()):
            lines.append(
                f'crowdllama_slo_requests_total{{objective="{name}",'
                f'verdict="good"}} {t.good_total}')
            lines.append(
                f'crowdllama_slo_requests_total{{objective="{name}",'
                f'verdict="bad"}} {t.bad_total}')
        lines.append("# TYPE crowdllama_slo_burn_rate gauge")
        for name, t in sorted(self.trackers.items()):
            for label, rate in t.burn_rates().items():
                lines.append(
                    f'crowdllama_slo_burn_rate{{objective="{name}",'
                    f'window="{label}"}} {rate:.4f}')
        lines.append("# TYPE crowdllama_slo_fast_burn gauge")
        lines.append(
            f"crowdllama_slo_fast_burn {1 if self.fast_burn() else 0}")
        lines.append("# TYPE crowdllama_slo_fast_burn_episodes_total counter")
        lines.append(
            f"crowdllama_slo_fast_burn_episodes_total "
            f"{self.fast_burn_episodes_total}")
        return lines
