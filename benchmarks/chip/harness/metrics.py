"""Metric arithmetic on the client's frame log (see loadgen.Record).

All times are offsets from the window's start, in seconds, on the load
generator's monotonic clock.  What an end-to-end metric measures:

* ``ttft``: for every request that was DUE (open loop) or sent (closed
  loop) inside the window, first token frame minus the due time — so a
  stall of the generator or of the system is charged to the requests that
  waited behind it.  The tail is the tail of all such requests.
* gaps: between consecutive output tokens of one stream, over every token
  after a stream's first that arrived inside the window.  A frame that
  carries k tokens gives one gap to the frame before and k-1 gaps of 0.
* ``out_tokens_per_s``: all output tokens that arrived inside the window,
  whichever request they belong to, over the window's length.
"""

from __future__ import annotations

import math

from .loadgen import Record


class TooFewSamples(ValueError):
    pass


def percentile(values: list[float], q: float, min_beyond: int = 10) -> float:
    """The q-quantile (0 < q < 1) by linear interpolation between order
    statistics.  A tail (q > 0.5) needs ``min_beyond`` samples beyond it —
    with fewer it is a maximum, not a percentile — and a median twice
    ``min_beyond`` samples; fewer is an error, never a number."""
    if not 0.0 < q < 1.0:
        raise ValueError(f"q={q} outside (0, 1)")
    n = len(values)
    beyond = n * (1.0 - max(q, 0.5))
    if beyond + 1e-9 < min_beyond:
        raise TooFewSamples(
            f"p{q * 100:g} over {n} samples leaves {beyond:.1f} beyond it "
            f"(need {min_beyond})")
    xs = sorted(values)
    pos = q * (n - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, n - 1)
    if math.isinf(xs[hi]):
        return xs[hi] if pos > lo else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def in_window(rec: Record, seconds: float) -> bool:
    """Was the request due (or, closed loop, sent) inside the window?"""
    start = rec.due if rec.due is not None else rec.sent
    return 0.0 <= start < seconds


def window_records(records: list[Record], seconds: float) -> list[Record]:
    return [r for r in records if in_window(r, seconds)]


def ttfts(records: list[Record], seconds: float) -> list[float]:
    """Seconds from due time to first token frame, one per window request;
    a request that never produced a token counts as infinitely late."""
    out = []
    for r in window_records(records, seconds):
        start = r.due if r.due is not None else r.sent
        out.append(r.frame_t[0] - start if r.frame_t else math.inf)
    return out


def lateness(records: list[Record], seconds: float) -> list[float]:
    """How late the generator sent each open-loop window request."""
    return [r.sent - r.due for r in window_records(records, seconds)
            if r.due is not None]


def token_times(rec: Record) -> list[float]:
    return [t for t, k in zip(rec.frame_t, rec.frame_tokens)
            for _ in range(k)]


def gaps(records: list[Record], seconds: float) -> list[float]:
    out = []
    for r in records:
        ts = token_times(r)
        out.extend(b - a for a, b in zip(ts, ts[1:]) if 0.0 <= b < seconds)
    return out


def tokens_in_window(records: list[Record], seconds: float) -> int:
    return sum(1 for r in records for t in token_times(r)
               if 0.0 <= t < seconds)


def frames_per_token(records: list[Record]) -> float:
    tokens = sum(sum(r.frame_tokens) for r in records)
    return sum(len(r.frame_t) for r in records) / tokens if tokens else 0.0


def one_frame_streams(records: list[Record]) -> list[Record]:
    """Streams of more than one token that arrived as ONE frame: the
    client could not have timed their tokens, so the run fails."""
    return [r for r in records
            if sum(r.frame_tokens) > 1 and len(r.frame_t) == 1]
