"""Open loop at a fixed rate: ``rate_per_s`` x window requests, each its own
stream, sent when DUE whatever the system is doing.  Gaps between arrivals
are the quantiles of an exponential distribution (a Poisson process),
prompt and output lengths the quantiles of clipped lognormals, each set
shuffled by the traffic file's ``schedule_seed``; ``--seed`` draws the
prompts' words and the sampling seeds.  So every seed offers the same
requests at the same times: at some 60 requests a window the ORDER of
arrivals alone moved the 80th percentile of TTFT by half between seeds
(two long prompts back to back, or not), which would have buried any
change of the system (PERF.md, PR 22).

Warm-up replay: a ladder of single requests, one per prompt length in
``ladder_prompt_tokens`` (every prefill bucket the mix can hit; a lone long
prompt takes the 8-step ragged programs through their window widths), then
``ladder_bursts`` — a long prompt with short ones queued behind it, which
takes the 1-step ragged programs through theirs — then ``ramp_s`` of the
same arrival process before the window, so that the window opens on a
system in its steady state with every program compiled.

Where the run traces a tail after the window (``ctx["tail_s"]``), the tail
is a segment of its own, made the way the ramp is: the window's turns are
the same with and without it.
"""

from __future__ import annotations

from ..loadgen import Plan, Turn
from . import (
    exponential_quantiles,
    lognormal_quantiles,
    random_ids,
    rng_for,
    shuffled,
)


class _One:
    def __init__(self, turn: Turn) -> None:
        self.turn: Turn | None = turn

    def next_turn(self, reply_ids):
        turn, self.turn = self.turn, None
        return turn


def _arrivals(n: int, span: float, rng) -> list[float]:
    gaps = shuffled(exponential_quantiles(n, 1.0), rng)
    scale = span / sum(gaps)
    t, out = 0.0, []
    for g in gaps:
        t += g * scale
        out.append(t - g * scale / 2)
    return out


def _turns(p: dict, ctx: dict, n: int, span: float, offset: float,
           salt: int, tag: str) -> list[Turn]:
    rng = rng_for(p["schedule_seed"], salt)       # sizes, order, arrivals
    words = rng_for(ctx["seed"], salt, 1)         # what the prompts say
    pl, ol = p["prompt_tokens"], p["output_tokens"]
    prompts = shuffled(lognormal_quantiles(
        n, pl["median"], pl["sigma"], pl["min"], pl["max"]), rng)
    outs = shuffled(lognormal_quantiles(
        n, ol["median"], ol["sigma"], ol["min"], ol["max"]), rng)
    times = _arrivals(n, span, rng)
    return [Turn(prompt_ids=random_ids(words, prompts[i], ctx["vocab_size"]),
                 max_tokens=min(outs[i], ctx["context"] - prompts[i] - 1),
                 due=offset + times[i], tag=tag) for i in range(n)]


def plan(p: dict, ctx: dict) -> Plan:
    rate, seconds, ramp = p["rate_per_s"], ctx["seconds"], p["ramp_s"]
    window = _turns(p, ctx, max(1, round(rate * seconds)), seconds, 0.0,
                    1, "")
    # chosen before the run: the longest request and three seeded others
    rng = rng_for(p["schedule_seed"], 2)
    longest = max(range(len(window)), key=lambda i: (
        len(window[i].prompt_ids) + window[i].max_tokens))
    others = [i for i in range(len(window)) if i != longest]
    for i in [longest, *rng.sample(others, min(p["checked"] - 1,
                                               len(others)))]:
        window[i].greedy = window[i].check = True
    warm = _turns(p, ctx, max(1, round(rate * ramp)), ramp, -ramp, 3, "ramp")
    tail_s = ctx.get("tail_s", 0.0)
    tail = (_turns(p, ctx, max(1, round(rate * tail_s)), tail_s, seconds, 5,
                   "tail") if tail_s else [])
    lrng = rng_for(ctx["seed"], 4)

    def rung(n: int) -> Turn:
        return Turn(prompt_ids=random_ids(lrng, n, ctx["vocab_size"]),
                    max_tokens=16, greedy=True, tag="ladder")

    ladder = [rung(n) for n in p["ladder_prompt_tokens"]]
    ladder += [[rung(n) for n in burst] for burst in p["ladder_bursts"]]
    return Plan(ladder=ladder,
                actors=[_One(t) for t in warm + window + tail],
                ramp_s=ramp, checked=sum(t.check for t in window))
