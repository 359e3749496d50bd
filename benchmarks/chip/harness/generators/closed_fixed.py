"""Closed loop, fixed sizes: ``clients_per_slot`` x the configuration's
slots clients, each sending its next request the moment the last one
completed.  Every request has ``prompt_tokens`` unshared random words and
asks for ``output_tokens``.

The ramp is the warm-up replay: the clients start ``ramp_s`` before the
window, and the first request of each of the first ``slots`` clients asks
for a staggered share of ``output_tokens``, so that completions — and the
admissions that follow them — are spread over the decode steps as they are
in a worker that has been saturated for a while, not bunched as at a cold
start.
"""

from __future__ import annotations

from ..loadgen import Plan, Turn
from . import random_ids, rng_for


class _Client:
    def __init__(self, i: int, p: dict, ctx: dict, first_out: int,
                 check_turn: int | None) -> None:
        self.i, self.p, self.ctx = i, p, ctx
        self.first_out = first_out
        self.check_turn = check_turn
        self.rng = rng_for(ctx["seed"], 1, i)
        self.n = 0

    def next_turn(self, reply_ids):
        out = self.first_out if self.n == 0 else self.p["output_tokens"]
        check = self.n == self.check_turn
        turn = Turn(
            prompt_ids=random_ids(self.rng, self.p["prompt_tokens"],
                                  self.ctx["vocab_size"]),
            max_tokens=out, greedy=check, check=check,
            tag="ramp" if self.n == 0 else "")
        self.n += 1
        return turn


def plan(p: dict, ctx: dict) -> Plan:
    slots = ctx["slots"]
    clients = p["clients_per_slot"] * slots
    out = p["output_tokens"]
    # the reference checks the SECOND request of four seeded clients
    checked = set(rng_for(ctx["seed"], 2).sample(range(clients),
                                                 min(p["checked"], clients)))
    actors = []
    for i in range(clients):
        first = max(8, out * (i + 1) // slots) if i < slots else out
        actors.append(_Client(i, p, ctx, first, 1 if i in checked else None))
    ladder = [Turn(prompt_ids=random_ids(rng_for(ctx["seed"], 3),
                                         p["prompt_tokens"],
                                         ctx["vocab_size"]),
                   max_tokens=16, greedy=True, tag="ladder")]
    return Plan(ladder=ladder, actors=actors, ramp_s=p["ramp_s"],
                checked=len(checked))
