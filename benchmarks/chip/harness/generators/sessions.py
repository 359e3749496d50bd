"""Closed loop with think time — conversations.  ``sessions`` seats each
hold one conversation at a time: a shared system prompt (one of
``system_prompts``, chosen Zipf(``zipf_s``)), then turns that each add
``user_tokens`` new words to everything said so far (the model's own
replies included) and ask for ``reply_tokens``; the user thinks for an
exponential time (mean ``think_mean_s``) between turns.  A conversation
ends after ``max_turns`` or when the next turn would not fit the context,
and a new seeded one takes its seat.

So that the window opens on a steady state, seat j's first conversation
starts ``j mod max_turns`` turns deep, with that much random history after
its system prompt.  Think times are the quantiles of the exponential.
Who takes which system prompt, the think times' order and which turns are
checked come from the traffic file's ``schedule_seed``; ``--seed`` draws the
words.  (With both from ``--seed`` the turns a window completed ranged from
143 to 162 and TTFT p80 from 541 to 619 ms: my chip runs, PR 22.)
"""

from __future__ import annotations

from ..loadgen import Plan, Turn
from . import exponential_quantiles, random_ids, rng_for, shuffled


def _zipf_weights(n: int, s: float) -> list[float]:
    w = [1.0 / (k + 1) ** s for k in range(n)]
    return [x / sum(w) for x in w]


class _Seat:
    def __init__(self, j: int, p: dict, ctx: dict, systems: list[list[int]],
                 first_system: int, check_turn: int | None) -> None:
        self.j, self.p, self.ctx, self.systems = j, p, ctx, systems
        self.rng = rng_for(ctx["seed"], 1, j)               # the words
        self.plan = rng_for(p["schedule_seed"], 1, j)       # who, when
        self.thinks = shuffled(
            exponential_quantiles(64, p["think_mean_s"]), self.plan)
        self.n = 0                      # turns taken in this seat
        self.check_turn = check_turn
        depth = j % p["max_turns"]
        per_turn = p["user_tokens"] + p["reply_tokens"]
        self.history = list(systems[first_system]) + random_ids(
            self.rng, per_turn * depth, ctx["vocab_size"])
        self.turns_left = p["max_turns"] - depth

    def _new_session(self) -> None:
        w = _zipf_weights(len(self.systems), self.p["zipf_s"])
        k = self.plan.choices(range(len(self.systems)), weights=w)[0]
        self.history = list(self.systems[k])
        self.turns_left = self.p["max_turns"]

    def next_turn(self, reply_ids):
        p = self.p
        if reply_ids:
            self.history += reply_ids
        room = self.ctx["context"] - 1 - p["reply_tokens"] - p["user_tokens"]
        if self.turns_left <= 0 or len(self.history) > room:
            self._new_session()
        self.history += random_ids(self.rng, p["user_tokens"],
                                   self.ctx["vocab_size"])
        self.turns_left -= 1
        check = self.n == self.check_turn
        turn = Turn(prompt_ids=list(self.history),
                    max_tokens=p["reply_tokens"],
                    think=self.thinks[self.n % len(self.thinks)],
                    greedy=check, check=check)
        self.n += 1
        return turn


def plan(p: dict, ctx: dict) -> Plan:
    srng = rng_for(p["schedule_seed"], 2)
    wrng = rng_for(ctx["seed"], 2)
    systems = [random_ids(wrng, p["system_tokens"], ctx["vocab_size"])
               for _ in range(p["system_prompts"])]
    seats = p["sessions"]
    # Zipf shares of the first conversations, by largest remainder
    w = _zipf_weights(len(systems), p["zipf_s"])
    first = [k for k, share in enumerate(w)
             for _ in range(round(share * seats))][:seats]
    first += [0] * (seats - len(first))
    first = shuffled(first, srng)
    # chosen before the run: the second turn of the deepest seat (the
    # longest context that is sure to run) and of three seeded others
    deepest = max(range(seats), key=lambda j: j % p["max_turns"])
    others = [j for j in range(seats) if j != deepest]
    checked = {deepest, *srng.sample(others, min(p["checked"] - 1,
                                                 len(others)))}
    actors = [_Seat(j, p, ctx, systems, first[j],
                    1 if j in checked else None) for j in range(seats)]
    # ladder: a cold conversation's first turn (ragged prefill), the same
    # again (prefix hit), and a follow-up turn (suffix prefill); then the
    # deepest seat's first turn (its system prompt a hit, a long suffix:
    # the widest page table the traffic reaches, alone on the chip) and,
    # sent together, a follow-up to it with first turns of other
    # conversations behind it (admissions while the long context decodes).
    # Without the last two a run on an empty compile cache compiled the
    # wide ragged-step programs inside its window (PERF.md, PR 26).
    lrng = rng_for(ctx["seed"], 3)
    words = lambda n: random_ids(lrng, n, ctx["vocab_size"])
    per_turn = p["user_tokens"] + p["reply_tokens"]
    cold = systems[0] + words(p["user_tokens"])
    more = cold + words(per_turn)
    room = ctx["context"] - 1 - p["reply_tokens"] - p["user_tokens"]
    depth = min(p["max_turns"] - 1,
                (room - p["system_tokens"] - per_turn) // per_turn)
    deep = systems[-1] + words(per_turn * depth + p["user_tokens"])
    rung = lambda ids, n=16: Turn(prompt_ids=ids, max_tokens=n, greedy=True,
                                  tag="ladder")
    ladder = [rung(cold), rung(cold), rung(more), rung(deep),
              [rung(deep + words(per_turn), p["reply_tokens"])]
              + [rung(systems[k % len(systems)] + words(p["user_tokens"]))
                 for k in range(3)]]
    return Plan(ladder=ladder, actors=actors, ramp_s=p["ramp_s"],
                checked=len(checked))
