"""Traffic generators: the same schedule for the same seed, another for
another, and the same SET of sizes and arrivals whatever the seed."""

import json

import pytest
from conftest import CHIP_DIR

from harness import generators

CTX = {"seconds": 20.0, "slots": 8, "context": 2048, "vocab_size": 32000}
MIXES = sorted(p.stem for p in (CHIP_DIR / "traffic").glob("*.json"))


def every_actor_turn(actors):
    """All turns of actors whose turns do not depend on replies."""
    for a in actors:
        while (t := a.next_turn([])) is not None:
            yield t


def traffic(name):
    return json.loads((CHIP_DIR / "traffic" / f"{name}.json").read_text())


def schedule(name, seed, turns_per_actor=3):
    """What a plan offers when every reply is its full length of zeros."""
    plan = generators.build_plan(traffic(name), {**CTX, "seed": seed})
    out = [("ladder", len(t.prompt_ids), t.max_tokens, tuple(t.prompt_ids))
           for rung in plan.ladder
           for t in (rung if isinstance(rung, list) else [rung])]
    for a in plan.actors:
        reply = None
        for _ in range(turns_per_actor):
            t = a.next_turn(reply)
            if t is None:
                break
            out.append((t.due, round(t.think, 9), t.max_tokens, t.check,
                        tuple(t.prompt_ids)))
            reply = [0] * t.max_tokens
    return out


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule(mix):
    assert schedule(mix, 7) == schedule(mix, 7)


@pytest.mark.parametrize("mix", MIXES)
def test_other_seed_other_schedule(mix):
    # (the open loop keeps sizes and arrivals; its prompts differ)
    assert schedule(mix, 7) != schedule(mix, 2**31 + 11)


@pytest.mark.parametrize("mix", MIXES)
def test_prompts_are_vocabulary_ids_and_fit_the_context(mix):
    for row in schedule(mix, 3):
        ids, max_tokens = row[-1], row[2]
        assert ids and all(0 <= i < CTX["vocab_size"] for i in ids)
        assert len(ids) + max_tokens <= CTX["context"]


def test_open_loop_offers_the_same_requests_at_the_same_times():
    def shape(seed):
        plan = generators.build_plan(traffic("chat_open"),
                                     {**CTX, "seed": seed})
        return [(t.due, len(t.prompt_ids), t.max_tokens, t.check)
                for t in every_actor_turn(plan.actors)]

    assert shape(1) == shape(2**31 + 5)


def test_open_loop_lengths_and_arrivals_are_quantiles_of_their_laws():
    def work(seed):
        plan = generators.build_plan(traffic("chat_open"),
                                     {**CTX, "seed": seed})
        turns = [t for t in every_actor_turn(plan.actors) if t.tag == ""]
        gaps = sorted(round(b.due - a.due, 6) for a, b in zip(
            sorted(turns, key=lambda t: t.due)[:-1],
            sorted(turns, key=lambda t: t.due)[1:]))
        return (sorted(len(t.prompt_ids) for t in turns),
                sorted(t.max_tokens for t in turns), len(turns), gaps)

    p = traffic("chat_open")
    a, b = work(1), work(2)
    assert a == b
    assert a[2] == round(p["rate_per_s"] * CTX["seconds"])
    lens = a[0]
    assert lens[0] >= p["prompt_tokens"]["min"]
    assert lens[-1] <= p["prompt_tokens"]["max"]
    assert abs(lens[len(lens) // 2] - p["prompt_tokens"]["median"]) <= 16
    # exponential gaps: mean 1/rate, and the median below the mean
    gaps = a[3]
    assert abs(sum(gaps) / len(gaps) - 1 / p["rate_per_s"]) < 0.05
    assert gaps[len(gaps) // 2] < sum(gaps) / len(gaps)


def test_open_loop_is_due_inside_the_window_and_ramp_before_it():
    p = traffic("chat_open")
    plan = generators.build_plan(p, {**CTX, "seed": 5})
    turns = list(every_actor_turn(plan.actors))
    window = [t for t in turns if t.tag == ""]
    ramp = [t for t in turns if t.tag == "ramp"]
    assert all(0.0 <= t.due < CTX["seconds"] for t in window)
    assert ramp and all(-p["ramp_s"] <= t.due < 0.0 for t in ramp)
    assert sum(t.check for t in window) == p["checked"] == plan.checked
    longest = max(window, key=lambda t: len(t.prompt_ids) + t.max_tokens)
    assert longest.check and all(t.greedy for t in window if t.check)


def test_closed_loop_has_two_clients_per_slot_and_a_staggered_ramp():
    p = traffic("decode_sat")
    plan = generators.build_plan(p, {**CTX, "seed": 5})
    assert len(plan.actors) == p["clients_per_slot"] * CTX["slots"]
    first = [a.next_turn(None) for a in plan.actors]
    outs = [t.max_tokens for t in first[:CTX["slots"]]]
    assert outs == sorted(outs) and len(set(outs)) == CTX["slots"]
    assert all(t.max_tokens == p["output_tokens"]
               for t in first[CTX["slots"]:])
    second = [a.next_turn([0]) for a in plan.actors]
    assert sum(t.check for t in second) == p["checked"] == plan.checked
    assert all(len(t.prompt_ids) == p["prompt_tokens"]
               and t.max_tokens == p["output_tokens"] for t in second)


def test_a_conversation_carries_its_history_and_the_reply():
    p = traffic("sessions_shared")
    plan = generators.build_plan(p, {**CTX, "seed": 9})
    seat = plan.actors[0]            # seat 0 starts a fresh conversation
    t0 = seat.next_turn(None)
    assert len(t0.prompt_ids) == p["system_tokens"] + p["user_tokens"]
    reply = [5] * p["reply_tokens"]
    t1 = seat.next_turn(reply)
    assert t1.prompt_ids[:len(t0.prompt_ids)] == t0.prompt_ids
    assert t1.prompt_ids[len(t0.prompt_ids):][:len(reply)] == reply
    assert len(t1.prompt_ids) == (len(t0.prompt_ids) + p["reply_tokens"]
                                  + p["user_tokens"])
    # the seats share the few system prompts
    heads = {tuple(a.next_turn(None).prompt_ids[:p["system_tokens"]])
             for a in plan.actors[1:]} | {tuple(
                 t0.prompt_ids[:p["system_tokens"]])}
    assert len(heads) <= p["system_prompts"]


def test_a_conversation_ends_at_max_turns_and_a_new_one_takes_the_seat():
    p = traffic("sessions_shared")
    plan = generators.build_plan(p, {**CTX, "seed": 9})
    seat = plan.actors[0]
    lens, reply = [], None
    for _ in range(p["max_turns"] + 1):
        t = seat.next_turn(reply)
        lens.append(len(t.prompt_ids))
        reply = [1] * p["reply_tokens"]
    assert lens[:-1] == sorted(lens[:-1])
    assert lens[-1] == p["system_tokens"] + p["user_tokens"]
    assert max(lens) + p["reply_tokens"] <= CTX["context"]


@pytest.mark.parametrize("sizes", ["as_served", "rehearsal"])
def test_the_sessions_ladder_reaches_the_widest_context_the_seats_reach(sizes):
    """Alone first, then with admissions behind it: the page-table widths
    of the ramp's deepest turns are compiled before the ramp (PR 26: on an
    empty compile cache they compiled inside the window)."""
    p = traffic("sessions_shared")
    ctx = {**CTX, "seed": 9}
    if sizes == "rehearsal":
        p, ctx = {**p, **p["rehearsal"]}, {**ctx, "context": 256,
                                           "vocab_size": 512, "slots": 4}
    plan = generators.build_plan(p, ctx)
    seat, reply, deepest = plan.actors[0], None, 0   # a whole conversation
    for _ in range(p["max_turns"]):
        deepest = max(deepest, len(seat.next_turn(reply).prompt_ids))
        reply = [1] * p["reply_tokens"]
    *singles, burst = plan.ladder
    assert not any(isinstance(r, list) for r in singles)
    assert max(len(t.prompt_ids) for t in singles) == deepest
    assert len(burst[0].prompt_ids) > deepest and len(burst) == 4
    assert burst[0].prompt_ids[:deepest] == singles[-1].prompt_ids
    for t in [*singles, *burst]:
        assert len(t.prompt_ids) + t.max_tokens <= ctx["context"]
        assert t.greedy and not t.check and t.tag == "ladder"


def test_unknown_generator_is_an_error():
    with pytest.raises(ValueError):
        generators.build_plan({"generator": "no_such_kind"}, {**CTX, "seed": 1})


@pytest.mark.parametrize("mix", MIXES)
def test_a_traced_tail_leaves_the_plan_before_it_as_it_is(mix):
    """``--trace 2`` traces a tail of the same traffic after the window:
    the ladder, every actor there was and every turn due before the
    window's end — arrivals, sizes, words, the ``check`` marks, and the
    actor indices the sampling seeds come from — stay what they are."""
    def rows(tail):
        plan = generators.build_plan(
            traffic(mix), {**CTX, "seed": 11, "tail_s": tail})
        ladder = [(len(t.prompt_ids), t.max_tokens, tuple(t.prompt_ids))
                  for rung in plan.ladder
                  for t in (rung if isinstance(rung, list) else [rung])]
        turns = []
        for i, a in enumerate(plan.actors):
            reply = None
            for n in range(3):
                t = a.next_turn(reply)
                if t is None:
                    break
                turns.append((i, n, t.due, round(t.think, 9), t.max_tokens,
                              t.greedy, t.check, t.tag, tuple(t.prompt_ids)))
                reply = [0] * t.max_tokens
        return ladder, turns, plan.ramp_s, plan.checked

    plain, tailed = rows(0.0), rows(12.0)
    assert tailed[0] == plain[0] and tailed[2:] == plain[2:]
    before = [r for r in tailed[1] if r[2] is None or r[2] < CTX["seconds"]]
    assert before == plain[1]
    extra = [r for r in tailed[1] if r not in plain[1]]
    if traffic(mix)["generator"] == "open_poisson":
        assert extra and all(
            CTX["seconds"] <= r[2] < CTX["seconds"] + 12.0
            and r[7] == "tail" and not r[6] for r in extra)
    else:       # a closed loop's actors simply go on
        assert not extra
