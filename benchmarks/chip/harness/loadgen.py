"""The load generator: one asyncio process, one coroutine per stream, every
frame of every stream stamped on arrival with the host's monotonic clock.

A traffic generator (``harness/generators/<kind>.py``) turns a traffic file
into a :class:`Plan`: a ladder of warm-up requests sent one after another
(or, a rung that is a list, together),
then a set of actors on one timeline whose zero is the start of the measured
window.  An actor yields :class:`Turn` objects: a turn with ``due`` is sent
at that offset whatever else is going on (open loop) and its latency is
timed from when it was DUE; a turn without is sent ``think`` seconds after
the actor's previous turn completed (closed loop).  Turns sent before zero
are the warm-up replay (the ramp): they bring the system to its steady state
and touch its shapes, and only their tokens that arrive inside the window
count.  No turn starts after the window's end — or, where the run traces a
``tail`` of the same traffic after it, after the tail's end; turns in
flight are drained.  Every end-to-end reader cuts at the window, so a turn
that starts in the tail counts nowhere.
"""

from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass, field
from typing import Protocol

import aiohttp

from . import synth_tokenizer


@dataclass
class Turn:
    prompt_ids: list[int]
    max_tokens: int
    due: float | None = None     # offset from the window's start, seconds
    think: float = 0.0           # closed loop: wait after the previous turn
    greedy: bool = False
    check: bool = False          # chosen before the run for the reference
    tag: str = ""


class Actor(Protocol):
    def next_turn(self, reply_ids: list[int] | None) -> Turn | None:
        """The actor's next turn, given the ids of its previous reply
        (None before the first turn); None when the actor is finished."""


@dataclass
class Plan:
    ladder: list[Turn | list[Turn]]   # a list inside: sent together
    actors: list[Actor]
    ramp_s: float                # the timeline starts this long before zero
    checked: int = 0             # turns marked ``check``, chosen beforehand


@dataclass
class Record:
    """One request as the client saw it.  Times are offsets from the
    window's start (negative in the ramp)."""

    actor: int
    turn: int
    tag: str
    prompt_len: int
    max_tokens: int
    greedy: bool
    check: bool
    due: float | None
    sent: float = 0.0
    frame_t: list[float] = field(default_factory=list)    # text frames only
    frame_tokens: list[int] = field(default_factory=list)
    done_t: float | None = None
    done_frame: dict = field(default_factory=dict)
    status: int = 0
    error: str = ""
    prompt_ids: list[int] = field(default_factory=list)
    reply_ids: list[int] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return (self.status == 200 and not self.error
                and self.done_frame.get("done") is True)

    def to_json(self, with_ids: bool) -> dict:
        d = {k: getattr(self, k) for k in (
            "actor", "turn", "tag", "prompt_len", "max_tokens", "greedy",
            "check", "due", "sent", "frame_t", "frame_tokens", "done_t",
            "status", "error")}
        d["done_reason"] = self.done_frame.get("done_reason")
        d["eval_count"] = self.done_frame.get("eval_count")
        d["prompt_eval_count"] = self.done_frame.get("prompt_eval_count")
        if with_ids:
            d["prompt_ids"] = self.prompt_ids
            d["reply_ids"] = self.reply_ids
        return d


class LoadGen:
    def __init__(self, port: int, model: str, sampling: dict, seed: int,
                 seconds: float, tail: float = 0.0) -> None:
        self.url = f"http://127.0.0.1:{port}/api/generate"
        self.model = model
        self.sampling = sampling
        self.seed = seed
        self.seconds = seconds
        self.tail = tail
        self.records: list[Record] = []
        self.t0 = 0.0            # monotonic clock at the window's start
        self._session: aiohttp.ClientSession | None = None

    def now(self) -> float:
        return time.monotonic() - self.t0

    async def _request(self, rec: Record, turn: Turn) -> None:
        if turn.greedy:
            options = {"temperature": 0.0}
        else:
            options = dict(self.sampling)
            # any whole number up to a little over 2**31 is a valid --seed
            options["seed"] = (self.seed * 1_000_003 + rec.actor * 1009
                               + rec.turn) % (2**31 - 1) + 1
        options["num_predict"] = turn.max_tokens
        body = {"model": self.model, "stream": True, "raw": True,
                "prompt": synth_tokenizer.text_of(turn.prompt_ids),
                "options": options}
        text: list[str] = []
        rec.sent = self.now()
        try:
            async with self._session.post(self.url, json=body) as resp:
                rec.status = resp.status
                if resp.status != 200:
                    rec.error = (await resp.text())[:300]
                    return
                while True:
                    line = await resp.content.readline()
                    if not line:
                        break
                    t = self.now()
                    if not line.strip():
                        continue
                    frame = json.loads(line)
                    piece = frame.get("response") or ""
                    if piece:
                        rec.frame_t.append(t)
                        rec.frame_tokens.append(
                            len(piece) // synth_tokenizer.WORD_LEN)
                        text.append(piece)
                    if frame.get("done"):
                        rec.done_t = t
                        rec.done_frame = frame
                    if frame.get("error"):
                        rec.error = str(frame["error"])[:300]
        except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as e:
            rec.error = f"{type(e).__name__}: {e}"[:300]
        try:
            rec.reply_ids = synth_tokenizer.ids_of("".join(text))
        except ValueError as e:
            rec.error = rec.error or str(e)

    async def _run_turn(self, actor_i: int, turn_i: int, turn: Turn) -> Record:
        rec = Record(actor=actor_i, turn=turn_i, tag=turn.tag,
                     prompt_len=len(turn.prompt_ids),
                     max_tokens=turn.max_tokens, greedy=turn.greedy,
                     check=turn.check, due=turn.due,
                     prompt_ids=turn.prompt_ids)
        self.records.append(rec)
        await self._request(rec, turn)
        return rec

    async def _run_actor(self, i: int, actor: Actor) -> None:
        reply: list[int] | None = None
        turn_i = 0
        while True:
            turn = actor.next_turn(reply)
            if turn is None:
                return
            if turn.due is not None:
                wait = turn.due - self.now()
            else:
                wait = turn.think
            if wait > 0:
                await asyncio.sleep(wait)
            if self.now() >= self.seconds + self.tail:
                return           # nothing starts after the window (+ tail)
            rec = await self._run_turn(i, turn_i, turn)
            if not rec.ok:
                return           # counted as failed; the actor stops
            reply = rec.reply_ids
            turn_i += 1

    async def run(self, plan: Plan, after_ladder=None, on_window_start=None,
                  on_window_end=None) -> None:
        """The ladder, then the timeline.  ``after_ladder`` is awaited
        between the two; ``on_window_start`` / ``_end`` at offsets 0 and
        ``seconds`` (scrapes, trace signals)."""
        timeout = aiohttp.ClientTimeout(total=None, sock_read=300)
        conn = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(timeout=timeout,
                                         connector=conn) as session:
            self._session = session
            self.t0 = time.monotonic() + 1e9      # ladder: far before zero
            for k, rung in enumerate(plan.ladder):
                group = rung if isinstance(rung, list) else [rung]

                async def send(j: int, turn: Turn) -> Record:
                    await asyncio.sleep(0.02 * j)     # in order, together
                    return await self._run_turn(-1, k, turn)

                for rec in await asyncio.gather(
                        *(send(j, t) for j, t in enumerate(group))):
                    if not rec.ok:
                        raise RuntimeError(
                            f"warm-up request {k} failed: {rec.status} "
                            f"{rec.error}")
            ladder = list(self.records)
            if after_ladder is not None:
                await after_ladder()
            self.t0 = time.monotonic() + plan.ramp_s
            for rec in ladder:                     # keep them before zero
                rec.sent = rec.done_t = -plan.ramp_s - 1.0
                rec.frame_t = [-plan.ramp_s - 1.0] * len(rec.frame_t)

            async def at(offset: float, hook) -> None:
                await asyncio.sleep(max(0.0, offset - self.now()))
                if hook is not None:
                    await hook()

            tasks = [asyncio.create_task(self._run_actor(i, a))
                     for i, a in enumerate(plan.actors)]
            hooks = [asyncio.create_task(at(0.0, on_window_start)),
                     asyncio.create_task(at(self.seconds, on_window_end))]
            await asyncio.gather(*tasks, *hooks)
            self._session = None
