"""An admission does not drain the device (engine/scheduler.py ``_place`` /
``_emit_firsts``; the runners' ``prefill`` / ``insert``).

``prefill`` hands the sampled first token back as the device scalar it is,
``insert`` takes it unread — the program writes it into the repeat-penalty
ring itself — and the scheduler reads and emits it only once the NEXT decode
flight, which already carries the new row, is in the device's queue.

Two halves.  With a recording runner whose "device scalar" notes the moment
the host converts it: the ORDER of the runner's calls across an admission,
and what a stream sees when its first token ends it or its client left
meanwhile.  With the real runners on the tiny CPU configurations: a seeded
request yields through the scheduler exactly the tokens it yields when the
host reads the token and seeds the ring itself, as the scheduler did before.
"""

import asyncio
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crowdllama_tpu.engine.runner import REPEAT_LAST_N, ModelRunner
from crowdllama_tpu.engine.scheduler import DONE, GenRequest, Scheduler
from crowdllama_tpu.obs.metrics import ENGINE_TELEMETRY

FIRST = 7          # every fake prefill samples this
FLIGHT = 1000      # fake flights emit FLIGHT + the flight's number


class _OnDevice:
    """A first token as the runners hand it back: not an int.  Converting
    it is the host's read, which this notes in the runner's log."""

    def __init__(self, value: int, log: list, of: int):
        self.value, self.log, self.of = value, log, of

    def __int__(self) -> int:
        self.log.append(("read", self.of))
        return self.value

    __index__ = __int__

    def __array__(self, dtype=None, copy=None):
        return np.asarray(int(self), dtype)


class _Recorder:
    """The runner protocol, every call noted in ``log`` in dispatch order.
    ``insert`` runs ``on_insert(first prompt token)`` first: the moment
    between an admission's prefill and its insert."""

    max_slots = 2
    max_seq = 1 << 20    # a loaded engine stays loaded for a whole test

    def __init__(self, on_insert=None):
        self.log: list[tuple] = []
        self.flights = 0
        self.on_insert = on_insert

    def init_state(self):
        return {}

    def prefill(self, ids, temp, top_p, key, state=None, **kw):
        self.log.append(("prefill", ids[0]))
        return _OnDevice(FIRST, self.log, ids[0]), None, None, len(ids)

    def insert(self, state, slot, ks, vs, plen, tok, t, p, **kw):
        if self.on_insert is not None:
            self.on_insert(kw["prompt_tokens"][0])
        assert isinstance(tok, _OnDevice), "insert was handed a host value"
        self.log.append(("insert", slot))
        return state

    def release(self, state, slot):
        self.log.append(("release", slot))
        return state

    def decode_steps_device(self, state, k):
        self.flights += 1
        self.log.append(("decode", self.flights))
        time.sleep(0.005)   # a flight takes a while: requests can queue
        return np.full((k, self.max_slots), FLIGHT + self.flights,
                       np.int32), state


async def _drain(req: GenRequest, timeout: float = 20.0):
    """Everything ``req`` is sent up to its terminal: (tokens, reason)."""
    toks = []
    while True:
        tok, reason = await asyncio.wait_for(req.out.get(), timeout)
        if tok is DONE:
            return toks, reason
        toks.append(tok)


async def _settled(sched: Scheduler, slot: int, timeout: float = 20.0,
                   flights_of: "_Recorder | None" = None):
    """``slot`` is free again and nothing is in the air — or, on a fake
    engine that stays loaded (``flights_of``), three more flights were
    dispatched since the slot's release, so those queued while it was
    taken have been retired."""
    deadline = time.monotonic() + timeout

    async def until(cond):
        while not cond():
            assert time.monotonic() < deadline, "slot never settled"
            await asyncio.sleep(0.005)

    if flights_of is None:
        await until(lambda: sched.slots[slot] is None
                    and sched._inflight is None)
        return
    await until(lambda: sched.slots[slot] is None
                and ("release", slot) in flights_of.log)
    seen = flights_of.flights
    await until(lambda: flights_of.flights >= seen + 3)


async def test_admission_under_load_reads_nothing_before_the_next_flight():
    """``prefill``, ``insert`` and the next ``decode_steps_device`` are all
    dispatched before the host converts the first token; the token is the
    stream's first frame, ahead of every flight token; both admissions
    count as ``device``."""
    runner = _Recorder()
    before = dict(ENGINE_TELEMETRY._admissions)
    sched = Scheduler(runner, decode_chunk=2)
    sched.start()
    try:
        a = GenRequest(prompt_ids=[11, 1, 2], max_tokens=200, eos_id=-1)
        await sched.submit(a)
        assert (await asyncio.wait_for(a.out.get(), 20))[0] == FIRST
        while runner.flights < 3:    # a is decoding: the engine is loaded
            await asyncio.sleep(0.002)
        b = GenRequest(prompt_ids=[22, 3], max_tokens=6, eos_id=-1)
        await sched.submit(b)
        toks, reason = await _drain(b)
    finally:
        await sched.stop()
    assert reason == "length" and len(toks) == 6
    assert toks[0] == FIRST and all(t > FLIGHT for t in toks[1:]), toks
    assert all(isinstance(t, int) for t in toks)

    log = runner.log
    at = {ev: log.index(ev) for ev in (("prefill", 22), ("insert", 1),
                                       ("read", 22))}
    assert at[("prefill", 22)] < at[("insert", 1)] < at[("read", 22)]
    between = log[at[("prefill", 22)] + 1:at[("read", 22)]]
    # nothing of the admission is read, and a flight is queued behind the
    # insert, before the host converts anything
    assert ("insert", 1) in between
    assert any(ev[0] == "decode" for ev in
               between[between.index(("insert", 1)) + 1:]), log
    assert not any(ev[0] == "read" for ev in between), log
    # that flight carried b's row: b's first flight token is its number
    queued = next(ev[1] for ev in log[at[("insert", 1)]:]
                  if ev[0] == "decode")
    assert toks[1] == FLIGHT + queued, (toks, log)
    grew = {k: ENGINE_TELEMETRY._admissions[k] - before[k] for k in before}
    assert grew == {"device": 2, "host": 0}
    assert ('crowdllama_admissions_total{first_token="device"} '
            f'{ENGINE_TELEMETRY._admissions["device"]}'
            in ENGINE_TELEMETRY.expose())


async def test_two_admissions_in_a_row_wait_for_neither():
    """Two requests waiting for two free slots are admitted in consecutive
    turns: the second one's prefill and insert are queued behind the
    flight that carries the first one's row BEFORE the host waits for the
    first one's token."""

    class _Three(_Recorder):
        max_slots = 3

    runner = _Three()
    sched = Scheduler(runner, decode_chunk=2)
    sched.start()
    try:
        a = GenRequest(prompt_ids=[11, 1, 2], max_tokens=10_000, eos_id=-1)
        await sched.submit(a)
        await asyncio.wait_for(a.out.get(), 20)
        b = GenRequest(prompt_ids=[22, 3], max_tokens=4, eos_id=-1)
        c = GenRequest(prompt_ids=[33, 4], max_tokens=4, eos_id=-1)
        sched.pending.put_nowait(b)     # both there when the loop looks
        sched.pending.put_nowait(c)
        sched._wake.set()
        (tb, _), (tc, _) = await asyncio.gather(_drain(b), _drain(c))
        sched.cancel(a)
    finally:
        await sched.stop()
    assert tb[0] == tc[0] == FIRST and len(tb) == len(tc) == 4
    log = [ev for ev in runner.log if ev[0] != "release"]
    start = log.index(("prefill", 22))
    kinds = [ev if ev[0] != "decode" else "decode" for ev in log[start:]]
    assert kinds[:8] == [("prefill", 22), ("insert", 1), "decode",
                         ("prefill", 33), ("insert", 2), ("read", 22),
                         "decode", ("read", 33)], kinds[:10]


@pytest.mark.parametrize("case", ["eos", "max_tokens_1", "cancelled"])
async def test_first_token_that_ends_the_stream_or_finds_no_client(case):
    """A first token that is the EOS, that fills ``max_tokens`` 1, or whose
    client left between prefill and insert: the slot is released ONCE, at
    a safe point of the loop; nothing follows the terminal; the flight
    already queued with that row is discarded; the slot serves again."""
    sched = None

    def leave(first_prompt_token):
        if case == "cancelled" and first_prompt_token == 22:
            sched.cancel(b)

    runner = _Recorder(on_insert=leave)
    sched = Scheduler(runner, decode_chunk=2)
    sched.start()
    try:
        a = GenRequest(prompt_ids=[11, 1, 2], max_tokens=10_000, eos_id=-1)
        await sched.submit(a)
        await asyncio.wait_for(a.out.get(), 20)
        b = GenRequest(prompt_ids=[22, 3], eos_id=-1,
                       max_tokens=1 if case == "max_tokens_1" else 50)
        if case == "eos":
            b.eos_id = FIRST
        await sched.submit(b)
        if case == "cancelled":
            await _settled(sched, 1, flights_of=runner)
            assert b.out.empty(), "a client that left was sent something"
        else:
            toks, reason = await _drain(b)
            assert toks == [FIRST]
            assert reason == ("stop" if case == "eos" else "length")
            await _settled(sched, 1, flights_of=runner)
        assert runner.log.count(("release", 1)) == 1
        # the flight queued behind the insert had b's row in it and was
        # retired since: none of its tokens, and nothing else, reached b
        assert ("insert", 1) in runner.log
        assert b.out.empty()
        assert sched._firsts() == []
        # the slot is whole again: the next request lives in it
        c = GenRequest(prompt_ids=[33], max_tokens=3, eos_id=-1)
        await sched.submit(c)
        toks, reason = await _drain(c)
        assert toks[0] == FIRST and len(toks) == 3 and reason == "length"
        assert runner.log.count(("insert", 1)) == 2
        sched.cancel(a)
    finally:
        await sched.stop()


async def test_an_int_from_the_runner_is_emitted_behind_its_insert():
    """A runner that hands back a Python int (the chunked finish, the
    multi-host wrapper) is served as before — first token out before the
    next flight is dispatched — and counts as ``host``."""

    class _Host(_Recorder):
        def prefill(self, ids, temp, top_p, key, state=None, **kw):
            self.log.append(("prefill", ids[0]))
            return FIRST, None, None, len(ids)

        def insert(self, state, slot, ks, vs, plen, tok, t, p, **kw):
            assert type(tok) is int
            self.log.append(("insert", slot))
            return state

    runner = _Host()
    before = dict(ENGINE_TELEMETRY._admissions)
    sched = Scheduler(runner, decode_chunk=2)
    sched.start()
    try:
        req = GenRequest(prompt_ids=[5, 6], max_tokens=4, eos_id=-1)
        await sched.submit(req)
        toks, reason = await _drain(req)
    finally:
        await sched.stop()
    assert toks[0] == FIRST and len(toks) == 4 and reason == "length"
    assert sched._firsts() == []
    grew = {k: ENGINE_TELEMETRY._admissions[k] - before[k] for k in before}
    assert grew == {"device": 0, "host": 1}


# ------------------------------------------------ the real runners' streams

LONG = REPEAT_LAST_N + 9     # the prompt laps the ring


def _contiguous():
    from crowdllama_tpu.models import transformer as T
    from crowdllama_tpu.models.config import get_config

    cfg = get_config("tiny-test", max_context_length=256)
    params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return ModelRunner(cfg, params=params, max_slots=2, max_seq=256,
                       dtype=jnp.float32)


def _paged():
    from crowdllama_tpu.engine.paged import PagedModelRunner
    from crowdllama_tpu.models import transformer as T
    from crowdllama_tpu.models.config import get_config

    cfg = get_config("tiny-test", max_context_length=256)
    params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return PagedModelRunner(cfg, params=params, max_slots=2, max_seq=256,
                            dtype=jnp.float32, page_size=32)


def _hybrid():
    from crowdllama_tpu.engine.hybrid import HybridPagedModelRunner
    from crowdllama_tpu.models import transformer as T
    from crowdllama_tpu.models.config import get_config

    cfg = get_config("tiny-test-nemotron-h")
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return HybridPagedModelRunner(cfg, params=params, max_slots=2,
                                  max_seq=256, page_size=16,
                                  dtype=jnp.float32)


RUNNERS = {"contiguous": (_contiguous, False), "paged_miss": (_paged, False),
           "paged_hit": (_paged, True), "hybrid": (_hybrid, False)}


def _prompts(vocab: int):
    rng = np.random.default_rng(32)
    shared = [int(t) for t in rng.integers(1, vocab, 32)]   # one whole page
    warm = shared + [int(t) for t in rng.integers(1, vocab, 5)]
    prompt = shared + [int(t) for t in rng.integers(1, vocab, LONG - 32)]
    return warm, prompt


def _host_ring_stream(runner, keys, req: GenRequest, warm, n: int):
    """``req``'s stream with the host in the middle, as the scheduler ran
    an admission before: the token is READ after the prefill, handed to
    ``insert`` as an int, and the ring is what the host would have seeded
    from the prompt and that token."""
    state = runner.init_state()
    if warm is not None:
        tok, ks, vs, plen = runner.prefill(warm, 0.0, 1.0,
                                           jax.random.PRNGKey(1), state=state)
        state = runner.insert(state, 0, ks, vs, plen, int(tok), 0.0, 1.0,
                              prompt_tokens=warm)
        state = runner.release(state, 0)
    prompt = req.prompt_ids
    tok, ks, vs, plen = runner.prefill(
        prompt, req.temperature, req.top_p, keys[0], state=state,
        top_k=req.top_k, repeat_penalty=req.repeat_penalty)
    first = int(tok)
    state = runner.insert(
        state, 0, ks, vs, plen, first, req.temperature, req.top_p,
        prompt_tokens=prompt, slot_key=keys[1], top_k=req.top_k,
        repeat_penalty=req.repeat_penalty)
    ring = runner._recent_from_prompt(prompt, first, plen)
    # the one write the insert program makes: slot plen % N, over the
    # prompt token N positions back, and nothing else of the ring
    assert ring[plen % REPEAT_LAST_N] == first
    alone = runner._recent_from_prompt(prompt, plen=plen)
    assert alone[plen % REPEAT_LAST_N] == prompt[plen - REPEAT_LAST_N]
    assert (ring != alone).sum() == (first != prompt[plen - REPEAT_LAST_N])
    np.testing.assert_array_equal(np.asarray(state.recent[0]), ring)
    toks, state = runner.decode_steps(state, n - 1)
    return [first] + [int(t) for t in np.asarray(toks)[:, 0]]


@pytest.mark.parametrize("temperature", [0.0, 0.8])
@pytest.mark.parametrize("which", list(RUNNERS))
async def test_seeded_streams_equal_the_host_ring_path(which, temperature):
    """Seeded requests with ``repeat_penalty`` 1.3 and a prompt longer
    than the ring, greedy and sampled: the scheduler's stream (the token
    never on the host before the next flight) equals the stream of the
    same runner driven with the host's read and the host's ring."""
    build, hit = RUNNERS[which]
    n = 12
    runner, ref = build(), build()
    warm, prompt = _prompts(runner.cfg.vocab_size)

    def request():
        return GenRequest(prompt_ids=list(prompt), max_tokens=n, eos_id=-1,
                          temperature=temperature, top_p=0.9,
                          repeat_penalty=1.3, seed=(1 << 33) + 32)

    sched = Scheduler(runner, decode_chunk=4, ragged=False)
    keys = [sched._req_key(request(), lane) for lane in (0, 1)]
    want = _host_ring_stream(ref, keys, request(),
                             warm if hit else None, n)
    sched.start()
    try:
        if hit:
            w = GenRequest(prompt_ids=list(warm), max_tokens=1, eos_id=-1)
            await sched.submit(w)
            await _drain(w, 120)
            await _settled(sched, 0, 120)
        req = request()
        await sched.submit(req)
        got, reason = await _drain(req, 120)
    finally:
        await sched.stop()
    assert reason == "length"
    assert got == want, (got, want)
    if which.startswith("paged"):
        assert runner.prefix_hits == ref.prefix_hits == int(hit)
    # the penalty bites on this prompt: without it the stream differs, so
    # a ring seeded wrongly would not have gone unseen
    if temperature == 0.0 and which == "contiguous":
        plain = request()
        plain.repeat_penalty = 1.0
        fresh = build()
        assert _stream_of(fresh, keys, plain, n) != want


def _stream_of(runner, keys, req: GenRequest, n: int):
    state = runner.init_state()
    tok, ks, vs, plen = runner.prefill(
        req.prompt_ids, req.temperature, req.top_p, keys[0], state=state,
        repeat_penalty=req.repeat_penalty)
    state = runner.insert(state, 0, ks, vs, plen, tok, req.temperature,
                          req.top_p, prompt_tokens=req.prompt_ids,
                          slot_key=keys[1],
                          repeat_penalty=req.repeat_penalty)
    toks, _ = runner.decode_steps(state, n - 1)
    return [int(tok)] + [int(t) for t in np.asarray(toks)[:, 0]]


# --------------------------------------------- the warm-up's programs stand

WARMED = {
    "paged": (dict(model="tiny-test", kv_layout="paged", kv_page_size=16), [
        "_decode_paged_impl", "_decode_paged_impl", "_embed_fwd",
        "_insert_paged_impl", "_prefill_ctx_impl", "_prefill_impl",
        "_release_paged_impl"]),
    "contiguous": (dict(model="tiny-test", kv_layout="contiguous"), [
        "_decode_impl", "_decode_impl", "_embed_fwd", "_insert_impl",
        "_prefill_impl", "_release_impl"]),
    "hybrid": (dict(model="tiny-test-nemotron-h", kv_page_size=16), [
        "_decode_paged_impl", "_decode_paged_impl", "_embed_fwd",
        "_insert_paged_impl", "_prefill_impl", "_release_paged_impl",
        "_take_counts_impl"]),
}


@pytest.mark.parametrize("which", list(WARMED))
async def test_warm_up_compiles_what_it_did_and_an_admission_nothing(which):
    """The engine's warm-up compiles the runner programs it compiled before
    this change, by name and number (the insert program changed; none was
    added), and hands ``insert`` the token as serving does — so an
    admission served afterwards, whose token never left the device,
    compiles none of them anew."""
    import logging
    import re

    from crowdllama_tpu.config import Configuration, Intervals
    from crowdllama_tpu.engine.engine import JaxEngine

    compiled: list[str] = []

    class Names(logging.Handler):
        def emit(self, record):
            m = re.match(r"Compiling jit\(([^)]*)\)", record.getMessage())
            if m and (m.group(1).endswith("_impl")
                      or m.group(1) == "_embed_fwd"):
                compiled.append(m.group(1))

    kw, want = WARMED[which]
    # every lowering is logged there, at DEBUG unless jax_log_compiles
    pxla = logging.getLogger("jax._src.interpreters.pxla")
    handler, level = Names(), pxla.level
    pxla.addHandler(handler)
    pxla.setLevel(logging.DEBUG)
    engine = JaxEngine(Configuration(
        max_context_length=256, max_batch_slots=2, mesh_shape="1x1",
        intervals=Intervals.default(), **kw))
    try:
        await engine.start()
        assert sorted(compiled) == want
        del compiled[:]
        req = GenRequest(prompt_ids=[4, 5, 6], max_tokens=5, eos_id=-1,
                         seed=3)
        await engine.scheduler.submit(req)
        toks, reason = await _drain(req, 120)
        assert len(toks) == 5 and reason == "length"
        assert compiled == []
    finally:
        pxla.removeHandler(handler)
        pxla.setLevel(level)
        await engine.stop()
