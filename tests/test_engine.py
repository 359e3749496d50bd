"""Engine tests: runner decode state, continuous batching, streaming, and the
BaseMessage handler seam — on the tiny model, virtual CPU devices."""

import asyncio

import numpy as np
import pytest

from crowdllama_tpu.config import Configuration
from crowdllama_tpu.core.messages import create_generate_request, extract_generate_response
from crowdllama_tpu.engine.engine import FakeEngine, JaxEngine
from crowdllama_tpu.engine.tokenizer import ByteTokenizer, get_tokenizer


def _mkengine(**kw) -> JaxEngine:
    cfg = Configuration.from_environment()
    cfg.model = "tiny-test"
    cfg.model_path = ""
    cfg.max_batch_slots = kw.pop("slots", 4)
    cfg.max_context_length = 128
    cfg.mesh_shape = kw.pop("mesh", "2x1x2")
    return JaxEngine(cfg)


async def test_generate_streams_tokens():
    eng = _mkengine()
    await eng.start()
    try:
        chunks = []
        async for c in eng.generate("hello world", max_tokens=8, temperature=0.0):
            chunks.append(c)
        assert chunks[-1].done
        assert chunks[-1].completion_tokens <= 8
        assert chunks[-1].prompt_tokens == len(ByteTokenizer().encode("hello world"))
        # deterministic under greedy: same prompt -> same text
        text1 = "".join(c.text for c in chunks)
        chunks2 = [c async for c in eng.generate("hello world", max_tokens=8)]
        assert "".join(c.text for c in chunks2) == text1
    finally:
        await eng.stop()


async def test_concurrent_requests_batched():
    eng = _mkengine(slots=4)
    await eng.start()
    try:
        async def run(i):
            out = []
            async for c in eng.generate(f"prompt {i}", max_tokens=6, temperature=0.5):
                out.append(c)
            return out

        results = await asyncio.gather(*(run(i) for i in range(6)))  # > slots
        for out in results:
            assert out[-1].done
            assert out[-1].completion_tokens <= 6
        assert eng.scheduler.requests_served == 6
        assert eng.scheduler.load == 0.0  # all retired
    finally:
        await eng.stop()


async def test_handler_seam_roundtrip():
    eng = _mkengine()
    await eng.start()
    try:
        msg = create_generate_request("tiny-test", "abc", max_tokens=5)
        reply = await eng.handle(msg, worker_id="w1")
        resp = extract_generate_response(reply)
        assert resp.done
        assert resp.worker_id == "w1"
        assert resp.total_duration > 0
        assert resp.completion_tokens <= 5

        frames = []
        async for frame in eng.handle_streaming(msg, worker_id="w1"):
            frames.append(extract_generate_response(frame))
        assert frames[-1].done
        assert all(not f.done for f in frames[:-1])
    finally:
        await eng.stop()


async def test_prompt_too_long_rejected():
    eng = _mkengine()
    await eng.start()
    try:
        with pytest.raises(ValueError):
            async for _ in eng.generate("x" * 500, max_tokens=4):
                pass
    finally:
        await eng.stop()


async def test_wrong_model_rejected():
    eng = _mkengine()
    await eng.start()
    try:
        with pytest.raises(ValueError):
            async for _ in eng.generate("hi", model="other-model"):
                pass
    finally:
        await eng.stop()


async def test_fake_engine_seam():
    eng = FakeEngine()
    reply = await eng.handle(create_generate_request("m", "hi there"))
    resp = extract_generate_response(reply)
    assert resp.response == "echo: hi there"
    assert resp.done


def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer()
    ids = tok.encode("héllo ✓")
    assert ids[0] == tok.bos_id
    assert tok.decode(ids) == "héllo ✓"
    # streaming decoder handles split multibyte sequences
    dec = tok.stream_decoder()
    out = "".join(dec.feed(i) for i in ids)
    assert out == "héllo ✓"


def test_get_tokenizer_fallback(tmp_path):
    assert isinstance(get_tokenizer(""), ByteTokenizer)
    assert isinstance(get_tokenizer(str(tmp_path / "nope")), ByteTokenizer)


def test_prefill_padding_invariance():
    """Bucket padding must not leak into attention: the same prompt prefilled
    into different bucket sizes yields the same greedy first token and the
    same KV for the real positions."""
    import jax
    from crowdllama_tpu.engine.runner import ModelRunner
    from crowdllama_tpu.models.config import get_config

    import jax.numpy as jnp
    from crowdllama_tpu.models import transformer as T

    cfg = get_config("tiny-test")
    r = ModelRunner(cfg, mesh_spec="1x1x1", max_slots=2, max_seq=128)
    prompt = [1, 7, 42, 99, 3]  # len 5 → bucket 32 (27 padding keys)
    tok_bucketed, ks_bucketed, _, _ = r.prefill(prompt, 0.0, 1.0, jax.random.PRNGKey(0))

    # Exact-length forward, no padding at all.
    pos = jnp.arange(5)[None, :]
    logits, ks_exact, _ = T.prefill(r.params, cfg, jnp.asarray([prompt]), pos)
    assert int(logits[0, -1].argmax()) == tok_bucketed
    np.testing.assert_allclose(
        np.asarray(ks_bucketed[:, :, :, :5], np.float32),
        np.asarray(ks_exact, np.float32), atol=2e-2)


async def test_event_loop_free_during_dispatch():
    """The control plane must stay responsive while a decode chunk / prefill
    blocks in the dispatch thread (VERDICT r1: an 8-step chunk on a big model
    froze DHT RPCs and health probes for its whole duration)."""
    import time

    from crowdllama_tpu.engine.scheduler import GenRequest, Scheduler

    class _SlowRunner:
        max_slots = 2
        max_seq = 128

        def init_state(self):
            return {}

        def prefill(self, ids, temp, top_p, key, state=None, **kw):
            time.sleep(0.4)  # blocking device wait
            return 5, None, None, len(ids)

        def insert(self, state, slot, ks, vs, plen, tok, t, p, **kw):
            return state

        def release(self, state, slot):
            return state

        def decode_steps_device(self, state, k):
            time.sleep(0.6)  # blocking device wait
            return np.zeros((k, self.max_slots), np.int32), state

    sched = Scheduler(_SlowRunner(), decode_chunk=4)
    sched.start()
    try:
        req = GenRequest(prompt_ids=[1, 2, 3], max_tokens=8, eos_id=-1)
        await sched.submit(req)
        max_gap, last = 0.0, time.monotonic()
        for _ in range(150):  # ~1.5 s of ticking while prefill+chunks run
            await asyncio.sleep(0.01)
            now = time.monotonic()
            max_gap = max(max_gap, now - last)
            last = now
        assert max_gap < 0.25, f"event loop stalled {max_gap:.2f}s"
        # Guard against the decode path silently erroring out (a fake that
        # doesn't match the runner protocol would make this test vacuous):
        # the request must have actually received tokens.
        assert not req.out.empty(), "no tokens emitted — decode never ran"
        tok, reason = req.out.get_nowait()
        assert reason == "" and isinstance(tok, int)
    finally:
        await sched.stop()


async def test_scheduler_churn_no_token_crosstalk():
    """Double-buffered decode under churn: many concurrent requests with
    mixed lengths and early EOS must each get a self-consistent stream —
    no request may receive tokens dispatched for another slot's occupant
    (the retire/readmit race the chunk snapshots exist to prevent)."""
    import jax

    from crowdllama_tpu.engine.runner import ModelRunner
    from crowdllama_tpu.engine.scheduler import DONE, GenRequest, Scheduler
    from crowdllama_tpu.models.config import get_config

    cfg = get_config("tiny-test", max_context_length=128)
    runner = ModelRunner(cfg, max_slots=2, max_seq=128)
    sched = Scheduler(runner, decode_chunk=4)
    sched.start()
    try:
        async def one(i):
            req = GenRequest(prompt_ids=[1 + i, 2, 3 + i],
                             max_tokens=3 + (i % 5), eos_id=-1)
            await sched.submit(req)
            toks = []
            while True:
                tok, reason = await asyncio.wait_for(req.out.get(), 30)
                if tok is DONE:
                    return toks, reason
                toks.append(tok)

        results = await asyncio.gather(*(one(i) for i in range(12)))
        for i, (toks, reason) in enumerate(results):
            want = 3 + (i % 5)
            assert reason in ("stop", "length"), reason
            # Exactly the budgeted number of tokens: crosstalk or dropped
            # chunks would show up as over- or under-emission.
            assert len(toks) == want, (i, len(toks), want)
        assert sched.requests_served == 12
        # All slots drained; scheduler is idle and reusable.
        assert all(s is None for s in sched.slots)
        toks, reason = await one(99)
        assert len(toks) == 3 + (99 % 5)
    finally:
        await sched.stop()


def test_sampling_shapes():
    import jax
    import jax.numpy as jnp
    from crowdllama_tpu.engine.sampling import sample_tokens

    logits = jnp.asarray(np.random.default_rng(0).normal(size=(4, 50)), jnp.float32)
    # greedy rows match argmax
    toks = sample_tokens(logits, jnp.zeros(4), jnp.ones(4), jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(logits.argmax(-1)))
    # top_p=0.01 with temp>0 collapses to argmax too
    toks = sample_tokens(logits, jnp.ones(4), jnp.full(4, 0.01), jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(logits.argmax(-1)))


def test_chat_template_preferred_over_flattening():
    """JaxEngine renders chats with the tokenizer's template when it has
    one, and falls back to the generic flattening when it doesn't."""
    from crowdllama_tpu.engine.engine import JaxEngine

    eng = JaxEngine.__new__(JaxEngine)  # formatting needs no started engine

    class Templated:
        def format_chat(self, messages):
            return "<tmpl>" + messages[-1]["content"]

    eng.tokenizer = Templated()
    msgs = [{"role": "user", "content": "hi"}]
    assert eng._format_chat(msgs) == "<tmpl>hi"

    class Untemplated:
        def format_chat(self, messages):
            raise ValueError("tokenizer has no chat template")

    eng.tokenizer = Untemplated()
    assert "user: hi" in eng._format_chat(msgs)


async def test_client_disconnect_frees_slot():
    """Closing the generate stream mid-flight (client disconnect) must free
    the decode slot — not keep generating until max_tokens."""
    from crowdllama_tpu.engine.runner import ModelRunner
    from crowdllama_tpu.engine.scheduler import Scheduler, GenRequest, DONE
    from crowdllama_tpu.models.config import get_config

    cfg = get_config("tiny-test", max_context_length=128)
    runner = ModelRunner(cfg, max_slots=2, max_seq=128)
    sched = Scheduler(runner, decode_chunk=2)
    sched.start()
    try:
        req = GenRequest(prompt_ids=[1, 2, 3], max_tokens=10_000, eos_id=-1)
        await sched.submit(req)
        await asyncio.wait_for(req.out.get(), 30)  # first token arrived
        sched.cancel(req)
        # The loop frees the slot at its next safe point.
        for _ in range(600):
            if all(s is None for s in sched.slots):
                break
            await asyncio.sleep(0.05)
        assert all(s is None for s in sched.slots)
        # Scheduler keeps serving new requests after the cancellation.
        req2 = GenRequest(prompt_ids=[4, 5], max_tokens=3, eos_id=-1)
        await sched.submit(req2)
        toks = []
        while True:
            tok, reason = await asyncio.wait_for(req2.out.get(), 30)
            if tok is DONE:
                break
            toks.append(tok)
        assert len(toks) == 3 and reason == "length"
        # A cancelled request still in the pending queue is dropped too.
        req3 = GenRequest(prompt_ids=[6], max_tokens=5, eos_id=-1)
        req3.cancelled = True
        await sched.submit(req3)
        req4 = GenRequest(prompt_ids=[7, 8], max_tokens=2, eos_id=-1)
        await sched.submit(req4)
        while True:
            tok, reason = await asyncio.wait_for(req4.out.get(), 30)
            if tok is DONE:
                break
        assert req3.out.empty()
    finally:
        await sched.stop()


async def test_scheduler_drain():
    from crowdllama_tpu.engine.runner import ModelRunner
    from crowdllama_tpu.engine.scheduler import Scheduler, GenRequest, DONE
    from crowdllama_tpu.models.config import get_config

    cfg = get_config("tiny-test", max_context_length=128)
    runner = ModelRunner(cfg, max_slots=2, max_seq=128)
    sched = Scheduler(runner, decode_chunk=2)
    sched.start()
    try:
        req = GenRequest(prompt_ids=[1, 2], max_tokens=6, eos_id=-1)
        await sched.submit(req)

        async def consume():
            got_done = False
            while True:
                tok, reason = await asyncio.wait_for(req.out.get(), 60)
                if tok is DONE:
                    return True
        consumer = asyncio.create_task(consume())
        assert await asyncio.wait_for(sched.drain(60), 90) is True
        # Drained means the request completed AND its stream was consumed.
        assert await consumer is True
        # A draining scheduler rejects new work so clients fail over.
        try:
            await sched.submit(GenRequest(prompt_ids=[9], max_tokens=1))
            raise AssertionError("submit during drain should raise")
        except RuntimeError:
            pass
    finally:
        await sched.stop()

    # Timeout path: a runner too slow to finish within the grace reports
    # False (tiny models finish 100k tokens in under the shortest useful
    # timeout, so use a deliberately slow fake).
    import time as _time

    class _Slow:
        max_slots = 1
        max_seq = 10_000

        def init_state(self):
            return {}

        def prefill(self, ids, temp, top_p, key, state=None, **kw):
            return 5, None, None, len(ids)

        def insert(self, state, slot, ks, vs, plen, tok, t, p, **kw):
            return state

        def release(self, state, slot):
            return state

        def decode_steps_device(self, state, k):
            _time.sleep(0.2)
            return np.zeros((k, 1), np.int32), state

    slow = Scheduler(_Slow(), decode_chunk=1)
    slow.start()
    try:
        req2 = GenRequest(prompt_ids=[3], max_tokens=100_000, eos_id=-1)
        await slow.submit(req2)
        await asyncio.wait_for(req2.out.get(), 30)
        assert await slow.drain(0.5) is False
    finally:
        await slow.stop()


def test_chunked_prefill_matches_monolithic():
    """prefill_begin/step/finish must produce the same first token and the
    same KV as one monolithic prefill."""
    import jax
    import jax.numpy as jnp
    from crowdllama_tpu.engine.runner import ModelRunner
    from crowdllama_tpu.models.config import get_config

    cfg = get_config("tiny-test", max_context_length=256)
    r = ModelRunner(cfg, max_slots=2, max_seq=256, dtype=jnp.float32)
    r.prefill_chunk = 32  # force several chunks
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 500, 100).tolist()  # 4 chunks (32/32/32/4)

    tok_ref, ks_ref, vs_ref, plen = r.prefill(prompt, 0.0, 1.0,
                                              jax.random.PRNGKey(3))
    job = r.prefill_begin(prompt)
    steps = 0
    while not r.prefill_step(job):
        steps += 1
    assert steps + 1 == 4
    tok, ks, vs, plen2 = r.prefill_finish(job, 0.0, 1.0, jax.random.PRNGKey(3))
    assert (tok, plen2) == (tok_ref, plen)
    np.testing.assert_allclose(
        np.asarray(ks[:, :, :, :plen], np.float32),
        np.asarray(ks_ref[:, :, :, :plen], np.float32), atol=2e-3)


async def test_chunked_admission_end_to_end():
    """A long prompt admits chunk-by-chunk through the scheduler and decodes
    the same greedy tokens as monolithic admission."""
    import jax
    import jax.numpy as jnp
    from crowdllama_tpu.engine.runner import ModelRunner
    from crowdllama_tpu.engine.scheduler import DONE, GenRequest, Scheduler
    from crowdllama_tpu.models.config import get_config

    cfg = get_config("tiny-test", max_context_length=256)
    rng = np.random.default_rng(1)
    prompt = rng.integers(1, 500, 90).tolist()

    async def serve(chunked: bool):
        r = ModelRunner(cfg, max_slots=2, max_seq=256, dtype=jnp.float32)
        if chunked:
            r.prefill_chunk = 32
        else:
            r.prefill_chunk = 0
        sched = Scheduler(r, decode_chunk=2)
        sched.start()
        try:
            req = GenRequest(prompt_ids=prompt, max_tokens=8, eos_id=-1)
            await sched.submit(req)
            toks = []
            while True:
                tok, reason = await asyncio.wait_for(req.out.get(), 60)
                if tok is DONE:
                    return toks, reason
                toks.append(tok)
        finally:
            await sched.stop()

    mono, r1 = await serve(False)
    chun, r2 = await serve(True)
    assert r1 == r2 == "length"
    assert mono == chun, (mono, chun)


async def test_short_requests_interleave_with_chunked_admission():
    """A short prompt submitted AFTER a long one must finish first: chunked
    admission reserves one slot and leaves the rest admitting."""
    import time as _time

    import jax.numpy as jnp
    from crowdllama_tpu.engine.runner import ModelRunner
    from crowdllama_tpu.engine.scheduler import DONE, GenRequest, Scheduler
    from crowdllama_tpu.models.config import get_config

    cfg = get_config("tiny-test", max_context_length=256)
    r = ModelRunner(cfg, max_slots=2, max_seq=256, dtype=jnp.float32)
    r.prefill_chunk = 32
    sched = Scheduler(r, decode_chunk=2)
    sched.start()
    try:
        rng = np.random.default_rng(7)
        long_req = GenRequest(prompt_ids=rng.integers(1, 500, 200).tolist(),
                              max_tokens=4, eos_id=-1)
        short_req = GenRequest(prompt_ids=[1, 2, 3], max_tokens=4, eos_id=-1)
        await sched.submit(long_req)
        await sched.submit(short_req)

        async def finish_time(req):
            while True:
                tok, _ = await asyncio.wait_for(req.out.get(), 120)
                if tok is DONE:
                    return _time.monotonic()

        t_long, t_short = await asyncio.gather(finish_time(long_req),
                                               finish_time(short_req))
        assert t_short <= t_long, "short request waited behind chunked prefill"
        assert sched.requests_served == 2
    finally:
        await sched.stop()


async def test_deferred_long_prompts_keep_fifo_and_dont_block_shorts():
    """Two long prompts + a short one: the short admits during the first
    long's chunked prefill, and the longs complete in submission order."""
    import time as _time

    import jax.numpy as jnp
    from crowdllama_tpu.engine.runner import ModelRunner
    from crowdllama_tpu.engine.scheduler import DONE, GenRequest, Scheduler
    from crowdllama_tpu.models.config import get_config

    cfg = get_config("tiny-test", max_context_length=256)
    r = ModelRunner(cfg, max_slots=4, max_seq=256, dtype=jnp.float32)
    r.prefill_chunk = 32
    sched = Scheduler(r, decode_chunk=2)
    sched.start()
    try:
        rng = np.random.default_rng(8)
        long1 = GenRequest(prompt_ids=rng.integers(1, 500, 180).tolist(),
                           max_tokens=3, eos_id=-1)
        long2 = GenRequest(prompt_ids=rng.integers(1, 500, 180).tolist(),
                           max_tokens=3, eos_id=-1)
        short = GenRequest(prompt_ids=[1, 2], max_tokens=3, eos_id=-1)
        for req in (long1, long2, short):
            await sched.submit(req)

        async def finish_time(req):
            while True:
                tok, _ = await asyncio.wait_for(req.out.get(), 120)
                if tok is DONE:
                    return _time.monotonic()

        t1, t2, ts = await asyncio.gather(finish_time(long1),
                                          finish_time(long2),
                                          finish_time(short))
        assert ts <= t1 <= t2, (ts, t1, t2)
        assert sched.requests_served == 3
    finally:
        await sched.stop()


@pytest.mark.parametrize("saturated,waiting,probing,want", [
    (False, "", False, 1),
    (False, "pending", False, 1),
    (False, "deferred", False, 1),
    (True, "pending", False, 8),
    (True, "", False, 8),
    (True, "", True, 1),
], ids=["free-slot", "free-slot+pending", "free-slot+deferred",
        "saturated+pending", "saturated", "spec-probe"])
def test_chunk_size_follows_slot_occupancy(saturated, waiting, probing, want):
    """A flight is ``decode_chunk`` steps long only while no slot is free:
    whatever arrives during a flight, or is queued already, could be
    admitted one step later.  At saturation there is nothing to admit into,
    and a queue of any length leaves the flight whole.  A spec probe is one
    step either way."""
    from crowdllama_tpu.engine.scheduler import (
        GenRequest,
        Scheduler,
        _SlotInfo,
    )

    class _Stub:
        max_slots = 2
        max_seq = 128

        def init_state(self):
            return {}

    sched = Scheduler(_Stub(), decode_chunk=8)
    req = GenRequest(prompt_ids=[1])
    sched.slots = [_SlotInfo(req=req), _SlotInfo(req=req) if saturated else None]
    if waiting == "pending":
        sched.pending.put_nowait(req)
    elif waiting == "deferred":
        sched._deferred.append(req)
    sched._spec_probing = probing
    assert sched._chunk_size() == want


def _flight_lengths(log: list) -> list[int]:
    return [ev[1] for ev in log if ev[0] == "steps"]


def _step_recorder(slots: int):
    """test_admission_pipeline's recording runner, noting every flight's
    length as ("steps", k)."""
    from test_admission_pipeline import _Recorder

    class _Steps(_Recorder):
        max_slots = slots

        def decode_steps_device(self, state, k):
            self.log.append(("steps", k))
            return super().decode_steps_device(state, k)

    return _Steps()


async def _next_token(req):
    tok, _ = await asyncio.wait_for(req.out.get(), 20)
    return tok


async def test_arrival_into_a_half_empty_batch_waits_two_short_flights():
    """A stream is decoding with slots free and ``decode_chunk`` 8: what a
    new request finds queued ahead of its prefill is one-step flights — at
    most the two the double buffering keeps in the air — never a chunk."""
    from crowdllama_tpu.engine.scheduler import GenRequest, Scheduler

    runner = _step_recorder(4)
    sched = Scheduler(runner, decode_chunk=8)
    sched.start()
    try:
        a = GenRequest(prompt_ids=[11, 2], max_tokens=10_000, eos_id=-1)
        await sched.submit(a)
        for _ in range(6):      # a's first token and a few flights
            await _next_token(a)
        mark = len(runner.log)
        b = GenRequest(prompt_ids=[22, 2], max_tokens=4, eos_id=-1)
        await sched.submit(b)
        await _next_token(b)
        until = runner.log.index(("prefill", 22))
        assert until >= mark
        assert len(_flight_lengths(runner.log[mark:until])) <= 2, runner.log
        assert set(_flight_lengths(runner.log)) == {1}, runner.log
    finally:
        await sched.stop()


async def test_full_flights_resume_while_every_slot_is_taken():
    """One step a flight while a slot is free, ``decode_chunk`` steps from
    the dispatch that finds every slot taken, one step again from the
    dispatch after a stream ended."""
    from test_admission_pipeline import _drain

    from crowdllama_tpu.engine.scheduler import GenRequest, Scheduler

    runner = _step_recorder(2)
    sched = Scheduler(runner, decode_chunk=8)
    sched.start()
    try:
        a = GenRequest(prompt_ids=[11, 2], max_tokens=40, eos_id=-1)
        b = GenRequest(prompt_ids=[22, 2], max_tokens=10_000, eos_id=-1)
        await sched.submit(a)
        for _ in range(4):
            await _next_token(a)
        await sched.submit(b)
        await _drain(a)
        ended = len(_flight_lengths(runner.log))
        while len(_flight_lengths(runner.log)) < ended + 4:
            await _next_token(b)
        lengths = _flight_lengths(runner.log)
        runs = [k for i, k in enumerate(lengths) if i == 0 or lengths[i - 1] != k]
        assert runs == [1, 8, 1], lengths
    finally:
        await sched.stop()


async def test_flight_length_never_changes_the_tokens():
    """Flight length is pacing only: each slot's sampling key is a carry of
    the decode state, advanced once a STEP.  The same seeded requests emit
    the same tokens at ``decode_chunk`` 1 and 8, in a batch that always has
    a slot free (one-step flights throughout) and in one they fill and then
    drain (full flights, then short ones).  (In float32 here; on the chip
    XLA's one-step and eight-step programs round bf16 differently, so a
    near-tie can fall either way whichever tree serves: PERF.md §6, PR 39.)"""
    import jax.numpy as jnp
    from test_admission_pipeline import _drain

    from crowdllama_tpu.engine.runner import ModelRunner
    from crowdllama_tpu.engine.scheduler import GenRequest, Scheduler
    from crowdllama_tpu.models.config import get_config

    cfg = get_config("tiny-test", max_context_length=128)

    def reqs():
        return [GenRequest(prompt_ids=[3, 1, 4, 1, 5], max_tokens=9, seed=7,
                           temperature=0.8, top_p=0.9, eos_id=-1),
                GenRequest(prompt_ids=[2, 7, 1, 8], max_tokens=21, eos_id=-1),
                GenRequest(prompt_ids=[9, 9, 8], max_tokens=34, seed=11,
                           temperature=1.0, top_k=20, eos_id=-1)]

    async def serve(runner, decode_chunk):
        lengths = []
        real = runner.decode_steps_device

        def noting(state, k):
            lengths.append(k)
            return real(state, k)

        runner.decode_steps_device = noting
        sched = Scheduler(runner, decode_chunk=decode_chunk)
        sched.start()
        try:
            rs = reqs()
            for r in rs:
                await sched.submit(r)
            return [(await _drain(r, 120))[0] for r in rs], set(lengths)
        finally:
            await sched.stop()
            runner.decode_steps_device = real

    for slots, want in ((4, {1}), (3, {1, 8})):
        runner = ModelRunner(cfg, max_slots=slots, max_seq=128,
                             mesh_spec="1", dtype=jnp.float32)
        assert runner.max_slots == slots
        one, _ = await serve(runner, 1)
        eight, lengths = await serve(runner, 8)
        assert [len(t) for t in one] == [9, 21, 34]
        assert eight == one, (slots, eight, one)
        assert lengths == want, (slots, lengths)


@pytest.mark.parametrize("walk", ["rectangle", "gathered"])
async def test_listed_decode_kernel_never_changes_the_tokens(monkeypatch,
                                                             walk):
    """The decode kernel's list of live page pairs changes which grid steps
    run, never a sum's order within a slot: a mixed batch — a short prompt,
    a long one admitted in ragged chunks whose context crosses pages, five
    requests on three slots so that slots are released and re-let mid-run,
    greedy and sampled — emits, in float32, the tokens it emitted when the
    kernel walked the whole slots x table rectangle (``rectangle``: the grid
    before PR 44, every slot listed for every page pair, bit for bit the
    same sums) and the tokens of the gathered jnp view (``gathered``)."""
    import jax.numpy as jnp
    from test_admission_pipeline import _drain

    from crowdllama_tpu.engine import paged
    from crowdllama_tpu.engine.scheduler import GenRequest, Scheduler
    from crowdllama_tpu.models.config import get_config
    from test_pallas import _rectangle

    cfg = get_config("tiny-test", max_context_length=128)
    long_prompt = [(7 * i + 3) % 500 + 1 for i in range(70)]

    def reqs():
        return [GenRequest(prompt_ids=[3, 1, 4, 1, 5], max_tokens=9, seed=7,
                           temperature=0.8, top_p=0.9, eos_id=-1),
                GenRequest(prompt_ids=long_prompt, max_tokens=40, eos_id=-1),
                GenRequest(prompt_ids=[9, 9, 8], max_tokens=34, seed=11,
                           temperature=1.0, top_k=20, eos_id=-1),
                GenRequest(prompt_ids=long_prompt[:33], max_tokens=12,
                           eos_id=-1),
                GenRequest(prompt_ids=[2, 7, 1, 8], max_tokens=21, eos_id=-1)]

    async def serve(kernel: bool, patched: bool):
        with monkeypatch.context() as m:
            if kernel:
                m.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
            if patched:
                m.setattr(paged, "decode_work", _rectangle)
            runner = paged.PagedModelRunner(
                cfg, max_slots=3, max_seq=128, page_size=32, mesh_spec="1",
                dtype=jnp.float32, step_token_budget=32 + 3)
            assert runner.ragged_chunk == 32
            assert (runner.attention_paths["decode"] == "jnp") != kernel
            chunks, real = [], runner.ragged_step

            def noting(state, job, k=1):
                chunks.append(k)
                return real(state, job, k)

            runner.ragged_step = noting
            sched = Scheduler(runner, decode_chunk=4)
            sched.start()
            try:
                rs = reqs()
                for r in rs:
                    await sched.submit(r)
                out = [(await _drain(r, 300))[0] for r in rs]
            finally:
                await sched.stop()
            assert sum(chunks) >= 3     # 70 tokens, 32 a ragged step
            return out

    listed = await serve(kernel=True, patched=False)
    assert [len(t) for t in listed] == [9, 40, 34, 12, 21]
    was = await serve(kernel=walk == "rectangle", patched=walk == "rectangle")
    assert listed == was


async def test_cancelled_chunked_admission_aborts_runner_job():
    """Cancelling a request mid-chunked-admission must tell the runner the
    job is abandoned (multi-host followers pin the job's KV accumulators
    until a PREFILL_ABORT frame arrives — ADVICE r4)."""
    import jax.numpy as jnp
    from crowdllama_tpu.engine.runner import ModelRunner
    from crowdllama_tpu.engine.scheduler import GenRequest, Scheduler
    from crowdllama_tpu.models.config import get_config

    cfg = get_config("tiny-test", max_context_length=256)
    r = ModelRunner(cfg, max_slots=2, max_seq=256, dtype=jnp.float32)
    r.prefill_chunk = 32
    aborted = []
    r.prefill_abort = aborted.append  # runners without it are a no-op
    # Slow each chunk down so the cancel lands mid-admission.
    real_step = r.prefill_step

    def slow_step(job):
        import time

        time.sleep(0.05)
        return real_step(job)

    r.prefill_step = slow_step
    sched = Scheduler(r, decode_chunk=2)
    sched.start()
    try:
        rng = np.random.default_rng(11)
        req = GenRequest(prompt_ids=rng.integers(1, 500, 220).tolist(),
                         max_tokens=4, eos_id=-1)
        await sched.submit(req)
        for _ in range(600):
            if sched._chunking is not None:
                break
            await asyncio.sleep(0.01)
        assert sched._chunking is not None, "chunked admission never started"
        sched.cancel(req)
        # _chunking clears before the abort's executor hop completes —
        # poll for the abort itself, not just the cleared reservation.
        for _ in range(600):
            if aborted and sched._chunking is None:
                break
            await asyncio.sleep(0.01)
        assert sched._chunking is None
        assert len(aborted) == 1, "runner was not told the job was abandoned"
        assert all(s is None for s in sched.slots)
    finally:
        await sched.stop()


async def test_chunked_admission_failure_recovers():
    """A prefill_step crash mid-chunked-admission fails that request cleanly
    and the scheduler keeps serving."""
    import jax.numpy as jnp
    from crowdllama_tpu.engine.runner import ModelRunner
    from crowdllama_tpu.engine.scheduler import DONE, GenRequest, Scheduler
    from crowdllama_tpu.models.config import get_config

    cfg = get_config("tiny-test", max_context_length=256)
    r = ModelRunner(cfg, max_slots=2, max_seq=256, dtype=jnp.float32)
    r.prefill_chunk = 32
    boom = {"armed": True}
    real_step = r.prefill_step

    def failing_step(job):
        if boom["armed"] and job.done_tokens >= 32:
            boom["armed"] = False
            raise RuntimeError("injected chunk failure")
        return real_step(job)

    r.prefill_step = failing_step
    sched = Scheduler(r, decode_chunk=2)
    sched.start()
    try:
        rng = np.random.default_rng(9)
        req = GenRequest(prompt_ids=rng.integers(1, 500, 120).tolist(),
                         max_tokens=4, eos_id=-1)
        await sched.submit(req)
        tok, reason = await asyncio.wait_for(req.out.get(), 60)
        assert tok is DONE and reason.startswith("error")
        # Scheduler recovered: a fresh request serves normally.
        req2 = GenRequest(prompt_ids=rng.integers(1, 500, 90).tolist(),
                          max_tokens=3, eos_id=-1)
        await sched.submit(req2)
        toks = []
        while True:
            tok, reason = await asyncio.wait_for(req2.out.get(), 60)
            if tok is DONE:
                break
            toks.append(tok)
        assert len(toks) == 3 and reason == "length"
        assert all(s is None for s in sched.slots)
    finally:
        await sched.stop()


async def test_stop_sequences():
    """Ollama options.stop parity: generation halts at the first stop
    sequence; the matched text (and anything after) is never emitted —
    including stops that span two decoded token chunks."""
    eng = _mkengine()
    await eng.start()
    try:
        # Greedy tiny-test output is deterministic; capture a baseline.
        base = []
        async for c in eng.generate("stop test", max_tokens=16):
            base.append(c.text)
        full = "".join(base)
        assert len(full) >= 4
        # Use a mid-output substring as the stop sequence (spans whatever
        # chunk boundary the decoder happened to produce).
        stop_seq = full[2:5]
        out, final = [], None
        async for c in eng.generate("stop test", max_tokens=16,
                                    stop=[stop_seq]):
            out.append(c.text)
            if c.done:
                final = c
        text = "".join(out)
        assert final is not None and final.done_reason == "stop"
        assert stop_seq not in text
        assert text == full[:full.find(stop_seq)]
    finally:
        await eng.stop()


async def test_top_k_sampling():
    """Ollama options.top_k parity: top_k=1 at high temperature must
    reproduce greedy decoding exactly (the distribution collapses to the
    argmax), where unrestricted sampling at that temperature diverges."""
    eng = _mkengine(mesh="1x1x1")
    await eng.start()
    try:
        async def run(**kw):
            out = []
            async for c in eng.generate("topk test", max_tokens=10, **kw):
                out.append(c.text)
            return "".join(out)

        greedy = await run(temperature=0.0)
        k1 = await run(temperature=5.0, top_k=1, seed=7)
        assert k1 == greedy, (k1, greedy)
        # Sanity: without the top_k restriction, t=5 sampling diverges
        # from greedy (astronomically unlikely to match for 10 tokens).
        free = await run(temperature=5.0, seed=7)
        assert free != greedy
    finally:
        await eng.stop()


async def test_repeat_penalty():
    """Ollama options.repeat_penalty parity: with a massive penalty over
    the last-64 window, greedy decode cannot emit the same token twice in
    a row (self-repetition is suppressed), while unpenalized greedy on the
    random tiny model typically loops."""
    eng = _mkengine(mesh="1x1x1")
    await eng.start()
    try:
        async def run_tokens(**kw):
            toks = []
            async for c in eng.generate("rp test", max_tokens=20, **kw):
                if not c.done:
                    toks.append(c.text)
            return toks

        plain = await run_tokens()
        pen = await run_tokens(repeat_penalty=1e9)
        # The huge penalty crushes any previously-seen token's logit, so
        # consecutive duplicates are impossible (window 64 > 20 tokens);
        # also verify it CHANGED something relative to plain greedy, which
        # repeats on this random model (guards against silent no-op).
        assert all(a != b for a, b in zip(pen, pen[1:])), pen
        assert len(set(pen)) == len(pen), pen  # no repeats at all in 20
        assert pen != plain or len(set(plain)) == len(plain)
    finally:
        await eng.stop()
