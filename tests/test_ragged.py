"""Unified ragged-paged batch (engine/paged.py ragged_* + scheduler
unified dispatch): chunked-ragged prefill + decode must be BYTE-identical
to the monolithic prefill path for the same prompt/seed — including while
other slots decode in the same dispatch, with a distilled spec draft
active, and across a mid-prefill draft-len retune.  bf16 pools make the
pool round-trip exact, so every assertion here is array_equal, not
allclose."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np

from crowdllama_tpu.engine.paged import PagedModelRunner
from crowdllama_tpu.models import transformer as T
from crowdllama_tpu.models.config import get_config

KEY = jax.random.PRNGKey(0)


def _mono_insert(runner, state, slot, prompt):
    first, ks, vs, plen = runner.prefill(prompt, 0.0, 1.0, KEY)
    state = runner.insert(state, slot, ks, vs, plen, first, 0.0, 1.0,
                          prompt_tokens=prompt)
    return first, state


def _ragged_insert(runner, state, slot, prompt, num_steps=1):
    """Drive one prompt through ragged_begin/step/finish; returns the
    first token, the new state, and the number of chunk dispatches."""
    job = runner.ragged_begin(prompt, slot, state=state)
    n = 0
    while not job.finished:
        _, state = runner.ragged_step(state, job, num_steps=num_steps)
        n += 1
    first, state = runner.ragged_finish(state, job, 0.0, 1.0, KEY)
    return first, state, n


def test_ragged_mixed_batch_matches_monolithic():
    """Decode slots keep advancing while a third slot chunk-prefills in
    the SAME dispatches, and every row — the concurrent decode rows, the
    ex-prefill slot's stream — is byte-identical to the monolithic
    sequence of the same events."""
    cfg = get_config("tiny-test", max_context_length=512)
    params = T.init_params(cfg, KEY, dtype=jnp.bfloat16)
    short = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8]]
    long_prompt = [int(x) % cfg.vocab_size for x in range(17, 17 + 200)]

    mr = PagedModelRunner(cfg, params=params, max_slots=4, max_seq=512,
                          page_size=32, mesh_spec="1")
    ms = mr.init_state()
    for slot, p in enumerate(short):
        _, ms = _mono_insert(mr, ms, slot, p)
    toks, ms = mr.decode_steps(ms, 4)
    mono_pre = np.asarray(toks)
    tL, ms = _mono_insert(mr, ms, 2, long_prompt)
    toks, ms = mr.decode_steps(ms, 4)
    mono_post = np.asarray(toks)

    rr = PagedModelRunner(cfg, params=params, max_slots=4, max_seq=512,
                          page_size=32, mesh_spec="1")
    rs = rr.init_state()
    for slot, p in enumerate(short):
        _, rs = _mono_insert(rr, rs, slot, p)
    toks, rs = rr.decode_steps(rs, 4)
    np.testing.assert_array_equal(np.asarray(toks), mono_pre)

    job = rr.ragged_begin(long_prompt, 2, state=rs)
    chunk_rows = []
    while not job.finished:
        toks, rs = rr.ragged_step(rs, job, num_steps=1)
        chunk_rows.append(np.asarray(toks))
    first, rs = rr.ragged_finish(rs, job, 0.0, 1.0, KEY)
    assert first == tL, (first, tL)
    toks, rs = rr.decode_steps(rs, 4)

    # Rows 0/1 of the chunk dispatches are the decode slots advancing —
    # they must continue the exact monolithic decode streams.
    ragged_rows = np.concatenate(
        [t[:, :2] for t in chunk_rows] + [np.asarray(toks)[:, :2]], axis=0)
    extra, ms = mr.decode_steps(ms, ragged_rows.shape[0] - 4)
    mono_rows = np.concatenate([mono_post[:, :2],
                                np.asarray(extra)[:, :2]], axis=0)
    np.testing.assert_array_equal(ragged_rows, mono_rows)
    # The ex-prefill slot's own decode stream matches too.
    np.testing.assert_array_equal(np.asarray(toks)[:4, 2], mono_post[:4, 2])


def test_ragged_multi_chunk_batching_and_prefix_reuse():
    """A 1200-token prompt needs ceil(1200/512)=3 chunk dispatches; the
    result is byte-identical to one-shot monolithic prefill whether the
    chunks go one per dispatch or batched num_steps=2 per dispatch.  An
    abort mid-prefill leaves the indexed pages prefix-cached, so the
    resubmit reuses every completed full page."""
    cfg = get_config("tiny-test", max_context_length=2048)
    params = T.init_params(cfg, KEY, dtype=jnp.bfloat16)
    prompt = [int(x) % cfg.vocab_size for x in range(23, 23 + 1200)]

    mr = PagedModelRunner(cfg, params=params, max_slots=2, max_seq=2048,
                          page_size=64, mesh_spec="1")
    ms = mr.init_state()
    tM, ms = _mono_insert(mr, ms, 0, prompt)
    mtoks = np.asarray(mr.decode_steps(ms, 6)[0])[:, 0]

    rr = PagedModelRunner(cfg, params=params, max_slots=2, max_seq=2048,
                          page_size=64, mesh_spec="1")
    rs = rr.init_state()
    first, rs, n = _ragged_insert(rr, rs, 0, prompt)
    assert n == 3, n
    assert first == tM, (first, tM)
    rtoks = np.asarray(rr.decode_steps(rs, 6)[0])[:, 0]
    np.testing.assert_array_equal(rtoks, mtoks)

    # num_steps=2: two chunks per dispatch, same bytes.
    rs = rr.init_state()
    first, rs, n = _ragged_insert(rr, rs, 0, prompt, num_steps=2)
    assert n == 2, n
    assert first == tM
    np.testing.assert_array_equal(
        np.asarray(rr.decode_steps(rs, 6)[0])[:, 0], mtoks)

    # Abort after one chunk; resubmit reuses the completed full pages
    # ((512-1)//64 = 7 pages = 448 tokens) and still matches bytewise.
    rs = rr.init_state()
    job = rr.ragged_begin(prompt, 0, state=rs)
    _, rs = rr.ragged_step(rs, job, num_steps=1)
    rr.ragged_abort(job)
    assert rr._ragged_slot is None
    reused0 = rr.prefix_tokens_reused
    job = rr.ragged_begin(prompt, 1, state=rs)
    assert rr.prefix_tokens_reused - reused0 >= 448
    while not job.finished:
        _, rs = rr.ragged_step(rs, job, num_steps=1)
    first, rs = rr.ragged_finish(rs, job, 0.0, 1.0, KEY)
    assert first == tM
    np.testing.assert_array_equal(
        np.asarray(rr.decode_steps(rs, 6)[0])[:, 1], mtoks)


def _spec_decode_toks(runner, state, steps):
    """Unpack the spec runners' packed [K, 2+J, B] emission block for
    slot 0 (same walk the scheduler does)."""
    packed, state = runner.decode_steps(state, steps)
    toks = []
    for step in range(packed.shape[0]):
        n = int(packed[step, 0, 0])
        toks.extend(int(t) for t in packed[step, 1:1 + n, 0])
    return toks, state


def test_ragged_with_draft_spec_matches_monolithic():
    """Chunked-ragged prefill under a distilled-draft spec runner: the
    draft cache is filled at ragged_finish exactly as insert() fills it,
    so the verify stream is byte-identical to the monolithic path.  A
    small step_token_budget forces multi-chunk on a short prompt (and
    covers the budget plumbing)."""
    from crowdllama_tpu.engine.spec import DraftSpecPagedModelRunner

    cfg = get_config("tiny-test", max_context_length=256)
    params = T.init_params(cfg, KEY, dtype=jnp.bfloat16)
    prompt = [int(x) % cfg.vocab_size for x in range(5, 5 + 150)]
    kw = dict(draft_cfg=cfg, draft_params=params, draft_len=3,
              max_slots=2, max_seq=256, page_size=32, mesh_spec="1",
              step_token_budget=96)

    mspec = DraftSpecPagedModelRunner(cfg, params=params, **kw)
    assert mspec.ragged_chunk == 64, mspec.ragged_chunk
    ms = mspec.init_state()
    tM, ms = _mono_insert(mspec, ms, 0, prompt)
    mono, ms = _spec_decode_toks(mspec, ms, 6)

    rspec = DraftSpecPagedModelRunner(cfg, params=params, **kw)
    rs = rspec.init_state()
    first, rs, n = _ragged_insert(rspec, rs, 0, prompt)
    assert n == 3, n  # ceil(150/64)
    assert first == tM, (first, tM)
    rag, rs = _spec_decode_toks(rspec, rs, 6)
    assert rag == mono, (rag, mono)
    # draft == main params: the draft cache must be warm enough to accept
    # beyond one token per dispatch (the whole point of the draft).
    assert len(rag) > 6, rag


def test_ragged_across_mid_prefill_retune():
    """An adaptive-k retune landing BETWEEN chunk dispatches (speculation
    is paused batch-wide during ragged prefill, so that is the only place
    one can land) must not change a single emitted byte."""
    from crowdllama_tpu.engine.spec import SpecPagedModelRunner

    cfg = get_config("tiny-test", max_context_length=256)
    params = T.init_params(cfg, KEY, dtype=jnp.bfloat16)
    prompt = [5, 9] * 75  # repetitive: the bigram proposer will accept
    kw = dict(max_slots=2, max_seq=256, page_size=32, mesh_spec="1",
              draft_len=3, step_token_budget=96)

    mspec = SpecPagedModelRunner(cfg, params=params, **kw)
    ms = mspec.init_state()
    tM, ms = _mono_insert(mspec, ms, 0, prompt)
    mono, ms = _spec_decode_toks(mspec, ms, 6)

    rspec = SpecPagedModelRunner(cfg, params=params, **kw)
    rs = rspec.init_state()
    job = rspec.ragged_begin(prompt, 0, state=rs)
    retunes = [0, 2, 3]  # pause, shrink, restore — one per chunk gap
    while not job.finished:
        rspec.set_draft_len(retunes.pop(0) if retunes else 3)
        _, rs = rspec.ragged_step(rs, job, num_steps=1)
    rspec.set_draft_len(3)
    first, rs = rspec.ragged_finish(rs, job, 0.0, 1.0, KEY)
    assert first == tM, (first, tM)
    rag, rs = _spec_decode_toks(rspec, rs, 6)
    assert rag == mono, (rag, mono)


async def test_ragged_scheduler_streams_identical():
    """End to end: the scheduler's unified ragged admission must produce
    the same token streams as the legacy chunked-prefill path."""
    from crowdllama_tpu.engine.scheduler import DONE, GenRequest, Scheduler

    cfg = get_config("tiny-test", max_context_length=2048)
    params = T.init_params(cfg, KEY, dtype=jnp.bfloat16)

    async def run_once(ragged):
        runner = PagedModelRunner(cfg, params=params, max_slots=4,
                                  max_seq=2048, page_size=64, mesh_spec="1")
        sched = Scheduler(runner, decode_chunk=4, ragged=ragged)
        sched.start()
        try:
            reqs = [
                GenRequest(prompt_ids=[3, 1, 4, 1, 5], max_tokens=12,
                           seed=7),
                GenRequest(prompt_ids=list(range(11, 11 + 900)),
                           max_tokens=12, seed=9),
                GenRequest(prompt_ids=[2, 7, 1, 8], max_tokens=12, seed=5),
            ]
            for r in reqs:
                await sched.submit(r)
            outs = []
            for r in reqs:
                toks = []
                while True:
                    tok, reason = await asyncio.wait_for(r.out.get(), 120)
                    if tok is DONE:
                        outs.append((toks, reason))
                        break
                    toks.append(tok)
            return outs, sched.ragged_chunks
        finally:
            await sched.stop()

    a, chunks = await run_once(ragged=True)
    assert chunks >= 2, chunks  # the 900-token prompt alone needs 2
    b, legacy_chunks = await run_once(ragged=False)
    assert legacy_chunks == 0
    for (ta, ra), (tb, rb) in zip(a, b):
        assert ra == rb, (ra, rb)
        assert ta == tb, (ta, tb)
