"""N-gram speculative decoding (engine/spec.py): greedy exactness, multi-
token acceptance on repetitive text, sampled slots unaffected, and the
scheduler's packed-emission path end to end."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crowdllama_tpu.engine.runner import ModelRunner
from crowdllama_tpu.engine.spec import SpecModelRunner
from crowdllama_tpu.models import transformer as T
from crowdllama_tpu.models.config import get_config


def _runners(draft_len=4):
    cfg = get_config("tiny-test", max_context_length=128)
    params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    base = ModelRunner(cfg, params=params, max_slots=2, max_seq=128,
                       dtype=jnp.float32)
    spec = SpecModelRunner(cfg, params=params, max_slots=2, max_seq=128,
                           dtype=jnp.float32, draft_len=draft_len)
    return base, spec


def _spec_rollout(spec, prompt, steps, temperature=0.0):
    state = spec.init_state()
    first, ks, vs, plen = spec.prefill(prompt, temperature, 1.0,
                                       jax.random.PRNGKey(7))
    state = spec.insert(state, 0, ks, vs, plen, first, temperature, 1.0,
                        prompt_tokens=prompt)
    toks = [int(first)]
    packed, state = spec.decode_steps(state, steps)
    for step in range(packed.shape[0]):
        n = int(packed[step, 0, 0])
        toks.extend(int(t) for t in packed[step, 1:1 + n, 0])
    return toks, packed


def test_spec_greedy_exactness():
    """Greedy spec decode must emit the exact tokens plain greedy decode
    does — drafts change how many tokens per dispatch, never which."""
    base, spec = _runners()
    prompt = [5, 9, 5, 9, 5, 9, 5]  # repetitive: drafts will accept

    state = base.init_state()
    first, ks, vs, plen = base.prefill(prompt, 0.0, 1.0, jax.random.PRNGKey(7))
    state = base.insert(state, 0, ks, vs, plen, first, 0.0, 1.0)
    out, state = base.decode_steps(state, 24)
    ref = [first] + [int(t) for t in out[:, 0]]

    toks, packed = _spec_rollout(spec, prompt, 24)
    n = min(len(ref), len(toks))
    assert toks[:n] == ref[:n], (toks[:n], ref[:n])


def test_spec_accepts_on_repetitive_model():
    """When the model's own greedy output repeats, drafts accept and one
    verify dispatch emits multiple tokens (the whole point).  A zeroed
    model decodes a constant token — fully predictable by its bigram."""
    cfg = get_config("tiny-test", max_context_length=128)
    params = jax.tree_util.tree_map(
        lambda a: a * 0, T.init_params(cfg, jax.random.PRNGKey(0),
                                       dtype=jnp.float32))
    spec = SpecModelRunner(cfg, params=params, max_slots=2, max_seq=128,
                           dtype=jnp.float32, draft_len=4)
    toks, packed = _spec_rollout(spec, [3, 1, 4, 1, 5], steps=6)
    counts = packed[:, 0, 0]
    assert counts.max() == 5, counts.tolist()  # 1 pending + 4 drafts
    assert sum(counts) == len(toks) - 1


def test_spec_sampled_slots_one_token_per_step():
    _, spec = _runners()
    toks, packed = _spec_rollout(spec, [3, 1, 4, 1, 5], steps=6,
                                 temperature=0.8)
    assert (packed[:, 0, 0] == 1).all()
    assert len(toks) == 7  # first + 6 steps x 1


def test_spec_history_proposals():
    """The bigram proposer drafts the continuation of the latest match."""
    _, spec = _runners(draft_len=3)
    hist = jnp.asarray([[7, 8, 21, 22, 23, 7, 8, 0, 0, 0]
                        + [0] * 118], jnp.int32)
    # cur=6: pending bigram (7, 8) matches positions 0-1 → draft 21, 22, 23.
    drafts, from_prompt = spec._propose(hist, jnp.asarray([6]),
                                        jnp.asarray([7]), spec.draft_len)
    assert drafts.tolist() == [[21, 22, 23]]
    assert bool(from_prompt[0]) is True  # matched inside the prompt region


async def test_spec_scheduler_end_to_end():
    from crowdllama_tpu.engine.scheduler import DONE, GenRequest, Scheduler

    _, spec = _runners()
    sched = Scheduler(spec, decode_chunk=4)
    sched.start()
    try:
        req = GenRequest(prompt_ids=[5, 9, 5, 9, 5], max_tokens=10, eos_id=-1)
        await sched.submit(req)
        toks = []
        while True:
            tok, reason = await asyncio.wait_for(req.out.get(), 60)
            if tok is DONE:
                break
            toks.append(tok)
        # Budget respected exactly despite multi-token spec steps.
        assert reason == "length"
        assert len(toks) == 10, toks
        assert req.out.empty()
    finally:
        await sched.stop()


async def test_spec_engine_config_path():
    from crowdllama_tpu.config import Configuration, Intervals
    from crowdllama_tpu.engine.engine import JaxEngine

    cfg = Configuration(model="tiny-test", max_context_length=128,
                        spec_decode="ngram", spec_draft=3,
                        max_batch_slots=2, warmup=False,
                        kv_layout="contiguous",
                        intervals=Intervals.default())
    eng = JaxEngine(cfg)
    await eng.start()
    try:
        n = 0
        async for c in eng.generate("abcabcabc", max_tokens=8):
            n += 1
            if c.done:
                assert c.completion_tokens == 8
                break
        d = eng.describe()
        # 8 completion tokens = 1 from prefill + >=7 from verify steps.
        assert d["spec_decode"]["tokens_emitted"] >= 7
        assert d["spec_decode"]["verify_steps"] > 0
    finally:
        await eng.stop()


# ------------------------- paged speculative decode (VERDICT r3 #4) --------


def _paged_spec_runner(params, cfg, kv_dtype="bf16", draft_len=4):
    from crowdllama_tpu.engine.spec import SpecPagedModelRunner

    return SpecPagedModelRunner(cfg, params=params, max_slots=2, max_seq=128,
                                page_size=32, mesh_spec="1",
                                kv_dtype=kv_dtype, draft_len=draft_len)


def test_paged_spec_matches_contiguous_spec():
    """Seeded greedy paged+ngram must equal contiguous+ngram token-for-token
    (same drafts, same verify results), bf16 pools."""
    cfg = get_config("tiny-test", max_context_length=128)
    params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    spec = SpecModelRunner(cfg, params=params, max_slots=2, max_seq=128,
                           dtype=jnp.float32, draft_len=4)
    prompt = [5, 9, 5, 9, 5, 9, 5]
    ref, _ = _spec_rollout(spec, prompt, 24)

    pspec = _paged_spec_runner(params, cfg)
    toks, packed = _spec_rollout(pspec, prompt, 24)
    n = min(len(ref), len(toks))
    assert toks[:n] == ref[:n], (toks[:n], ref[:n])


def test_paged_spec_accepts_on_repetitive_model():
    """A zeroed model decodes a constant token — fully predictable by its
    bigram — so the paged verify must accept whole draft windows (the
    acceptance machinery, through the page indirection)."""
    cfg = get_config("tiny-test", max_context_length=128)
    params = jax.tree_util.tree_map(
        lambda a: a * 0, T.init_params(cfg, jax.random.PRNGKey(0),
                                       dtype=jnp.float32))
    pspec = _paged_spec_runner(params, cfg, draft_len=4)
    toks, packed = _spec_rollout(pspec, [3, 1, 4, 1, 5], steps=6)
    counts = packed[:, 0, 0]
    assert counts.max() == 5, counts.tolist()  # 1 pending + 4 drafts
    assert sum(counts) == len(toks) - 1


def test_paged_spec_int8_matches_paged_greedy():
    """int8 pools: paged spec greedy tokens must equal the plain paged
    runner's greedy tokens on the SAME int8 pools (drafts change how many
    tokens per dispatch, never which — the dequantized verify context must
    agree with the int8 decode attention)."""
    from crowdllama_tpu.engine.paged import PagedModelRunner

    cfg = get_config("tiny-test", max_context_length=128)
    params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    prompt = [5, 9, 5, 9, 5, 9, 5]

    base = PagedModelRunner(cfg, params=params, max_slots=2, max_seq=128,
                            page_size=32, mesh_spec="1", kv_dtype="int8")
    state = base.init_state()
    first, ks, vs, plen = base.prefill(prompt, 0.0, 1.0,
                                       jax.random.PRNGKey(7))
    state = base.insert(state, 0, ks, vs, plen, first, 0.0, 1.0)
    out, state = base.decode_steps(state, 24)
    ref = [first] + [int(t) for t in out[:, 0]]

    pspec = _paged_spec_runner(params, cfg, kv_dtype="int8")
    toks, _ = _spec_rollout(pspec, prompt, 24)
    n = min(len(ref), len(toks))
    assert toks[:n] == ref[:n], (toks[:n], ref[:n])


# --------------------- draft-model speculation (VERDICT r3 #4 stretch) ----


def _draft_runner(params, cfg, draft_cfg, draft_params, draft_len=3):
    from crowdllama_tpu.engine.spec import DraftSpecPagedModelRunner

    return DraftSpecPagedModelRunner(
        cfg, params=params, draft_cfg=draft_cfg, draft_params=draft_params,
        max_slots=2, max_seq=128, page_size=32, mesh_spec="1",
        draft_len=draft_len)


def test_draft_spec_greedy_exactness():
    """With an UNRELATED draft model, greedy tokens still match the plain
    paged runner exactly (drafts only decide how many emit per dispatch)."""
    from crowdllama_tpu.engine.paged import PagedModelRunner

    cfg = get_config("tiny-test", max_context_length=128)
    params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    draft_cfg = get_config("tiny-test", max_context_length=128)
    draft_params = T.init_params(draft_cfg, jax.random.PRNGKey(99),
                                 dtype=jnp.float32)  # different weights
    prompt = [5, 9, 5, 9, 5, 9, 5]

    base = PagedModelRunner(cfg, params=params, max_slots=2, max_seq=128,
                            page_size=32, mesh_spec="1")
    state = base.init_state()
    first, ks, vs, plen = base.prefill(prompt, 0.0, 1.0,
                                       jax.random.PRNGKey(7))
    state = base.insert(state, 0, ks, vs, plen, first, 0.0, 1.0)
    out, state = base.decode_steps(state, 20)
    ref = [first] + [int(t) for t in out[:, 0]]

    spec = _draft_runner(params, cfg, draft_cfg, draft_params)
    toks, _ = _spec_rollout(spec, prompt, 20)
    n = min(len(ref), len(toks))
    assert toks[:n] == ref[:n], (toks[:n], ref[:n])


def test_draft_spec_accepts_when_draft_is_main():
    """Draft == main model ⇒ the draft's greedy proposals ARE the main
    model's greedy continuations, so every verify step accepts the whole
    window (the acceptance machinery through the draft cache)."""
    cfg = get_config("tiny-test", max_context_length=128)
    params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    spec = _draft_runner(params, cfg, cfg, params, draft_len=4)
    toks, packed = _spec_rollout(spec, [3, 1, 4, 1, 5], steps=6)
    counts = packed[:, 0, 0]
    assert counts.max() == 5, counts.tolist()  # 1 pending + 4 drafts
    # Full acceptance every step (identical models, greedy).
    assert all(c == 5 for c in counts.tolist()), counts.tolist()
    assert sum(counts) == len(toks) - 1


async def test_draft_spec_engine_config_path():
    """spec_decode=draft end to end: the engine builds the draft runner,
    serves, and reports acceptance telemetry with the draft model name."""
    from crowdllama_tpu.config import Configuration, Intervals
    from crowdllama_tpu.engine.engine import JaxEngine
    from crowdllama_tpu.engine.spec import DraftSpecPagedModelRunner

    cfg = Configuration(model="tiny-test", max_context_length=128,
                        spec_decode="draft", spec_draft=3,
                        spec_draft_model="tiny-test",
                        max_batch_slots=2, warmup=False,
                        intervals=Intervals.default())
    eng = JaxEngine(cfg)
    await eng.start()
    try:
        assert isinstance(eng._runner, DraftSpecPagedModelRunner)
        async for c in eng.generate("abcabcabc", max_tokens=8):
            if c.done:
                assert c.completion_tokens == 8
                break
        d = eng.describe()
        sd = d["spec_decode"]
        assert sd["mode"] == "draft"
        assert sd["draft_model"] == "tiny-test"
        assert 0.0 <= sd["acceptance_rate"] <= 1.0
        assert sd["tokens_emitted"] >= 7
    finally:
        await eng.stop()


async def test_paged_spec_engine_config_path():
    """The out-of-the-box config (kv_layout defaults to paged) + spec no
    longer downgrades the layout: the engine builds SpecPagedModelRunner
    and serves with acceptance telemetry."""
    from crowdllama_tpu.config import Configuration, Intervals
    from crowdllama_tpu.engine.engine import JaxEngine
    from crowdllama_tpu.engine.spec import SpecPagedModelRunner

    cfg = Configuration(model="tiny-test", max_context_length=128,
                        spec_decode="ngram", spec_draft=3,
                        max_batch_slots=2, warmup=False,
                        intervals=Intervals.default())
    assert cfg.kv_layout == "paged"  # the default survives
    eng = JaxEngine(cfg)
    await eng.start()
    try:
        assert isinstance(eng._runner, SpecPagedModelRunner)
        async for c in eng.generate("abcabcabc", max_tokens=8):
            if c.done:
                assert c.completion_tokens == 8
                break
        d = eng.describe()
        assert d["spec_decode"]["tokens_emitted"] >= 7
        assert d["spec_decode"]["verify_steps"] > 0
    finally:
        await eng.stop()


def test_ngram_acceptance_source_attribution():
    """propose_ngram_drafts attributes matches to prompt-echo (bigram
    inside the prompt region) vs generative (match arose in generated
    history) — the telemetry split operators read before enabling spec
    (VERDICT r4 weak #4)."""
    from crowdllama_tpu.engine.spec import propose_ngram_drafts

    s = 16
    # Slot 0: prompt [1,2,9,1], pending token 2 at position 4 — bigram
    # (1,2) matches at j=0, inside plen=5.
    # Slot 1: prompt [9,8] then generated 1,2,9,1, pending 2 at pos 6 —
    # the (1,2) match (j=2) lies past plen=2: generative.
    hist = np.zeros((2, s), np.int32)
    hist[0, :5] = [1, 2, 9, 1, 2]
    hist[1, :7] = [9, 8, 1, 2, 9, 1, 2]
    seq_lens = jnp.asarray([4, 6], jnp.int32)
    plens = jnp.asarray([5, 2], jnp.int32)
    drafts, from_prompt = propose_ngram_drafts(
        jnp.asarray(hist), seq_lens, 3, s, plens)
    assert bool(from_prompt[0]) is True
    assert bool(from_prompt[1]) is False
    # Drafts follow the matched bigram: slot 0 j=0 -> row[2:5] = 9,1,2.
    np.testing.assert_array_equal(np.asarray(drafts[0]), [9, 1, 2])


def test_packed_source_row_marks_echo_acceptance():
    """End to end: a repetitive PROMPT makes accepting steps carry source
    code 1 (prompt-echo) in the packed block's last row."""
    _, spec = _runners()
    toks, packed = _spec_rollout(spec, [3, 1, 4, 1, 5] * 4, steps=6)
    counts = packed[:, 0, 0]
    srcs = packed[:, -1, 0]
    # Wherever a draft was accepted, the source must be attributed (1 or
    # 2, never 0); steps with no acceptance must carry 0.
    assert ((counts > 1) == (srcs > 0)).all(), (counts, srcs)

# ------- distilled draft + acceptance-adaptive draft length (ISSUE 4) -----


def _unpack_into(packed, toks):
    """Append a decode chunk's tokens: packed spec layout [K, 2+J, B] or
    plain [K, B] (speculation paused) — the same branch the scheduler's
    _retire_inflight takes."""
    if packed.ndim == 3:
        for step in range(packed.shape[0]):
            n = int(packed[step, 0, 0])
            toks.extend(int(t) for t in packed[step, 1:1 + n, 0])
    else:
        toks.extend(int(t) for t in packed[:, 0])


@pytest.mark.train
def test_trained_draft_exactness_across_k_changes():
    """A DISTILLED draft through the paged draft runner emits byte-identical
    greedy tokens vs the plain paged runner — including across mid-stream
    ``set_draft_len`` retunes (3 -> 1 -> 0 pause -> 4 resume), exactly the
    transitions the scheduler's adaptive controller applies."""
    from crowdllama_tpu.engine.paged import PagedModelRunner
    from crowdllama_tpu.train.distill import DistillConfig, distill_draft

    cfg = get_config("tiny-test", max_context_length=128)
    params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    res = distill_draft(
        DistillConfig(steps=30, batch=8, seq_len=32, corpus_seqs=16,
                      log_every=0),
        teacher_cfg=cfg, teacher_params=params)
    prompt = [5, 9, 5, 9, 5, 9, 5]

    base = PagedModelRunner(cfg, params=params, max_slots=2, max_seq=128,
                            page_size=32, mesh_spec="1")
    state = base.init_state()
    first, ks, vs, plen = base.prefill(prompt, 0.0, 1.0,
                                       jax.random.PRNGKey(7))
    state = base.insert(state, 0, ks, vs, plen, first, 0.0, 1.0)
    out, state = base.decode_steps(state, 40)
    ref = [first] + [int(t) for t in out[:, 0]]

    spec = _draft_runner(params, cfg, res["draft_config"],
                         res["draft_params"], draft_len=3)
    sstate = spec.init_state()
    sfirst, ks, vs, plen = spec.prefill(prompt, 0.0, 1.0,
                                        jax.random.PRNGKey(7))
    sstate = spec.insert(sstate, 0, ks, vs, plen, sfirst, 0.0, 1.0,
                         prompt_tokens=prompt)
    toks = [sfirst]
    for steps, k in ((8, 3), (6, 1), (6, 0), (6, 4)):
        spec.set_draft_len(k)
        packed, sstate = spec.decode_steps(sstate, steps)
        _unpack_into(packed, toks)
    n = min(len(ref), len(toks))
    assert n > 20
    assert toks[:n] == ref[:n], (toks[:n], ref[:n])


def test_adaptive_retune_shrinks_geometrically_to_pause():
    """Zero acceptance shrinks draft_len geometrically (4 -> 2 -> 1 -> 0)
    once each window holds >= 2k offered draft tokens; at 0 the runner
    dispatches the plain program (speculation paused)."""
    from crowdllama_tpu.engine.scheduler import Scheduler

    _, spec = _runners(draft_len=4)
    sched = Scheduler(spec, spec_draft_max=8)
    assert sched._spec_adaptive
    for expect in (2, 1, 0):
        sched._spec_retune(0, 2 * max(1, spec.draft_len))
        assert spec.draft_len == expect, expect
    assert sched.spec_retunes == 3
    # Below-threshold evidence must NOT move k.
    spec.set_draft_len(4)
    sched._spec_retune(0, 3)  # < 2*4 offered
    assert spec.draft_len == 4


def test_adaptive_retune_grows_toward_max():
    """Full acceptance grows draft_len linearly, capped at spec_draft_max."""
    from crowdllama_tpu.engine.scheduler import Scheduler

    _, spec = _runners(draft_len=2)
    sched = Scheduler(spec, spec_draft_max=4)
    for expect in (3, 4, 4):  # capped at max
        off = 2 * max(1, spec.draft_len)
        sched._spec_retune(off, off)
        assert spec.draft_len == expect, expect
    assert sched.spec_retunes == 2


async def test_adaptive_pause_probe_arming():
    """Paused speculation re-samples acceptance: after spec_probe_interval
    plain decode steps the controller arms a k=1 probe and shrinks the
    next dispatch to a single step."""
    import time as _time

    from crowdllama_tpu.engine.scheduler import Scheduler, _InFlightChunk

    _, spec = _runners(draft_len=4)
    sched = Scheduler(spec, spec_draft_max=8)
    spec.set_draft_len(0)  # as if the controller paused it
    loop = asyncio.get_running_loop()
    plain = np.zeros((sched.spec_probe_interval, 2), np.int32)  # [K, B]
    sched._inflight = _InFlightChunk(plain, [None, None], _time.monotonic())
    await sched._retire_inflight(loop)
    assert sched._spec_probing
    assert sched.spec_probes == 1
    assert spec.draft_len == 1
    assert sched._chunk_size() == 1
    # The probe's retune decision clears the probing state either way.
    sched._spec_retune(2, 2)
    assert not sched._spec_probing
    assert spec.draft_len == 2  # probe accepted -> resume and grow


async def test_adaptive_k_grows_end_to_end():
    """Scheduler end to end on a fully-predictable (zeroed) model: the
    controller grows draft_len from 1 toward spec_draft_max as windows
    fully accept."""
    from crowdllama_tpu.engine.scheduler import DONE, GenRequest, Scheduler

    cfg = get_config("tiny-test", max_context_length=128)
    params = jax.tree_util.tree_map(
        lambda a: a * 0, T.init_params(cfg, jax.random.PRNGKey(0),
                                       dtype=jnp.float32))
    spec = SpecModelRunner(cfg, params=params, max_slots=2, max_seq=128,
                           dtype=jnp.float32, draft_len=1)
    sched = Scheduler(spec, decode_chunk=4, spec_draft_max=3)
    sched.start()
    try:
        req = GenRequest(prompt_ids=[3, 1, 4, 1, 5], max_tokens=48,
                         eos_id=-1)
        await sched.submit(req)
        while True:
            tok, _ = await asyncio.wait_for(req.out.get(), 60)
            if tok is DONE:
                break
        assert spec.draft_len > 1
        assert spec.draft_len <= 3
        assert sched.spec_retunes >= 1
        g = sched.telemetry_gauges()
        assert g["spec_draft_len"] == float(spec.draft_len)
        assert g["spec_accept_echo"] + g["spec_accept_gen"] > 0
    finally:
        await sched.stop()


def test_paused_spec_throughput_matches_plain_paged():
    """The ISSUE 4 cost guard: with a USELESS (random) draft the adaptive
    controller pauses speculation, and the paused runner must dispatch
    what the plain paged runner dispatches — the parent's own decode
    program, once a flight, and neither the verify nor the draft program —
    so it costs the device nothing per emitted token."""
    from crowdllama_tpu.engine.paged import PagedModelRunner

    cfg = get_config("tiny-test", max_context_length=128)
    params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    draft_cfg = get_config("tiny-test", max_context_length=128)
    draft_params = T.init_params(draft_cfg, jax.random.PRNGKey(99),
                                 dtype=jnp.float32)
    prompt = [5, 9, 5, 9, 5, 9, 5]
    jitted = type(jax.jit(lambda: None))

    def _count_dispatches(runner, flights=3, steps=8):
        state = runner.init_state()
        first, ks, vs, plen = runner.prefill(prompt, 0.0, 1.0,
                                             jax.random.PRNGKey(7))
        kw = {"prompt_tokens": prompt} if hasattr(runner, "set_draft_len") \
            else {}
        state = runner.insert(state, 0, ks, vs, plen, first, 0.0, 1.0, **kw)
        # Count every call of every jitted entry point the runner holds.
        calls: dict[str, int] = {}
        for name, fn in list(vars(runner).items()):
            if isinstance(fn, jitted):
                def counted(*a, _name=name, _fn=fn, **k):
                    calls[_name] = calls.get(_name, 0) + 1
                    return _fn(*a, **k)
                setattr(runner, name, counted)
        emitted = []
        for _ in range(flights):
            out, state = runner.decode_steps(state, steps)
            emitted.append(np.asarray(out)[:, 0])
        emitted = np.concatenate(emitted)
        assert emitted.shape == (flights * steps,)
        return calls, emitted

    plain = PagedModelRunner(cfg, params=params, max_slots=2, max_seq=128,
                             page_size=32, mesh_spec="1")
    plain_calls, plain_tokens = _count_dispatches(plain)

    spec = _draft_runner(params, cfg, draft_cfg, draft_params, draft_len=3)
    spec.set_draft_len(0)  # what the controller converges to here
    spec_calls, spec_tokens = _count_dispatches(spec)

    # One device program a flight, the same one, for the same tokens.
    assert plain_calls == {"_decode_paged": 3}
    assert spec_calls == plain_calls
    np.testing.assert_array_equal(spec_tokens, plain_tokens)
