"""Kernel-looped decode megastep (docs/MEGASTEP.md): K full decode steps
per host dispatch with on-device sampling and done-flags.

The contract under test is BYTE-IDENTITY: for any K, the megastep path
must emit exactly the token streams the legacy one-chunk-per-dispatch
path emits — through the raw runner API, through the scheduler (plain,
ragged mixed-batch, and spec-adaptive runs), and across a chaos drain
landing at a megastep boundary.  What K buys is economy, not different
bytes: host dispatches per token drop ~K×, which the
host_dispatches_total / tokens_per_dispatch pair makes observable.

Compile economy matters here as much as in production: runners (and
their jitted-program caches) are shared at module scope — safe because
every test builds fresh per-test state (decode_megastep donates its
input), and the scheduler runs share one runner because every prompt is
shorter than a KV page (32), so no prefix pages index between runs.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crowdllama_tpu.engine.paged import PagedModelRunner
from crowdllama_tpu.engine.runner import ModelRunner
from crowdllama_tpu.models import transformer as T
from crowdllama_tpu.models.config import get_config

KEY = jax.random.PRNGKey(0)


def _insert(runner, state, slot, prompt):
    first, ks, vs, plen = runner.prefill(prompt, 0.0, 1.0, KEY)
    state = runner.insert(state, slot, ks, vs, plen, first, 0.0, 1.0,
                          prompt_tokens=prompt)
    return first, state


@pytest.fixture(scope="module")
def tiny128():
    cfg = get_config("tiny-test", max_context_length=128)
    return cfg, T.init_params(cfg, KEY, dtype=jnp.float32)


@pytest.fixture(scope="module", params=["contiguous", "paged"])
def runner_pair(request, tiny128):
    """One (kind, ctrl, mega) runner pair per kind for the whole module.
    A PAIR, not one instance: the paged runner's host-side page table is
    per-instance, so the control and megastep states need their own."""
    cfg, params = tiny128
    kw = dict(max_slots=2, max_seq=128, dtype=jnp.float32)
    if request.param == "paged":
        mk = lambda: PagedModelRunner(cfg, params=params, page_size=32,
                                      mesh_spec="1", **kw)
    else:
        mk = lambda: ModelRunner(cfg, params=params, mesh_spec="1", **kw)
    return request.param, mk(), mk()


# ------------------------------------------------------------ runner units


@pytest.mark.parametrize("k", [1, 4, 8])
def test_megastep_matches_per_step_runner(runner_pair, k):
    """decode_megastep(state, K) emits the exact token block K chained
    decode_steps dispatches emit — on both runner kinds, at K ∈ {1,4,8}."""
    _, ctrl, mega = runner_pair
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8]]

    cs, ms = ctrl.init_state(), mega.init_state()
    for slot, p in enumerate(prompts):
        fc, cs = _insert(ctrl, cs, slot, p)
        fm, ms = _insert(mega, ms, slot, p)
        assert fc == fm
    ctoks, cs = ctrl.decode_steps(cs, k)
    mtoks, done, ms = mega.decode_megastep(ms, k)
    np.testing.assert_array_equal(np.asarray(mtoks), np.asarray(ctoks))
    # No EOS ids and NO_BUDGET defaults: nothing may have fired.
    assert not np.asarray(done).any()
    # The returned state keeps decoding identically (megastep leaves no
    # residue a later dispatch could see).
    ctoks, _ = ctrl.decode_steps(cs, 4)
    mtoks, done, _ = mega.decode_megastep(ms, 4)
    np.testing.assert_array_equal(np.asarray(mtoks), np.asarray(ctoks))


def test_megastep_done_flags_and_early_exit(runner_pair):
    """Per-slot budgets fire the done flag exactly once at the retiring
    step; when every live slot has fired, the loop exits — trailing
    rows are zero — and the rows BEFORE the exit are still byte-identical
    to the per-step control (slots run hot after their own flag)."""
    _, ctrl, mega = runner_pair
    prompts = [[3, 1, 4, 1, 5], [2, 7, 1, 8]]

    cs, ms = ctrl.init_state(), mega.init_state()
    for slot, p in enumerate(prompts):
        _, cs = _insert(ctrl, cs, slot, p)
        _, ms = _insert(mega, ms, slot, p)
    ctoks = np.asarray(ctrl.decode_steps(cs, 8)[0])
    budgets = np.array([3, 2], np.int32)
    mtoks, done, _ = mega.decode_megastep(
        ms, 8, budgets=budgets)
    mtoks, done = np.asarray(mtoks), np.asarray(done)
    # Budget b retires at step index b-1; one fire per slot.
    fired = [tuple(np.nonzero(done[:, s])[0]) for s in range(2)]
    assert fired == [(2,), (1,)], fired
    # Up to the whole-batch exit (after step index 2) every row matches.
    np.testing.assert_array_equal(mtoks[:3], ctoks[:3])
    # Past it the loop exited: zero tokens, no flags.
    assert not mtoks[3:].any() and not done[3:].any()


def test_megastep_eos_flag_matches_emitted_token(runner_pair):
    """An eos_ids entry fires the flag on the exact step the token equals
    it — the device-side twin of the scheduler's _emit check."""
    _, _, mega = runner_pair

    def fresh_state():
        # The megastep donates its input state, so the replay needs its
        # own (deterministic prefill: byte-identical) copy.
        ms = mega.init_state()
        _, ms = _insert(mega, ms, 0, [3, 1, 4, 1, 5, 9, 2, 6])
        return ms

    toks, _, _ = mega.decode_megastep(fresh_state(), 8)
    toks = np.asarray(toks)
    # Replay with the 4th emitted token as slot 0's EOS id.
    eos = np.array([int(toks[3, 0]), -1], np.int32)
    etoks, done, _ = mega.decode_megastep(fresh_state(), 8, eos_ids=eos)
    etoks, done = np.asarray(etoks), np.asarray(done)
    hits = np.nonzero(done[:, 0])[0]
    assert len(hits) == 1 and int(hits[0]) == int(
        np.nonzero(toks[:, 0] == eos[0])[0][0])
    np.testing.assert_array_equal(etoks[: hits[0] + 1], toks[: hits[0] + 1])


def test_megastep_compile_buckets_per_k(runner_pair):
    """Each K claims exactly ONE new (program, K) compile signature per
    runner kind — decode_megastep / decode_megastep_paged — and re-running
    a claimed K never recompiles (xla_compiles_total stays flat)."""
    from crowdllama_tpu.obs.metrics import ENGINE_TELEMETRY

    kind, _, mega = runner_pair
    program = ("decode_megastep_paged" if kind == "paged"
               else "decode_megastep")
    ms = mega.init_state()
    _, ms = _insert(mega, ms, 0, [3, 1, 4, 1, 5])
    # K values no other test dispatches: ENGINE_TELEMETRY is a
    # process-global singleton and counts each signature ONCE.  (The two
    # kinds may share K — the program name disambiguates the key.)
    before = ENGINE_TELEMETRY.snapshot_compiles()
    _, _, ms = mega.decode_megastep(ms, 5)
    after = ENGINE_TELEMETRY.snapshot_compiles()
    new = {k for k in after if k not in before
           and k[0].startswith("decode_megastep")}
    assert new == {(program, "5")}, (kind, new)
    # A different K is a different static signature...
    _, _, ms = mega.decode_megastep(ms, 3)
    again = ENGINE_TELEMETRY.snapshot_compiles()
    assert again[(program, "3")] == 1
    # ...but a repeat of a claimed K is cached.
    _, _, ms = mega.decode_megastep(ms, 5)
    assert ENGINE_TELEMETRY.snapshot_compiles()[(program, "5")] == \
        after[(program, "5")]


# ---------------------------------------------------- fused ragged runner

# Params for the fused ragged-megastep units: a 512-token context fits a
# 300-token prompt that CANNOT finish chunking inside K <= 8 steps of the
# 32-token ragged chunk below, so the per-step control never has to call
# ragged_step on a finished job.  bf16 pools: every assertion is
# array_equal (see tests/test_ragged.py).
_RAGGED = {}


def _ragged_pair():
    if "cfg" not in _RAGGED:
        _RAGGED["cfg"] = get_config("tiny-test", max_context_length=512)
        _RAGGED["params"] = T.init_params(_RAGGED["cfg"], KEY,
                                          dtype=jnp.bfloat16)
    mk = lambda: PagedModelRunner(
        _RAGGED["cfg"], params=_RAGGED["params"], max_slots=4, max_seq=512,
        page_size=32, mesh_spec="1", step_token_budget=36,
        prefix_cache=False)
    return mk(), mk()


@pytest.mark.parametrize("k", [1, 4, 8])
def test_ragged_megastep_matches_per_step_runner(k):
    """ragged_megastep(state, job, K) emits the exact [K, B] token block
    K chained single-step ragged_step dispatches emit while a prefill
    chunk is advancing in the same flights — even though the fused
    dispatch provisions all K chunks up front and therefore runs at a
    WIDER density-proportional page-table window than the control's
    early dispatches (the window is bitwise-invisible by design), and
    the chunk-slot bookkeeping (done_tokens, last_logits) lands
    identically."""
    ctrl, mega = _ragged_pair()
    c = ctrl.ragged_chunk
    assert c == 32
    vocab = _RAGGED["cfg"].vocab_size
    prompt = [int(x) % vocab for x in range(17, 17 + 300)]
    short = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8]]

    cs, ms = ctrl.init_state(), mega.init_state()
    for slot, p in enumerate(short):
        fc, cs = _insert(ctrl, cs, slot, p)
        fm, ms = _insert(mega, ms, slot, p)
        assert fc == fm
    cjob = ctrl.ragged_begin(prompt, 2, state=cs)
    mjob = mega.ragged_begin(prompt, 2, state=ms)

    crows = []
    for _ in range(k):
        toks, cs = ctrl.ragged_step(cs, cjob, num_steps=1)
        crows.append(np.asarray(toks))
    mtoks, done, ms = mega.ragged_megastep(ms, mjob, k)
    np.testing.assert_array_equal(np.asarray(mtoks),
                                  np.concatenate(crows, axis=0))
    # NO_BUDGET / no-EOS defaults: nothing fires, and the in-flight
    # chunk pins the loop open — all K rows carry real decode tokens.
    assert not np.asarray(done).any()
    assert mjob.done_tokens == cjob.done_tokens == k * c

    # Both paths finish the prompt (fused keeps using the fused entry)
    # and hand the SAME stream on: first sampled token and the next
    # decode block match byte for byte.
    while not cjob.finished:
        _, cs = ctrl.ragged_step(cs, cjob, num_steps=1)
    while not mjob.finished:
        _, _, ms = mega.ragged_megastep(ms, mjob, 1)
    fc, cs = ctrl.ragged_finish(cs, cjob, 0.0, 1.0, KEY)
    fm, ms = mega.ragged_finish(ms, mjob, 0.0, 1.0, KEY)
    assert fc == fm
    ctoks, _ = ctrl.decode_steps(cs, 4)
    mtoks, done, _ = mega.decode_megastep(ms, 4)
    np.testing.assert_array_equal(np.asarray(mtoks), np.asarray(ctoks))


# ------------------------------------------------------- scheduler streams


async def _drain_streams(sched, reqs):
    from crowdllama_tpu.engine.scheduler import DONE

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
    return outs


async def _sched_run(runner, megastep_k, reqs, **sched_kw):
    from crowdllama_tpu.engine.scheduler import Scheduler

    sched = Scheduler(runner, megastep_k=megastep_k, **sched_kw)
    sched.start()
    try:
        outs = await _drain_streams(sched, reqs)
        return outs, sched.host_dispatches, sched.telemetry_gauges()
    finally:
        await sched.stop()


# One runner (and its compiled programs) for the control AND every K,
# plus the control run computed once: every prompt below is shorter
# than a KV page (32), so no prefix pages index between runs and each
# Scheduler sees identical admission behavior.
_SCHED = {}


def _sched_runner():
    if "runner" not in _SCHED:
        cfg = get_config("tiny-test", max_context_length=512)
        params = T.init_params(cfg, KEY, dtype=jnp.bfloat16)
        _SCHED["runner"] = PagedModelRunner(cfg, params=params, max_slots=4,
                                            max_seq=512, page_size=32,
                                            mesh_spec="1")
    return _SCHED["runner"]


def _sched_reqs():
    from crowdllama_tpu.engine.scheduler import GenRequest

    return [GenRequest(prompt_ids=[3, 1, 4, 1, 5], max_tokens=24, seed=7),
            GenRequest(prompt_ids=[2, 7, 1, 8], max_tokens=17, seed=5),
            GenRequest(prompt_ids=list(range(11, 31)), max_tokens=9,
                       seed=3)]


async def _sched_base():
    if "base" not in _SCHED:
        _SCHED["base"] = await _sched_run(_sched_runner(), 0, _sched_reqs(),
                                          decode_chunk=1)
    return _SCHED["base"]


@pytest.mark.parametrize("k", [1, 4, 8])
async def test_megastep_scheduler_streams_identical(k):
    """End to end through the scheduler: megastep_k ∈ {1,4,8} emits the
    exact streams the PER-STEP control (decode_chunk=1, megastep off)
    emits, while host dispatches drop ≥ K/2× at K=4+ and the
    dispatch-economy gauges move."""
    base, base_disp, _ = await _sched_base()
    mega, mega_disp, gauges = await _sched_run(_sched_runner(), k,
                                               _sched_reqs(), decode_chunk=1)
    assert mega == base, (k, mega, base)
    assert gauges["host_dispatches_total"] == float(mega_disp)
    # The gauge mirrors the LAST retired flight: a trailing pipelined
    # flight can legitimately retire empty, so presence + sanity only.
    assert gauges["tokens_per_dispatch"] >= 0.0
    if k >= 4:
        # ISSUE acceptance: ≥ K/2 reduction in host dispatches per token
        # vs the per-step control (token totals are equal, so the
        # dispatch ratio IS the per-token ratio).
        assert base_disp / mega_disp >= k / 2, (base_disp, mega_disp)


async def test_megastep_ragged_mixed_batch_streams_identical():
    """A long prompt chunk-prefilling mid-stream (unified ragged batch)
    forces the scheduler to interleave ragged dispatches with megasteps —
    the streams must still match the legacy path byte for byte.

    One SHARED runner for both runs (compiles once): prefix_cache=False,
    or the 200-token prompt would index its pages in run 1 and hand run
    2 a cached-context prefill instead of the chunked admission under
    test.  A tight step_token_budget (ragged_chunk = 64) keeps the
    compiled chunk small and still forces multi-chunk admission."""
    from crowdllama_tpu.engine.scheduler import GenRequest, Scheduler

    cfg = get_config("tiny-test", max_context_length=256)
    params = T.init_params(cfg, KEY, dtype=jnp.bfloat16)
    runner = PagedModelRunner(cfg, params=params, max_slots=4,
                              max_seq=256, page_size=32, mesh_spec="1",
                              step_token_budget=96, prefix_cache=False)

    def reqs():
        return [GenRequest(prompt_ids=[3, 1, 4, 1, 5], max_tokens=16,
                           seed=7),
                GenRequest(prompt_ids=list(range(11, 11 + 200)),
                           max_tokens=12, seed=9),
                GenRequest(prompt_ids=[2, 7, 1, 8], max_tokens=16, seed=5)]

    async def run(megastep_k):
        sched = Scheduler(runner, decode_chunk=4, ragged=True,
                          megastep_k=megastep_k)
        sched.start()
        try:
            outs = await _drain_streams(sched, reqs())
            return outs, sched.ragged_chunks
        finally:
            await sched.stop()

    base, _ = await run(0)
    mega, chunks = await run(4)
    assert chunks >= 2, chunks  # the 200-token prompt really chunked
    assert mega == base, (mega, base)


async def test_megastep_spec_adaptive_retune_streams_identical():
    """Spec runner with the acceptance-adaptive controller: verify
    dispatches keep the packed spec program (verify chunk = K is already
    a megastep), and when the controller pauses the draft mid-stream the
    scheduler's megastep takes over the plain-decode stretches — the
    emitted streams must equal the legacy path across every transition.

    One SHARED runner for both runs (the spec programs compile once):
    the n-gram proposer matches against the slot's in-state history, so
    nothing leaks between runs — except the controller's retunes land on
    the RUNNER's draft_len, which is reset to 3 before each run."""
    from crowdllama_tpu.engine.scheduler import GenRequest, Scheduler
    from crowdllama_tpu.engine.spec import SpecPagedModelRunner

    cfg = get_config("tiny-test", max_context_length=256)
    params = T.init_params(cfg, KEY, dtype=jnp.bfloat16)
    runner = SpecPagedModelRunner(cfg, params=params, max_slots=2,
                                  max_seq=256, page_size=32,
                                  mesh_spec="1", draft_len=3)

    def reqs():
        # Non-repetitive prompt: the bigram proposer misses, acceptance
        # collapses, and the controller shrinks 3 → … → 0 (pause)
        # mid-stream, handing the tail to the megastep path.
        return [GenRequest(prompt_ids=[3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5],
                           max_tokens=24, seed=7),
                GenRequest(prompt_ids=[5, 9] * 8, max_tokens=18, seed=5)]

    async def run(megastep_k):
        runner.set_draft_len(3)
        sched = Scheduler(runner, decode_chunk=4, spec_draft_max=4,
                          megastep_k=megastep_k)
        assert sched._spec_adaptive
        sched.start()
        try:
            outs = await _drain_streams(sched, reqs())
            return outs, sched.spec_retunes
        finally:
            await sched.stop()

    base, base_retunes = await run(0)
    mega, mega_retunes = await run(4)
    assert base_retunes > 0, "controller never retuned — test is vacuous"
    assert mega_retunes == base_retunes
    assert mega == base, (mega, base)


async def test_ragged_megastep_spec_retune_streams_identical():
    """The fused ragged gate has NO draft-len condition (the unified
    step is draft-independent; drafting pauses during a ragged prefill),
    so a spec runner mid acceptance-adaptive retune must take the fused
    path for the chunked admission and still emit the legacy streams —
    with the same retune count — while the ragged_mega duty-cycle series
    proves the fused class actually dispatched."""
    from crowdllama_tpu.engine.scheduler import GenRequest, Scheduler
    from crowdllama_tpu.engine.spec import SpecPagedModelRunner

    cfg = get_config("tiny-test", max_context_length=256)
    params = T.init_params(cfg, KEY, dtype=jnp.bfloat16)
    # three slots for three requests: the fused path, like every full-size
    # flight, is dispatched only while no slot is free
    runner = SpecPagedModelRunner(cfg, params=params, max_slots=3,
                                  max_seq=256, page_size=32, mesh_spec="1",
                                  draft_len=3, step_token_budget=96,
                                  prefix_cache=False)

    def reqs():
        # Non-repetitive short prompts collapse draft acceptance (the
        # controller retunes mid-stream) while the 150-token prompt
        # forces a multi-chunk ragged admission into the same flights.
        return [GenRequest(prompt_ids=[3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5],
                           max_tokens=20, seed=7),
                GenRequest(prompt_ids=list(range(11, 11 + 150)),
                           max_tokens=12, seed=9),
                GenRequest(prompt_ids=[5, 9] * 8, max_tokens=16, seed=5)]

    async def run(megastep_k):
        runner.set_draft_len(3)
        sched = Scheduler(runner, decode_chunk=4, ragged=True,
                          spec_draft_max=4, megastep_k=megastep_k)
        assert sched._spec_adaptive
        sched.start()
        try:
            outs = await _drain_streams(sched, reqs())
            return (outs, sched.spec_retunes, sched.ragged_chunks,
                    sched.telemetry_gauges())
        finally:
            await sched.stop()

    base, base_retunes, base_chunks, _ = await run(0)
    mega, mega_retunes, mega_chunks, gauges = await run(4)
    assert base_chunks >= 2, base_chunks  # the long prompt really chunked
    assert mega_chunks >= 2, mega_chunks
    assert base_retunes > 0, "controller never retuned — test is vacuous"
    assert mega_retunes == base_retunes
    assert mega == base, (mega, base)
    assert gauges["duty_cycle|dispatch=ragged_mega"] > 0.0


# --------------------------------------------- chaos: drain at a boundary


@pytest.mark.chaos
async def test_megastep_drain_at_boundary_migrates_without_replay():
    """A drain landing between megastep flights (the scheduler's safe
    point IS the megastep boundary) must hand the stream off exactly like
    the per-chunk path: the successor imports the donor's KV pages, zero
    prefill tokens replay, and the client's stream is byte-identical —
    the uncommitted tail of the in-flight [K, B] block is recomputed on
    the successor, never double-delivered."""
    import aiohttp

    from test_drain import LONG_CONTENT, _chat_body, _content, \
        _ndjson_lines, _topology
    from crowdllama_tpu.engine.engine import JaxEngine
    from crowdllama_tpu.testing import faults
    from crowdllama_tpu.testing.faults import FaultPlan, FaultRule

    MODEL = "tiny-test"
    kv_cfg = dict(model=MODEL, kv_layout="paged", kv_page_size=16,
                  kv_ship=True, kv_ship_min_tokens=16, kv_ship_timeout=2.0,
                  decode_chunk=4, megastep_k=4)
    workers, engines, _obs, consumer, gateway, gw_port, teardown = \
        await _topology(
            lambda cfg: JaxEngine(cfg, max_context_length=256,
                                  warmup=False),
            cfg_kw=kv_cfg, kv_ship=True)
    try:
        by_id = {w.peer_id: (w, e) for w, e in zip(workers, engines)}
        url = f"http://127.0.0.1:{gw_port}/api/chat"
        body = _chat_body(LONG_CONTENT, num_predict=32)
        # Drain on the FIRST streamed chunk: ~31 decode tokens (≈7 more
        # megastep flights) remain, so the migrate safe point is reached
        # with an uncommitted [K, B] block verifiably in flight.
        plan = FaultPlan(seed=11, rules=[
            FaultRule(site="engine.stream_chunk", action="drain",
                      after=1, times=1)])
        async with aiohttp.ClientSession() as s:
            with faults.installed(plan):
                async with s.post(url, json=body) as resp:
                    assert resp.status == 200
                    lines = _ndjson_lines(await resp.text())
            assert plan.log and plan.log[0][2] == "drain"
            donor_id = plan.log[0][1]["worker"]
            _, donor_eng = by_id[donor_id]
            succ_id = next(p for p in by_id if p != donor_id)
            _, succ_eng = by_id[succ_id]
            # Both sides actually ran the megastep path.
            assert donor_eng.scheduler._megastep
            assert succ_eng.scheduler._megastep
            assert donor_eng.scheduler.host_dispatches > 0

            # Clean completion on the successor...
            assert lines[-1]["done"] is True
            assert lines[-1].get("done_reason") in ("stop", "length")
            assert lines[-1]["worker_id"] == succ_id
            migrated_text = _content(lines)
            assert migrated_text

            # ...byte-identical to a post-drain rerun (greedy decode,
            # same weights) — so no token from the uncommitted megastep
            # block was delivered twice or dropped.
            async with s.post(url, json=body) as resp:
                assert resp.status == 200
                reference = _content(_ndjson_lines(await resp.text()))
            assert migrated_text == reference

            # Fetch-instead-of-recompute across the boundary: pages
            # moved, zero prefill tokens replayed.
            assert succ_eng._runner.kv_pages_imported > 0
            assert donor_eng._runner.kv_pages_exported > 0
            assert succ_eng.obs.metrics.replayed_prefill_tokens == 0
            assert gateway.obs.metrics.migrated_streams == 1
    finally:
        await teardown()


@pytest.mark.chaos
async def test_ragged_megastep_drain_at_fused_boundary_resumes():
    """A drain landing at a FUSED-flight boundary: with megastep_k=4 the
    "scheduler.ragged_chunk" chaos site fires once per fused dispatch —
    which IS the fused safe point — so the drain must migrate the
    mid-prefill request exactly like the per-chunk ragged path does:
    pages the donor's completed fused flights built move to the
    successor, replayed_prefill_tokens counts ONLY the unshipped tail,
    and the client's stream is byte-identical to a clean rerun even
    though whole [K, B] fused blocks were in flight around the drain."""
    import aiohttp

    from test_drain import RAGGED_CONTENT, _chat_body, _content, \
        _ndjson_lines, _topology
    from crowdllama_tpu.engine.engine import JaxEngine
    from crowdllama_tpu.testing import faults
    from crowdllama_tpu.testing.faults import FaultPlan, FaultRule

    MODEL = "tiny-test"
    # step_token_budget 32 on 16-token pages → 16-token ragged chunks;
    # megastep_k 4 → 64 prompt tokens per FUSED dispatch, so the
    # ~190-token prompt needs ~3 fused dispatches and the after=1 drain
    # fires with most of the prompt still unbuilt.
    kv_cfg = dict(model=MODEL, kv_layout="paged", kv_page_size=16,
                  kv_ship=True, kv_ship_min_tokens=16, kv_ship_timeout=2.0,
                  step_token_budget=32, decode_chunk=4, megastep_k=4,
                  # one slot: the lone request saturates the batch, and
                  # only a saturated batch flies full-size (fused) flights
                  max_batch_slots=1,
                  # Byte-identity vs the prefix-hit rerun is a per-device-
                  # program contract: see the mesh note in
                  # tests/test_drain.py::test_drain_mid_chunked_prefill_
                  # resumes_on_successor (tp=2 programs land 1 bf16 ulp
                  # apart under jax 0.9.0's XLA:CPU).
                  mesh_shape="1x1")
    workers, engines, _obs, consumer, gateway, gw_port, teardown = \
        await _topology(
            lambda cfg: JaxEngine(cfg, max_context_length=256,
                                  warmup=False),
            cfg_kw=kv_cfg, kv_ship=True)
    try:
        by_id = {w.peer_id: (w, e) for w, e in zip(workers, engines)}
        url = f"http://127.0.0.1:{gw_port}/api/chat"
        body = _chat_body(RAGGED_CONTENT, num_predict=16)
        # The delay rules park the scheduler loop between the later fused
        # dispatches so the drain task reaches its migrate safe point
        # while the job is still mid-prefill (same choreography as the
        # per-chunk drain test, one site pass per FUSED flight).
        plan = FaultPlan(seed=13, rules=[
            FaultRule(site="scheduler.ragged_chunk", action="delay",
                      delay_s=0.3, after=2, times=2),
            FaultRule(site="scheduler.ragged_chunk", action="drain",
                      after=1, times=1)])
        async with aiohttp.ClientSession() as s:
            with faults.installed(plan):
                async with s.post(url, json=body) as resp:
                    assert resp.status == 200
                    lines = _ndjson_lines(await resp.text())
            # The drain fired at a fused boundary mid-prefill.
            assert plan.log and plan.log[0][2] == "drain"
            attrs = plan.log[0][1]
            assert 0 < attrs["done"] < attrs["total"], attrs

            donor_id = next(w.peer_id for w in workers
                            if w.obs.metrics.drain["initiated"])
            _, donor_eng = by_id[donor_id]
            succ_id = next(p for p in by_id if p != donor_id)
            _, succ_eng = by_id[succ_id]
            # Both sides ran the megastep scheduler, and the donor
            # retired at least one FUSED ragged flight before handing
            # off (the duty-cycle series is the fused class's witness).
            assert donor_eng.scheduler._megastep
            assert succ_eng.scheduler._megastep
            donor_gauges = donor_eng.scheduler.telemetry_gauges()
            assert donor_gauges["duty_cycle|dispatch=ragged_mega"] > 0.0

            # Clean completion on the successor, one uninterrupted
            # stream for the client.
            assert lines[-1]["done"] is True
            assert lines[-1].get("done_reason") in ("stop", "length")
            assert lines[-1]["worker_id"] == succ_id
            migrated_text = _content(lines)
            assert migrated_text

            # Partial handoff: fused-flight pages moved, the replay
            # counter holds only the unshipped tail.
            assert donor_eng._runner.kv_pages_exported > 0
            assert succ_eng._runner.kv_pages_imported > 0
            replayed = succ_eng.obs.metrics.replayed_prefill_tokens
            assert 0 < replayed < attrs["total"], (replayed, attrs)
            assert donor_eng.scheduler.ragged_chunks > 0
            assert succ_eng.scheduler.ragged_chunks > 0
            assert gateway.obs.metrics.migrated_streams == 1

            # Byte-identity: a clean rerun on the surviving worker is
            # the reference — no token from an in-flight fused block
            # was double-delivered or dropped across the boundary.
            async with s.post(url, json=body) as resp:
                assert resp.status == 200
                reference = _content(_ndjson_lines(await resp.text()))
            assert migrated_text == reference
    finally:
        await teardown()
