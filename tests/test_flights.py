"""A decode flight is ``decode_chunk`` steps on the device in one host
dispatch (``decode_steps_device`` / ``ragged_step``: a ``lax.scan`` over the
step body).  What the flight's length may change is pacing, never bytes:

* at the runner, a K-step flight is K one-step flights, in tokens and in
  the state it leaves (contiguous and paged, plain and ragged);
* through the scheduler the emitted streams are the same at every
  ``decode_chunk`` — with a slot that meets its EOS, its budget or the
  context's end mid-flight (nothing past it is emitted, its pages are
  freed), with a long prompt admitted in ragged chunks mid-stream, across a
  speculative draft-length retune and across a drain at a flight boundary;
* on the hybrid runners (``nemotron_h``, ``kimi_linear``, ``afmoe``) the
  state a step carries — conv tail, SSM and KDA state, ring pages — goes
  through the scan as it goes through K dispatches;
* a flight length claims one compile signature, once; and after
  ``JaxEngine``'s warm-up a short and a long prompt dispatch nothing the
  warm-up did not.

Tiny models, float32, the CPU.  Runners (and their jitted programs) are
shared at module scope: every test builds its own state, and every prompt
that is not admitted in chunks is shorter than a KV page, so no prefix
pages index between runs.  Also here: a node takes its ports before its
engine starts (cli/main.py ``run_node``), and two things that hold once
the second dispatch and the tuner are gone.
"""

import ast
import asyncio
import errno
import json
import re
import socket
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crowdllama_tpu.engine.paged import PagedModelRunner
from crowdllama_tpu.engine.runner import ModelRunner
from crowdllama_tpu.engine.scheduler import DONE, GenRequest, Scheduler
from crowdllama_tpu.models import transformer as T
from crowdllama_tpu.models.config import get_config
from crowdllama_tpu.obs.metrics import ENGINE_TELEMETRY

REPO = Path(__file__).resolve().parent.parent
KEY = jax.random.PRNGKey(0)
KS = [1, 4, 8]


def _insert(runner, state, slot, prompt):
    first, ks, vs, plen = runner.prefill(prompt, 0.0, 1.0, KEY)
    state = runner.insert(state, slot, ks, vs, plen, first, 0.0, 1.0,
                          prompt_tokens=prompt)
    return int(first), state


def _same_state(a, b):
    """Every leaf of two decode states: integers and flags equal, floats
    to a float32 rounding (a scan and K dispatches may fuse apart)."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if np.issubdtype(x.dtype, np.floating):
            np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)
        else:
            np.testing.assert_array_equal(x, y)


async def _streams(sched, reqs):
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


async def _serve(runner, decode_chunk, reqs, **sched_kw):
    """Serve ``reqs`` at ``decode_chunk``: (streams, the scheduler, the
    lengths of the plain flights, the lengths of the ragged ones)."""
    plain, ragged = [], []
    real_plain = runner.decode_steps_device
    real_ragged = getattr(runner, "ragged_step", None)

    def note_plain(state, k=1):
        plain.append(k)
        return real_plain(state, k)

    def note_ragged(state, job, k=1):
        ragged.append(k)
        return real_ragged(state, job, k)

    runner.decode_steps_device = note_plain
    if real_ragged is not None:
        runner.ragged_step = note_ragged
    sched = Scheduler(runner, decode_chunk=decode_chunk, **sched_kw)
    sched.start()
    try:
        return await _streams(sched, reqs), sched, set(plain), set(ragged)
    finally:
        await sched.stop()
        del runner.decode_steps_device
        if real_ragged is not None:
            del runner.ragged_step


# ------------------------------------------------------------ runner units


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tiny-test", max_context_length=128)
    return cfg, T.init_params(cfg, KEY, dtype=jnp.float32)


@pytest.fixture(scope="module", params=["contiguous", "paged"])
def runner_pair(request, tiny):
    """(kind, a runner, its twin): two instances, because a paged runner's
    page table lives on the host, one to an instance."""
    cfg, params = tiny
    kw = dict(max_slots=2, max_seq=128, dtype=jnp.float32, mesh_spec="1")
    if request.param == "paged":
        def mk():
            return PagedModelRunner(cfg, params=params, page_size=32, **kw)
    else:
        def mk():
            return ModelRunner(cfg, params=params, **kw)
    return request.param, mk(), mk()


@pytest.mark.parametrize("k", KS)
def test_a_k_step_flight_is_k_one_step_flights(runner_pair, k):
    """``decode_steps(state, K)`` emits the token block of K chained
    one-step dispatches and leaves their state, and what follows is the
    same too."""
    _, one, many = runner_pair
    s1, sk = one.init_state(), many.init_state()
    for slot, p in enumerate([[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8]]):
        f1, s1 = _insert(one, s1, slot, p)
        fk, sk = _insert(many, sk, slot, p)
        assert f1 == fk
    rows = []
    for _ in range(k):
        toks, s1 = one.decode_steps(s1, 1)
        rows.append(np.asarray(toks))
    toks, sk = many.decode_steps(sk, k)
    np.testing.assert_array_equal(np.asarray(toks), np.concatenate(rows))
    _same_state(s1, sk)
    t1, _ = one.decode_steps(s1, 4)
    tk, _ = many.decode_steps(sk, 4)
    np.testing.assert_array_equal(np.asarray(tk), np.asarray(t1))


def _two(eos_a: int = -1):
    return [GenRequest(prompt_ids=[3, 1, 4, 1, 5], max_tokens=3, seed=7,
                       eos_id=-1),
            GenRequest(prompt_ids=[2, 7, 1, 8], max_tokens=20, seed=5,
                       eos_id=eos_a)]


def _all_given_back(runner, sched):
    assert sched.slots == [None] * runner.max_slots
    assert not np.asarray(sched.state.active).any()
    if isinstance(runner, PagedModelRunner):
        assert not runner._slot_pages
        assert len(runner._free_pages) == runner.total_pages


async def test_a_slot_out_of_budget_mid_flight_emits_nothing_past_it(
        runner_pair):
    """Both slots taken, so flights are eight steps: the request whose
    budget ends at the third emits three tokens, the one-step stream's,
    and its slot and pages are given back."""
    _, _, runner = runner_pair
    base, *_ = await _serve(runner, 1, _two())
    outs, sched, plain, _ = await _serve(runner, 8, _two())
    assert 8 in plain
    assert outs == base
    assert [(len(t), why) for t, why in outs] == [(3, "length"),
                                                  (20, "length")]
    _all_given_back(runner, sched)


async def test_a_slot_at_its_eos_mid_flight_emits_nothing_past_it(
        runner_pair):
    """The same with an EOS that is met mid-flight: the stream stops on it,
    as the one-step stream does."""
    _, _, runner = runner_pair
    (_, (free, _)), *_ = await _serve(runner, 1, _two())
    # the first token of the free-running stream that it had not emitted
    # before, from its fifth on: met inside the first eight-step flight
    at = next(i for i in range(4, 20) if free[i] not in free[:i])
    base, *_ = await _serve(runner, 1, _two(eos_a=free[at]))
    outs, sched, plain, _ = await _serve(runner, 8, _two(eos_a=free[at]))
    assert 8 in plain
    assert outs == base
    toks, why = outs[1]
    assert why == "stop" and len(toks) <= at + 1 < 20
    assert toks == free[:len(toks)]
    _all_given_back(runner, sched)


def _unclaimed(program: str, n: int) -> list[int]:
    """``n`` flight lengths no test of this process has dispatched under
    ``program``: ENGINE_TELEMETRY is the process's, and counts a signature
    once."""
    taken = {b for p, b in ENGINE_TELEMETRY.snapshot_compiles()
             if p == program}
    return [k for k in range(11, 64) if str(k) not in taken][:n]


def test_a_flight_length_compiles_once(runner_pair):
    """Each K claims ONE (program, K) signature, and a K seen before claims
    none: the cached-hit counter moves, ``xla_compiles_total`` does not."""
    kind, _, runner = runner_pair
    program = "decode_paged" if kind == "paged" else "decode"
    ka, kb = _unclaimed(program, 2)
    st = runner.init_state()
    _, st = _insert(runner, st, 0, [3, 1, 4, 1, 5])
    before = ENGINE_TELEMETRY.snapshot_compiles()
    _, st = runner.decode_steps(st, ka)
    after = ENGINE_TELEMETRY.snapshot_compiles()
    assert {k for k in after if k not in before} == {(program, str(ka))}
    _, st = runner.decode_steps(st, kb)
    assert ENGINE_TELEMETRY.snapshot_compiles()[(program, str(kb))] == 1
    hits = ENGINE_TELEMETRY.snapshot_cache_hits().get(program, 0)
    _, st = runner.decode_steps(st, ka)
    assert ENGINE_TELEMETRY.snapshot_compiles() == {
        **after, (program, str(kb)): 1}
    assert ENGINE_TELEMETRY.snapshot_cache_hits()[program] == hits + 1


@pytest.fixture(scope="module")
def tiny512():
    cfg = get_config("tiny-test", max_context_length=512)
    return cfg, T.init_params(cfg, KEY, dtype=jnp.float32)


@pytest.mark.parametrize("k", KS)
def test_a_k_step_ragged_flight_is_k_one_step_flights(tiny512, k):
    """``ragged_step(state, job, K)`` emits the [K, B] block of K chained
    one-step dispatches while the job's chunks advance in the same flights
    — though it provisions all K chunks at once and so runs at a WIDER
    page-table window than the control's early dispatches (the window is
    invisible by design) — and the job's bookkeeping lands the same."""
    cfg, params = tiny512

    def mk():
        # chunks of 32: a 300-token prompt is not through in 8 steps
        return PagedModelRunner(cfg, params=params, max_slots=4, max_seq=512,
                                page_size=32, mesh_spec="1",
                                step_token_budget=36, prefix_cache=False,
                                dtype=jnp.float32)

    one, many = mk(), mk()
    c = one.ragged_chunk
    assert c == 32
    prompt = [x % cfg.vocab_size for x in range(17, 17 + 300)]
    s1, sk = one.init_state(), many.init_state()
    for slot, p in enumerate([[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8]]):
        _, s1 = _insert(one, s1, slot, p)
        _, sk = _insert(many, sk, slot, p)
    j1 = one.ragged_begin(prompt, 2, state=s1)
    jk = many.ragged_begin(prompt, 2, state=sk)
    rows = []
    for _ in range(k):
        toks, s1 = one.ragged_step(s1, j1, 1)
        rows.append(np.asarray(toks))
    toks, sk = many.ragged_step(sk, jk, k)
    np.testing.assert_array_equal(np.asarray(toks), np.concatenate(rows))
    assert jk.done_tokens == j1.done_tokens == k * c
    # both finish the prompt (in as many steps: the decoding slots keep
    # pace) and hand the same stream on
    while not j1.finished:
        _, s1 = one.ragged_step(s1, j1, 1)
        _, sk = many.ragged_step(sk, jk, 1)
    assert jk.finished
    f1, s1 = one.ragged_finish(s1, j1, 0.0, 1.0, KEY)
    fk, sk = many.ragged_finish(sk, jk, 0.0, 1.0, KEY)
    assert int(f1) == int(fk)
    t1, _ = one.decode_steps(s1, 4)
    tk, _ = many.decode_steps(sk, 4)
    np.testing.assert_array_equal(np.asarray(tk), np.asarray(t1))


# ------------------------------------------------------- scheduler streams


@pytest.fixture(scope="module")
def sched_runner(tiny512):
    cfg, params = tiny512
    return PagedModelRunner(cfg, params=params, max_slots=2, max_seq=512,
                            page_size=32, mesh_spec="1", dtype=jnp.float32)


def _three():
    """Three requests for two slots: full flights while both are taken, a
    finish mid-flight, an admission into the slot it frees."""
    return [GenRequest(prompt_ids=[3, 1, 4, 1, 5], max_tokens=24, seed=7,
                       eos_id=-1),
            GenRequest(prompt_ids=[2, 7, 1, 8], max_tokens=17, seed=5,
                       temperature=0.8, top_p=0.9, eos_id=-1),
            GenRequest(prompt_ids=list(range(11, 31)), max_tokens=9, seed=3,
                       eos_id=-1)]


async def _base(cache: dict, runner, reqs, **kw):
    """The one-step streams of ``reqs``, served once a module."""
    if "base" not in cache:
        outs, sched, plain, _ = await _serve(runner, 1, reqs(), **kw)
        assert plain <= {1}
        cache["base"] = outs, sched.host_dispatches
    return cache["base"]


_SCHED_BASE: dict = {}


@pytest.mark.parametrize("k", KS)
async def test_scheduler_streams_are_the_same_at_every_flight_length(
        sched_runner, k):
    """``decode_chunk`` 1, 4 and 8 emit the streams of the one-step
    control, in fewer host dispatches once flights are longer, and the
    dispatch gauges move with them."""
    base, base_disp = await _base(_SCHED_BASE, sched_runner, _three)
    outs, sched, plain, _ = await _serve(sched_runner, k, _three())
    assert outs == base, (k, outs, base)
    assert [len(t) for t, _ in outs] == [24, 17, 9]
    assert plain == {1, k}
    gauges = sched.telemetry_gauges()
    assert gauges["host_dispatches_total"] == float(sched.host_dispatches)
    assert gauges["tokens_per_dispatch"] >= 0.0
    if k > 1:
        assert sched.host_dispatches < base_disp, (sched.host_dispatches,
                                                   base_disp)


async def test_a_ragged_admission_mid_stream_keeps_the_streams(tiny512):
    """A long prompt admitted in chunks beside two decoding slots, every
    slot taken: four unified steps a dispatch emit what one does."""
    cfg, params = tiny512
    # chunks of 64 and no prefix cache: the second run admits as the first
    runner = PagedModelRunner(cfg, params=params, max_slots=3, max_seq=512,
                              page_size=32, mesh_spec="1", dtype=jnp.float32,
                              step_token_budget=96, prefix_cache=False)

    def reqs():
        return [GenRequest(prompt_ids=[3, 1, 4, 1, 5], max_tokens=16, seed=7,
                           eos_id=-1),
                GenRequest(prompt_ids=list(range(11, 11 + 200)),
                           max_tokens=12, seed=9, eos_id=-1),
                GenRequest(prompt_ids=[2, 7, 1, 8], max_tokens=16, seed=5,
                           eos_id=-1)]

    base, _, _, ragged = await _serve(runner, 1, reqs())
    assert ragged == {1}
    outs, sched, _, ragged = await _serve(runner, 4, reqs())
    assert 4 in ragged
    assert sched.ragged_chunks >= 2     # the prompt did go in chunks
    assert outs == base, (outs, base)
    assert sched.telemetry_gauges()["duty_cycle|dispatch=ragged"] > 0.0


def _spec_runner(tiny512, **kw):
    from crowdllama_tpu.engine.spec import SpecPagedModelRunner

    cfg, params = tiny512
    return SpecPagedModelRunner(cfg, params=params, max_seq=512,
                                page_size=32, mesh_spec="1", draft_len=3,
                                dtype=jnp.float32, **kw)


async def _spec_serve(runner, decode_chunk, reqs, **kw):
    runner.set_draft_len(3)     # a retune lands on the RUNNER
    outs, sched, _, ragged = await _serve(runner, decode_chunk, reqs,
                                          spec_draft_max=4, **kw)
    assert sched._spec_adaptive
    return outs, sched, ragged


async def test_a_spec_retune_keeps_the_streams(tiny512):
    """The acceptance-adaptive controller shrinks a useless draft to its
    pause mid-stream and the plain flights take over: the streams at four
    verify steps a dispatch are those at one, across every transition
    (greedy exactness: a draft decides how MANY tokens a dispatch emits)."""
    runner = _spec_runner(tiny512, max_slots=2)

    def reqs():
        # nothing repeats: the bigram proposer misses and acceptance falls
        return [GenRequest(prompt_ids=[3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5],
                           max_tokens=24, seed=7, eos_id=-1),
                GenRequest(prompt_ids=[5, 9] * 8, max_tokens=18, seed=5,
                           eos_id=-1)]

    base, sched, _ = await _spec_serve(runner, 1, reqs())
    assert sched.spec_retunes > 0, "the controller never retuned"
    outs, sched, _ = await _spec_serve(runner, 4, reqs())
    assert sched.spec_retunes > 0
    assert outs == base, (outs, base)


async def test_a_ragged_admission_across_a_spec_retune_keeps_the_streams(
        tiny512):
    """Drafting pauses while a ragged job is in flight and resumes after
    it: a chunked admission in the middle of the retunes, every slot
    taken, at four steps a dispatch against one."""
    runner = _spec_runner(tiny512, max_slots=3, step_token_budget=96,
                          prefix_cache=False)

    def reqs():
        return [GenRequest(prompt_ids=[3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5],
                           max_tokens=20, seed=7, eos_id=-1),
                GenRequest(prompt_ids=list(range(11, 11 + 150)),
                           max_tokens=12, seed=9, eos_id=-1),
                GenRequest(prompt_ids=[5, 9] * 8, max_tokens=16, seed=5,
                           eos_id=-1)]

    base, sched, _ = await _spec_serve(runner, 1, reqs(), ragged=True)
    assert sched.spec_retunes > 0 and sched.ragged_chunks >= 2
    outs, sched, ragged = await _spec_serve(runner, 4, reqs(), ragged=True)
    assert sched.spec_retunes > 0 and sched.ragged_chunks >= 2
    assert 4 in ragged
    assert outs == base, (outs, base)


# ------------------------------------- chaos: a drain at a flight boundary


async def _drain_topology(**cfg_kw):
    from test_drain import _topology

    from crowdllama_tpu.engine.engine import JaxEngine

    # one slot: the lone request takes every slot, and only then does a
    # flight run decode_chunk steps
    kv_cfg = dict(model="tiny-test", kv_layout="paged", kv_page_size=16,
                  kv_ship=True, kv_ship_min_tokens=16, kv_ship_timeout=2.0,
                  decode_chunk=4, max_batch_slots=1, **cfg_kw)
    return await _topology(
        lambda cfg: JaxEngine(cfg, max_context_length=256, warmup=False),
        cfg_kw=kv_cfg, kv_ship=True)


@pytest.mark.chaos
async def test_a_drain_between_full_flights_migrates_without_replay():
    """A drain lands between four-step flights (the scheduler's safe point
    IS the flight boundary) with an unread [K, B] block in the air: the
    successor imports the donor's pages, no prompt token is prefilled
    again, and the client's stream is a clean rerun's — the block's tail is
    computed again on the successor and delivered once."""
    import aiohttp
    from test_drain import LONG_CONTENT, _chat_body, _content, _ndjson_lines

    from crowdllama_tpu.testing import faults
    from crowdllama_tpu.testing.faults import FaultPlan, FaultRule

    workers, engines, _obs, _consumer, gateway, gw_port, teardown = \
        await _drain_topology()
    try:
        by_id = {w.peer_id: e for w, e in zip(workers, engines)}
        url = f"http://127.0.0.1:{gw_port}/api/chat"
        body = _chat_body(LONG_CONTENT, num_predict=32)
        # on the FIRST streamed chunk: ~31 tokens, seven flights, remain
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
            donor = by_id[donor_id]
            succ_id = next(p for p in by_id if p != donor_id)
            succ = by_id[succ_id]
            for eng in (donor, succ):
                assert eng.scheduler.decode_chunk == 4
                assert eng.scheduler.host_dispatches > 0
            assert lines[-1]["done"] is True
            assert lines[-1].get("done_reason") in ("stop", "length")
            assert lines[-1]["worker_id"] == succ_id
            migrated = _content(lines)
            assert migrated
            async with s.post(url, json=body) as resp:
                assert resp.status == 200
                reference = _content(_ndjson_lines(await resp.text()))
            assert migrated == reference
            assert succ._runner.kv_pages_imported > 0
            assert donor._runner.kv_pages_exported > 0
            assert succ.obs.metrics.replayed_prefill_tokens == 0
            assert gateway.obs.metrics.migrated_streams == 1
    finally:
        await teardown()


@pytest.mark.chaos
async def test_a_drain_between_full_ragged_flights_resumes_on_the_successor():
    """The same at a RAGGED flight's boundary, mid-prefill: the
    ``scheduler.ragged_chunk`` site fires once a flight of four unified
    steps; the pages the donor's finished flights built move, only the
    tail that was not shipped is prefilled again, and the stream is a
    clean rerun's."""
    import aiohttp
    from test_drain import RAGGED_CONTENT, _chat_body, _content, \
        _ndjson_lines

    from crowdllama_tpu.testing import faults
    from crowdllama_tpu.testing.faults import FaultPlan, FaultRule

    # 16-token chunks, four a flight: the ~190-token prompt takes three
    # flights, and the drain after the first finds most of it unbuilt.
    # (mesh: tests/test_drain.py::test_drain_mid_chunked_prefill_resumes_
    # on_successor has why a rerun's bytes need one device's programs)
    workers, engines, _obs, _consumer, gateway, gw_port, teardown = \
        await _drain_topology(step_token_budget=32, mesh_shape="1x1")
    try:
        by_id = {w.peer_id: e for w, e in zip(workers, engines)}
        url = f"http://127.0.0.1:{gw_port}/api/chat"
        body = _chat_body(RAGGED_CONTENT, num_predict=16)
        # the delays park the loop between the later flights, so that the
        # drain reaches its safe point while the job is still mid-prefill
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
            assert plan.log and plan.log[0][2] == "drain"
            attrs = plan.log[0][1]
            assert 0 < attrs["done"] < attrs["total"], attrs
            donor_id = next(w.peer_id for w in workers
                            if w.obs.metrics.drain["initiated"])
            donor = by_id[donor_id]
            succ_id = next(p for p in by_id if p != donor_id)
            succ = by_id[succ_id]
            # the donor retired a ragged flight before it handed off, and
            # a flight there carries four chunks
            assert donor.scheduler.telemetry_gauges()[
                "duty_cycle|dispatch=ragged"] > 0.0
            assert attrs["done"] == 4 * donor._runner.ragged_chunk
            assert lines[-1]["done"] is True
            assert lines[-1].get("done_reason") in ("stop", "length")
            assert lines[-1]["worker_id"] == succ_id
            migrated = _content(lines)
            assert migrated
            assert donor._runner.kv_pages_exported > 0
            assert succ._runner.kv_pages_imported > 0
            replayed = succ.obs.metrics.replayed_prefill_tokens
            assert 0 < replayed < attrs["total"], (replayed, attrs)
            assert donor.scheduler.ragged_chunks > 0
            assert succ.scheduler.ragged_chunks > 0
            assert gateway.obs.metrics.migrated_streams == 1
            async with s.post(url, json=body) as resp:
                assert resp.status == 200
                reference = _content(_ndjson_lines(await resp.text()))
            assert migrated == reference
    finally:
        await teardown()


# --------------------------------- the hybrid runners: state through a scan

# family -> (configuration, page size, step_token_budget: chunks of 32, of
# 16 on the two-kind cache)
FAMILIES = {"nemotron_h": ("tiny-test-nemotron-h", 16, 34),
            "kimi_linear": ("tiny-test-kimi-linear", 16, 34),
            "afmoe": ("tiny-test-afmoe", 8, 18)}
hybrid = pytest.mark.usefixtures("_programs_go_with_their_test")


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    """(runner, the module's cache of its one-step streams): two slots, a
    context of 128."""
    from crowdllama_tpu.engine.hybrid import HybridPagedModelRunner

    name, page, budget = FAMILIES[request.param]
    cfg = get_config(name)
    runner = HybridPagedModelRunner(
        cfg, params=T.init_params(cfg, KEY, jnp.float32), max_slots=2,
        max_seq=128, page_size=page, step_token_budget=budget,
        dtype=jnp.float32)
    return runner, {}


def _prompt(n: int, seed: int) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(1, 500, n)]


def _hybrid_three():
    return [GenRequest(prompt_ids=_prompt(9, 1), max_tokens=21, eos_id=-1),
            GenRequest(prompt_ids=_prompt(5, 2), max_tokens=12, eos_id=-1),
            GenRequest(prompt_ids=_prompt(13, 3), max_tokens=10, eos_id=-1)]


@hybrid
@pytest.mark.parametrize("k", [2, 4, 8])
async def test_hybrid_streams_are_the_same_at_every_flight_length(family, k):
    """The flight lengths the cells run (2, 4, 8) against one step a
    dispatch: the conv tail, the SSM or KDA state and the ring's pages go
    through a scan as they go through K dispatches, a finish mid-flight
    and the admission into its slot included."""
    from crowdllama_tpu.models import hybrid as H

    runner, cache = family
    base, _ = await _base(cache.setdefault("three", {}), runner,
                          _hybrid_three)

    def plain_series(name: str) -> float:
        return sum(float(ln.rsplit(" ", 1)[1])
                   for ln in ENGINE_TELEMETRY.expose()
                   if ln.startswith(name) and 'dispatch="plain"' in ln)

    counters = ("crowdllama_moe_banks_total",
                "crowdllama_moe_banks_fetched_total",
                "crowdllama_engine_flight_steps_total")
    before = [plain_series(n) for n in counters]
    outs, _, plain, _ = await _serve(runner, k, _hybrid_three())
    assert plain == {1, k}
    assert [len(t) for t, _ in outs] == [21, 12, 10]
    assert outs == base, (k, outs, base)
    # every step of a K-step flight books every held bank of every expert
    # layer once, under the one class such a flight has
    banks, fetched, steps = (plain_series(n) - b
                             for n, b in zip(counters, before))
    cfg = runner.cfg
    a_step = H.sizes(cfg)["held"] * sum(cfg.layers_of(kind) for kind in "ES")
    assert steps > 0 and banks == fetched == a_step * steps


def _hybrid_ragged():
    return [GenRequest(prompt_ids=_prompt(9, 1), max_tokens=40, eos_id=-1),
            GenRequest(prompt_ids=_prompt(100, 4), max_tokens=8, eos_id=-1)]


@hybrid
async def test_hybrid_streams_with_a_ragged_admission(family):
    """A 100-token prompt admitted in chunks beside a decoding slot, both
    slots taken: four unified steps a dispatch — the chunk continuing its
    slot's own state from step to step inside the scan — against one."""
    runner, cache = family
    base, _ = await _base(cache.setdefault("ragged", {}), runner,
                          _hybrid_ragged)
    outs, sched, _, ragged = await _serve(runner, 4, _hybrid_ragged())
    assert ragged == {4} and sched.ragged_chunks >= 4
    assert [len(t) for t, _ in outs] == [40, 8]
    assert outs == base, (outs, base)


@hybrid
async def test_hybrid_stream_to_the_contexts_end_mid_flight():
    """A request that runs to the end of the context finishes there at
    every flight length — eight steps a dispatch do not end on it — with
    the tokens of one step a dispatch, and gives back what it held."""
    from crowdllama_tpu.engine.hybrid import HybridPagedModelRunner

    cfg = get_config("tiny-test-kimi-linear")
    runner = HybridPagedModelRunner(
        cfg, params=T.init_params(cfg, KEY, jnp.float32), max_slots=1,
        max_seq=64, page_size=16, dtype=jnp.float32)

    def reqs():
        return [GenRequest(prompt_ids=_prompt(10, 5), max_tokens=500,
                           eos_id=-1)]

    base, *_ = await _serve(runner, 1, reqs())
    outs, sched, plain, _ = await _serve(runner, 8, reqs())
    assert plain == {8}
    (toks, why), = outs
    assert why == "length" and len(toks) == 64 - 1 - 10
    assert (len(toks) - 1) % 8      # not on a flight's boundary
    assert outs == base
    _all_given_back(runner, sched)


# ------------------------------- dense and MoE, the KV as it is or in int8


@pytest.fixture(scope="module", params=[
    (model, kv) for model in ("tiny-test", "tiny-test-moe")
    for kv in ("bf16", "int8")], ids="-".join)
def kv_runner(request):
    """(runner, the module's cache of its one-step streams) for a dense and
    an expert model, the pool in the runner's dtype (``kv_dtype`` "bf16")
    or in int8 with its scales."""
    model, kv = request.param
    cfg = get_config(model, max_context_length=128)
    runner = PagedModelRunner(
        cfg, params=T.init_params(cfg, KEY, dtype=jnp.float32), max_slots=2,
        max_seq=128, page_size=32, mesh_spec="1", dtype=jnp.float32,
        kv_dtype=kv)
    return runner, {}


@pytest.mark.parametrize("k", [2, 4, 8])
async def test_dense_and_moe_streams_at_every_flight_length(kv_runner, k):
    runner, cache = kv_runner
    base, base_disp = await _base(cache, runner, _three)
    outs, sched, plain, _ = await _serve(runner, k, _three())
    assert plain == {1, k}
    assert outs == base, (k, outs, base)
    assert sched.host_dispatches < base_disp


async def test_a_budget_ends_mid_flight_and_its_pages_serve_the_next(
        sched_runner):
    """Paged, ``max_tokens``: the slot a budget frees mid-flight and the
    pages it gives back go to the request that waited, whose stream is the
    one-step stream too; in the end every page is free."""
    def reqs():
        return [GenRequest(prompt_ids=_prompt(30, 6), max_tokens=5,
                           eos_id=-1),
                GenRequest(prompt_ids=_prompt(7, 7), max_tokens=30,
                           eos_id=-1),
                GenRequest(prompt_ids=_prompt(31, 8), max_tokens=11,
                           eos_id=-1)]

    base, *_ = await _serve(sched_runner, 1, reqs())
    outs, sched, plain, _ = await _serve(sched_runner, 8, reqs())
    assert 8 in plain
    assert [(len(t), why) for t, why in outs] == [
        (5, "length"), (30, "length"), (11, "length")]
    assert outs == base
    _all_given_back(sched_runner, sched)


async def test_the_contexts_end_mid_flight_on_a_paged_runner(tiny):
    """Paged, the context's end: ``max_seq - 1 - prompt`` tokens and
    "length", at eight steps a dispatch as at one, and the pool whole."""
    cfg, params = tiny
    runner = PagedModelRunner(cfg, params=params, max_slots=1, max_seq=128,
                              page_size=32, mesh_spec="1", dtype=jnp.float32)

    def reqs():
        return [GenRequest(prompt_ids=_prompt(20, 9), max_tokens=500,
                           eos_id=-1)]

    base, *_ = await _serve(runner, 1, reqs())
    outs, sched, plain, _ = await _serve(runner, 8, reqs())
    assert plain == {8}
    (toks, why), = outs
    assert why == "length" and len(toks) == 128 - 1 - 20
    assert (len(toks) - 1) % 8
    assert outs == base
    _all_given_back(runner, sched)


# ------------------------------------------ nothing compiles after warm-up


@pytest.mark.parametrize("model, slots", [
    ("tiny-test", 4), ("tiny-test-moe", 4), ("tiny-test-afmoe", 2)],
    ids=["dense", "moe", "ragged_width_fixed"])
async def test_after_warmup_a_short_and_a_long_prompt_compile_nothing(
        model, slots, monkeypatch):
    """``JaxEngine``'s warm-up dispatches every (program, shape) that a
    short prompt and one admitted in chunks dispatch afterwards, so they
    add nothing to ``crowdllama_xla_compiles_total``.  With a slot free a
    flight is one step; the runner whose unified table has ONE width
    (``ragged_width_fixed``: the two-kind cache) is warmed at
    ``decode_chunk`` unified steps too, and here has no slot free."""
    from crowdllama_tpu.config import Configuration, Intervals
    from crowdllama_tpu.engine.engine import JaxEngine

    claimed: list[tuple[str, str]] = []
    real = ENGINE_TELEMETRY.compile_begin

    def noting(program, bucket):
        claimed.append((program, str(bucket)))
        return real(program, bucket)

    monkeypatch.setattr(ENGINE_TELEMETRY, "compile_begin", noting)
    engine = JaxEngine(Configuration(
        model=model, max_context_length=256, max_batch_slots=slots,
        decode_chunk=4, kv_page_size=16, step_token_budget=32 + slots,
        intervals=Intervals.default()))
    await engine.start()
    try:
        r = engine._runner
        assert r.ragged_width_fixed == (model == "tiny-test-afmoe")
        warmed, compiles = set(claimed), ENGINE_TELEMETRY.snapshot_compiles()
        del claimed[:]
        short = "a short one"
        long = "a prompt that is admitted in chunks, it is"
        n = len(engine.tokenizer.encode(long))
        # in chunks, and inside the table window the warm-up's job had
        assert r.ragged_chunk < n and n + 8 <= 4 * r.page_size

        async def one(prompt, max_tokens):
            out = [c async for c in engine.generate(prompt,
                                                    max_tokens=max_tokens)]
            assert out[-1].done, out[-1]

        await asyncio.gather(one(short, 24), one(long, 8))
        assert engine.scheduler.ragged_chunks >= 2
        assert set(claimed) <= warmed, set(claimed) - warmed
        assert ENGINE_TELEMETRY.snapshot_compiles() == compiles
        if r.ragged_width_fixed:
            assert ("ragged_step", f"4x{r.ragged_chunk}w"
                    f"{r.max_pages_per_slot}") in claimed
    finally:
        await engine.stop()


# --------------------------- a node takes its ports before its engine starts


def _node_cfg(tmp_path, listen_port, metrics_port):
    from crowdllama_tpu.config import Configuration, Intervals

    return Configuration(
        listen_host="127.0.0.1", listen_port=listen_port,
        worker_metrics_port=metrics_port, engine_backend="fake",
        model="tiny-test", relay_mode="off",
        key_path=str(tmp_path / "key"), intervals=Intervals.default())


def _free_ports(n: int) -> list[int]:
    socks = [socket.socket() for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


async def test_a_taken_port_ends_the_node_before_any_weight_is_loaded(
        tmp_path, monkeypatch):
    from crowdllama_tpu.cli import main as cli

    made = []
    monkeypatch.setattr(cli, "_make_engine",
                        lambda *a, **kw: made.append(a) or 1 / 0)
    listen, metrics = _free_ports(2)
    for taken in (listen, metrics):
        with socket.socket() as holder:
            holder.bind(("127.0.0.1", taken))
            holder.listen()
            with pytest.raises(OSError) as e:
                await cli.run_node(_node_cfg(tmp_path, listen, metrics),
                                   worker_mode=True)
            assert e.value.errno == errno.EADDRINUSE
    assert not made
    # and the port it did take went back with the failure
    with socket.socket() as again:
        again.bind(("127.0.0.1", listen))


async def test_before_the_engine_is_up_a_dial_is_refused_and_the_ports_held(
        tmp_path, monkeypatch):
    from crowdllama_tpu.cli import main as cli
    from crowdllama_tpu.engine.engine import FakeEngine

    listen, metrics = _free_ports(2)
    seen = {}

    class Starting(FakeEngine):
        async def start(self):
            for name, port in (("listen", listen), ("metrics", metrics)):
                with socket.socket() as s:
                    seen[name, "dial"] = s.connect_ex(("127.0.0.1", port))
                with socket.socket() as s:
                    try:
                        s.bind(("127.0.0.1", port))
                        seen[name, "bind"] = 0
                    except OSError as e:
                        seen[name, "bind"] = e.errno
            raise RuntimeError("the engine's start failed")

    monkeypatch.setattr(cli, "_make_engine",
                        lambda cfg, worker_mode: Starting(models=[]))
    with pytest.raises(RuntimeError, match="the engine's start failed"):
        await cli.run_node(_node_cfg(tmp_path, listen, metrics),
                           worker_mode=True)
    for name in ("listen", "metrics"):
        assert seen[name, "dial"] == errno.ECONNREFUSED, seen
        assert seen[name, "bind"] == errno.EADDRINUSE, seen
    for port in (listen, metrics):      # given back when the start failed
        with socket.socket() as again:
            again.bind(("127.0.0.1", port))


async def test_after_the_engine_is_up_the_node_serves_on_the_ports_it_took(
        tmp_path):
    import aiohttp

    from crowdllama_tpu.cli import main as cli

    listen, metrics = _free_ports(2)
    node = asyncio.create_task(
        cli.run_node(_node_cfg(tmp_path, listen, metrics), worker_mode=True))
    try:
        async with aiohttp.ClientSession() as s:
            for _ in range(200):
                assert not node.done(), node.exception()
                try:
                    async with s.get(
                            f"http://127.0.0.1:{metrics}/metrics") as resp:
                        assert resp.status == 200
                        assert "crowdllama_engine_" in await resp.text()
                        break
                except aiohttp.ClientConnectorError:
                    await asyncio.sleep(0.05)
            else:
                raise AssertionError("the metrics port never answered")
        # the peer's listener came up before the metrics endpoint
        _, writer = await asyncio.open_connection("127.0.0.1", listen)
        writer.close()
        await writer.wait_closed()
    finally:
        node.cancel()
        with pytest.raises(asyncio.CancelledError):
            await node


# ------------------------------------- what holds once the two are deleted


def test_gateway_and_swarm_import_nothing_of_the_engine():
    """The gateway's request path and the gossip map know nothing of the
    worker's engine package; the one import left is the gateway draft's
    weight loader (ROADMAP D5)."""
    pkg = REPO / "crowdllama_tpu"
    found = set()
    for path in [*(pkg / "gateway").rglob("*.py"),
                 *(pkg / "swarm").rglob("*.py")]:
        for node in ast.walk(ast.parse(path.read_text())):
            names = ([a.name for a in node.names]
                     if isinstance(node, ast.Import)
                     else [node.module or ""]
                     if isinstance(node, ast.ImportFrom) else [])
            found |= {(path.relative_to(pkg).as_posix(), n) for n in names
                      if n.startswith("crowdllama_tpu.engine")}
    assert found == {("gateway/draft.py", "crowdllama_tpu.engine.weights")}


def test_the_flight_counters_render_every_series_the_benchmark_sums():
    """``step.decode_wall_ms`` sums the flight counters over dispatch
    classes BY NAME, and its reducer gives nothing when a term has no
    series: a worker's exposition carries every term from the first
    scrape — ``dispatch="megastep"`` among them, at 0, though no flight is
    of that class — and over such a window the benchmark's own reducer
    gives the metric a value; without the zero series it gives none."""
    import sys
    import types

    from crowdllama_tpu.obs.metrics import DISPATCH_CLASSES, EngineTelemetry

    sys.path.insert(0, str(REPO / "benchmarks" / "chip"))
    try:
        from harness.reducers import counter_ratio
    finally:
        sys.path.pop(0)
    spec = json.loads((REPO / "benchmarks" / "chip" / "layer_metrics"
                       / "step.decode_wall_ms.json").read_text())
    tele = EngineTelemetry()
    start = "\n".join(tele.expose())
    for fam in ("seconds", "steps"):
        assert re.search(rf'^crowdllama_engine_flight_{fam}_total'
                         r'\{dispatch="megastep"\} 0(\.0+)?$', start, re.M)
    # the window: 8 plain steps in 0.1 s, and one flight of every class
    # the scheduler names besides
    assert "megastep" in DISPATCH_CLASSES
    tele.flight_inc("plain", seconds=0.1, steps=8, useful=8, waste=0,
                    short=False)
    for cls in ("ragged", "spec"):
        tele.flight_inc(cls, seconds=0.5, steps=4, useful=4, waste=0,
                        short=False)
    end = "\n".join(tele.expose())

    def metric(start, end):
        run = types.SimpleNamespace(
            scrapes={"worker": {"start": start, "end": end}})
        return counter_ratio.reduce(spec, run)

    assert metric(start, end) == pytest.approx(12.5)

    def without(text):
        return "\n".join(ln for ln in text.splitlines()
                         if 'dispatch="megastep"' not in ln)

    assert metric(without(start), without(end)) is None
