"""Paged KV cache (engine/paged.py): decode parity with the contiguous
layout on mixed prompt lengths, memory footprint at long context, page
accounting, and overcommit exhaustion behavior."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crowdllama_tpu.engine.paged import PagedModelRunner, PagesExhausted
from crowdllama_tpu.engine.runner import ModelRunner
from crowdllama_tpu.models.config import get_config


def _assert_all_pages_accounted(runner):
    """After every slot retires, each page is either free or held ONLY by
    the prefix cache (indexed, refcount 0) — nothing leaks."""
    cached = sum(1 for p in runner._page_key
                 if runner._page_refs.get(p, 0) == 0)
    assert len(runner._free_pages) + cached == runner.total_pages, (
        len(runner._free_pages), cached, runner.total_pages)


def _fill(pr, cr, prompts, key):
    ps, cs = pr.init_state(), cr.init_state()
    for slot, prompt in enumerate(prompts):
        t1, ks, vs, plen = pr.prefill(prompt, 0.0, 1.0, key)
        ps = pr.insert(ps, slot, ks, vs, plen, t1, 0.0, 1.0)
        t2, ks2, vs2, plen2 = cr.prefill(prompt, 0.0, 1.0, key)
        cs = cr.insert(cs, slot, ks2, vs2, plen2, t2, 0.0, 1.0)
        assert t1 == t2
    return ps, cs


def test_paged_matches_contiguous_mixed_lengths():
    cfg = get_config("tiny-test", max_context_length=256)
    pr = PagedModelRunner(cfg, max_slots=4, max_seq=256, page_size=32,
                          mesh_spec="1")
    cr = ModelRunner(cfg, params=pr.params, max_slots=4, max_seq=256,
                     mesh_spec="1")
    prompts = [[1, 2, 3], list(range(1, 40)), [7] * 30, list(range(5, 90))]
    ps, cs = _fill(pr, cr, prompts, jax.random.PRNGKey(0))
    # Decode across chunk sizes, including page-boundary crossings.
    for chunk in (1, 8, 32):
        ptoks, ps = pr.decode_steps(ps, chunk)
        ctoks, cs = cr.decode_steps(cs, chunk)
        np.testing.assert_array_equal(ptoks, ctoks)
    # Release frees the slot's pages.
    before = len(pr._free_pages)
    ps = pr.release(ps, 3)
    assert len(pr._free_pages) > before
    # Slots 0-2 keep decoding correctly after the release.
    ptoks, ps = pr.decode_steps(ps, 4)
    ctoks, cs = cr.decode_steps(cr.release(cs, 3), 4)
    np.testing.assert_array_equal(ptoks[:, :3], ctoks[:, :3])


def test_paged_pool_smaller_than_contiguous_at_long_ctx():
    """At ctx 8192 an overcommitted pool's device footprint is a fraction of
    the contiguous cache (the capacity win paging exists for)."""
    cfg = get_config("tiny-test", max_context_length=8192)
    slots = 8
    pr = PagedModelRunner(cfg, max_slots=slots, max_seq=8192, page_size=128,
                          pool_tokens=2 * 8192, mesh_spec="1")  # 4x overcommit
    ps = pr.init_state()
    paged_bytes = ps.pool_k.nbytes + ps.pool_v.nbytes
    cr = ModelRunner(cfg, params=pr.params, max_slots=slots, max_seq=8192,
                     mesh_spec="1")
    cs = cr.init_state()
    contiguous_bytes = cs.k_cache.nbytes + cs.v_cache.nbytes
    assert paged_bytes < contiguous_bytes / 3.5, (
        f"paged {paged_bytes} !<< contiguous {contiguous_bytes}")


def test_paged_overcommit_exhaustion_raises_cleanly():
    cfg = get_config("tiny-test", max_context_length=256)
    # pool_tokens clamps to one slot's full page count (a lone slot must
    # always be able to reach max_seq): 8 pages here.
    pr = PagedModelRunner(cfg, max_slots=4, max_seq=256, page_size=32,
                          pool_tokens=64, mesh_spec="1")
    assert pr.total_pages == 8
    ps = pr.init_state()
    key = jax.random.PRNGKey(0)
    t, ks, vs, plen = pr.prefill(list(range(1, 200)), 0.0, 1.0, key)
    ps = pr.insert(ps, 0, ks, vs, plen, t, 0.0, 1.0)  # bucket 256 -> all 8
    t2, ks2, vs2, plen2 = pr.prefill([1, 2, 3], 0.0, 1.0, key)
    with pytest.raises(PagesExhausted):
        pr.insert(ps, 1, ks2, vs2, plen2, t2, 0.0, 1.0)  # 0 pages free
    # PagesExhausted is a ValueError: the scheduler's admission error path
    # fails the request instead of killing the engine.
    assert issubclass(PagesExhausted, ValueError)


async def test_paged_overcommit_starves_one_slot_not_engine():
    """When an overcommitted pool runs dry mid-decode, the scheduler
    finishes the starved slot with 'length' and the other request
    completes normally (no engine-wide failure)."""
    from crowdllama_tpu.config import Configuration, Intervals
    from crowdllama_tpu.engine.engine import JaxEngine

    cfg = Configuration(model="tiny-test", max_context_length=512,
                        kv_layout="paged", kv_page_size=32,
                        kv_pool_tokens=512,  # clamps to 16 pages
                        max_batch_slots=2, warmup=False,
                        intervals=Intervals.default())
    engine = JaxEngine(cfg)
    await engine.start()
    try:
        async def run_one(n):
            reasons = []
            async for chunk in engine.generate("grow " * 20, max_tokens=n):
                if chunk.done:
                    reasons.append(chunk.done_reason)
            return reasons[0]

        # Two big requests racing for 16 pages: at least one must finish
        # (stop/length), neither may error, and the engine survives.
        r1, r2 = await asyncio.gather(run_one(400), run_one(400))
        assert r1 in ("stop", "length") and r2 in ("stop", "length")
        runner = engine.scheduler.runner
        _assert_all_pages_accounted(runner)
        # Engine still serves after the squeeze.
        r3 = await run_one(4)
        assert r3 in ("stop", "length")
    finally:
        await engine.stop()


async def test_paged_engine_end_to_end():
    """JaxEngine with kv_layout=paged serves concurrent mixed-length
    requests through the scheduler."""
    from crowdllama_tpu.config import Configuration, Intervals
    from crowdllama_tpu.engine.engine import JaxEngine

    cfg = Configuration(model="tiny-test", max_context_length=256,
                        kv_layout="paged", kv_page_size=32,
                        max_batch_slots=2, warmup=False,
                        intervals=Intervals.default())
    engine = JaxEngine(cfg)
    await engine.start()
    try:
        async def one(prompt, n):
            text = []
            async for chunk in engine.generate(prompt, max_tokens=n):
                text.append(chunk.text)
                if chunk.done:
                    assert chunk.done_reason in ("stop", "length")
                    assert chunk.completion_tokens >= 1
            return "".join(text)

        outs = await asyncio.gather(
            one("short", 6), one("a much longer prompt " * 5, 10))
        assert len(outs) == 2
        # All pages returned after both requests retired.
        runner = engine.scheduler.runner
        _assert_all_pages_accounted(runner)
    finally:
        await engine.stop()


def test_paged_int8_matches_contiguous_greedy():
    """int8 paged pools (per-page scales, VERDICT r2 feature composition):
    greedy decode must agree with the bf16 contiguous reference on the tiny
    model (quantization noise tolerance is generous; exactness on the tiny
    model has held in practice)."""
    cfg = get_config("tiny-test", max_context_length=256)
    pr = PagedModelRunner(cfg, max_slots=2, max_seq=256, page_size=32,
                          mesh_spec="1", kv_dtype="int8")
    cr = ModelRunner(cfg, params=pr.params, max_slots=2, max_seq=256,
                     mesh_spec="1")
    prompts = [list(range(1, 70)), list(range(5, 40))]
    ps, cs = _fill(pr, cr, prompts, jax.random.PRNGKey(0))
    pt, ps = pr.decode_steps(ps, 8)
    ct, cs = cr.decode_steps(cs, 8)
    agree = float(np.mean(pt == ct))
    assert agree >= 0.8, f"int8-paged vs bf16-contiguous agreement {agree}"


def test_paged_int8_prefix_cache_hit():
    """Prefix caching composes with int8 pools: the shared prefix's int8
    pages are reused as (dequantized) attention context for the suffix."""
    cfg = get_config("tiny-test", max_context_length=256)
    pr = PagedModelRunner(cfg, max_slots=2, max_seq=256, page_size=32,
                          mesh_spec="1", kv_dtype="int8")
    state = pr.init_state()
    shared = list(range(1, 65))
    t1, ks, vs, plen = pr.prefill(shared + [70, 71], 0.0, 1.0,
                                  jax.random.PRNGKey(0), state=state)
    state = pr.insert(state, 0, ks, vs, plen, t1, 0.0, 1.0)
    t2, ks2, vs2, plen2 = pr.prefill(shared + [80, 81, 82], 0.0, 1.0,
                                     jax.random.PRNGKey(1), state=state)
    state = pr.insert(state, 1, ks2, vs2, plen2, t2, 0.0, 1.0)
    assert pr.prefix_hits == 1 and pr.prefix_tokens_reused == 64
    toks, state = pr.decode_steps(state, 4)
    assert toks.shape == (4, 2)


def test_paged_fused_kernel_matches_gather(monkeypatch):
    """The fused pallas paged-decode kernel (interpret mode on CPU) must
    produce the same greedy tokens as the jnp gather fallback, bf16 and
    int8 pools alike (ops/pallas/paged.py)."""
    from crowdllama_tpu.ops.pallas import paged as pp_mod

    cfg = get_config("tiny-test", max_context_length=256)
    for kvd in ("bf16", "int8"):
        outs = {}
        for mode in ("gather", "kernel"):
            if mode == "kernel":
                monkeypatch.delenv("CROWDLLAMA_NO_PALLAS", raising=False)
                monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
            else:
                # Force the jnp fallback even on a TPU-attached host (where
                # the backend alone would enable the kernel path).
                monkeypatch.setenv("CROWDLLAMA_NO_PALLAS", "1")
                monkeypatch.delenv("CROWDLLAMA_PALLAS_INTERPRET",
                                   raising=False)
            assert pp_mod.paged_pallas_supported(32, 16) == (
                mode == "kernel")
            pr = PagedModelRunner(cfg, max_slots=2, max_seq=256,
                                  page_size=32, mesh_spec="1",
                                  kv_dtype=kvd, seed=0)
            state = pr.init_state()
            for slot, prompt in enumerate(
                    [list(range(1, 70)), list(range(3, 45))]):
                t, ks, vs, plen = pr.prefill(prompt, 0.0, 1.0,
                                             jax.random.PRNGKey(0))
                state = pr.insert(state, slot, ks, vs, plen, t, 0.0, 1.0)
            toks, state = pr.decode_steps(state, 6)
            outs[mode] = toks.tolist()
        assert outs["kernel"] == outs["gather"], (kvd, outs)


def test_paged_kernel_odd_page_count_tail(monkeypatch):
    """Page-PAIRED grid with an odd per-slot page count: the clamped tail
    pair must not contribute (its duplicate page's compute is skipped by
    the seq_len bound), matching the gather reference exactly."""
    import jax.numpy as jnp

    from crowdllama_tpu.ops.attention import decode_attention
    from crowdllama_tpu.ops.pallas.paged import flash_paged_decode_attention

    monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
    B, H, HKV, DH, PAGE, NP_ = 2, 8, 2, 32, 32, 3
    P = B * NP_ + 1
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (B, H, DH), jnp.float32)
    pk = jax.random.normal(kk, (P, HKV, PAGE, DH), jnp.float32)
    pv = jax.random.normal(kv_, (P, HKV, PAGE, DH), jnp.float32)
    # Guard the test's purpose: this shape must actually select page
    # PAIRING (the clamped tail path) — a budget/gating tweak that drops
    # it to pairs=1 should fail here, not silently detune the test.
    from crowdllama_tpu.ops.pallas.paged import (
        _VMEM_TILE_BUDGET,
        _pairs_bytes,
    )

    assert 4 * _pairs_bytes(HKV, PAGE, DH, 4) <= _VMEM_TILE_BUDGET
    table = jnp.asarray([[0, 1, 2], [3, 4, 5]], jnp.int32)
    lens = jnp.asarray([70, 95], jnp.int32)  # partial last pages
    out = flash_paged_decode_attention(q, pk[None], pv[None], 0, table,
                                       lens, DH ** -0.5)
    kc = pk[table].transpose(0, 2, 1, 3, 4).reshape(B, HKV, NP_ * PAGE, DH)
    vc = pv[table].transpose(0, 2, 1, 3, 4).reshape(B, HKV, NP_ * PAGE, DH)
    ref = decode_attention(q, kc, vc, lens, DH ** -0.5)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_paged_fused_kernel_tp_sharded(monkeypatch):
    """tp>1 meshes must take the fused kernel path via the shard_map
    wrapper — not the virtual-contiguous gather (VERDICT r3 missing #2) —
    and produce identical greedy tokens, bf16 and int8 pools alike."""
    from crowdllama_tpu.ops.pallas import paged as pp_mod

    cfg = get_config("tiny-test", max_context_length=256)
    monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
    monkeypatch.delenv("CROWDLLAMA_NO_PALLAS", raising=False)
    # Supported matrix: tp must divide the kv heads (2 here).
    assert pp_mod.paged_pallas_supported(32, 16, 2, 2)
    assert not pp_mod.paged_pallas_supported(32, 16, 4, 2)  # 2 heads / 4 tp

    prompts = [list(range(1, 70)), list(range(3, 45))]
    # "2" = tp2; "1x2x1" = ep2×tp1 — BOTH multi-device meshes must route
    # through the shard_map wrapper (a raw pallas_call can't be partitioned
    # or replicated by GSPMD), with identical tokens to the gather.
    for mesh_spec, kvd in (("2", "bf16"), ("2", "int8"), ("1x2x1", "bf16")):
        outs = {}
        for mode in ("kernel", "gather"):
            if mode == "kernel":
                monkeypatch.delenv("CROWDLLAMA_NO_PALLAS", raising=False)
                calls = []
                orig = pp_mod.flash_paged_decode_attention_tp

                def spy(*a, **kw):
                    calls.append(1)
                    return orig(*a, **kw)

                monkeypatch.setattr(
                    "crowdllama_tpu.engine.paged."
                    "flash_paged_decode_attention_tp", spy)
            else:
                monkeypatch.setenv("CROWDLLAMA_NO_PALLAS", "1")
            pr = PagedModelRunner(cfg, max_slots=2, max_seq=256,
                                  page_size=32, mesh_spec=mesh_spec,
                                  kv_dtype=kvd, seed=0)
            assert pr.mesh.size == 2
            state = pr.init_state()
            for slot, prompt in enumerate(prompts):
                t, ks, vs, plen = pr.prefill(prompt, 0.0, 1.0,
                                             jax.random.PRNGKey(0))
                state = pr.insert(state, slot, ks, vs, plen, t, 0.0, 1.0)
            toks, state = pr.decode_steps(state, 6)
            outs[mode] = toks.tolist()
            if mode == "kernel":
                assert calls, (
                    f"{mesh_spec} mesh did not take the shard_map kernel path")
            monkeypatch.delenv("CROWDLLAMA_NO_PALLAS", raising=False)
        assert outs["kernel"] == outs["gather"], (mesh_spec, kvd, outs)


def test_config_paged_int8_composes():
    """config.py must accept the paged + int8 KV + prefix cache combination
    (round-2's pairwise exclusions are lifted) and default to paged."""
    from crowdllama_tpu.config import Configuration

    cfg = Configuration.from_environment(kv_layout="paged", kv_dtype="int8")
    assert cfg.kv_layout == "paged" and cfg.kv_dtype == "int8"
    assert Configuration().kv_layout == "paged"
    # Spec now composes with paged (int8 pools included, VERDICT r3 #4)...
    cfg = Configuration.from_environment(spec_decode="ngram",
                                         kv_layout="paged", kv_dtype="int8")
    assert cfg.kv_layout == "paged" and cfg.spec_decode == "ngram"
    # ...while contiguous spec still needs the bf16 cache.
    with pytest.raises(ValueError):
        Configuration.from_environment(spec_decode="ngram",
                                       kv_layout="contiguous",
                                       kv_dtype="int8")


def test_paged_chunked_admission_matches_monolithic():
    """Chunked admission (prefill_begin/step/finish) on the paged runner:
    greedy tokens match monolithic prefill, and the chunk-admitted pages
    are prefix-indexed so later prompts sharing the prefix hit."""
    cfg = get_config("tiny-test", max_context_length=256)
    pr = PagedModelRunner(cfg, max_slots=2, max_seq=256, page_size=32,
                          mesh_spec="1", kv_dtype="int8")
    pr.prefill_chunk = 64  # force chunking for the 100-token prompt
    prompt = list(range(1, 101))

    state = pr.init_state()
    job = pr.prefill_begin(prompt)
    while not pr.prefill_step(job):
        pass
    tok, ks, vs, plen = pr.prefill_finish(job, 0.0, 1.0, jax.random.PRNGKey(0))
    state = pr.insert(state, 0, ks, vs, plen, tok, 0.0, 1.0,
                      prompt_tokens=prompt)
    t_chunked, state = pr.decode_steps(state, 6)

    pr2 = PagedModelRunner(cfg, params=pr.params, max_slots=2, max_seq=256,
                           page_size=32, mesh_spec="1", kv_dtype="int8")
    s2 = pr2.init_state()
    tok2, ks2, vs2, plen2 = pr2.prefill(prompt, 0.0, 1.0,
                                        jax.random.PRNGKey(0), state=s2)
    s2 = pr2.insert(s2, 0, ks2, vs2, plen2, tok2, 0.0, 1.0,
                    prompt_tokens=prompt)
    t_mono, s2 = pr2.decode_steps(s2, 6)
    assert tok == tok2
    assert t_chunked[:, 0].tolist() == t_mono[:, 0].tolist()

    # Chunk-admitted pages feed the prefix cache (and the monolithic hint).
    assert pr.prefill_prefers_monolithic(prompt)
    pr.prefill(prompt[:96] + [7, 8, 9], 0.0, 1.0, jax.random.PRNGKey(1),
               state=state)
    assert pr.prefix_hits == 1


def test_paged_chunked_admission_seeds_from_prefix_cache():
    """Chunked admission with a cached prefix: the job's context is seeded
    from the cached pages (prefill_begin state path), so a mostly-cached
    long prompt prefills only its uncovered suffix — and the result matches
    an uncached monolithic prefill exactly."""
    for kvd in ("bf16", "int8"):
        cfg = get_config("tiny-test", max_context_length=256)
        pr = PagedModelRunner(cfg, max_slots=2, max_seq=256, page_size=32,
                              mesh_spec="1", kv_dtype=kvd)
        pr.prefill_chunk = 64
        base = list(range(1, 129))  # 4 full pages
        state = pr.init_state()
        tok, ks, vs, plen = pr.prefill(base + [50, 51], 0.0, 1.0,
                                       jax.random.PRNGKey(0), state=state)
        state = pr.insert(state, 0, ks, vs, plen, tok, 0.0, 1.0,
                          prompt_tokens=base + [50, 51])
        hits0, reused0 = pr.prefix_hits, pr.prefix_tokens_reused

        promptB = base + list(range(200, 300))  # suffix 100 > chunk 64
        job = pr.prefill_begin(promptB, state=state)
        assert job.done_tokens == 128  # seeded past the cached prefix
        while not pr.prefill_step(job):
            pass
        tokB, ksB, vsB, plenB = pr.prefill_finish(job, 0.0, 1.0,
                                                  jax.random.PRNGKey(2))
        state = pr.insert(state, 1, ksB, vsB, plenB, tokB, 0.0, 1.0,
                          prompt_tokens=promptB)
        assert pr.prefix_hits == hits0 + 1
        assert pr.prefix_tokens_reused == reused0 + 128

        pr2 = PagedModelRunner(cfg, params=pr.params, max_slots=2,
                               max_seq=256, page_size=32, mesh_spec="1",
                               kv_dtype=kvd)
        s2 = pr2.init_state()
        tok2, ks2, vs2, plen2 = pr2.prefill(promptB, 0.0, 1.0,
                                            jax.random.PRNGKey(2))
        s2 = pr2.insert(s2, 1, ks2, vs2, plen2, tok2, 0.0, 1.0)
        assert tokB == tok2
        tB, state = pr.decode_steps(state, 5)
        t2, s2 = pr2.decode_steps(s2, 5)
        assert tB[:, 1].tolist() == t2[:, 1].tolist()


@pytest.mark.parametrize("scales", [False, True])
@pytest.mark.parametrize("start,valid", [
    (0, 64),     # page-aligned, every row real
    (32, 64),    # aligned start on the second page
    (40, 64),    # starts mid-page: the chunk meets three pages
    (40, 23),    # a short tail: rows past `valid` are not written
    (96, 20),    # runs into the slot's last page
    (64, 0),     # no chunk this step
])
def test_kv_write_helpers_match_the_scatter(start, valid, scales):
    """The step bodies write KV with dynamic-update-slices (``_put_rows``
    per decode row, ``_put_chunk`` per page of the prefill chunk) so the
    stacked pool is updated in place in its own layout.  Both are, on the
    positions that hold real tokens, exactly ``pool.at[layer, page, :,
    offset].set(row)`` — and touch no other position of a real page."""
    import jax.numpy as jnp

    from crowdllama_tpu.engine.paged import _put_chunk, _put_rows

    layers, pages, hkv, page, dh, c = 3, 6, 2, 32, 8, 64
    dump = pages  # the reserved extra page
    shape = (layers, pages + 1, hkv, page) + (() if scales else (dh,))
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(start + valid), 3)
    pool = jax.random.normal(k0, shape)
    page_row = jnp.asarray([4, 1, 3, 5], jnp.int32)
    layer = jnp.int32(1)

    # chunk rows [C, Hkv, ...] -> kv-head-major like a page
    rows = jax.random.normal(k1, (c, hkv) + shape[4:])
    got = jax.jit(_put_chunk, static_argnames=("dump_page",))(
        pool, jnp.swapaxes(rows, 0, 1), layer=layer, page_row=page_row,
        start=jnp.int32(start), valid=jnp.int32(valid), dump_page=dump)
    pos = start + np.arange(valid)
    want = pool.at[1, page_row[pos // page], :, pos % page].set(rows[:valid])
    np.testing.assert_array_equal(np.asarray(got)[:, :pages],
                                  np.asarray(want)[:, :pages])

    # decode rows: two live slots and one routed to the dump page
    drows = jax.random.normal(k2, (3, hkv) + shape[4:])
    dpages = jnp.asarray([2, dump, 0], jnp.int32)
    doffs = jnp.asarray([5, 0, 31], jnp.int32)
    got = jax.jit(_put_rows)(pool, drows, layer=layer, pages=dpages,
                             offsets=doffs)
    want = pool.at[1, dpages, :, doffs].set(drows)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _page_partition(runner):
    """(free, evictable, live) page sets; they must partition the pool."""
    free = set(runner._free_pages)
    assert len(free) == len(runner._free_pages), "a page is free twice"
    live = {p for pages in runner._slot_pages.values() for p in pages}
    evictable = {p for p in runner._page_key
                 if runner._page_refs.get(p, 0) == 0} - live
    return free, evictable, live


def test_pool_survives_hundreds_of_unshared_requests():
    """free + evictable + live = total, through a few hundred admissions
    and releases of prompts that share nothing (the chip benchmark's
    decode_sat: one full prompt page that gets indexed, then growth pages).
    Recycled pages used to keep the refcount of their last life; after
    ~150 requests on a 128-page pool every page sat in the prefix index at
    -1, unevictable, and admission failed with ``kv pool exhausted``."""
    cfg = get_config("tiny-test", max_context_length=128)
    pr = PagedModelRunner(cfg, max_slots=2, max_seq=128, page_size=32,
                          mesh_spec="1", seed=0)
    assert pr.total_pages == 8
    state = pr.init_state()
    key = jax.random.PRNGKey(0)
    rng = np.random.default_rng(0)
    held: dict[int, int] = {}  # slot -> decode steps still to run
    admitted = 0
    while admitted < 300 or held:
        for slot in range(pr.max_slots):
            if slot not in held and admitted < 300:
                prompt = rng.integers(1, 200, 32).tolist()  # one full page
                tok, ks, vs, plen = pr.prefill(prompt, 0.0, 1.0, key,
                                               state=state)
                state = pr.insert(state, slot, ks, vs, plen, tok, 0.0, 1.0,
                                  prompt_tokens=prompt)
                held[slot] = 5  # x 8 steps: grows to 3 pages
                admitted += 1
        _, state = pr.decode_steps(state, 8)
        for slot in list(held):
            held[slot] -= 1
            if not held[slot]:
                state = pr.release(state, slot)
                del held[slot]
        free, evictable, live = _page_partition(pr)
        assert not (free & live) and not (free & evictable)
        assert len(free) + len(evictable) + len(live) == pr.total_pages, (
            admitted, sorted(free), sorted(evictable), sorted(live),
            dict(pr._page_refs))
    assert all(v >= 0 for v in pr._page_refs.values()), pr._page_refs
    _assert_all_pages_accounted(pr)


def _grid_steps() -> dict[str, int]:
    from crowdllama_tpu.obs.metrics import ENGINE_TELEMETRY

    return {line.split(" ")[0].split("_total")[1]: int(line.split(" ")[1])
            for line in ENGINE_TELEMETRY.expose()
            if line.startswith("crowdllama_attn_grid_steps_total{")}


@pytest.mark.parametrize("path", ["kernel", "gathered"])
def test_flight_books_the_decode_kernels_grid_steps(monkeypatch, path):
    """crowdllama_attn_grid_steps_total{kind,walk}: a flight books, from the
    lengths the runner holds on the host, the grid steps its decode kernel
    calls walk (one a live page pair of a live slot, a call a layer a step)
    and those of the slots x table rectangle — and nothing where the
    gathered jnp view serves, which walks no grid."""
    if path == "kernel":
        monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
    cfg = get_config("tiny-test", max_context_length=128)    # two layers
    pr = PagedModelRunner(cfg, max_slots=4, max_seq=128, page_size=32,
                          mesh_spec="1", dtype=jnp.float32)
    assert (pr.attention_paths["decode"] == "jnp") == (path == "gathered")
    st = pr.init_state()
    for slot, n in ((0, 5), (2, 70)):
        tok, ks, vs, plen = pr.prefill(list(range(1, n + 1)), 0.0, 1.0,
                                       jax.random.PRNGKey(0))
        st = pr.insert(st, slot, ks, vs, plen, tok, 0.0, 1.0)
    before = _grid_steps()
    _, st = pr.decode_steps(st, 2)
    booked = {k: v - before[k] for k, v in _grid_steps().items()}
    # 4 columns, two pages a grid step: slot 0 reads 6 then 7 tokens (one
    # pair), slot 2 reads 71 then 72 (two); slots 1 and 3 have no tenant
    want = {'{kind="full",walk="live"}': 2 * (1 + 2) * 2,
            '{kind="full",walk="rectangle"}': 2 * (4 * 2) * 2,
            '{kind="window",walk="live"}': 0,
            '{kind="window",walk="rectangle"}': 0}
    assert booked == (want if path == "kernel" else dict.fromkeys(want, 0))
    if path == "kernel":
        # the slot that left is walked no more; one pair is the least
        st = pr.release(pr.release(st, 2), 0)
        _, st = pr.decode_steps(st, 1)
        after = _grid_steps()
        assert after['{kind="full",walk="live"}'] - before[
            '{kind="full",walk="live"}'] == 12 + 1 * 2
