"""Pallas flash-attention kernels vs the jnp reference semantics.

Runs the kernels in interpret mode (CROWDLLAMA_PALLAS_INTERPRET) on the CPU
test platform — the same numerics the Mosaic-compiled kernel executes on TPU.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crowdllama_tpu.ops.attention import (
    decode_attention_ref,
    prefill_attention_ref,
)
from crowdllama_tpu.ops.pallas.flash import (
    _tile,
    flash_decode_attention,
    flash_prefill_attention,
)


@pytest.fixture(autouse=True)
def _interpret_mode():
    os.environ["CROWDLLAMA_PALLAS_INTERPRET"] = "1"
    yield
    os.environ.pop("CROWDLLAMA_PALLAS_INTERPRET", None)


def _rand_qkv(key, b, t, h, hkv, dh, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (b, t, h, dh), dtype)
    k = jax.random.normal(k2, (b, hkv, t, dh), dtype)  # head-major layout
    v = jax.random.normal(k3, (b, hkv, t, dh), dtype)
    return q, k, v


def test_tile_divisibility():
    assert _tile(1024) == 512
    assert _tile(96) == 32
    assert _tile(8) == 8
    assert _tile(1) == 1
    assert _tile(256, cap=256) == 256


@pytest.mark.parametrize("softcap,window", [(0.0, 0), (30.0, 0), (0.0, 5)])
def test_prefill_matches_reference(softcap, window):
    b, t, h, hkv, dh = 2, 64, 4, 2, 16
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), b, t, h, hkv, dh)
    positions = jnp.broadcast_to(jnp.arange(t), (b, t)).astype(jnp.int32)
    scale = dh ** -0.5

    ref = prefill_attention_ref(q, k, v, positions, scale, softcap=softcap,
                                sliding_window=window)
    got = flash_prefill_attention(q, k, v, positions, scale, softcap=softcap,
                                  sliding_window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_prefill_with_clamped_padding_matches_reference():
    """The serving path: positions clamped at plen-1, kv_valid masks padding."""
    b, t, h, hkv, dh = 1, 64, 4, 4, 8
    plen = 37
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), b, t, h, hkv, dh)
    positions = jnp.minimum(jnp.arange(t)[None, :], plen - 1).astype(jnp.int32)
    kv_valid = (jnp.arange(t) < plen)[None, :]
    scale = dh ** -0.5

    ref = prefill_attention_ref(q, k, v, positions, scale, kv_valid=kv_valid)
    got = flash_prefill_attention(q, k, v, positions, scale, kv_valid=kv_valid)
    np.testing.assert_allclose(np.asarray(got[:, :plen]),
                               np.asarray(ref[:, :plen]),
                               rtol=2e-5, atol=2e-5)


def test_prefill_traced_window_scalar():
    """sliding_window arrives as a traced int32 scalar inside lax.scan."""
    b, t, h, hkv, dh = 1, 32, 2, 1, 8
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), b, t, h, hkv, dh)
    positions = jnp.arange(t)[None, :].astype(jnp.int32)
    scale = dh ** -0.5

    def f(window):
        return flash_prefill_attention(q, k, v, positions, scale,
                                       sliding_window=window)

    got = jax.jit(f)(jnp.int32(7))
    ref = prefill_attention_ref(q, k, v, positions, scale, sliding_window=7)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("softcap,window", [(0.0, 0), (50.0, 0), (0.0, 9)])
def test_decode_matches_reference(softcap, window):
    b, s, h, hkv, dh = 4, 128, 8, 2, 16
    key = jax.random.PRNGKey(3)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (b, h, dh))
    kc = jax.random.normal(k2, (b, hkv, s, dh))
    vc = jax.random.normal(k3, (b, hkv, s, dh))
    seq_lens = jnp.asarray([1, 17, 64, 128], jnp.int32)
    scale = dh ** -0.5

    ref = decode_attention_ref(q, kc, vc, seq_lens, scale, softcap=softcap,
                               sliding_window=window)
    got = flash_decode_attention(q, kc, vc, seq_lens, scale, softcap=softcap,
                                 sliding_window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_decode_inactive_slot_is_finite_free():
    """seq_len=0 slots produce zeros (not NaN/Inf) from the kernel."""
    b, s, h, hkv, dh = 2, 64, 4, 2, 8
    key = jax.random.PRNGKey(4)
    q = jax.random.normal(key, (b, h, dh))
    kc = jnp.zeros((b, hkv, s, dh))
    vc = jnp.zeros((b, hkv, s, dh))
    seq_lens = jnp.asarray([0, 5], jnp.int32)
    out = flash_decode_attention(q, kc, vc, seq_lens, dh ** -0.5)
    assert np.isfinite(np.asarray(out)).all()


def test_ragged_v2_on_bf16_pages_is_the_float32_kernel():
    """bf16 queries and pages go to the MXU as they are (exact products,
    float32 sums; the probabilities in two bf16 pieces): the chunk's rows
    read what the float32 kernel reads of the same values, to bf16's own
    rounding of the output."""
    from crowdllama_tpu.ops.pallas.paged import flash_ragged_paged_attention

    b, h, hkv, dh, page = 2, 12, 2, 16, 32
    c, ctx = 40, 50
    q, pool_k, pool_v = (
        jax.random.normal(k, shape).astype(jnp.bfloat16)
        for k, shape in zip(jax.random.split(jax.random.PRNGKey(8), 3),
                            ((b + c, h, dh), (1, 9, hkv, page, dh),
                             (1, 9, hkv, page, dh))))
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    args = (0, table, jnp.asarray([1, 0, c], jnp.int32),
            jnp.asarray([70, 0, ctx + c], jnp.int32), jnp.int32(1),
            dh ** -0.5)
    got = flash_ragged_paged_attention(q, pool_k, pool_v, *args)
    want = flash_ragged_paged_attention(*(
        a.astype(jnp.float32) for a in (q, pool_k, pool_v)), *args)
    rows = np.r_[0, b:b + c]
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[rows], np.asarray(want)[rows],
        rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("softcap,window", [(0.0, 0), (30.0, 0), (0.0, 9)])
def test_ragged_v2_matches_reference(softcap, window):
    """Ragged-paged attention v2 (ONE kernel, head-packed query blocks,
    scalar-driven decode/chunk behavior) vs the pure-JAX unified ref:
    decode rows at mixed lengths — including an inactive q_len=0 slot,
    which must not contaminate its neighbors — plus a prefill chunk
    spanning a partial second query block."""
    from crowdllama_tpu.ops.pallas.paged import (
        flash_ragged_paged_attention,
        ragged_paged_attention_ref,
    )

    b, h, hkv, dh, page, np_ = 3, 4, 2, 16, 32, 4
    g = h // hkv
    c, ctx, chunk_len = 40, 16, 40  # 2 q blocks; second holds 8 valid rows
    chunk_slot = 2
    pool_pages = 16
    key = jax.random.PRNGKey(6)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    q = jax.random.normal(k1, (b + c, h, dh))
    pool_k = jax.random.normal(k2, (pool_pages, hkv, page, dh))
    pool_v = jax.random.normal(k3, (pool_pages, hkv, page, dh))
    # Distinct pages per slot; the chunk slot owns rows ctx..ctx+c-1.
    page_table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]],
                             jnp.int32)
    q_lens = jnp.asarray([1, 0, 1, chunk_len], jnp.int32)  # slot 1 inactive
    kv_lens = jnp.asarray([33, 0, 1, ctx + chunk_len], jnp.int32)
    # The contract: the chunk's fresh KV is ALREADY scattered into the
    # pool (the engine writes it in the same layer pass).  The ref reads
    # the self block from explicit operands; carve them back out of the
    # pool so both paths see identical bytes.
    cpages = page_table[chunk_slot]
    cpos = ctx + jnp.arange(c)
    chunk_k = pool_k[cpages[cpos // page], :, cpos % page].transpose(
        1, 0, 2)[None]
    chunk_v = pool_v[cpages[cpos // page], :, cpos % page].transpose(
        1, 0, 2)[None]
    del k4
    scale = dh ** -0.5

    ref = ragged_paged_attention_ref(
        q, chunk_k, chunk_v, pool_k[None], pool_v[None], 0, page_table,
        q_lens, kv_lens, jnp.int32(chunk_slot), scale, softcap=softcap,
        sliding_window=window)
    got = flash_ragged_paged_attention(
        q, pool_k[None], pool_v[None], 0, page_table, q_lens, kv_lens,
        jnp.int32(chunk_slot), scale, softcap=softcap,
        sliding_window=window)
    # Compare rows that carry real queries: active decode rows + the
    # chunk's valid rows (the runner discards everything else).
    live = [0, 2] + [b + i for i in range(chunk_len)]
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(ref)[live],
                               rtol=2e-5, atol=2e-5)
    # A dead decode row is visited by no grid step of the decode kernel: it
    # keeps its query (finite, not NaN), and is no one's answer.
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got)[1], np.asarray(q)[1])


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("kernel", ["decode", "ragged_v2", "decode_tp"])
def test_stacked_pool_kernel_reads_its_layer(kernel, kv):
    """Every paged kernel takes the whole ``[L, P, Hkv, page, Dh]`` pool and
    a (traced) layer index: its answer at layer ``l`` is bit for bit the
    answer of the same kernel on ``pool[l]`` cut out beforehand — and not
    that of another layer."""
    from crowdllama_tpu.ops.pallas import paged as pp
    from crowdllama_tpu.ops.quant import quantize_kv
    from crowdllama_tpu.parallel.mesh import build_mesh

    layers, b, h, hkv, dh, page, np_ = 3, 2, 4, 2, 16, 32, 3
    c, ctx = 40, 16
    pool_pages = b * np_ + 2
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    dt = jnp.bfloat16
    pool_k = jax.random.normal(ks[0], (layers, pool_pages, hkv, page, dh), dt)
    pool_v = jax.random.normal(ks[1], (layers, pool_pages, hkv, page, dh), dt)
    k_sc = v_sc = None
    if kv == "int8":
        pool_k, k_sc = quantize_kv(pool_k)
        pool_v, v_sc = quantize_kv(pool_v)
    table = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    lens = jnp.asarray([70, 33], jnp.int32)
    scale = dh ** -0.5

    if kernel in ("decode", "decode_tp"):
        q = jax.random.normal(ks[2], (b, h, dh), dt)
        if kernel == "decode":
            def run(pk, pv, sk, sv, layer):
                return pp.flash_paged_decode_attention(
                    q, pk, pv, layer, table, lens, scale, sliding_window=40,
                    k_scale=sk, v_scale=sv)
        else:
            mesh = build_mesh("2")  # tp=2 over the two kv heads

            def run(pk, pv, sk, sv, layer):
                return pp.flash_paged_decode_attention_tp(
                    q, pk, pv, layer, table, lens, scale, mesh,
                    sliding_window=40, k_scale=sk, v_scale=sv)
    else:
        q = jax.random.normal(ks[2], (b + c, h, dh), dt)
        q_lens = jnp.asarray([1, 0, c], jnp.int32)
        kv_lens = jnp.asarray([70, 0, ctx + c], jnp.int32)

        def run(pk, pv, sk, sv, layer):
            return pp.flash_ragged_paged_attention(
                q, pk, pv, layer, table, q_lens, kv_lens, jnp.int32(1),
                scale, sliding_window=40, k_scale=sk, v_scale=sv)

    def cut(a, layer):
        return None if a is None else a[layer][None]

    stacked = jax.jit(run)
    outs = []
    for layer in range(layers):
        got = np.asarray(stacked(pool_k, pool_v, k_sc, v_sc,
                                 jnp.int32(layer)), np.float32)
        want = np.asarray(run(cut(pool_k, layer), cut(pool_v, layer),
                              cut(k_sc, layer), cut(v_sc, layer), 0),
                          np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(got, want)
        outs.append(got)
    assert not np.array_equal(outs[0], outs[1])
    assert not np.array_equal(outs[1], outs[2])


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("reader", ["mla_decode", "gqa_decode", "ragged_v2"])
def test_latent_readers_take_the_row_as_it_is_stored(reader, dtype, tol):
    """The three kernels that read a latent pool — the MLA decode kernel of
    the plain step, the GQA decode kernel on the one pool as key AND value
    for a ragged step's decode rows, the v2 ragged kernel for a chunk's
    blocks — at the row as Kimi's pool STORES it: ``[c ; k_rope]`` of 576
    in 640 columns, the last 64 zero in rows and queries alike, the value
    the first 512.  Each gives what plain attention over the 576 computed
    columns gives: a pad column adds an exact zero to a score and is no
    part of a value."""
    from crowdllama_tpu.ops.attention import decode_attention
    from crowdllama_tpu.ops.pallas import paged as pp

    row, stored, latent = 576, 640, 512
    layers, b, h, page, np_, c, ctx = 2, 3, 4, 32, 4, 40, 16
    ks = jax.random.split(jax.random.PRNGKey(13), 2)

    def stored_wide(a):
        return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, stored - row)])

    q = jax.random.normal(ks[0], (b + c, h, row)).astype(dtype)
    pool = jax.random.normal(
        ks[1], (layers, b * np_ + 1, 1, page, row)).astype(dtype)
    table = jnp.arange(b * np_, dtype=jnp.int32).reshape(b, np_)
    lens = jnp.asarray([1, 40, 128], jnp.int32)  # a page, a pair, all four
    scale, li = 192 ** -0.5, jnp.int32(1)
    rows = pool[1, table].transpose(0, 2, 1, 3, 4).reshape(
        b, 1, np_ * page, row)
    want = decode_attention(q[:b], rows, rows, lens, scale)[..., :latent]
    live = slice(0, b)
    if reader == "mla_decode":
        got = pp.paged_decode_attention_mla(
            stored_wide(q[:b]), stored_wide(pool), li, table, lens, scale,
            latent)
    elif reader == "gqa_decode":
        wide = stored_wide(pool)
        got = pp.flash_paged_decode_attention(
            stored_wide(q[:b]), wide, wide, li, table, lens, scale)
    else:
        # slot 1 takes no decode row: its rows ctx .. ctx + c are the chunk
        q_lens = jnp.asarray([1, 0, 1, c], jnp.int32)
        kv_lens = jnp.asarray([1, 0, 128, ctx + c], jnp.int32)
        wide = stored_wide(pool)
        got = pp.flash_ragged_paged_attention(
            stored_wide(q), wide, wide, li, table, q_lens, kv_lens,
            jnp.int32(1), scale)
        # a chunk row i is a decode over the slot's first ctx + i + 1 rows
        chunk = decode_attention(
            q[b:], jnp.broadcast_to(rows[1], (c, *rows.shape[1:])),
            jnp.broadcast_to(rows[1], (c, *rows.shape[1:])),
            ctx + 1 + jnp.arange(c), scale)[..., :latent]
        want = jnp.concatenate([want, chunk])
        live = np.r_[0, 2, b:b + c]
    assert got.shape[-1] in (latent, stored)
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live, :, :latent],
        np.asarray(want, np.float32)[live], rtol=tol, atol=tol)


def test_decode_bf16():
    b, s, h, hkv, dh = 2, 64, 4, 4, 32
    key = jax.random.PRNGKey(5)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (b, h, dh), jnp.bfloat16)
    kc = jax.random.normal(k2, (b, hkv, s, dh), jnp.bfloat16)
    vc = jax.random.normal(k3, (b, hkv, s, dh), jnp.bfloat16)
    seq_lens = jnp.asarray([33, 64], jnp.int32)
    scale = dh ** -0.5
    ref = decode_attention_ref(q, kc, vc, seq_lens, scale)
    got = flash_decode_attention(q, kc, vc, seq_lens, scale)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


def _rectangle(pool, page_table, seq_lens=None, live=None):
    """The grid the decode kernel walked before it had a list, as
    ``decode_work`` would give it: every (slot, page pair) of the table, dead
    or live."""
    from crowdllama_tpu.ops.pallas.paged import DecodeWork, _pairs

    b, cols = page_table.shape
    pairs = _pairs(pool, cols)
    steps = -(-cols // pairs)
    slot = jnp.repeat(jnp.arange(b, dtype=jnp.int32), steps)
    pair = jnp.tile(jnp.arange(steps, dtype=jnp.int32), b)
    return DecodeWork(
        slot, pair,
        tuple(page_table[slot, jnp.minimum(pair * pairs + j, cols - 1)]
              for j in range(pairs)),
        jnp.asarray([b * steps], jnp.int32))


@pytest.mark.parametrize("case", [
    # lens: the kernel's own (a released slot's reads 1, as the engine's)
    dict(id="skewed", lens=[1, 32, 33, 128]),
    dict(id="one_page_pair_each", lens=[64, 64, 64, 64]),
    dict(id="all_but_one_inactive", lens=[1, 1, 70, 1], live=[0, 0, 1, 0]),
    dict(id="first_slot_inactive", lens=[1, 128, 5, 90], live=[0, 1, 1, 1]),
    dict(id="none_active", lens=[1, 1, 1, 1], live=[0, 0, 0, 0]),
    dict(id="zero_lengths_unlisted", lens=[0, 40, 0, 0]),
    dict(id="one_column", lens=[1, 32, 7, 20], cols=1),
    dict(id="odd_columns", lens=[96, 65, 1, 33], cols=3),
    dict(id="bf16", lens=[1, 32, 33, 128], dtype="bf16"),
    dict(id="int8", lens=[1, 32, 33, 128], dtype="int8"),
    dict(id="int8_inactive", lens=[1, 90, 1, 128], live=[0, 1, 0, 1],
         dtype="int8"),
    dict(id="window", lens=[1, 32, 33, 128], window=40),
    dict(id="softcap", lens=[100, 32, 33, 128], softcap=30.0),
    dict(id="ring_before_wrap", lens=[1, 32, 97, 160], ring=(6, 70)),
    dict(id="ring_after_wrap", lens=[200, 431, 193, 1000], ring=(6, 70)),
    dict(id="ring_inactive", lens=[1, 431, 1, 1000], live=[0, 1, 0, 1],
         ring=(6, 70)),
], ids=lambda c: c["id"])
def test_listed_decode_kernel_is_the_rectangular_one(case):
    """The decode kernel over its list of live (slot, page pair) entries
    gives, for every listed slot, bit for bit what it gives over the whole
    slots x table rectangle — the grid it walked before PR 44: the list
    changes which grid steps run, never a sum's order within a slot — and
    what the gathered jnp view gives; a slot with no entry is visited by no
    step and keeps its query."""
    from crowdllama_tpu.ops.attention import (decode_attention,
                                              decode_attention_q)
    from crowdllama_tpu.ops.pallas import paged as pp
    from crowdllama_tpu.ops.quant import quantize_kv

    b, h, hkv, dh, page = 4, 4, 2, 16, 32
    cols = case.get("cols", 4)
    dtype = {"bf16": jnp.bfloat16}.get(case.get("dtype"), jnp.float32)
    window, softcap = case.get("window", 0), case.get("softcap", 0.0)
    ks = jax.random.split(jax.random.PRNGKey(44), 3)
    lens = jnp.asarray(case["lens"], jnp.int32)
    live = (None if "live" not in case
            else jnp.asarray(case["live"], bool))
    ring = None
    if "ring" in case:
        ring = pp.Ring(*case["ring"])
        pool_pages = b * ring.pages + 1
        table, klens = ring.decode_view(lens, page)
        window = ring.window
    else:
        pool_pages = b * cols + 1
        table = 1 + jnp.arange(b * cols, dtype=jnp.int32).reshape(b, cols)
        klens = lens
    q = jax.random.normal(ks[0], (b, h, dh), dtype)
    pool_k = jax.random.normal(ks[1], (1, pool_pages, hkv, page, dh), dtype)
    pool_v = jax.random.normal(ks[2], (1, pool_pages, hkv, page, dh), dtype)
    sk = sv = None
    if case.get("dtype") == "int8":
        (pool_k, sk), (pool_v, sv) = quantize_kv(pool_k), quantize_kv(pool_v)
    np_ = table.shape[1]

    def run(work):
        return np.asarray(pp.flash_paged_decode_attention(
            q, pool_k, pool_v, 0, table, klens, dh ** -0.5, softcap=softcap,
            sliding_window=window, k_scale=sk, v_scale=sv, work=work),
            np.float32)

    work = pp.decode_work(pool_k, table, klens, live)
    listed = np.asarray(klens) > 0
    if live is not None:
        listed &= np.asarray(live)
    pairs = pp._pairs(pool_k, np_)
    want_total = int(sum(-(-min(int(n), np_ * page) // (page * pairs))
                         for n, on in zip(np.asarray(klens), listed) if on))
    assert int(work.total[0]) == max(want_total, 1)
    got = run(work)
    assert np.isfinite(got).all()
    # no entry, no visit: the row is its query's
    np.testing.assert_array_equal(got[~listed],
                                  np.asarray(q, np.float32)[~listed])
    if live is None:
        # the wrapper builds this very list when it is handed none
        np.testing.assert_array_equal(run(None), got)
    np.testing.assert_array_equal(
        got[listed], run(_rectangle(pool_k, table))[listed])

    def view(pool):     # what the CPU path gathers
        return pool[0, table].transpose(0, 2, 1, 3, 4).reshape(
            b, hkv, np_ * page, -1)

    if sk is not None:
        ref = decode_attention_q(
            q, view(pool_k), view(sk[..., None])[..., 0], view(pool_v),
            view(sv[..., None])[..., 0], klens, dh ** -0.5, softcap=softcap,
            sliding_window=window)
    else:
        ref = decode_attention(q, view(pool_k), view(pool_v), klens,
                               dh ** -0.5, softcap=softcap,
                               sliding_window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got[listed],
                               np.asarray(ref, np.float32)[listed],
                               rtol=tol, atol=tol)
