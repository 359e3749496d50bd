"""Paged-attention decode kernel: reads KV pages directly via the page
table (scalar-prefetched), no virtual-contiguous gather.

The jnp paged path (engine/paged.py round 2) materialized a
``pool[page_table]`` view per layer — [B, max_pages, Hkv, page, Dh] of HBM
traffic and scratch for what should be a streaming read (VERDICT r2
missing #3; PAPERS.md names ragged paged attention as the TPU north star).
Here the page table is a scalar-prefetch operand, so each grid step DMAs
up to two [Hkv, page, Dh] K tiles and two V tiles straight from a slot's
pages in the pool — all kv heads at once, and two pages per step when VMEM
allows (serving-shape per-page compute is tiny, so grid bubbles, not bytes,
set the kernel's speed); online softmax carries (m, l, acc) in VMEM scratch
from a slot's first step to its last.  The GQA decode kernel's grid is ONE
sequential dimension over a list of the LIVE (slot, page pair) entries
(:class:`DecodeWork`, built once a step on the device from the lengths and
the page table, each entry with its tiles' pool pages already looked up; its
length is the grid's run-time bound), so a page pair past a slot's length
costs no grid step at all — on a rectangular (slot, table column pair) grid
a dead step skipped its compute and still cost ~0.3 us, 44% of a call at
eight short contexts on a 16-column table (PERF.md section 6, PR 44) — and
a slot with no tenant none: no step visits its output row, which aliases
its query row and keeps it.  The latent (MLA) decode kernel and the ragged
chunk kernel keep rectangular grids: dead pages are compute-skipped there.
HBM traffic is one read of the live pages and one [Hkv, G, Dh] output write
per live slot.

Every kernel takes the WHOLE stacked pool ``[L, P, Hkv, page, Dh]`` and a
layer index (one more scalar-prefetch operand; the index maps return
``(layer, page, 0, 0, 0)``), never a layer's slice: the engine carries
the stack through its layer loop and updates it in place, and a kernel
that wanted ``pool[l]`` would make XLA cut 34 MB out of it per layer
per step (ROADMAP S7).  The page DMA is the same either way.

int8 pools: K/V tiles stay int8 through the DMA (the bandwidth-bound
bytes) and dequantize on the fly — K scales on the [Hkv, G, page] score
plane,
V scales folded into the probabilities — mirroring the contiguous
``decode_attention_q`` math (ops/attention.py), so paged + int8 KV compose
(VERDICT r2 weak #2: the features must stop being pairwise exclusive).

The unified ragged batch (docs/RAGGED_BATCH.md) gets the v2 layout
(:func:`flash_ragged_paged_attention`): ONE kernel whose grid rows are
uniform head-packed [Hkv, QB, G, Dh] query blocks — B decode rows and
ceil(C/QB) prefill-chunk blocks differ only in their scalar-prefetched
(q_start, kv_len, q_valid) metadata and page-table row, the sequential
kv walk stops at each block's causal/validity bound (density-
proportional cost), and the page-gather DMA is the double-buffered
BlockSpec pipeline itself.  The decode kernel remains as the plain
decode path and the TP building block.

The reference has no kernels at all (compute is delegated to Ollama,
/root/reference/pkg/crowdllama/api.go:108-160).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from crowdllama_tpu.ops.attention import NEG_INF, _softcap
from crowdllama_tpu.ops.pallas.flash import _interpret
from crowdllama_tpu.utils.env import env_flag

# m/l carries are stored 128-lane wide (hardware-friendly layout); only
# column 0 is meaningful.
_LANES = 128
# K+V tile bytes per fetched page must fit the budget x (pairs, double
# buffering) alongside q/output/scratch.
_VMEM_TILE_BUDGET = 8 * 1024 * 1024


class Ring(NamedTuple):
    """How a WINDOW layer's pool is laid out (engine/hybrid.py): slot ``s``
    owns pages ``s * pages .. (s + 1) * pages - 1`` of it for as long as it
    lives, and its logical page ``p`` (tokens ``p * page ..``) lies in
    ``s * pages + p % pages`` until page ``p + pages`` is written over it —
    by which time no query of the slot looks ``window`` tokens back that far
    (``pages * page >= window + the widest write + page``).  A reader gets a
    page table of its own, :meth:`view`: only the pages its window reaches,
    oldest first, and positions counted from the first of them."""

    pages: int
    window: int

    def page_of(self, slot, logical):
        return slot * self.pages + logical % self.pages

    def cols(self, span: int, page: int) -> int:
        """Pages a view of ``span`` consecutive queries reaches."""
        return -(-(self.window + max(span, 1) - 2) // page) + 1

    def first_page(self, q_start, page: int):
        """The logical page the oldest key a query at ``q_start`` sees lies
        in (an array of jax's or, on the host, of numpy's)."""
        return (q_start - self.window + 1).clip(0) // page

    def view(self, slots, q_start, span: int, page: int):
        """For queries at positions ``q_start .. q_start + span - 1`` of
        ``slots`` (both ``[N]``): (first ``[N]`` — the logical page the
        oldest key any of them sees lies in, table ``[N, cols]`` — that page
        and the ``cols - 1`` after it, as pool pages)."""
        cols = self.cols(span, page)
        assert cols <= self.pages, (self, span, page)
        first = self.first_page(q_start, page)
        logical = first[:, None] + jnp.arange(cols, dtype=jnp.int32)[None, :]
        return first, self.page_of(slots[:, None], logical).astype(jnp.int32)

    def decode_lens(self, lens, page: int):
        """``lens`` counted from the first page of each slot's decode view."""
        return lens - self.first_page(lens - 1, page) * page

    def decode_view(self, lens, page: int):
        """For every slot's one decode query, the newest of its ``lens``
        tokens: (table ``[B, cols]``, lengths counted from its first page)."""
        slots = jnp.arange(lens.shape[0], dtype=jnp.int32)
        _, table = self.view(slots, lens - 1, 1, page)
        return table, self.decode_lens(lens, page)


def _pairs_bytes(hkv: int, page: int, dh: int, itemsize: int) -> int:
    return 2 * hkv * page * dh * itemsize  # one page's K + V tiles


def _pairs(pool, np_: int) -> int:
    """Pages one sequential grid step fetches of a table ``np_`` columns
    wide: two when the VMEM budget allows (tiles are double-buffered) — the
    grid is bubble-bound at serving shapes, so halving its length is nearly
    free bandwidth."""
    _, _, hkv, page, dh = pool.shape
    return 2 if (np_ >= 2 and 4 * _pairs_bytes(
        hkv, page, dh, pool.dtype.itemsize) <= _VMEM_TILE_BUDGET) else 1


def _page_stream(pool_k, pool_v, k_scale, v_scale, pairs: int, page_at):
    """BlockSpecs + operands that stream ONE layer's pages out of the
    stacked pool, ``pairs`` (:func:`_pairs`) of them a sequential grid step.

    ``page_at(j, *grid indices, *scalar refs)`` names the pool page a grid
    step's ``j``-th tile reads (a step past a row's last page names any real
    page: its compute is skipped by the kernels' length bound); the layer
    index is the last scalar-prefetch operand.  Returns ``(in_specs,
    operands)``."""
    _, _, hkv, page, dh = pool_k.shape

    # Index maps receive (grid indices..., *scalar-prefetch refs).
    def kv_map_at(j, tail):
        def kv_map(*args):
            return (args[-1][0], page_at(j, *args), *tail)
        return kv_map

    in_specs, operands = [], []
    for j in range(pairs):
        in_specs += [pl.BlockSpec((None, None, hkv, page, dh),
                                  kv_map_at(j, (0, 0, 0)))] * 2
        operands += [pool_k, pool_v]
    if k_scale is not None:
        # Scales arrive as the [Hkv, page] plane of one page — the block's
        # last two dims are the array's, which Mosaic accepts — and the
        # kernels lift it to [Hkv, 1, page].  Reshaping the [L, P, Hkv,
        # page] stack to a unit sublane dim out here instead would have XLA
        # re-tile the whole stack for every layer.
        for j in range(pairs):
            in_specs += [pl.BlockSpec((None, None, hkv, page),
                                      kv_map_at(j, (0, 0)))] * 2
            operands += [k_scale, v_scale]
    return in_specs, operands


def _layer_operand(layer) -> jnp.ndarray:
    return jnp.asarray(layer, jnp.int32).reshape(1)


def paged_pallas_refusal(page_size: int, head_dim: int,
                         n_shards: int = 1,
                         num_kv_heads: int = 0,
                         itemsize: int = 2,
                         quant: bool = False) -> str:
    """Why the fused paged kernel does NOT apply ("" when it does).  It
    applies on TPU (or forced interpret mode) with hardware-aligned page
    tiles.  tp-sharded pools are supported via the shard_map wrapper
    (:func:`flash_paged_decode_attention_tp`) when every shard owns whole
    kv heads; ``n_shards`` is the TP axis extent.  ``itemsize`` is the KV
    POOL's element size (1 for int8 pools — gating on the bf16 size
    refused the kernel for wide-Hkv int8 configs that actually fit,
    ADVICE r4); ``quant`` adds the int8 scale tiles to the VMEM budget,
    matching the kernel's real footprint."""
    if env_flag("CROWDLLAMA_NO_PALLAS"):
        return "CROWDLLAMA_NO_PALLAS is set"
    if not _interpret() and jax.default_backend() != "tpu":
        return f"backend is {jax.default_backend()}, not tpu"
    if n_shards > 1 and (num_kv_heads <= 0 or num_kv_heads % n_shards):
        # pallas_call cannot be auto-partitioned by GSPMD; tp meshes run
        # the kernel per-shard via shard_map, which needs the kv-head dim
        # (pool axis 1) to split evenly so each shard's grid is whole heads.
        return (f"{num_kv_heads} kv heads do not split evenly over "
                f"tp={n_shards}")
    # Per grid step the kernel holds [Hkv/shard, page, Dh] K and V tiles
    # (double-buffered) in VMEM; gate wide-Hkv (MHA-style) configs that
    # would blow the budget.  num_kv_heads=0 (a generic availability
    # probe) checks the single-head minimum — callers deciding the REAL
    # kernel path must pass the model's kv-head count.
    hkv_local = max(max(num_kv_heads, 1) // max(n_shards, 1), 1)
    step_bytes = 2 * _pairs_bytes(hkv_local, page_size, head_dim, itemsize)
    if quant:
        # Two [Hkv, 1, page] bf16 scale tiles (K + V) per page, double-
        # buffered like the KV tiles they ride with.
        step_bytes += 2 * 2 * hkv_local * page_size * 2
    if step_bytes > _VMEM_TILE_BUDGET:
        return (f"per-step K/V tiles ({step_bytes} B for {hkv_local} kv "
                f"heads) exceed the VMEM budget")
    # Block last-two dims are (page, head_dim); Mosaic pads sub-tile
    # extents, so sublane alignment suffices (TinyLlama Dh=64, Llama 128).
    if page_size % 8 or page_size < 32 or head_dim % 8:
        return (f"page {page_size} / head_dim {head_dim} not tile-aligned "
                f"(page % 8 == 0, page >= 32, head_dim % 8 == 0)")
    return ""


def paged_pallas_supported(page_size: int, head_dim: int,
                           n_shards: int = 1,
                           num_kv_heads: int = 0,
                           itemsize: int = 2,
                           quant: bool = False) -> bool:
    return not paged_pallas_refusal(page_size, head_dim, n_shards,
                                    num_kv_heads, itemsize, quant)


class DecodeWork(NamedTuple):
    """The grid the GQA decode kernel walks: one entry a LIVE (slot, page
    pair), sorted by slot and, within a slot, by pair — so a slot's first
    entry is its pair 0 and its last the pair its length ends in — and, for
    each entry, the pool pages of its tiles, looked up in the page table
    HERE, once a step: a grid step's index maps then read a page where they
    chased slot -> table column -> page.  ``slot``, ``pair`` and each of
    ``pages`` have the static size ``slots x steps`` of the whole rectangle
    and hold slot 0 and pair ``steps`` past ``total``, a pair of no table
    that computes nothing; ``total`` (``[1]``) is the kernel's run-time grid
    bound, and never under 1: a list of no entries is walked for one such
    pad, which hands slot 0 its query back.  Lengths and table are the same
    for every layer of a step, so the engine builds one a step (and a second
    for its window layers' ring view) and hands it to every layer's call."""

    slot: jnp.ndarray
    pair: jnp.ndarray
    pages: tuple[jnp.ndarray, ...]
    total: jnp.ndarray


@jax.named_scope("decode_work")
def decode_work(pool, page_table, seq_lens, live=None) -> DecodeWork:
    """:class:`DecodeWork` for ``seq_lens [B]`` (the kernel's own, counted
    from the first column of ``page_table [B, cols]`` over ``pool``): slot
    ``b`` gets ``ceil(seq_lens[b] / (page x pairs))`` entries — none where
    its length is 0 or ``live[b]`` is false.  Such a slot's output row is
    never written: it keeps its query (:func:`flash_paged_decode_attention`)."""
    page, cols = pool.shape[3], page_table.shape[1]
    pairs = _pairs(pool, cols)
    steps = -(-cols // pairs)
    lens = jnp.clip(seq_lens.astype(jnp.int32), 0, cols * page)
    n = -(-lens // (page * pairs))
    if live is not None:
        n = jnp.where(live, n, 0)
    ends = jnp.cumsum(n)
    i = jnp.arange(lens.shape[0] * steps, dtype=jnp.int32)
    inside = i < ends[-1]
    # an entry's slot: how many slots' entries end at or before it
    slot = jnp.where(inside, jnp.sum(i[:, None] >= ends[None, :], axis=1), 0)
    pair = jnp.where(inside, i - (ends - n)[slot], steps)
    table = page_table.astype(jnp.int32)
    # the tail pair's second column clamps to the last (its compute skipped)
    pages = tuple(table[slot, jnp.minimum(pair * pairs + j, cols - 1)]
                  for j in range(pairs))
    return DecodeWork(slot.astype(jnp.int32), pair.astype(jnp.int32), pages,
                      jnp.maximum(ends[-1:], 1).astype(jnp.int32))


def decode_grid_steps(pool, seq_lens, cols: int, slots: int) -> tuple[int, int]:
    """On the host, for ``seq_lens [calls, live slots]`` (numpy) as
    :func:`decode_work` would be handed them call by call: (grid steps the
    kernel walks over those calls, grid steps of the ``slots x steps``
    rectangle it walked before it had a list)."""
    page = pool.shape[3]
    pairs = _pairs(pool, cols)
    n = -(-np.clip(seq_lens, 0, cols * page) // (page * pairs))
    return (int(np.maximum(n.sum(axis=-1), 1).sum()),
            seq_lens.shape[0] * slots * -(-cols // pairs))


def _decode_kernel(
    # scalar prefetch
    seqlen_ref,   # [B] int32 — valid positions incl. the pending token
    window_ref,   # [1] int32 — sliding window (<=0 disables)
    slot_ref,     # [B * steps] int32 — DecodeWork.slot
    pair_ref,     # [B * steps] int32 — DecodeWork.pair
    # then PAIRS x DecodeWork.pages and the [1] pool layer (both read by the
    # index maps only); operands: q [Hkv, G, Dh] — ALL kv heads of this
    # entry's slot — then PAIRS x (k, v), then PAIRS x (ks, vs) if quant;
    # output + scratch trail (pallas passes refs positionally).
    *refs,
    scale: float,
    softcap: float,
    page: int,
    pairs: int,
    np_: int,
    quant: bool,
):
    q_ref, *refs = refs[pairs + 1:]
    kv = refs[: 2 * pairs]                    # [Hkv, page, Dh] tiles
    scs = refs[2 * pairs: 4 * pairs] if quant else ()
    o_ref, acc_ref, m_ref, l_ref = refs[-4:]

    i = pl.program_id(0)
    p = pair_ref[i]
    seq_len = seqlen_ref[slot_ref[i]]
    window = window_ref[0]

    # Entries are sorted by slot, then pair: the carry starts at a slot's
    # pair 0 and its row is written at the pair its length ends in.
    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _tile(j):
        # One page's online-softmax update; unrolled ``pairs`` times per
        # grid step.  Fetching several pages per step halves (or better)
        # the SEQUENTIAL grid length — at serving shapes the kernel is
        # bubble-bound, not byte-bound, so fewer/fatter steps win
        # (measured on-chip: head-batching alone took 1,428 -> 1,644
        # tok/s/chip; page-pairing targets the remaining gap).
        k_ref, v_ref = kv[2 * j], kv[2 * j + 1]
        base = (p * pairs + j) * page

        @pl.when(base < seq_len)
        def _body():
            q = q_ref[...].astype(jnp.float32)       # [Hkv, G, Dh]
            k_tile = k_ref[...].astype(jnp.float32)  # [Hkv, page, Dh]
            v_tile = v_ref[...].astype(jnp.float32)
            kpos = base + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)

            # [Hkv, G, page] = [Hkv, G, Dh] · [Hkv, page, Dh]^T — one
            # batched MXU issue for every kv head of the slot.
            logits = jax.lax.dot_general(
                q, k_tile, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ) * scale
            if quant:
                # int8 K: per-position scales act on the score plane, so
                # no dequantized [page, Dh] tensor materializes.
                logits = logits * scs[2 * j][...].astype(jnp.float32)[:, None]
            logits = _softcap(logits, softcap)

            mask = kpos < seq_len
            mask &= (window <= 0) | (kpos > (seq_len - 1) - window)
            logits = jnp.where(mask, logits, NEG_INF)

            m_prev = m_ref[:, :, :1]                 # [Hkv, G, 1]
            l_prev = l_ref[:, :, :1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(logits, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pr = jnp.exp(logits - m_new) * mask.astype(jnp.float32)
            l_new = l_prev * alpha + jnp.sum(pr, axis=-1, keepdims=True)
            if quant:
                pr = pr * scs[2 * j + 1][...].astype(jnp.float32)[:, None]
            pv = jax.lax.dot_general(
                pr, v_tile, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            acc_ref[...] = acc_ref[...] * alpha + pv
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    for j in range(pairs):
        _tile(j)

    span = pairs * page
    end = jnp.minimum(seq_len, np_ * page)

    @pl.when((p * span < end) & ((p + 1) * span >= end))
    def _finalize():
        l = l_ref[:, :, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)

    # Every step writes its output block back, so the pad entry an empty
    # list is walked for (DecodeWork) must fill it: with the row's own query.
    @pl.when(p * pairs >= np_)
    def _keep():
        o_ref[...] = q_ref[...]


def flash_paged_decode_attention(
    q: jnp.ndarray,           # [B, H, Dh]
    pool_k: jnp.ndarray,      # [L, P, Hkv, page, Dh] (bf16 or int8)
    pool_v: jnp.ndarray,
    layer: int | jnp.ndarray,  # scalar int32 — the layer whose pages to read
    page_table: jnp.ndarray,  # [B, NP] int32
    seq_lens: jnp.ndarray,    # [B] int32 (incl. the pending token)
    scale: float,
    softcap: float = 0.0,
    sliding_window: int | jnp.ndarray = 0,
    k_scale: jnp.ndarray | None = None,  # [L, P, Hkv, page] int8 pools only
    v_scale: jnp.ndarray | None = None,
    name: str = "paged_decode_attention",  # the call's name in a trace
    work: DecodeWork | None = None,
) -> jnp.ndarray:
    """One cached decode step over layer ``layer`` of the stacked paged
    pool; output [B, H, Dh].

    The grid is ONE sequential dimension over ``work``, the live (slot,
    page pair) entries of ``seq_lens`` (:func:`decode_work` of the same
    lengths and table width; built here when the caller has none), its
    length a run-time value: a page pair past a slot's length costs no grid
    step.  A slot with NO entry (length 0, or not ``live`` in the caller's
    list) is visited by no step; its output row aliases its query row and
    keeps it — finite, and no one's answer."""
    b, h, dh = q.shape
    _, _, hkv, page, _ = pool_k.shape
    g = h // hkv
    np_ = page_table.shape[1]
    quant = k_scale is not None

    qg = q.reshape(b, hkv, g, dh)
    seq_lens = seq_lens.astype(jnp.int32)
    window = jnp.asarray(sliding_window, jnp.int32).reshape(1)
    if work is None:
        work = decode_work(pool_k, page_table, seq_lens)
    pairs = _pairs(pool_k, np_)
    assert len(work.pages) == pairs and work.slot.shape == (
        b * -(-np_ // pairs),), (work.slot.shape, len(work.pages), b, np_)

    def q_map(i, lens, win, slot, *refs):
        return (slot[i], 0, 0, 0)

    kv_specs, kv_operands = _page_stream(
        pool_k, pool_v, k_scale, v_scale, pairs,
        lambda j, i, lens, win, slot, pair, *pages: pages[j][i])

    kernel = functools.partial(
        _decode_kernel,
        scale=scale, softcap=float(softcap or 0.0), page=page,
        pairs=pairs, np_=np_, quant=quant,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 + pairs,
        grid=(work.total[0],),
        in_specs=[pl.BlockSpec((None, hkv, g, dh), q_map), *kv_specs],
        out_specs=pl.BlockSpec((None, hkv, g, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, dh), jnp.float32),
            pltpu.VMEM((hkv, g, _LANES), jnp.float32),
            pltpu.VMEM((hkv, g, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
        # the queries follow the scalars: a row no entry names keeps its query
        input_output_aliases={5 + pairs: 0},
        interpret=_interpret(),
        name=name,
    )(seq_lens, window, work.slot, work.pair, *work.pages,
      _layer_operand(layer), qg, *kv_operands)
    return out.reshape(b, h, dh)


def _mla_decode_kernel(table_ref, seqlen_ref, layer_ref, q_ref, *refs,
                       scale: float, page: int, pairs: int, latent: int):
    """:func:`_decode_kernel` for a latent page: ONE shared kv head whose
    row ``[c ; k_rope]`` is the key, and whose first ``latent`` values are
    the value — the row is fetched once and read twice."""
    del table_ref, layer_ref  # the index maps' alone
    rows = refs[:pairs]                          # [page, Dh] tiles
    o_ref, acc_ref, m_ref, l_ref = refs[-4:]
    b, p = pl.program_id(0), pl.program_id(1)
    seq_len = seqlen_ref[b]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    for j in range(pairs):
        base = (p * pairs + j) * page

        @pl.when(base < seq_len)
        def _body(row_ref=rows[j], base=base):
            row = row_ref[...]                   # [page, Dh]
            # bf16 x bf16 products are exact in the float32 accumulator
            logits = jax.lax.dot_general(
                q_ref[...], row, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [H, page]
            kpos = base + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
            mask = kpos < seq_len
            logits = jnp.where(mask, logits, NEG_INF)
            m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(logits, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pr = jnp.exp(logits - m_new) * mask.astype(jnp.float32)
            l_new = l_prev * alpha + jnp.sum(pr, axis=-1, keepdims=True)
            value = row[:, :latent]
            # the probabilities in two pieces of the value's type (16 bits
            # of a bf16 pool's 24; all of a float32 pool's): two passes of
            # the MXU where a float32 matmul is six
            hi = pr.astype(value.dtype)
            pv = jnp.dot(hi, value, preferred_element_type=jnp.float32)
            if value.dtype != jnp.float32:
                lo = (pr - hi.astype(jnp.float32)).astype(value.dtype)
                pv += jnp.dot(lo, value, preferred_element_type=jnp.float32)
            acc_ref[...] = acc_ref[...] * alpha + pv
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(p == pl.num_programs(1) - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(o_ref.dtype)


def paged_decode_attention_mla(
    q: jnp.ndarray,           # [B, H, Dh] absorbed queries
    pool: jnp.ndarray,        # [L, P, 1, page, Dh] latent rows [c ; k_rope]
    layer: int | jnp.ndarray,
    page_table: jnp.ndarray,  # [B, NP] int32
    seq_lens: jnp.ndarray,    # [B] int32 (incl. the pending token)
    scale: float,
    latent: int,
) -> jnp.ndarray:
    """One cached decode step of absorbed latent attention (MLA) over
    layer ``layer`` of the stacked latent pool: every head's query against
    the one shared row a token, the value that row's first ``latent``
    entries; output ``[B, H, latent]`` (still to be expanded by the value
    half of the kv up-projection).  The pool has no V twin: a page is
    fetched once."""
    b, h, dh = q.shape
    _, _, hkv, page, _ = pool.shape
    assert hkv == 1 and latent % _LANES == 0, (pool.shape, latent)
    np_ = page_table.shape[1]
    pairs = 2 if np_ >= 2 else 1

    def row_map_at(j):
        def row_map(bi, pi, table, lens, layer):
            return (layer[0], table[bi, jnp.minimum(pi * pairs + j, np_ - 1)],
                    0, 0, 0)
        return row_map

    def q_map(bi, pi, *refs):
        return (bi, 0, 0)

    kernel = functools.partial(_mla_decode_kernel, scale=scale, page=page,
                               pairs=pairs, latent=latent)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, -(-np_ // pairs)),
            in_specs=[pl.BlockSpec((None, h, dh), q_map),
                      *(pl.BlockSpec((None, None, None, page, dh),
                                     row_map_at(j)) for j in range(pairs))],
            out_specs=pl.BlockSpec((None, h, latent), q_map),
            scratch_shapes=[
                pltpu.VMEM((h, latent), jnp.float32),
                pltpu.VMEM((h, _LANES), jnp.float32),
                pltpu.VMEM((h, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, latent), q.dtype),
        interpret=_interpret(),
        name="paged_decode_attention_mla",
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      _layer_operand(layer), q, *([pool] * pairs))


# Query rows per ragged-kernel grid block.  32 keeps the fp32 online-
# softmax scratch ([Hkv, QB*G, Dh] acc + two [Hkv, QB*G, _LANES] carries)
# comfortably inside VMEM for Llama-class head counts.
_CHUNK_QB = 32


def chunk_query_block(hkv: int, g: int, head_dim: int) -> int:
    """Queries a block of the v2 ragged kernel holds: :data:`_CHUNK_QB`,
    halved (to 8 at the least) while the block's float32 scratch — ``[Hkv,
    QB*G, Dh]`` of accumulator and two ``[Hkv, QB*G, _LANES]`` carries — is
    over half the tile budget.  The block's query and output tiles, each
    double-buffered, and the scores lie beside the scratch under the
    compiler's 16 MiB: 64 query heads on one 640-wide latent row took 18.7
    MB at 32 queries and were refused (deviceless compile, PR 54); every
    shape served before keeps its 32."""
    qb = _CHUNK_QB
    while qb > 8 and (hkv * qb * g * (head_dim + 2 * _LANES) * 4
                      > _VMEM_TILE_BUDGET // 2):
        qb //= 2
    return qb


def ragged_pallas_refusal(page_size: int, head_dim: int,
                          n_shards: int = 1,
                          num_kv_heads: int = 0,
                          itemsize: int = 2,
                          quant: bool = False,
                          num_heads: int = 0) -> str:
    """Why the fused ragged (decode + prefill-chunk) kernel does NOT apply
    ("" when it does).

    The unified step runs the whole mixed batch through the v2 kernel
    (:func:`flash_ragged_paged_attention`), whose blocks are uniform
    [Hkv, QB, G, Dh] query tiles, so the constraints are the decode gate
    plus the chunk-sized VMEM footprint (QB*G query rows instead of G
    per kv head)."""
    why = paged_pallas_refusal(page_size, head_dim, n_shards,
                               num_kv_heads, itemsize, quant)
    if why:
        return why
    # A query block holds [Hkv, QB*G, Dh] fp32 acc + 2x [Hkv, QB*G, _LANES]
    # carries; with num_kv_heads=0 (availability probe) assume one head,
    # with num_heads=0 a generous 16 query heads a kv head.
    hkv_local = max(max(num_kv_heads, 1) // max(n_shards, 1), 1)
    g = num_heads // max(num_kv_heads, 1) if num_heads else 16
    rows = chunk_query_block(hkv_local, g, head_dim) * g
    scratch = hkv_local * rows * (head_dim + 2 * _LANES) * 4
    if scratch > _VMEM_TILE_BUDGET // 2:
        return (f"chunk scratch ({scratch} B for {hkv_local} kv heads of "
                f"{g} query heads) exceeds the VMEM budget")
    return ""


def ragged_pallas_supported(page_size: int, head_dim: int,
                            n_shards: int = 1,
                            num_kv_heads: int = 0,
                            itemsize: int = 2,
                            quant: bool = False,
                            num_heads: int = 0) -> bool:
    return not ragged_pallas_refusal(page_size, head_dim, n_shards,
                                     num_kv_heads, itemsize, quant, num_heads)


def ragged_paged_attention_ref(
    q: jnp.ndarray,            # [B + C, H, Dh] — decode rows then chunk rows
    chunk_k: jnp.ndarray,      # [1, Hkv, C, Dh] — the chunk's fresh keys
    chunk_v: jnp.ndarray,      # [1, Hkv, C, Dh]
    pool_k: jnp.ndarray,       # [L, P, Hkv, page, Dh]
    pool_v: jnp.ndarray,
    layer: int | jnp.ndarray,  # scalar int32 — the layer whose pages to read
    page_table: jnp.ndarray,   # [B, NP] int32
    q_lens: jnp.ndarray,       # [B + 1] int32 — per-sequence query lengths
    kv_lens: jnp.ndarray,      # [B + 1] int32 — incl. this step's tokens
    chunk_slot: jnp.ndarray,   # scalar int32 — page-table row of seq B
    scale: float,
    softcap: float = 0.0,
    sliding_window: int | jnp.ndarray = 0,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    ring: Ring | None = None,
) -> jnp.ndarray:
    """Pure-JAX unified ragged batch attention (reference semantics).

    One call covers B+1 ragged sequences over layer ``layer`` of the same
    stacked paged pool (the gathers index ``pool[layer, pages]``): B
    decode sequences (q_len 0 or 1, rows 0..B-1) plus one prefill-chunk
    sequence (q_len = q_lens[B] <= C, rows B..).  Query i of sequence s
    attends kv positions < kv_lens[s] - q_lens[s] + i + 1.

    Byte-identity contract (tier-1, CPU): decode rows run exactly the
    gather + :func:`decode_attention` math of the plain paged decode
    step, and chunk rows run exactly :func:`prefill_attention_ctx` with
    the paged prefix as the cached context — the same code paths the
    monolithic admission path uses — so unified streams match monolithic
    streams bitwise on bf16 pools.

    With ``ring`` the pool is a window layer's (:class:`Ring`):
    ``page_table`` gives only the number of slots, every sequence reads its
    ring through a view that starts where its window does, and lengths and
    positions are counted from there (the masks are differences of
    positions, so nothing else changes)."""
    from crowdllama_tpu.ops.attention import (
        decode_attention,
        decode_attention_q,
        prefill_attention_ctx,
    )

    b = page_table.shape[0]
    c = chunk_k.shape[2]
    _, _, hkv, page, dh = pool_k.shape
    quant = k_scale is not None
    ctx = kv_lens[b] - q_lens[b]
    chunk_row = page_table[chunk_slot]
    if ring is not None:
        assert not quant, "a window layer's pool is not int8"
        sliding_window = ring.window
        page_table, lens_dec = ring.decode_view(kv_lens[:b], page)
        kv_lens = kv_lens.at[:b].set(lens_dec)
        first_c, chunk_row = ring.view(chunk_slot[None], ctx[None], 1, page)
        ctx, chunk_row = ctx - first_c[0] * page, chunk_row[0]
    np_ = page_table.shape[1]
    w = np_ * page

    # --- decode rows: identical to the plain paged decode fallback ---
    view_k = pool_k[layer, page_table].transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, w, dh)
    view_v = pool_v[layer, page_table].transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, w, dh)
    if quant:
        vs_k = k_scale[layer, page_table].transpose(0, 2, 1, 3).reshape(
            b, hkv, w)
        vs_v = v_scale[layer, page_table].transpose(0, 2, 1, 3).reshape(
            b, hkv, w)
        out_dec = decode_attention_q(
            q[:b], view_k, vs_k, view_v, vs_v, kv_lens[:b], scale,
            softcap=softcap, sliding_window=sliding_window)
    else:
        out_dec = decode_attention(
            q[:b], view_k, view_v, kv_lens[:b], scale, softcap=softcap,
            sliding_window=sliding_window)

    # --- chunk rows: prefix pages as cached context + fresh self block ---
    cpk = pool_k[layer, chunk_row]
    cpv = pool_v[layer, chunk_row]
    ctx_k = cpk.transpose(1, 0, 2, 3).reshape(1, hkv, w, dh)
    ctx_v = cpv.transpose(1, 0, 2, 3).reshape(1, hkv, w, dh)
    if quant:
        csk = k_scale[layer, chunk_row].transpose(
            1, 0, 2).reshape(1, hkv, w, 1)
        csv = v_scale[layer, chunk_row].transpose(
            1, 0, 2).reshape(1, hkv, w, 1)
        ctx_k = ctx_k.astype(jnp.float32) * csk.astype(jnp.float32)
        ctx_v = ctx_v.astype(jnp.float32) * csv.astype(jnp.float32)
    kvpos = jnp.arange(w)[None, :]
    ctx_valid = kvpos < ctx
    positions = (ctx + jnp.arange(c))[None, :]
    kv_valid = (jnp.arange(c) < q_lens[b])[None, :]
    out_chunk = prefill_attention_ctx(
        q[b:][None], chunk_k, chunk_v, positions, ctx_k, ctx_v, ctx_valid,
        scale, softcap=softcap, sliding_window=sliding_window,
        kv_valid=kv_valid)[0]

    return jnp.concatenate([out_dec, out_chunk], axis=0)


def _ragged_v2_kernel(
    # scalar prefetch
    table_ref,    # [NB, NP] int32 — page-table row per query block
    info_ref,     # [NB, 3] int32 — (q_start, kv_len, q_valid) per block
    window_ref,   # [1] int32 — sliding window (<=0 disables)
    layer_ref,    # [1] int32 — pool layer (read by the index maps only)
    # operands: q, then PAIRS x (k, v), then PAIRS x (ks, vs) if quant
    q_ref,        # [Hkv, QB, G, Dh] — one head-packed query block
    *refs,
    scale: float,
    softcap: float,
    page: int,
    pairs: int,
    quant: bool,
):
    """Ragged-paged attention v2: ONE kernel for the whole mixed batch.

    Every grid row is a uniform head-packed [Hkv, QB, G, Dh] query
    block; what makes it a decode row or a prefill-chunk block is pure
    scalar metadata.  Block n attends kv positions ``< kv_len[n]`` with
    the causal bound ``kpos <= q_start[n] + row_query`` per row, and only
    its first ``q_valid[n]`` queries are real:

    - a DECODE block has ``q_start = kv_len - 1, q_valid = 1`` (0 when
      the slot is inactive — the block skips entirely), so row 0 sees
      exactly the decode kernel's ``kpos < seq_len`` window;
    - a CHUNK block j has ``q_start = ctx + j*QB`` and ``q_valid =
      clip(chunk_len - j*QB, 0, QB)`` — a causal prefill over the
      slot's pages.

    Cost is density-proportional by construction: the sequential kv grid
    walks ``table_ref[n]`` only up to ``min(kv_len, q_start + q_valid)``
    (later pages compute-skip), and the page-gather DMA is the BlockSpec
    pipeline itself — the index map reads the scalar-prefetched table,
    and Pallas double-buffers the [Hkv, page, Dh] tiles so page p+1
    streams in while p computes.
    """
    kv = refs[: 2 * pairs]
    scs = refs[2 * pairs: 4 * pairs] if quant else ()
    o_ref, acc_ref, m_ref, l_ref = refs[-4:]

    n = pl.program_id(0)
    p = pl.program_id(1)
    num_steps = pl.num_programs(1)
    q_start = info_ref[n, 0]
    kv_len = info_ref[n, 1]
    q_valid = info_ref[n, 2]
    window = window_ref[0]
    hkv, qbw, g, dh = q_ref.shape
    rows = qbw * g

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Keys this block can see: validity bound AND the causal bound of its
    # last REAL row — later pages are compute-skipped entirely, which is
    # what keeps an idle decode row (q_valid 0) and a short sequence from
    # paying for the pool's widest resident.
    block_bound = jnp.minimum(kv_len, q_start + q_valid)
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (1, rows, 1), 1)
    qpos = q_start + row_iota // g
    row_ok = row_iota // g < q_valid

    # bf16 queries meet bf16 pages on the MXU as they are: a product of two
    # bf16 values is exact in the float32 accumulator, so the scores are a
    # float32 matmul's up to the order of the sums, in one pass of the MXU
    # where a float32 matmul takes six (a block of QB x G query rows is
    # bound by its matmuls: 3 us a page at 192 rows, PERF.md section 6,
    # PR 42); the probabilities then go to the value matmul in two bf16
    # pieces, 16 of their 24 bits, as in the latent decode kernel above.
    native = (not quant and q_ref.dtype == jnp.bfloat16
              and kv[0].dtype == jnp.bfloat16)

    def _tile(j):
        k_ref, v_ref = kv[2 * j], kv[2 * j + 1]
        base = (p * pairs + j) * page

        @pl.when((base < block_bound) & (q_valid > 0))
        def _body():
            q = q_ref[...].astype(jnp.float32).reshape(hkv, rows, dh)
            if native:
                q = q.astype(jnp.bfloat16)
                k_tile, v_tile = k_ref[...], v_ref[...]
            else:
                k_tile = k_ref[...].astype(jnp.float32)  # [Hkv, page, Dh]
                v_tile = v_ref[...].astype(jnp.float32)
            kpos = base + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)

            logits = jax.lax.dot_general(
                q, k_tile, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ) * scale
            if quant:
                logits = logits * scs[2 * j][...].astype(jnp.float32)[:, None]
            logits = _softcap(logits, softcap)

            mask = row_ok & (kpos < kv_len) & (kpos <= qpos)
            mask &= (window <= 0) | (kpos > qpos - window)
            logits = jnp.where(mask, logits, NEG_INF)

            m_prev = m_ref[:, :, :1]
            l_prev = l_ref[:, :, :1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(logits, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            pr = jnp.exp(logits - m_new) * mask.astype(jnp.float32)
            l_new = l_prev * alpha + jnp.sum(pr, axis=-1, keepdims=True)
            if quant:
                pr = pr * scs[2 * j + 1][...].astype(jnp.float32)[:, None]

            def by_values(probs):
                return jax.lax.dot_general(
                    probs, v_tile, (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)

            if native:
                hi = pr.astype(jnp.bfloat16)
                lo = (pr - hi.astype(jnp.float32)).astype(jnp.bfloat16)
                pv = by_values(hi) + by_values(lo)
            else:
                pv = by_values(pr)
            acc_ref[...] = acc_ref[...] * alpha + pv
            m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
            l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    for j in range(pairs):
        _tile(j)

    @pl.when(p == num_steps - 1)
    def _finalize():
        l = l_ref[:, :, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        out = (acc_ref[...] / l).astype(o_ref.dtype)
        o_ref[...] = out.reshape(hkv, qbw, g, dh)


def flash_ragged_paged_attention(
    q: jnp.ndarray,            # [B + C, H, Dh] — decode rows then chunk rows
    pool_k: jnp.ndarray,       # [L, P, Hkv, page, Dh]
    pool_v: jnp.ndarray,
    layer: int | jnp.ndarray,  # scalar int32 — the layer whose pages to read
    page_table: jnp.ndarray,   # [B, NP] int32
    q_lens: jnp.ndarray,       # [B + 1] int32
    kv_lens: jnp.ndarray,      # [B + 1] int32
    chunk_slot: jnp.ndarray,   # scalar int32
    scale: float,
    softcap: float = 0.0,
    sliding_window: int | jnp.ndarray = 0,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    ring: Ring | None = None,
    work: DecodeWork | None = None,
) -> jnp.ndarray:
    """Ragged-paged attention over the mixed batch — B decode sequences +
    one prefill chunk: the decode rows through
    :func:`flash_paged_decode_attention` (over ``work``, the step's list of
    their live page pairs; built here from the rows with a query when the
    caller has none), the chunk through the v2 kernel.

    The chunk is one grid of ``ceil(C/QB)`` uniform head-packed query blocks
    whose behavior is driven entirely by a scalar-prefetched ``(q_start,
    kv_len, q_valid)`` row and a per-block page-table row
    (``chunk_slot``'s).  Pages come from layer
    ``layer`` of the stacked pool; the chunk's fresh KV must already be
    scattered into it.  Output [B + C, H, Dh].

    With ``ring`` the pool is a window layer's (:class:`Ring`): every
    block's table row is its own view of its slot's ring, from the page its
    first query's window starts in, and its metadata counts from there."""
    bc, h, dh = q.shape
    _, _, hkv, page, _ = pool_k.shape
    g = h // hkv
    b = page_table.shape[0]
    c = bc - b
    np_ = page_table.shape[1]
    quant = k_scale is not None

    qb = chunk_query_block(hkv, g, dh)
    jblocks = -(-c // qb)
    # The decode rows go through the decode kernel, the very call of the
    # plain decode step; only the chunk's rows are packed into QB-row
    # blocks [Hkv, C, G, Dh], kv-head-major.  (As block row 0 of a block of
    # its own, rows 1.. dead weight, a decode row cost QB times its math:
    # 3.3 of the 5.0 ms a window layer's call took at 32 slots, six query
    # heads a kv head and a 4096-token window — PERF.md section 6, PR 42.)
    table = page_table.astype(jnp.int32)
    table_dec, lens_dec = table, kv_lens[:b]
    if ring is not None:
        sliding_window = ring.window
        table_dec, lens_dec = ring.decode_view(lens_dec, page)
    if work is None:
        work = decode_work(pool_k, table_dec, lens_dec, q_lens[:b] > 0)
    out_dec = flash_paged_decode_attention(
        q[:b], pool_k, pool_v, layer, table_dec, lens_dec, scale,
        softcap=softcap, sliding_window=sliding_window, k_scale=k_scale,
        v_scale=v_scale, name=("paged_decode_attention" if ring is None
                               else "paged_decode_attention_window"),
        work=work)
    qc = q[b:].reshape(c, hkv, g, dh).transpose(1, 0, 2, 3)
    if jblocks * qb != c:
        qc = jnp.pad(qc, ((0, 0), (0, jblocks * qb - c), (0, 0), (0, 0)))
    qx = qc.reshape(hkv, jblocks, qb, g, dh).transpose(1, 0, 2, 3, 4)

    ctx = (kv_lens[b] - q_lens[b]).astype(jnp.int32)
    j_idx = jnp.arange(jblocks, dtype=jnp.int32)
    blk_table = jnp.broadcast_to(table[chunk_slot][None], (jblocks, np_))
    q_start = ctx + j_idx * qb
    kv_len_blk = jnp.broadcast_to(kv_lens[b], (jblocks,))
    q_valid = jnp.clip(q_lens[b] - j_idx * qb, 0, qb)
    if ring is not None:
        slots = jnp.broadcast_to(chunk_slot.astype(jnp.int32), (jblocks,))
        first, blk_table = ring.view(slots, q_start, qb, page)
        np_ = blk_table.shape[1]
        q_start, kv_len_blk = q_start - first * page, kv_len_blk - first * page
    blk_info = jnp.stack(
        [q_start, kv_len_blk, q_valid], axis=1).astype(jnp.int32)
    window = jnp.asarray(sliding_window, jnp.int32).reshape(1)

    def q_map(ni, pi, *refs):
        return (ni, 0, 0, 0, 0)

    pairs = _pairs(pool_k, np_)
    steps = -(-np_ // pairs)
    # the tail pair's second column clamps to the last (its compute skipped)
    kv_specs, kv_operands = _page_stream(
        pool_k, pool_v, k_scale, v_scale, pairs,
        lambda j, ni, pi, tr, *refs: tr[
            ni, jnp.minimum(pi * pairs + j, np_ - 1)])

    kernel = functools.partial(
        _ragged_v2_kernel,
        scale=scale, softcap=float(softcap or 0.0), page=page,
        pairs=pairs, quant=quant,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(jblocks, steps),
        in_specs=[pl.BlockSpec((None, hkv, qb, g, dh), q_map), *kv_specs],
        out_specs=pl.BlockSpec((None, hkv, qb, g, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((hkv, qb * g, dh), jnp.float32),
            pltpu.VMEM((hkv, qb * g, _LANES), jnp.float32),
            pltpu.VMEM((hkv, qb * g, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((jblocks, hkv, qb, g, dh), q.dtype),
        interpret=_interpret(),
        name=("ragged_paged_attention" if ring is None
              else "ragged_paged_attention_window"),
    )(blk_table, blk_info, window, _layer_operand(layer), qx, *kv_operands)
    out_chunk = out.transpose(1, 0, 2, 3, 4).reshape(
        hkv, jblocks * qb, g, dh)[:, :c].transpose(1, 0, 2, 3).reshape(
        c, h, dh)
    return jnp.concatenate([out_dec, out_chunk], axis=0)


def ragged_paged_attention(
    q: jnp.ndarray,            # [B + C, H, Dh]
    chunk_k: jnp.ndarray,      # [1, Hkv, C, Dh]
    chunk_v: jnp.ndarray,
    pool_k: jnp.ndarray,       # [L, P, Hkv, page, Dh]
    pool_v: jnp.ndarray,
    layer: int | jnp.ndarray,  # scalar int32
    page_table: jnp.ndarray,   # [B, NP] int32
    q_lens: jnp.ndarray,       # [B + 1] int32
    kv_lens: jnp.ndarray,      # [B + 1] int32
    chunk_slot: jnp.ndarray,
    scale: float,
    softcap: float = 0.0,
    sliding_window: int | jnp.ndarray = 0,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    use_pallas: bool = False,
    ring: Ring | None = None,
    work: DecodeWork | None = None,
) -> jnp.ndarray:
    """Unified ragged batch attention over layer ``layer`` of the stacked
    paged pool.

    ``use_pallas`` (a static flag the runner resolves via
    :func:`ragged_pallas_supported`) routes the whole mixed batch
    through the single v2 kernel (:func:`flash_ragged_paged_attention`);
    otherwise the pure-JAX reference runs (tier-1 / CPU).  Both require
    the chunk's fresh KV to already be scattered into the pool; the ref
    additionally takes it as ``chunk_k``/``chunk_v`` operands so its
    self block matches the monolithic prefill bitwise.  The plain decode
    path and the TP wrapper use :func:`flash_paged_decode_attention`."""
    if not use_pallas:
        return ragged_paged_attention_ref(
            q, chunk_k, chunk_v, pool_k, pool_v, layer, page_table, q_lens,
            kv_lens, chunk_slot, scale, softcap=softcap,
            sliding_window=sliding_window, k_scale=k_scale, v_scale=v_scale,
            ring=ring)
    return flash_ragged_paged_attention(
        q, pool_k, pool_v, layer, page_table, q_lens, kv_lens, chunk_slot,
        scale, softcap=softcap, sliding_window=sliding_window,
        k_scale=k_scale, v_scale=v_scale, ring=ring, work=work)


def flash_paged_decode_attention_tp(
    q: jnp.ndarray,           # [B, H, Dh] — heads tp-sharded (kv-major)
    pool_k: jnp.ndarray,      # [L, P, Hkv, page, Dh] — kv heads tp-sharded
    pool_v: jnp.ndarray,
    layer: int | jnp.ndarray,  # scalar int32 (replicated)
    page_table: jnp.ndarray,  # [B, NP] int32 (replicated)
    seq_lens: jnp.ndarray,    # [B] int32 (replicated)
    scale: float,
    mesh,
    softcap: float = 0.0,
    sliding_window: int | jnp.ndarray = 0,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """The fused kernel on a tp-sharded pool, via ``shard_map``.

    Every (batch, page) grid cell is independent, and the engine
    shards BOTH q's heads and the pool's kv heads over the same tp axis in
    the same kv-major order (engine/paged.py init_state / runner.py q
    projection) — so each shard just runs the kernel over its own heads
    with the table/lengths replicated; no collectives, and the per-shard
    result concatenates over heads into exactly the unsharded answer
    (VERDICT r3 missing #2: multi-chip paged decode previously paid the
    virtual-contiguous gather).  Axes other than tp (ep on MoE meshes) are
    unmentioned, i.e. the kernel is replicated across them — matching how
    GSPMD treats attention on an ep×tp mesh."""
    from jax.sharding import PartitionSpec as P

    from crowdllama_tpu.parallel.mesh import AXIS_TP

    window = jnp.asarray(sliding_window, jnp.int32).reshape(1)
    q_spec = P(None, AXIS_TP, None)
    pool_spec = P(None, None, AXIS_TP, None, None)
    sc_spec = P(None, None, AXIS_TP, None)
    rep = P(None)

    args = (q, pool_k, pool_v, _layer_operand(layer), page_table, seq_lens,
            window)
    in_specs = (q_spec, pool_spec, pool_spec, rep, rep, rep, rep)
    if k_scale is not None:
        args += (k_scale, v_scale)
        in_specs += (sc_spec, sc_spec)

    def local(q, pk, pv, lyr, tbl, lens, win, *scales):
        return flash_paged_decode_attention(
            q, pk, pv, lyr, tbl, lens, scale, softcap=softcap,
            sliding_window=win,
            k_scale=scales[0] if scales else None,
            v_scale=scales[1] if scales else None)

    return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                         out_specs=q_spec, check_vma=False)(*args)
