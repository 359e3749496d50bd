"""The host's accounts of a cache of two kinds of page (engine/hybrid.py): the
full pool's allocator beside the window layers' rings, driven as the
scheduler drives them — admission, growth before a flight, a prompt arriving
in chunks, release, a cancelled admission — without running a program of the
model (the logits are tests/test_afmoe.py's)."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

from crowdllama_tpu.engine.hybrid import HybridPagedModelRunner
from crowdllama_tpu.engine.paged import PagesExhausted
from crowdllama_tpu.models import transformer as T
from crowdllama_tpu.models.config import get_config
from crowdllama_tpu.ops.pallas.paged import Ring

CFG = replace(get_config("tiny-test-afmoe"), max_context_length=512)
WINDOW, PAGE, CHUNK = CFG.sliding_window, 8, 16


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)


def runner(params, **kwargs):
    return HybridPagedModelRunner(
        CFG, params=params, max_slots=4, max_seq=512, page_size=PAGE,
        step_token_budget=CHUNK + 4, dtype=jnp.float32, **kwargs)


def admitted(r, slot: int, tokens: int) -> None:
    """What ``insert`` books for a prompt of ``tokens``, the program aside."""
    r._free(slot)
    pages = r._alloc(r.bucket_for(tokens) // PAGE)
    r._slot_pages[slot] = pages
    r._host_seq[slot] = tokens
    r.page_table[slot, :len(pages)] = pages


def flown(r, steps: int) -> None:
    """What ``decode_steps_device`` books around a flight of ``steps``."""
    r._ensure_capacity(steps)
    for slot in r._slot_pages:
        r._host_seq[slot] = min(r._host_seq[slot] + steps, r.max_seq)


def live(r) -> tuple[float, float]:
    g = r.kv_gauges()
    return g["kv_live_bytes|kind=full"], g["kv_live_bytes|kind=window"]


def test_the_ring_is_the_window_a_chunk_and_a_page(params):
    r = runner(params)
    assert r.ragged_chunk == CHUNK
    assert r.ring == Ring((WINDOW + CHUNK + PAGE) // PAGE, WINDOW)
    # ...and never longer than a slot's context
    short = HybridPagedModelRunner(
        CFG, params=params, max_slots=2, max_seq=24, page_size=PAGE,
        dtype=jnp.float32)
    assert short.ring.pages == short.max_pages_per_slot == 3


def test_a_slot_run_to_twenty_windows_keeps_to_its_ring(params):
    r = runner(params)
    bound = r.ring.pages
    admitted(r, 1, 40)
    seen, full = [], []
    while r._host_seq[1] < 20 * WINDOW + 40:
        flown(r, 4)
        seen.append(r.window_pages(1))
        full.append(len(r._slot_pages[1]))
    assert max(seen) == bound == 5         # never more than the ring
    assert full[-1] == -(-(int(r._host_seq[1]) + 1) // PAGE) > 8 * bound
    g = r.kv_gauges()
    page = 2 * CFG.num_kv_heads * PAGE * CFG.head_dim * 4
    assert g["kv_live_bytes|kind=window"] == bound * 4 * page
    assert g["kv_pool_bytes|kind=window"] == 4 * bound * 4 * page
    assert g["kv_live_bytes|kind=full"] == full[-1] * page
    # every page beyond the ring's was written over a page of the ring
    assert g["kv_window_pages_recycled_total"] == (
        -(-int(r._host_seq[1]) // PAGE) - bound)


def test_release_returns_both_pools_to_their_start(params):
    r = runner(params)
    free0 = sorted(r._free_pages)
    assert live(r) == (0.0, 0.0)
    for slot, tokens in ((0, 24), (1, 100), (3, 300)):
        admitted(r, slot, tokens)
    for _ in range(30):
        flown(r, 2)
    assert all(x > 0 for x in live(r))
    recycled = r.kv_gauges()["kv_window_pages_recycled_total"]
    for slot in (0, 1, 3):
        r._free(slot)
    assert sorted(r._free_pages) == free0 and live(r) == (0.0, 0.0)
    assert not r._slot_pages and not r.page_table.any()
    assert [r.window_pages(s) for s in range(4)] == [0, 0, 0, 0]
    # the count of pages written over does not go back
    assert r.kv_gauges()["kv_window_pages_recycled_total"] == recycled > 0


def test_exhaustion_names_the_pool(params):
    """Only the full pool can be found empty: a slot's ring is its own."""
    r = runner(params, pool_tokens=64 * PAGE)
    assert r.total_pages == 64
    admitted(r, 0, 200)
    admitted(r, 1, 200)
    with pytest.raises(PagesExhausted, match="the full pool"):
        admitted(r, 2, 200)
    # ...and growth names the slots it could not serve, as before
    r._free(2)
    while not (starved := r.pre_decode_check(8)):
        for slot in r._slot_pages:
            r._host_seq[slot] += 8
    assert starved and r.window_pages(starved[0]) == r.ring.pages
    assert r.kv_gauges()["kv_pool_bytes|kind=window"] == (
        4 * r.ring.pages * 4 * 2 * CFG.num_kv_heads * PAGE * CFG.head_dim * 4)


def test_a_cancelled_ragged_prefill_leaves_no_page_behind(params):
    r = runner(params)
    free0 = sorted(r._free_pages)
    admitted(r, 0, 64)
    job = r.ragged_begin(list(range(1, 200)), 2, state=None)
    for _ in range(5):      # five dispatches of two chunks: 160 tokens in
        _, _, end, wp = r._ragged_provision(job, 2)
        r._ragged_commit(job, end, 2, None, wp)
    assert job.done_tokens == 160 and not job.finished
    assert r.window_pages(2) == r.ring.pages and len(r._slot_pages[2]) == 20
    r.ragged_abort(job)
    assert r._ragged_slot is None and r.window_pages(2) == 0
    assert 2 not in r._slot_pages and not r.page_table[2].any()
    r._free(0)
    assert sorted(r._free_pages) == free0 and live(r) == (0.0, 0.0)


def test_one_table_width_for_every_ragged_dispatch(params):
    """The window layers never read the page table and the full layers'
    kernel skips what lies past a slot's length: one width, so that the
    warm-up's two programs are every admission's."""
    r = runner(params)
    assert r.ragged_width_fixed
    admitted(r, 0, 24)
    assert r._ragged_window() == r.max_pages_per_slot == 64
