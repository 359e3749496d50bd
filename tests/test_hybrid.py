"""A model whose layers differ in kind (family ``nemotron_h``: Mamba-2,
latent mixture of experts with a shared expert, attention without
rotation) on the paged engine, against its plain reference
(benchmarks/chip/harness/reference/nemotron_h.py) — LOGITS, at tiny size on
the CPU, seeded random weights: prefill; prefill then decode steps through
the paged state; a prompt admitted in chunks through the ragged step beside
decoding slots.

THE LIMITS (``LIMITS``), in standard deviations of the reference's logits at
the position, (worst position, mean over positions):

* float32 (1e-3, 1e-4): both sides compute the same equations in float32;
  what is left is the order of the sums (chunked scan against a scan over
  tokens, sorted dispatch against every expert masked): read 5e-6 at most.
  This is the row that holds the STATE's precision: a bf16 state-space
  state in place of float32 reads up to 4.4e-3 (``test_bf16_state...``).
* bfloat16 and int8 (0.15, 0.08): bf16 activations (and bf16 or int8
  weights) against float32 over the same weights: rounding of 2^-8 a
  product through five layers, on logits of unit scale (int8's: 0.6).
  Read up to 0.057 / 0.034 (bf16) and 0.044 / 0.032 (int8): the limits are
  about 2.5 times that.  A missing scale, a wrong cast or a dropped layer
  moves both by whole standard deviations.  These rows run a router that
  chooses all its experts (``ALL_CHOSEN``, and why).

The float32 row runs twice: as the CPU serves it (the decode step's state
update as XLA's two fusions), and with the Pallas ``ssm_update`` in
interpret mode in every decode-type program (``float32-kernel``: a state
size of whole lanes, which the kernel asks for) — the kernel's precision
gate, since the chip's own check would pass a bf16 state (PERF.md §7).
"""

import json
import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                       / "benchmarks" / "chip"))

from harness.reference import nemotron_h as R  # noqa: E402

from crowdllama_tpu.engine.hybrid import HybridPagedModelRunner  # noqa: E402
from crowdllama_tpu.models import hybrid as H  # noqa: E402
from crowdllama_tpu.models import transformer as T  # noqa: E402
from crowdllama_tpu.models.config import get_config  # noqa: E402
from crowdllama_tpu.ops import ssm  # noqa: E402
from crowdllama_tpu.ops.quant import random_quantized_params  # noqa: E402

KEY = jax.random.PRNGKey(0)
CFG = get_config("tiny-test-nemotron-h")
# The bf16 and int8 rows run a router that chooses ALL its experts (this
# rank holds half): where k of 16 are chosen, bf16 rounding sends a token
# whose k-th and (k+1)-th scores tie to another expert than the float32
# reference, and the Mamba state and the KV carry that difference to every
# later position (read: 1.6 at the worst position, 0.4 in the mean, twice in
# 300 token-layers).  The choice itself is held by the float32 row, exactly.
ALL_CHOSEN = replace(CFG, num_experts=8, num_experts_per_tok=8,
                     experts_held=4)
LIMITS = {"float32": (1e-3, 1e-4), "bfloat16": (0.15, 0.08),
          "int8": (0.15, 0.08)}
PATHS = ("prefill", "decode", "ragged")
# the rows of LIMITS, and the float32 row with the state-update kernel
ROWS = [*LIMITS, "float32-kernel"]

pytestmark = pytest.mark.usefixtures("_programs_go_with_their_test")


@pytest.fixture
def state_kernel(monkeypatch):
    """``(row) -> precision``: for a ``-kernel`` row, the Pallas
    ``ssm_update`` in interpret mode, which a runner built afterwards
    takes for a state size of whole lanes (``kernel_cfg``)."""
    def steer(row: str) -> str:
        if row.endswith("-kernel"):
            monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
        return row.removesuffix("-kernel")

    return steer


def kernel_cfg(row: str):
    """The configuration a row runs: with the kernel, a state of 128."""
    cfg = cfg_of(row.removesuffix("-kernel"))
    return replace(cfg, ssm_state=128) if row.endswith("-kernel") else cfg


def hf_of(cfg) -> dict:
    """The config.json keys the reference reads, of a registry config."""
    return {
        "model_type": "nemotron_h", "hidden_size": cfg.hidden_size,
        "hybrid_override_pattern": cfg.layer_pattern,
        "num_hidden_layers": cfg.num_layers,
        "layer_norm_epsilon": cfg.rms_norm_eps,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.resolved_head_dim(), "vocab_size": cfg.vocab_size,
        "intermediate_size": cfg.intermediate_size,
        "mamba_num_heads": cfg.ssm_heads, "mamba_head_dim": cfg.ssm_head_dim,
        "expand": cfg.ssm_heads * cfg.ssm_head_dim // cfg.hidden_size,
        "n_groups": cfg.ssm_groups, "ssm_state_size": cfg.ssm_state,
        "conv_kernel": cfg.ssm_conv_kernel, "chunk_size": cfg.ssm_chunk,
        "n_routed_experts": cfg.experts_held or cfg.num_experts,
        "n_routed_experts_published": cfg.num_experts,
        "expert_parallel_rank": cfg.expert_rank,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "moe_latent_size": cfg.moe_latent_size,
        "moe_shared_expert_intermediate_size":
            cfg.moe_shared_intermediate_size,
        "routed_scaling_factor": cfg.moe_routed_scaling,
        "norm_topk_prob": cfg.moe_norm_topk,
        "max_position_embeddings": cfg.max_context_length,
    }


def cfg_of(precision: str):
    return CFG if precision == "float32" else ALL_CHOSEN


def make_params(precision: str, cfg=None):
    cfg = cfg or cfg_of(precision)
    if precision == "int8":
        return random_quantized_params(cfg, KEY, jnp.bfloat16)
    return T.init_params(cfg, KEY, jnp.dtype(precision))


class Probe(HybridPagedModelRunner):
    """The runner, telling the test each step's decode logits [B, V]."""

    def __init__(self, *args, **kwargs):
        self.seen: list[np.ndarray] = []
        super().__init__(*args, **kwargs)

    def _sampled(self, st, logits, pools, changed):
        jax.debug.callback(lambda x: self.seen.append(np.asarray(x)), logits,
                           ordered=True)
        return super()._sampled(st, logits, pools, changed)


def make_runner(precision: str, cls=Probe, cfg=None, **kwargs):
    cfg = cfg or cfg_of(precision)
    dtype = jnp.float32 if precision == "float32" else jnp.bfloat16
    # 4 slots + chunks of 32 tokens: a 100-token prompt takes four steps
    return cls(cfg, params=make_params(precision, cfg), max_slots=4,
               max_seq=256, page_size=16, step_token_budget=36, dtype=dtype,
               **kwargs)


def prompt_of(n: int, seed: int) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n)]


def distance(got, ids: list[int], positions, r, controls=()):
    """(worst position, mean over positions) of |logits - reference| in
    standard deviations of the reference's logits at the position."""
    with jax.default_matmul_precision("highest"):
        ref = R.forward(r.params, hf_of(r.cfg), ids, list(positions),
                        controls)
    err = jnp.max(jnp.abs(jnp.asarray(got, jnp.float32) - ref), -1)
    err = err / jnp.std(ref, -1)
    return float(jnp.max(err)), float(jnp.mean(err))


def admit(r, st, slot, prompt):
    tok, ks, vs, plen = r.prefill(prompt, 0.0, 1.0, KEY)
    return tok, r.insert(st, slot, ks, vs, plen, tok, 0.0, 1.0,
                         prompt_tokens=prompt)


def slot_rows(r, slot: int) -> np.ndarray:
    jax.effects_barrier()
    rows, r.seen[:] = np.stack([x[slot] for x in r.seen]), []
    return rows


def run_path(r, path: str) -> list[tuple]:
    """Drive ``path`` greedily; [(what, logits [n, V], ids, positions)]: the
    system's logits and the token sequence they belong to."""
    params, a = r.params, prompt_of(40, 1)
    if path == "prefill":
        toks = np.zeros((1, 64), np.int32)
        toks[0, :40] = a
        logits = H.prefill(
            params, r.cfg, jnp.asarray(toks),
            jnp.minimum(jnp.arange(64), 39)[None],
            (jnp.arange(64) < 40)[None])[0][0, :40]
        return [("prefill", logits, a, range(40))]
    st = r.init_state()
    first, st = admit(r, st, 1, a)
    seq = a + [first]
    out = []

    def advance(st, n):
        toks, st = r.decode_steps_device(st, n)
        return np.asarray(toks), st

    toks, st = advance(st, 8)
    seq += [int(t) for t in toks[:, 1]]
    out.append(("decode", slot_rows(r, 1), seq[:-1], range(40, 48)))
    if path == "decode":
        return out
    # a 100-token prompt admitted in chunks of 32 beside slot 1's decoding:
    # one step a dispatch, then two
    b = prompt_of(100, 2)
    assert r.ragged_chunk == 32
    job = r.ragged_begin(b, 2, state=st)
    n0 = len(seq)
    for k in (1, 2, 2):
        toks, st = r.ragged_step(st, job, k)
        seq += [int(t) for t in np.asarray(toks)[:, 1]]
    assert job.finished
    n = len(seq) - n0
    out.append(("decode beside chunks", slot_rows(r, 1), seq[:-1],
                range(n0 - 1, n0 - 1 + n)))
    out.append(("chunked prompt's last token", job.last_logits[None], b,
                [99]))
    first_b, st = r.ragged_finish(st, job, 0.0, 1.0, KEY)
    toks, st = advance(st, 4)
    seq_b = b + [first_b] + [int(t) for t in toks[:, 2]]
    out.append(("decode after chunks", slot_rows(r, 2), seq_b[:-1],
                range(100, 104)))
    return out


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("row", ROWS)
def test_logits_match_the_reference(row, path, state_kernel):
    precision = state_kernel(row)
    r = make_runner(precision, cfg=kernel_cfg(row))
    assert r.ssm_update_path == ("pallas" if row.endswith("-kernel")
                                 else "xla")
    worst_lim, mean_lim = LIMITS[precision]
    for what, logits, ids, positions in run_path(r, path):
        worst, mean = distance(logits, ids, positions, r)
        assert worst <= worst_lim and mean <= mean_lim, (what, worst, mean)


@pytest.mark.parametrize("row", ["float32", "float32-kernel"])
def test_bf16_state_in_place_of_float32_reads_over_the_limit(
        row, monkeypatch, state_kernel):
    """The float32 row of LIMITS holds the state's precision: a state
    rounded to bf16 after every update fails it, on either path."""
    from crowdllama_tpu.ops.pallas import ssm as kernel

    def rounded(fn):
        def wrapped(*args, **kwargs):
            y, state = fn(*args, **kwargs)
            return y, state.astype(jnp.bfloat16).astype(jnp.float32)
        return wrapped

    monkeypatch.setattr(ssm, "ssm_update", rounded(ssm.ssm_update))
    monkeypatch.setattr(ssm, "ssd_scan", rounded(ssm.ssd_scan))
    monkeypatch.setattr(kernel, "ssm_update", rounded(kernel.ssm_update))
    r = make_runner(state_kernel(row), cfg=kernel_cfg(row))
    worst = max(distance(logits, ids, positions, r)[0]
                for _, logits, ids, positions in run_path(r, "ragged"))
    assert worst > 3 * LIMITS["float32"][0], worst


@pytest.mark.parametrize("control", [
    "no_correction_bias", "no_scaling", "silu_experts", "softmax_router",
    "no_conv_bias"])
def test_a_wrong_equation_reads_over_the_limit(control):
    """Each is a reading of the model that a careless port would make."""
    r = make_runner("float32", cls=HybridPagedModelRunner)
    (_, logits, ids, positions), = run_path(r, "prefill")
    worst, mean = distance(logits, ids, positions, r,
                           controls=(control,))
    assert worst > 0.05 and mean > 100 * LIMITS["float32"][1], (worst, mean)


def test_state_not_zeroed_on_release_reads_over_the_limit(monkeypatch):
    """A prompt admitted in chunks continues its slot's own state, so a
    slot must end as it began: with the release that only clears the
    flags (the parent's), the next prompt there starts from the last
    one's state."""
    from crowdllama_tpu.engine.paged import PagedModelRunner

    def admit_in_chunks_after_a_release(r):
        st = r.init_state()
        _, st = admit(r, st, 2, prompt_of(40, 1))
        _, st = r.decode_steps_device(st, 4)
        st = r.release(st, 2)
        b = prompt_of(100, 2)
        job = r.ragged_begin(b, 2, state=st)
        while not job.finished:
            _, st = r.ragged_step(st, job, 1)
        return distance(job.last_logits[None], b, [99], r)[0]

    assert admit_in_chunks_after_a_release(
        make_runner("float32", cls=HybridPagedModelRunner)
    ) <= LIMITS["float32"][0]
    monkeypatch.setattr(HybridPagedModelRunner, "_release_paged_impl",
                        PagedModelRunner._release_paged_impl)
    assert admit_in_chunks_after_a_release(
        make_runner("float32", cls=HybridPagedModelRunner)
    ) > 10 * LIMITS["float32"][0]


def test_a_cancelled_chunked_prompt_leaves_no_state_behind():
    r = make_runner("float32", cls=HybridPagedModelRunner)
    st = r.init_state()
    job = r.ragged_begin(prompt_of(100, 3), 0, state=st)
    _, st = r.ragged_step(st, job, 2)
    r.ragged_abort(job)
    b = prompt_of(70, 2)
    job = r.ragged_begin(b, 0, state=st)
    while not job.finished:
        _, st = r.ragged_step(st, job, 1)
    assert distance(job.last_logits[None], b, [69], r
                    )[0] <= LIMITS["float32"][0]


def test_the_shares_add_up_to_the_uncut_layer():
    """Four ranks' routed parts, plus the shared expert counted once, are
    the uncut reference's expert layer."""
    whole = replace(CFG, experts_held=0)
    params = T.init_params(whole, KEY, jnp.float32)
    lp = params["layers"]["moe"][0]
    x = jax.random.normal(jax.random.PRNGKey(3), (24, CFG.hidden_size))
    h = R.rms_norm(x, R.dequant(lp["norm"]), CFG.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        uncut = R.mixer("E", h, lp, R.hyper(hf_of(whole)))
        shared = R.shared_part(h, lp)
        total, rows = 0.0, np.zeros(len(H.COUNTS), np.int64)
        for rank in range(4):
            cfg = replace(CFG, experts_held=4, expert_rank=rank)
            mine = {**lp, "w1": lp["w1"][4 * rank:4 * rank + 4],
                    "w2": lp["w2"][4 * rank:4 * rank + 4]}
            out, counts = H.moe_body(mine, cfg, x, jnp.ones((24,), bool))
            total = total + (out - x - shared)
            rows += np.asarray(counts)
            # and the reference, given the same share, says the same
            part = R.mixer("E", h, mine, R.hyper(hf_of(cfg)))
            np.testing.assert_allclose(out - x, part, atol=2e-5)
    np.testing.assert_allclose(total + shared, uncut, atol=5e-5)
    # every token-expert row is computed by exactly one rank
    assert rows[0] == 24 * CFG.num_experts_per_tok
    assert rows[1] == 3 * rows[0]
    # and of the 16 banks the four ranks hold, none routed to twice
    assert 0 < rows[2] <= rows[3] == rows[4] == 16


def routing(case: str, experts: int = 16, held: int = 8, k: int = 4):
    """(topi ``[6, k]``, live ``[6]``) of a call whose held banks are the
    first ``held``: each token's k distinct experts, some of them another
    rank's."""
    rng = np.random.default_rng(7)
    topi = np.stack([rng.permutation(experts)[:k] for _ in range(6)])
    live = np.ones(6, bool)
    if case == "padded":        # padding rows are routed and multiplied too
        live[2:] = False
    elif case == "empty-banks":  # every held choice falls on bank 0 or 1
        topi = np.where(topi < held, topi % 2, topi)
    elif case == "none-routed":
        topi = held + topi % (experts - held)
    return topi, live


def banks_visited(held_choices, held: int, rows: int, width: int) -> int:
    """How many of ``held`` banks ``[width, width]`` the grouped matmul
    kernel visits for ``rows`` float32 rows, ``held_choices`` of them with a
    held expert: the distinct groups among the visits ``_visits`` lays out
    — the rule the ``fetched`` counter has to follow."""
    from crowdllama_tpu.ops.pallas import moe as K

    tm = K.choose_tiles(rows, held, width, width, 4)[0]
    group_ids, _, _, _, visits = K._visits(
        jnp.bincount(jnp.asarray(held_choices), length=held), tm,
        -(-rows // tm))
    return len(np.unique(np.asarray(group_ids)[:int(visits)]))


@pytest.mark.parametrize("path", ["ragged_dot", "kernel"])
@pytest.mark.parametrize("case", ["live-only", "padded", "empty-banks",
                                  "none-routed"])
def test_held_sum_counts_the_banks_routed_and_the_banks_fetched(
        case, path, monkeypatch):
    """``routed`` is a recount of ``topi``; ``fetched`` is what the grouped
    matmul's own rule visits (``ops/pallas/moe.py`` ``_visits``) and every
    held bank where ``lax.ragged_dot`` multiplies."""
    from crowdllama_tpu.ops.quant import QTensor, ragged_dot_path

    if path == "kernel":
        monkeypatch.setenv("CROWDLLAMA_PALLAS_INTERPRET", "1")
    held, k, w = 8, CFG.num_experts_per_tok, 128
    bank = QTensor(
        q=jax.random.randint(KEY, (held, w, w), -127, 128, jnp.int8),
        s=jnp.full((held, w), 0.01, jnp.float32))
    assert (ragged_dot_path(bank)[0] == "int8_kernel") == (path == "kernel")
    topi, live = routing(case, CFG.num_experts, held, k)
    n = len(topi)
    _, counts = H.held_sum(
        CFG, jax.random.normal(KEY, (n, w)), jnp.ones((n, k)),
        jnp.asarray(topi), jnp.asarray(live), lambda xs, dot: dot(xs, bank))
    counts = dict(zip(H.COUNTS, np.asarray(counts).tolist()))
    mine = topi < held
    assert counts["rows_held"] == (mine & live[:, None]).sum()
    assert counts["rows_left_out"] == live.sum() * k - counts["rows_held"]
    assert counts["banks_routed"] == len(np.unique(topi[mine]))
    assert counts["banks_held"] == held
    assert counts["banks_fetched"] == (
        banks_visited(topi[mine], held, n * k, w) if path == "kernel"
        else held)
    assert counts["banks_routed"] <= counts["banks_fetched"] <= held


# ------------------------------------------------------------- the engine

def test_two_kinds_of_state_in_one_donated_pytree():
    """Pools over the attention layers only, the recurrent state beside
    them; every byte of both handed back in place by the step program
    (tests/test_tpu_compile.py reads the chip compiler's temporaries at
    the benchmark's widths)."""
    r = make_runner("bfloat16", cls=HybridPagedModelRunner)
    st = r.init_state()
    assert st.pool_k.shape[0] == CFG.layers_of("*") == 1
    assert st.ssm.shape == (2, 4, 8, 16, 16) and st.ssm.dtype == jnp.float32
    assert st.conv.shape == (2, 4, 192, 3)
    compiled = r._decode_paged.lower(
        r.params, st, jnp.asarray(r.page_table), 8).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= (
        st.ssm.nbytes + st.conv.nbytes + 2 * st.pool_k.nbytes)


def test_what_rests_on_exportable_pages_declines_by_name():
    from crowdllama_tpu.config import Configuration
    from crowdllama_tpu.engine.factory import build_runner
    from crowdllama_tpu.engine.plan import resolve_serving_plan
    from crowdllama_tpu.engine.runner import ModelRunner
    from crowdllama_tpu.engine.spec import SpecPagedModelRunner

    r = make_runner("bfloat16", cls=HybridPagedModelRunner, prefix_cache=True)
    st = r.init_state()
    assert not r.prefix_cache
    assert not r.prefill_prefers_monolithic(prompt_of(200, 1))
    with pytest.raises(ValueError, match=H.NO_PAGES):
        r.export_pages(st, [b"x"])
    with pytest.raises(ValueError, match=H.NO_PAGES):
        r.import_pages(st, {"keys": [b"x"], "k_pages": [b""],
                            "v_pages": [b""]})
    with pytest.raises(ValueError, match=H.NO_PAGES):
        SpecPagedModelRunner(CFG, params=r.params, max_slots=2, max_seq=64)
    with pytest.raises(ValueError, match="engine/hybrid.py"):
        ModelRunner(CFG, params=r.params, max_slots=2, max_seq=64)
    for spec in ("ngram", "draft"):
        config = Configuration(model=CFG.name, spec_decode=spec,
                               spec_draft_model="tiny-test")
        with pytest.raises(ValueError, match=H.NO_PAGES):
            build_runner(config, resolve_serving_plan(config, 1), CFG,
                         r.params)
    config = Configuration(model=CFG.name, kv_layout="contiguous")
    with pytest.raises(ValueError, match="paged layout only"):
        build_runner(config, resolve_serving_plan(config, 1), CFG, r.params)


# the expert layers' bank counters, whole and of the ragged flights
BANKS = ("crowdllama_moe_banks_total", "crowdllama_moe_banks_fetched_total",
         'crowdllama_moe_banks_total{dispatch="ragged",state="routed"}',
         'crowdllama_moe_banks_total{dispatch="ragged",state="unrouted"}',
         'crowdllama_moe_banks_fetched_total{dispatch="ragged"}')


def check_banks(grew: dict, banks_a_step: int) -> None:
    """What a served engine's scrape says of the banks: every step of
    every flight counts every held bank of every expert layer once, as
    routed or as unrouted; on the CPU ``lax.ragged_dot`` reads them all; a
    prompt admitted in chunks flew ragged flights, counted as such."""
    total, fetched, routed, unrouted, ragged_fetched = (grew[n] for n in BANKS)
    assert total == fetched == banks_a_step * grew[
        "crowdllama_engine_flight_steps_total"]
    assert 0 < routed and routed + unrouted == ragged_fetched < total


async def test_served_through_the_engine_with_its_counters():
    """The normal path: JaxEngine -> scheduler -> the hybrid runner, ragged
    admission on; every admission a prefix miss; the expert
    layers' assignment counts read back with the flights; nothing to
    export for the KV plane or a drain."""
    from crowdllama_tpu.config import Configuration, Intervals
    from crowdllama_tpu.engine.engine import JaxEngine
    from crowdllama_tpu.obs.metrics import ENGINE_TELEMETRY

    def series(name: str) -> float:
        return sum(float(ln.rsplit(" ", 1)[1])
                   for ln in ENGINE_TELEMETRY.expose()
                   if ln.startswith(name))

    engine = JaxEngine(Configuration(
        model=CFG.name, max_context_length=256, max_batch_slots=2,
        warmup=False, kv_page_size=16, step_token_budget=34,
        kv_ship=True, intervals=Intervals.default()))
    await engine.start()
    try:
        assert isinstance(engine._runner, HybridPagedModelRunner)
        before = {n: series(n) for n in (
            "crowdllama_moe_assignments_total",
            "crowdllama_prompt_tokens_total",
            "crowdllama_prefix_tokens_reused_total",
            *BANKS, "crowdllama_engine_flight_steps_total")}
        prompt = "one two three four five six seven eight nine ten " * 2
        n = len(engine.tokenizer.encode(prompt))
        assert n > 2 * engine._runner.ragged_chunk     # admitted in chunks
        for _ in range(2):                              # the same prompt twice
            out = [c async for c in engine.generate(prompt, max_tokens=12)]
            assert out[-1].done and out[-1].completion_tokens == 12, out[-1]
        grew = {k: series(k) - v for k, v in before.items()}
        assert grew["crowdllama_prompt_tokens_total"] == 2 * n
        assert grew["crowdllama_prefix_tokens_reused_total"] == 0
        rows = grew["crowdllama_moe_assignments_total"]
        # two expert layers, k experts a live token, prompt and output
        k = CFG.layers_of("E") * CFG.num_experts_per_tok
        # (and a flight may run a step or two past a request's last token)
        assert 2 * n * k <= rows <= 2 * (n + 16) * k
        held = series('crowdllama_moe_assignments_total{held="yes"}')
        assert 0.3 < held / series("crowdllama_moe_assignments_total") < 0.7
        check_banks(grew, CFG.layers_of("E") * H.sizes(CFG)["held"])
        st = engine.scheduler.state
        # a list of rank-2 layers takes no layout (its attention layer has
        # a wq and a wk), and the CPU none either
        for leaf in ("wq", "wk"):
            assert series(f'crowdllama_weight_layout{{leaf="{leaf}",'
                          'layout="default"}') == 1
        assert series("crowdllama_weight_layout") == 2
        assert series('crowdllama_engine_state_bytes{kind="ssm"}'
                      ) == st.ssm.nbytes
        assert series('crowdllama_engine_state_bytes{kind="kv_pool"}'
                      ) == 2 * st.pool_k.nbytes
        assert await engine.export_kv_pages(CFG.name, [b"k"], 16) is None
        assert not engine._kv_ship_ready()
    finally:
        await engine.stop()


# ----------------------------------------------------------- config.json

PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": "MEMEMEM*EME", "intermediate_size": 2688,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_num_heads": 128, "max_position_embeddings": 262144,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_routed_experts_published": 512,
    "expert_parallel_rank": 0, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 11, "num_key_value_heads": 2, "rope_theta": 10000,
    "routed_scaling_factor": 5, "ssm_state_size": 128,
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 32768,
}


def _dir(tmp_path, doc: dict) -> str:
    (tmp_path / "config.json").write_text(json.dumps(doc))
    return str(tmp_path)


def test_a_nemotron_h_config_json_is_read_as_what_it_is(tmp_path):
    from crowdllama_tpu.engine.weights import resolve_model_config

    cfg = resolve_model_config("nemotron-cut", _dir(tmp_path, PUBLISHED))
    assert (cfg.family, cfg.layer_pattern) == ("nemotron_h", "MEMEMEM*EME")
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_rank) == (512, 128, 0)
    assert (cfg.num_experts_per_tok, cfg.moe_routed_scaling) == (22, 5.0)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
            ) == (128, 64, 8, 128)
    assert H.sizes(cfg) == {"d_inner": 8192, "bc": 1024, "conv_dim": 10240,
                            "in_proj": 18560, "held": 128}
    assert [cfg.layers_of(k) for k in "ME*"] == [5, 5, 1]
    # the issue's arithmetic: int8 bytes of the three kinds and the ends
    shapes = H._shapes(cfg)
    weights = {k: sum(int(np.prod(s)) for n, s in v.items() if len(s) > 1
                      and n not in ("router", "conv_w"))
               for k, v in shapes.items()}
    assert round(weights["moe"] / 1e6, 1) == 757.1     # + 4.2 router = 761
    assert round(weights["mamba"] / 1e6, 1) == 109.6
    assert round(weights["attn"] / 1e6, 1) == 35.7


@pytest.mark.parametrize("change, names", [
    ({"model_type": "nemotron_x"}, "nemotron_x"),
    ({"model_type": "llama"}, "hybrid_override_pattern"),
    ({"model_type": "mistral", "hybrid_override_pattern": None,
      "n_routed_experts": 0}, "mamba_head_dim"),
    ({"hybrid_override_pattern": "MEM"}, "num_hidden_layers"),
    ({"mlp_hidden_act": "silu"}, "relu2"),
])
def test_it_can_no_longer_be_read_as_a_llama(tmp_path, change, names):
    from crowdllama_tpu.engine.weights import config_from_hf_dir

    with pytest.raises(ValueError, match=names):
        config_from_hf_dir(_dir(tmp_path, {**PUBLISHED, **change}))
