"""Closed-loop performance autopilot (docs/AUTOTUNE.md).

Two contracts under test:

1. BYTE-IDENTITY — the tuner moves dials (megastep K, ragged
   step_token_budget, prefill chunk) at the scheduler's between-dispatch
   safe point, so an aggressively-cadenced autotune run must emit the
   exact token streams the autotune-off control emits, through ≥3 dial
   moves including a revert and a fault-injected fast-burn backoff.
2. REVERT IS FREE — stepping a dial back to its prior value re-uses the
   already-claimed jit signature; EngineTelemetry's
   crowdllama_xla_compile_cache_hits_total witness proves no recompile.

The unit tests below drive :class:`AutoTuner` against a fake scheduler
(dial application, keep/revert scoring, fast-burn backoff + the
process-wide BACKOFF_LOG, gossip warm-start, exposition rendering);
the scheduler-level test at the bottom runs the real engine loop.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from crowdllama_tpu.engine.autotune import (
    BACKOFF_LOG,
    DIALS,
    AutoTuner,
    decode_point,
    encode_point,
)
from crowdllama_tpu.obs.slo import WindowBurn

KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------------- fakes


class FakeRunner:
    supports_megastep = True
    supports_ragged = True

    def __init__(self, page_size=32, max_slots=4, step_token_budget=96,
                 prefill_chunk=64):
        self.page_size = page_size
        self.max_slots = max_slots
        self.max_seq = 256
        self.step_token_budget = step_token_budget
        self.prefill_chunk = prefill_chunk
        c = min(prefill_chunk, max(step_token_budget - max_slots, page_size))
        self.ragged_chunk = max(page_size, (c // page_size) * page_size)
        self.draft_len = 3
        self.draft_sets = []

    def set_draft_len(self, k):
        self.draft_len = k
        self.draft_sets.append(k)


class FakeScheduler:
    def __init__(self, runner=None, megastep_k=4, spec_draft_max=4,
                 spec_adaptive=True):
        self.runner = runner or FakeRunner()
        self.megastep_k = megastep_k
        self._megastep = megastep_k > 0
        self.spec_draft_max = spec_draft_max
        self._spec_adaptive = spec_adaptive


class FakeGossip:
    def __init__(self):
        self.points = {}

    def record_operating_point(self, model_id, point):
        self.points[model_id] = encode_point(point)

    def lookup_operating_point(self, model_id, max_age_s=0.0):
        return decode_point(self.points.get(model_id, ""))


def _tuner(sched=None, **kw):
    kw.setdefault("interval", 1)
    return AutoTuner(sched or FakeScheduler(), model_id="m", **kw)


def _settle(t, score=1.0, n=None):
    """Feed one full measurement phase of identical windows: duty=score,
    1 token per window, 1 ms per window → phase score == `score`."""
    for _ in range(n or t.interval):
        t.on_window("plain", score, 1, 0.001)


# ---------------------------------------------------------- WindowBurn


def test_window_burn_requires_objective_and_full_short_window():
    wb = WindowBurn(objective_ms=0.0, short=2, long=4)
    for _ in range(8):
        wb.observe(1e9)  # no objective: every window is "good"
    assert wb.burn() == 0.0 and not wb.in_fast_burn()

    wb = WindowBurn(objective_ms=10.0, short=2, long=4)
    assert wb.observe(5.0) is False
    assert not wb.in_fast_burn()  # short window not full yet
    assert wb.observe(50.0) is True
    # 1 of 2 breaching (50%) is under the 14×5% fast-burn line? No —
    # 0.5/0.05 = 10 < 14: still not burning.
    assert not wb.in_fast_burn()
    for _ in range(4):
        wb.observe(50.0)
    assert wb.in_fast_burn()
    assert wb.burn() >= 14.0
    assert wb.breaches_total == 5


# ------------------------------------------------------ grids & gating


def test_grid_gating_tracks_runner_capabilities():
    sched = FakeScheduler()
    sched.runner.supports_remote_draft = True  # hosted spec verify program
    sched.spec_pipeline_depth = 2
    t = _tuner(sched)
    assert list(t._order) == list(DIALS)  # fully-capable fake: all five
    # Without the remote-draft verify program the fifth dial is gated off.
    assert list(_tuner()._order) == [d for d in DIALS
                                     if d != "pipeline_depth"]

    r = FakeRunner(prefill_chunk=0, step_token_budget=0)
    r.supports_megastep = False
    sched = FakeScheduler(runner=r, megastep_k=0, spec_adaptive=False)
    t = _tuner(sched)
    assert t._order == []  # nothing to tune; the loop is inert
    _settle(t, n=4)
    assert t.moves == 0


def test_grids_always_contain_the_current_point():
    sched = FakeScheduler(megastep_k=3)  # off-grid K
    sched.runner.step_token_budget = 90  # off the 2*page stride
    t = _tuner(sched)
    vals, idx = t._grids["megastep_k"]
    assert vals[idx] == 3
    vals, idx = t._grids["step_token_budget"]
    assert vals[idx] == 90
    assert list(vals) == sorted(vals)


# ------------------------------------------------------- keep / revert


def test_trial_kept_when_score_beats_baseline_and_published():
    g = FakeGossip()
    sched = FakeScheduler()
    t = _tuner(sched, gossip=g)
    _settle(t, score=0.5)       # baseline phase → proposes move #1
    assert t.moves == 1 and t._pending is not None
    moved = t._pending["dial"]
    _settle(t, score=2.0)       # trial wins by far more than min_gain
    assert t.reverts == 0
    assert t._last_good[moved] == t._read(moved)
    assert decode_point(g.points["m"]) == t._last_good


def test_trial_reverted_when_score_does_not_clear_min_gain():
    sched = FakeScheduler()
    t = _tuner(sched)
    before = t._snapshot()
    _settle(t, score=1.0)       # baseline → move #1
    move = dict(t._pending)
    assert t._read(move["dial"]) == move["to"] != move["frm"]
    _settle(t, score=1.0)       # flat trial: inside min_gain → revert
    assert t.moves == 1 and t.reverts == 1
    assert t._snapshot() == before
    assert t._dir[move["dial"]] == -1  # direction flipped after revert


def test_draft_cap_dial_clamps_live_draft():
    sched = FakeScheduler()
    sched.runner.draft_len = 4
    t = _tuner(sched)
    t._apply("draft_k", 2)
    assert sched.spec_draft_max == 2
    assert sched.runner.draft_sets == [2]  # live draft clamped under cap


def test_budget_dial_recomputes_ragged_chunk_like_paged_boot():
    sched = FakeScheduler()
    r = sched.runner
    t = _tuner(sched)
    t._apply("step_token_budget", 132)
    c = min(r.prefill_chunk, max(132 - r.max_slots, r.page_size))
    assert r.ragged_chunk == max(r.page_size,
                                 (c // r.page_size) * r.page_size)
    t._apply("prefill_chunk", 32)
    assert r.ragged_chunk == 32


# --------------------------------------------------- fast-burn backoff


def test_fast_burn_backoff_restores_last_good_and_logs():
    sched = FakeScheduler()
    t = _tuner(sched, decode_ms=10.0, burn_short=2, burn_long=4)
    good = t._snapshot()
    _settle(t, score=1.0)       # baseline → pending move #1
    assert t._pending is not None
    total0 = BACKOFF_LOG.snapshot()[0]
    # 3 windows at 100 ms/token vs a 10 ms objective: the short deque
    # fills and the long rate crosses FAST_BURN on the 3rd — the edge.
    # (Window 1 ends the trial phase as a revert; window 2's baseline
    # proposes move #2, which is the one the backoff catches in flight.)
    for _ in range(3):
        t.on_window("plain", 1.0, 1, 0.1)
    assert t.backoffs == 1
    assert t._pending is None and t._snapshot() == good
    assert t._cooldown == 2
    total, last = BACKOFF_LOG.snapshot()
    assert total == total0 + 1
    assert last["model"] == "m" and last["dial"] in DIALS
    assert last["restored"] == good
    # Level-triggered episode backs off ONCE (edge), not per window.
    t.on_window("plain", 1.0, 1, 0.1)
    assert t.backoffs == 1


def test_cooldown_blocks_probing_after_backoff():
    t = _tuner(FakeScheduler(), decode_ms=10.0, burn_short=2, burn_long=4)
    _settle(t)
    for _ in range(3):
        t.on_window("plain", 1.0, 1, 0.1)
    assert t.backoffs == 1 and t._cooldown == 2
    moves = t.moves
    _settle(t, score=1.0)       # cooldown phase 1: no proposal
    _settle(t, score=1.0)       # cooldown phase 2: no proposal
    assert t.moves == moves
    _settle(t, score=1.0)       # cooled down: baseline → propose again
    assert t.moves == moves + 1


# --------------------------------------------------------------- gossip


def test_gossip_point_roundtrip_and_junk_tolerance():
    p = {"megastep_k": 8, "draft_k": 2}
    assert decode_point(encode_point(p)) == p
    assert decode_point("not json") == {}
    assert decode_point('["a"]') == {}
    assert decode_point('{"megastep_k": "x", "bogus": 1}') == {}


def test_warm_start_from_gossip_clamps_to_grid():
    g = FakeGossip()
    g.points["m"] = encode_point({"megastep_k": 7,  # off-grid → 8
                                  "step_token_budget": 10_000,  # over bound
                                  "bogus_dial": 3})
    sched = FakeScheduler()
    t = _tuner(sched, gossip=g, interval=4)  # window 1 ends no phase
    t.on_window("plain", 1.0, 1, 0.001)
    assert t.warm_starts == 1
    assert sched.megastep_k == 8
    budget_grid, _ = t._grids["step_token_budget"]
    assert sched.runner.step_token_budget == budget_grid[-1]
    assert t._last_good == t._snapshot()


def test_warm_start_skipped_once_local_moves_exist():
    g = FakeGossip()
    t = _tuner(FakeScheduler())
    _settle(t)                   # baseline → a local move happened
    assert t.moves == 1
    g.points["m"] = encode_point({"megastep_k": 16})
    t.set_gossip(g)
    t.on_window("plain", 1.0, 1, 0.001)
    assert t.warm_starts == 0    # local search already in flight


def test_operating_point_rides_the_gossip_crdt():
    from types import SimpleNamespace

    from crowdllama_tpu.swarm.gossip import TUNE_PREFIX, GossipNode

    a = GossipNode(SimpleNamespace(peer_id="gw1"), peers=())
    a.record_operating_point("llama", {"megastep_k": 8, "draft_k": 2})
    v0 = a.state.get(TUNE_PREFIX + "llama").version
    a.record_operating_point("llama", {"megastep_k": 8, "draft_k": 2})
    assert a.state.get(TUNE_PREFIX + "llama").version == v0  # no churn
    assert a.lookup_operating_point("llama") == {"megastep_k": 8,
                                                 "draft_k": 2}
    assert a.lookup_operating_point("other") == {}
    assert a.lookup_operating_point("llama", max_age_s=1e-9) == {}

    b = GossipNode(SimpleNamespace(peer_id="gw2"), peers=())
    for e in a.state.snapshot():  # anti-entropy frame contents
        b.state.apply(e)
    assert b.lookup_operating_point("llama") == {"megastep_k": 8,
                                                 "draft_k": 2}


# ----------------------------------------------------------- exposition


def test_autotune_gauges_render_as_their_own_families():
    from crowdllama_tpu.engine.autotune import METRIC_FAMILIES
    from crowdllama_tpu.obs.metrics import engine_gauge_lines

    t = _tuner(FakeScheduler())
    _settle(t)
    text = "\n".join(engine_gauge_lines(t.gauges()))
    for fam in METRIC_FAMILIES:
        assert f"# TYPE {fam} " in text, fam
    assert "crowdllama_engine_autotune" not in text
    assert '# TYPE crowdllama_autotune_moves_total counter' in text
    for dial in DIALS:
        assert f'crowdllama_autotune_dial{{dial="{dial}"}}' in text


def test_scheduler_gauges_zero_filled_without_tuner():
    from crowdllama_tpu.engine.scheduler import Scheduler

    sched = Scheduler.__new__(Scheduler)
    sched.runner = FakeRunner()
    del sched.runner.draft_len  # plain runner: no spec gauge block
    sched.slots = [None, None]
    sched.pending = asyncio.Queue()
    sched._deferred = []
    sched._admitting = 0
    sched._chunking = None
    sched._step_budget_used = 0.0
    sched.host_dispatches = 0
    sched._tokens_per_dispatch = 0.0
    g = sched.telemetry_gauges()
    assert g["autotune_moves_total"] == 0.0
    assert g['autotune_dial|dial=megastep_k'] == 0.0
    sched._autotune = _tuner(FakeScheduler(megastep_k=8))
    assert sched.telemetry_gauges()['autotune_dial|dial=megastep_k'] == 8.0


def test_compile_cache_hit_witness_counts_and_exposes():
    from crowdllama_tpu.obs.metrics import ENGINE_TELEMETRY

    before = ENGINE_TELEMETRY.snapshot_cache_hits().get("_autotune_t", 0)
    compiles = dict(ENGINE_TELEMETRY.snapshot_compiles())
    t0 = ENGINE_TELEMETRY.compile_begin("_autotune_t", 7)
    ENGINE_TELEMETRY.compile_end("_autotune_t", 7, t0)
    assert ENGINE_TELEMETRY.compile_begin("_autotune_t", 7) == 0.0  # hit
    ENGINE_TELEMETRY.compile_begin("_autotune_t", 7)
    hits = ENGINE_TELEMETRY.snapshot_cache_hits()
    assert hits["_autotune_t"] == before + 2
    # Hits claim no new signatures: the compile counter is unmoved.
    after = dict(ENGINE_TELEMETRY.snapshot_compiles())
    key = ("_autotune_t", "7")
    assert after.get(key, 0) == compiles.get(key, 0) + 1
    text = "\n".join(ENGINE_TELEMETRY.expose())
    assert "# TYPE crowdllama_xla_compile_cache_hits_total counter" in text
    assert 'crowdllama_xla_compile_cache_hits_total{program="_autotune_t"}' \
        in text


def test_cluster_rollup_sums_autotune_moves():
    from types import SimpleNamespace

    from crowdllama_tpu.obs.cluster import ClusterScraper

    pm = SimpleNamespace(get_workers=lambda: [])
    sc = ClusterScraper(SimpleNamespace(peer_manager=pm))
    snaps = [("w1", "", "crowdllama_autotune_moves_total 3\n"),
             ("w2", "", "crowdllama_autotune_moves_total 4\n")]
    text = "\n".join(sc._rollup_lines(snaps))
    assert "crowdllama_cluster_autotune_moves_total 7" in text


def test_top_renders_dials_column():
    from crowdllama_tpu.cli.main import render_top

    text = "\n".join([
        'crowdllama_worker_healthy{peer="w1"} 1',
        'crowdllama_autotune_dial{worker="w1",dial="megastep_k"} 8',
        'crowdllama_autotune_dial{worker="w1",dial="draft_k"} 2',
        'crowdllama_autotune_dial{worker="w1",dial="step_token_budget"} 96',
        'crowdllama_autotune_dial{worker="w1",dial="prefill_chunk"} 64',
        'crowdllama_autotune_moves_total{worker="w1"} 5',
        'crowdllama_worker_healthy{peer="w2"} 1',
    ])
    out = render_top(text)
    assert "DIALS" in out
    assert "K8/k2/B96/C64 m5" in out
    w2 = [ln for ln in out.splitlines() if ln.startswith("w2")][0]
    assert w2.rstrip().endswith("-")  # no tuner on w2: placeholder


async def test_gateway_flight_reason_autotune_backoff_edge():
    """Satellite 1: a backoff recorded by any in-process tuner is an
    edge-triggered flight-recorder reason — the first request finished
    after it captures with ``autotune_backoff`` and the stitched trace
    carries the offending dial move; the next request does not."""
    from types import SimpleNamespace

    from crowdllama_tpu.gateway.gateway import Gateway
    from crowdllama_tpu.obs.collector import FlightRecorder
    from crowdllama_tpu.obs.slo import SloEngine

    gw = Gateway.__new__(Gateway)
    gw._flight_min_count = 30
    gw.slo = SloEngine(ttft_ms=0.0, decode_ms=0.0)  # disabled
    gw.obs = SimpleNamespace(trace=SimpleNamespace(get=lambda tid: None))
    gw._autotune_backoffs_seen = BACKOFF_LOG.snapshot()[0]
    hist = SimpleNamespace(count=0, quantile=lambda q: 1e9)

    assert gw._flight_reasons("t0", hist, 0.01, 200) == []
    BACKOFF_LOG.record({"model": "m", "dial": "megastep_k",
                        "frm": 2, "to": 4, "restored": {"megastep_k": 2},
                        "burn": 15.0})
    assert gw._flight_reasons("t1", hist, 0.01, 200) == ["autotune_backoff"]
    assert gw._flight_reasons("t2", hist, 0.01, 200) == []  # edge consumed

    async def collect(tid):
        return {"trace_id": tid, "spans": []}

    gw.flight = FlightRecorder(capacity=4)
    gw.collector = SimpleNamespace(collect=collect)
    gw._flight_inflight = 0
    gw._flight_max_inflight = 4
    gw._flight_capture("t1", ["autotune_backoff"])
    for _ in range(10):
        await asyncio.sleep(0)
    entry = gw.flight.get("t1")
    assert entry is not None
    assert entry["reasons"] == ["autotune_backoff"]
    move = entry["trace"]["autotune_backoff"]
    assert move["dial"] == "megastep_k" and move["to"] == 4


# ------------------------------------------- scheduler-level byte identity


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


@pytest.mark.chaos
async def test_autotune_scheduler_streams_byte_identical():
    """The satellite-3 gate: a fixed workload through (a) an autotune-off
    control and (b) a tuner cadenced to move every other retire window —
    through ≥3 dial moves, ≥1 revert, and a fast-burn backoff forced by
    an injected-latency fault on the ragged-chunk dispatch path — must
    emit byte-identical client streams, and the reverts must land as
    XLA cache hits (no new compile claims: revert is free)."""
    from crowdllama_tpu.engine.paged import PagedModelRunner
    from crowdllama_tpu.engine.scheduler import GenRequest, Scheduler
    from crowdllama_tpu.models import transformer as T
    from crowdllama_tpu.models.config import get_config
    from crowdllama_tpu.obs.metrics import ENGINE_TELEMETRY
    from crowdllama_tpu.testing import faults
    from crowdllama_tpu.testing.faults import FaultPlan, FaultRule

    cfg = get_config("tiny-test", max_context_length=256)
    params = T.init_params(cfg, KEY, dtype=jnp.bfloat16)
    runner = PagedModelRunner(cfg, params=params, max_slots=4,
                              max_seq=256, page_size=32, mesh_spec="1",
                              step_token_budget=96, prefix_cache=False)

    def reqs(long=False):
        out = [GenRequest(prompt_ids=[3, 1, 4, 1, 5], max_tokens=20,
                          seed=7),
               GenRequest(prompt_ids=[2, 7, 1, 8], max_tokens=16, seed=5)]
        if long:
            # Chunk-prefills through the ragged path — the fault site.
            out.append(GenRequest(prompt_ids=list(range(11, 11 + 200)),
                                  max_tokens=8, seed=9))
        return out

    async def run(tuned):
        # Identical constructor point for both runs; the tuner (run b)
        # walks dials from here and the fault plan injects 60 ms into
        # every ragged-chunk dispatch of the long prompt.
        runner.step_token_budget = 96
        runner.prefill_chunk = 64
        runner.ragged_chunk = 64
        sched = Scheduler(runner, decode_chunk=4, ragged=True, megastep_k=2)
        tuner = None
        if tuned:
            # burn windows of ONE: the fused megastep-ragged loop packs a
            # whole chunked prefill into ~one dispatch, so the injected
            # delay surfaces as a single (enormous) breaching window —
            # which must BE the fast-burn edge for the backoff to fire.
            tuner = AutoTuner(sched, model_id="tiny-test", interval=1,
                              bounds={"megastep_k": 4,
                                      "step_token_budget": 160,
                                      "prefill_chunk": 64},
                              decode_ms=30.0, burn_short=1, burn_long=1,
                              min_gain=1e6)  # every trial must revert
            sched.attach_autotuner(tuner)
        # 350 ms per ragged-chunk dispatch: even a megastep window
        # emitting ~8 decode tokens reads ≥ ~40 ms/token against the
        # 30 ms objective, so the chunked-prefill stretch is a clean
        # run of breaching windows — the fast-burn edge.
        plan = FaultPlan(seed=3, rules=[
            FaultRule(site="scheduler.ragged_chunk", action="delay",
                      delay_s=0.35, times=0)])
        sched.start()
        try:
            outs = await _drain_streams(sched, reqs())
            with faults.installed(plan):
                outs += await _drain_streams(sched, reqs(long=True))
            outs += await _drain_streams(sched, reqs())
            return outs, tuner
        finally:
            await sched.stop()

    def sched_k(t):
        return t.sched.megastep_k

    base, _ = await run(tuned=False)
    backoffs0 = BACKOFF_LOG.snapshot()[0]
    hits0 = sum(ENGINE_TELEMETRY.snapshot_cache_hits().values())
    compiles0 = ENGINE_TELEMETRY.snapshot_compiles()
    tuned, tuner = await run(tuned=True)

    assert tuned == base, "autotune run diverged from control streams"
    assert tuner.moves >= 3, tuner.describe()
    assert tuner.reverts >= 1, tuner.describe()
    assert tuner.backoffs >= 1, tuner.describe()
    total, last = BACKOFF_LOG.snapshot()
    assert total >= backoffs0 + 1
    assert last["model"] == "tiny-test"
    # Revert-is-free witness (satellite 2): every signature the control
    # run claimed — including every revert-TO point the tuner returned
    # to — was re-dispatched in the tuned run as a cache HIT, never a
    # fresh compile claim: its per-signature compile count is unmoved.
    hits1 = sum(ENGINE_TELEMETRY.snapshot_cache_hits().values())
    assert hits1 > hits0, "no cache-hit witness — reverts recompiled?"
    compiles1 = ENGINE_TELEMETRY.snapshot_compiles()
    for key, n in compiles0.items():
        assert compiles1[key] == n, f"pre-claimed signature recompiled: {key}"
    # The dials gauge plane reflects the tuner's live point.
    g = tuner.gauges()
    assert g["autotune_moves_total"] == float(tuner.moves)
    assert g['autotune_dial|dial=megastep_k'] == float(sched_k(tuner))
