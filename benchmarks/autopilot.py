"""Autopilot pricing: grid-search-best static dials vs autotune-from-defaults.

Three scenario shapes (docs/AUTOTUNE.md "Pricing the autopilot"), each run
two ways on the CPU reference path (tiny-test):

- ``static``  — offline grid search over the scenario's dial grid, every
  point measured with the autopilot OFF; the best point is what an
  operator with unlimited tuning time would hand-set.
- ``autopilot`` — the same workload starting from the unflagged defaults
  with ``AutoTuner`` attached at an aggressive cadence, steady-state
  throughput measured over the tail waves after the walk settles.

Scenarios:

- ``decode_heavy``  — short prompts, long generations: megastep K is the
  dial that matters (docs/MEGASTEP.md).
- ``mixed_ragged``  — a long chunk-prefilling prompt riding each wave of
  decodes: step_token_budget / prefill_chunk trade against K
  (docs/RAGGED_BATCH.md).
- ``spec_heavy``    — repetitive prompts on the ngram spec runner: the
  draft-cap dial bounds the acceptance-adaptive controller
  (docs/SPECULATIVE.md).

Per scenario the JSON reports grid-best and autopilot steps/sec, their
ratio (the acceptance bar is ~0.9: within 10% of the grid optimum with
zero hand-set flags), moves-to-converge, and the full dial trajectory.

Prints ONE JSON line (bench.py's ``autopilot`` phase parses it) and also
writes the ``benchmarks/results/AUTOTUNE_cpu_<date>.json`` artifact.

Run (repo root, CPU):
    JAX_PLATFORMS=cpu python benchmarks/autopilot.py
"""

import _common  # noqa: E402 - repo path + compile cache bootstrap

import argparse
import asyncio
import datetime
import json
import time
from pathlib import Path

# Measurement shape: every (scenario, dial point) run drives WAVES waves
# of requests through a fresh Scheduler on a SHARED runner (compiled
# programs cache across points — same idiom as tests/test_megastep.py),
# timing only the tail so compile cost and tuner search both amortize out.
STATIC_WAVES = 6          # warmup wave + 5 measured waves per dial point
AUTOPILOT_WAVES = 16      # enough windows for the walk to settle
# Retire windows per measurement phase.  Aggressive next to the
# production default (32) so the walk fits the bench budget, but long
# enough that a phase score averages real signal — at 2 the keep/revert
# decision is wave-jitter, not the dial.
TUNER_INTERVAL = 6


def _set_dials(runner, budget: int, chunk: int) -> None:
    """Pin the runner-side dials, re-deriving the page-aligned ragged
    chunk exactly like engine/paged.py construction does."""
    runner.step_token_budget = budget
    runner.prefill_chunk = chunk
    page = runner.page_size
    c = min(chunk, max(budget - runner.max_slots, page))
    runner.ragged_chunk = max(page, (c // page) * page)


def _waves(scenario: str, vocab: int):
    """One wave of GenRequests; a fresh list per call (queues are
    single-use)."""
    from crowdllama_tpu.engine.scheduler import GenRequest

    if scenario == "decode_heavy":
        return [GenRequest(prompt_ids=[(7 * i + j) % vocab
                                       for j in range(8)],
                           max_tokens=64, seed=i + 1) for i in range(4)]
    if scenario == "mixed_ragged":
        reqs = [GenRequest(prompt_ids=[(5 * i + j) % vocab
                                       for j in range(6)],
                           max_tokens=24, seed=i + 1) for i in range(3)]
        reqs.append(GenRequest(prompt_ids=[(j * 3 + 1) % vocab
                                           for j in range(160)],
                               max_tokens=8, seed=9))
        return reqs
    # spec_heavy: repetitive prompts the bigram proposer can extend.
    return [GenRequest(prompt_ids=[5, 9, 5, 9, 5, 9, 5],
                       max_tokens=48, seed=1),
            GenRequest(prompt_ids=[2, 7, 2, 7, 2, 7],
                       max_tokens=48, seed=2)]


async def _drain(sched, reqs):
    from crowdllama_tpu.engine.scheduler import DONE

    for r in reqs:
        await sched.submit(r)
    total = 0
    for r in reqs:
        while True:
            tok, _ = await asyncio.wait_for(r.out.get(), 120)
            if tok is DONE:
                break
            total += 1
    return total


async def _run(runner, scenario: str, vocab: int, *, sched_kw,
               tuner_kw=None, waves: int, decode_chunk: int = 4):
    """Drive `waves` waves; returns (per-wave tok/s, trajectory, tuner)."""
    from crowdllama_tpu.engine.scheduler import Scheduler

    sched = Scheduler(runner, decode_chunk=decode_chunk, **sched_kw)
    tuner = None
    if tuner_kw is not None:
        from crowdllama_tpu.engine.autotune import AutoTuner

        tuner = AutoTuner(sched, model_id="tiny-test",
                          interval=TUNER_INTERVAL, **tuner_kw)
        sched.attach_autotuner(tuner)
    sched.start()
    traj, rates = [], []
    try:
        for w in range(waves):
            t0 = time.monotonic()
            toks = await _drain(sched, _waves(scenario, vocab))
            rates.append(toks / max(1e-9, time.monotonic() - t0))
            if tuner is not None:
                d = tuner.describe()
                traj.append({"wave": w, "moves": d["moves"],
                             "reverts": d["reverts"],
                             "backoffs": d["backoffs"],
                             "dials": d["dials"],
                             "last_good": dict(tuner._last_good)})
        return rates, traj, tuner
    finally:
        await sched.stop()


async def _measure_point(runner, scenario: str, vocab: int, point: dict,
                         decode_chunk: int = 4) -> float:
    """Measure one static dial point: one warmup wave, then the median
    of the timed waves (host jitter on the CPU reference path is the
    same order as one tiny-model wave; the median ignores the outlier
    waves instead of crowning them)."""
    import statistics

    if "step_token_budget" in point:
        _set_dials(runner, point["step_token_budget"],
                   point.get("prefill_chunk", runner.prefill_chunk))
    elif "prefill_chunk" in point:
        runner.prefill_chunk = point["prefill_chunk"]
    sched_kw = {"megastep_k": point.get("megastep_k", 0)}
    if "draft_k" in point:
        sched_kw["spec_draft_max"] = point["draft_k"]
        runner.set_draft_len(min(point["draft_k"], 4))
    rates, _, _ = await _run(runner, scenario, vocab, sched_kw=sched_kw,
                             tuner_kw=None, waves=STATIC_WAVES,
                             decode_chunk=decode_chunk)
    return statistics.median(rates[1:])


async def _paired(runner, scenario: str, vocab: int, converged: dict,
                  best_point: dict,
                  decode_chunk: int = 4) -> tuple[float, float]:
    """Measure the converged and grid-best points back to back.  When
    the autopilot landed ON the grid-best point the comparison is an
    identity — one measurement serves as both sides, instead of letting
    host jitter report a fake gap between two runs of the same config."""
    tok_s = await _measure_point(runner, scenario, vocab, converged,
                                 decode_chunk)
    if all(converged.get(k) == v for k, v in best_point.items()):
        return tok_s, tok_s
    best_now = await _measure_point(runner, scenario, vocab, best_point,
                                    decode_chunk)
    return tok_s, best_now


def _moves_to_converge(traj) -> int:
    """Moves spent up to the last wave that still improved the
    last-known-good point (later probes keep running — that is the
    autopilot's steady state — but they no longer change the answer)."""
    last_change = 0
    for i in range(1, len(traj)):
        if traj[i]["last_good"] != traj[i - 1]["last_good"]:
            last_change = i
    return traj[last_change]["moves"] if traj else 0


async def _scenario_paged(runner, scenario: str, vocab: int) -> dict:
    """decode_heavy / mixed_ragged: grid over (megastep K, budget, chunk)
    vs the autopilot from the unflagged defaults (K=0, 96, 64).

    Both arms run per-step dispatch (decode_chunk=1, the same control
    arm `make bench-megastep` prices against): the megastep dial then
    amortizes host turnarounds monotonically, which is the axis this
    scenario prices — K riding on a multi-step legacy chunk would bury
    the dial's effect under the chunk's own amortization."""
    if scenario == "decode_heavy":
        grid = [(k, 96, 64) for k in (0, 2, 4, 8)] + [(4, 164, 64)]
    else:
        grid = [(k, b, c) for k in (0, 4) for b in (96, 164)
                for c in (64, 128)]
    static = []
    for k, budget, chunk in grid:
        tok_s = await _measure_point(
            runner, scenario, vocab,
            {"megastep_k": k, "step_token_budget": budget,
             "prefill_chunk": chunk}, decode_chunk=1)
        static.append({"megastep_k": k, "step_token_budget": budget,
                       "prefill_chunk": chunk,
                       "steps_per_sec": round(tok_s, 2)})
    best = max(static, key=lambda p: p["steps_per_sec"])

    _set_dials(runner, 96, 64)  # autopilot starts from the defaults
    _, traj, tuner = await _run(
        runner, scenario, vocab, sched_kw={"megastep_k": 0},
        tuner_kw={"bounds": {"megastep_k": 8, "step_token_budget": 164,
                             "prefill_chunk": 128}},
        waves=AUTOPILOT_WAVES, decode_chunk=1)
    # Steady state = the converged point, measured like the grid points.
    # (With the deliberately aggressive cadence above, probe phases still
    # visit fresh compile signatures during the tail waves — measuring
    # through them would price XLA compiles, not the operating point.)
    # The grid-best point is RE-measured back to back with it: host-load
    # drift over the run would otherwise dominate the ratio.
    converged = dict(tuner._last_good)
    best_point = {k: best[k] for k in ("megastep_k", "step_token_budget",
                                       "prefill_chunk")}
    tok_s, best_now = await _paired(runner, scenario, vocab, converged,
                                    best_point, decode_chunk=1)
    return _report(scenario, static, best, tok_s, best_now, traj, tuner,
                   converged)


async def _scenario_spec(spec, vocab: int) -> dict:
    """spec_heavy: grid over the draft cap vs the autopilot walking it."""
    static = []
    for cap in (1, 2, 4, 8):
        tok_s = await _measure_point(spec, "spec_heavy", vocab,
                                     {"draft_k": cap})
        static.append({"draft_k": cap, "steps_per_sec": round(tok_s, 2)})
    best = max(static, key=lambda p: p["steps_per_sec"])

    spec.set_draft_len(2)
    # Pin the non-scenario dials through their ceiling bounds (single-
    # value grids are skipped by the walk): this scenario prices the
    # draft-cap coordinate against the same space the grid explored.
    spec.prefill_chunk = 64
    _, traj, tuner = await _run(
        spec, "spec_heavy", vocab, sched_kw={"spec_draft_max": 2},
        tuner_kw={"bounds": {"draft_k": 8, "megastep_k": 0,
                             "prefill_chunk": 64}},
        waves=AUTOPILOT_WAVES)
    converged = dict(tuner._last_good)
    tok_s, best_now = await _paired(spec, "spec_heavy", vocab, converged,
                                    {"draft_k": best["draft_k"]})
    return _report("spec_heavy", static, best, tok_s, best_now, traj,
                   tuner, converged)


def _report(scenario, static, best, tok_s, best_now, traj, tuner,
            converged) -> dict:
    d = tuner.describe()
    return {
        "scenario": scenario,
        "grid": static,
        "grid_best": best,
        "grid_best_steps_per_sec_paired": round(best_now, 2),
        "autopilot_point": converged,
        "autopilot_steps_per_sec": round(tok_s, 2),
        "ratio_vs_grid_best": round(tok_s / max(1e-9, best_now), 3),
        "moves_to_converge": _moves_to_converge(traj),
        "moves": d["moves"], "reverts": d["reverts"],
        "backoffs": d["backoffs"],
        "trajectory": traj,
    }


async def _main_async() -> dict:
    import jax
    import jax.numpy as jnp

    from crowdllama_tpu.engine.paged import PagedModelRunner
    from crowdllama_tpu.engine.spec import SpecModelRunner
    from crowdllama_tpu.models import transformer as T
    from crowdllama_tpu.models.config import get_config

    cfg = get_config("tiny-test", max_context_length=256)
    params = T.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    paged = PagedModelRunner(cfg, params=params, max_slots=4, max_seq=256,
                             page_size=32, mesh_spec="1",
                             step_token_budget=96, prefix_cache=False)
    _set_dials(paged, 96, 64)
    scfg = get_config("tiny-test", max_context_length=128)
    sparams = T.init_params(scfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    spec = SpecModelRunner(scfg, params=sparams, max_slots=2, max_seq=128,
                           dtype=jnp.float32, draft_len=2)
    vocab = cfg.vocab_size

    scenarios = [await _scenario_paged(paged, "decode_heavy", vocab),
                 await _scenario_paged(paged, "mixed_ragged", vocab),
                 await _scenario_spec(spec, vocab)]
    return {
        "bench": "autopilot",
        "platform": jax.devices()[0].platform,
        "tuner_interval": TUNER_INTERVAL,
        "scenarios": scenarios,
        "min_ratio_vs_grid_best": min(s["ratio_vs_grid_best"]
                                      for s in scenarios),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    result = asyncio.run(_main_async())
    out = args.out
    if not out:
        date = datetime.date.today().isoformat()
        out = str(Path(__file__).resolve().parent / "results" /
                  f"AUTOTUNE_{result['platform']}_{date}.json")
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    Path(out).write_text(json.dumps(result, indent=1) + "\n")
    _common.emit(result)


if __name__ == "__main__":
    main()
