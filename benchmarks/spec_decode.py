"""Speculative-decode proposer sweep on a GENERATIVE workload.

Sweeps {ngram, random-draft, distilled-draft} x draft_len k on natural-text
prompts the distillation corpus never saw, and reports emitted tokens per
verify step positioned against the bracket the r5 bench measured: 1.12
(random-init draft — speculation priced at ~zero acceptance) and 4.79
(self-draft — every proposal accepts).  The distilled cell is the number
that matters: it is what a real deployment gets from
``crowdllama-tpu distill-draft`` + ``--spec-decode draft``.

The distilled checkpoint comes from ``CROWDLLAMA_TPU_SPEC_DRAFT_PATH``
when set (bench.py's ``decode_spec_draft`` phase sets it when the
operator has one); otherwise the script distills one here, at tiny scale
on CPU, from the repo's own prose (README + ROADMAP) — the eval prompts
below are NOT drawn from those files, so acceptance is held-out.

Prints ONE JSON line like every benchmarks/ script; ``--out`` also writes
it to a file (benchmarks/results/ convention).

Run (repo root, CPU):
    JAX_PLATFORMS=cpu python benchmarks/spec_decode.py
"""

import _common  # noqa: E402 - repo path + compile cache bootstrap

import argparse
import hashlib
import json
import os
import sys
import tempfile
import time
from dataclasses import replace
from pathlib import Path

# Bracket from the r5 bench artifact (BENCH_r05 decode_spec draft cells;
# ROADMAP VERDICT #7): tokens/verify-step of the random-init draft floor
# and the self-draft ceiling on the natural workload.
FLOOR_RANDOM_DRAFT = 1.12
CEILING_SELF_DRAFT = 4.79

# Held-out generative prompts: English prose, byte-tokenized, deliberately
# absent from README/ROADMAP (the default distillation corpus).
_EVAL_PROMPTS = (
    b"The scheduler retires in-flight chunks before dispatching the next "
    b"batch of decode work.",
    b"Acceptance-adaptive speculation tunes the draft length from the "
    b"measured acceptance rate.",
)


def _sha256_dir(path: str) -> str:
    h = hashlib.sha256()
    for f in sorted(Path(path).rglob("*")):
        if f.is_file():
            h.update(f.name.encode())
            h.update(f.read_bytes())
    return h.hexdigest()


def _distill_default(out_dir: str) -> str:
    """Distill a draft from the repo's own prose (held out from the eval
    prompts above) — the zero-setup CPU path."""
    from crowdllama_tpu.train.distill import DistillConfig, distill_draft

    root = Path(__file__).resolve().parent.parent
    corpus = os.path.join(out_dir, "corpus.txt")
    with open(corpus, "wb") as f:
        f.write((root / "README.md").read_bytes())
        f.write((root / "ROADMAP.md").read_bytes())
    ckpt = os.path.join(out_dir, "draft")
    distill_draft(DistillConfig(teacher="tiny-test", corpus_path=corpus,
                                out=ckpt, log_every=0))
    return ckpt


def _measure(runner, prompt_tokens, steps: int) -> dict:
    import jax
    import numpy as np

    state = runner.init_state()
    key = jax.random.PRNGKey(0)
    for slot in range(runner.max_slots):
        key, sub = jax.random.split(key)
        first, ks, vs, plen = runner.prefill(prompt_tokens, 0.0, 1.0, sub,
                                             state=state)
        state = runner.insert(state, slot, ks, vs, plen, first, 0.0, 1.0,
                              prompt_tokens=prompt_tokens)
    chunk = min(8, steps)
    packed, state = runner.decode_steps(state, chunk)  # warmup + compile
    t0 = time.monotonic()
    chunks, done = [], 0
    while done + chunk <= steps:
        packed, state = runner.decode_steps_device(state, chunk)
        chunks.append(packed)
        done += chunk
    rows = [np.asarray(p) for p in chunks]  # sync
    dt = time.monotonic() - t0
    counts = np.concatenate([r[:, 0, :] for r in rows])
    srcs = np.concatenate([r[:, -1, :] for r in rows])
    accepted = np.maximum(counts - 1, 0)
    emitted = int(counts.sum())
    for slot in range(runner.max_slots):
        state = runner.release(state, slot)
    return {
        "emitted_tok_s": round(emitted / dt, 2),
        "verify_steps": done * runner.max_slots,
        "tokens_per_step": round(emitted / max(1, done * runner.max_slots),
                                 3),
        "accepted_prompt_echo": int((accepted * (srcs == 1)).sum()),
        "accepted_generative": int((accepted * (srcs == 2)).sum()),
    }


def run_sweep(model: str = "tiny-test", draft_path: str = "",
              ks=(1, 2, 3, 4), steps: int = 24, slots: int = 2) -> dict:
    """The sweep as a callable (bench.py's decode_spec_draft phase):
    returns the one-line JSON dict instead of printing it."""
    import jax

    from crowdllama_tpu.engine.spec import (
        DraftSpecPagedModelRunner,
        SpecPagedModelRunner,
    )
    from crowdllama_tpu.engine.weights import (
        load_or_init_params,
        native_config_from_dir,
    )
    from crowdllama_tpu.models import transformer as T
    from crowdllama_tpu.models.config import get_config

    ctx = 256
    cfg = get_config(model, max_context_length=ctx)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    platform = jax.devices()[0].platform
    ks = list(ks)

    tmp = None
    if not draft_path:
        tmp = tempfile.TemporaryDirectory(prefix="spec-decode-bench-")
        print("# no draft checkpoint given: distilling one from repo "
              "prose (held out from eval prompts)", file=sys.stderr)
        draft_path = _distill_default(tmp.name)
    draft_sha = _sha256_dir(draft_path)
    draft_cfg = replace(native_config_from_dir(draft_path),
                        max_context_length=ctx)
    draft_params = load_or_init_params(draft_cfg, draft_path)

    prompts = [[t % cfg.vocab_size for t in p] for p in _EVAL_PROMPTS]
    # Budget: each verify step can advance 1+k tokens; keep the longest
    # run inside the context window (warmup chunk included).
    steps = min(steps,
                (ctx - max(len(p) for p in prompts) - 2
                 - 8 * (1 + max(ks))) // (1 + max(ks)))

    def cell(make_runner) -> dict:
        per_prompt = [_measure(make_runner(), p, steps) for p in prompts]
        agg = {
            "tokens_per_step": round(
                sum(r["tokens_per_step"] for r in per_prompt)
                / len(per_prompt), 3),
            "emitted_tok_s": round(
                sum(r["emitted_tok_s"] for r in per_prompt)
                / len(per_prompt), 2),
            "accepted_prompt_echo": sum(r["accepted_prompt_echo"]
                                        for r in per_prompt),
            "accepted_generative": sum(r["accepted_generative"]
                                       for r in per_prompt),
            "verify_steps": sum(r["verify_steps"] for r in per_prompt),
        }
        return agg

    kw = dict(params=params, max_slots=slots, max_seq=ctx)
    sweep: dict[str, dict] = {}
    for k in ks:
        sweep[f"ngram_k{k}"] = cell(lambda: SpecPagedModelRunner(
            cfg, draft_len=k, **kw))
        sweep[f"draft_random_k{k}"] = cell(
            lambda: DraftSpecPagedModelRunner(
                cfg, draft_cfg=replace(
                    cfg, name=cfg.name + "-rand2l",
                    num_layers=min(2, cfg.num_layers)),
                draft_params=None, draft_seed=12345, draft_len=k, **kw))
        sweep[f"draft_distilled_k{k}"] = cell(
            lambda: DraftSpecPagedModelRunner(
                cfg, draft_cfg=draft_cfg, draft_params=draft_params,
                draft_len=k, **kw))

    best_k, best = max(
        ((k, sweep[f"draft_distilled_k{k}"]) for k in ks),
        key=lambda kv: kv[1]["tokens_per_step"])
    ngram_best = max(sweep[f"ngram_k{k}"]["tokens_per_step"] for k in ks)
    line = {
        "metric": f"{cfg.name} distilled-draft speculation, emitted tokens "
                  f"per verify step (generative workload, best k)",
        "value": best["tokens_per_step"],
        "unit": "tokens/verify-step",
        "vs_baseline": None,
        "extra": {
            "platform": platform,
            "best_k": best_k,
            "floor_random_draft": FLOOR_RANDOM_DRAFT,
            "ceiling_self_draft": CEILING_SELF_DRAFT,
            "position_in_bracket": round(
                (best["tokens_per_step"] - FLOOR_RANDOM_DRAFT)
                / (CEILING_SELF_DRAFT - FLOOR_RANDOM_DRAFT), 3),
            "ngram_best_tokens_per_step": ngram_best,
            "draft_checkpoint": draft_path,
            "draft_checkpoint_sha256": draft_sha,
            "timed_steps_per_cell": steps,
            "slots": slots,
            "workload": "generative (held-out natural text; no prompt "
                        "echo by construction)",
            "sweep": sweep,
        },
    }
    if tmp is not None:
        tmp.cleanup()
    return line


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default="tiny-test")
    ap.add_argument("--draft-path",
                    default=os.environ.get("CROWDLLAMA_TPU_SPEC_DRAFT_PATH",
                                           ""))
    ap.add_argument("--ks", default="1,2,3,4",
                    help="comma-separated draft lengths to sweep")
    ap.add_argument("--steps", type=int, default=24,
                    help="timed verify steps per cell")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--out", default="", help="also write the JSON here")
    args = ap.parse_args()
    line = run_sweep(model=args.model, draft_path=args.draft_path,
                     ks=[int(k) for k in args.ks.split(",") if k],
                     steps=args.steps, slots=args.slots)
    line.setdefault("device", _common.device_info())
    out = json.dumps(line)
    print(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(out + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
