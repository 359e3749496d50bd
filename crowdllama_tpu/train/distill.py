"""Draft-model distillation for speculative decoding (ISSUE 4 tentpole).

Trains a small (default 2-layer) draft transformer to mimic the MAIN
model's next-token distribution, so ``spec_decode=draft`` proposes tokens
the verifier actually accepts: a random-init draft is accepted almost
never, a self-draft always; this loop is what moves a deployment off the
floor.

Pure JAX, no training framework: the corpus is synthetic sequences
SAMPLED FROM THE TEACHER ITSELF (plus an optional text file), the loss is
a temperature-scaled KL to the teacher's logits mixed with CE to the
teacher's argmax — argmax agreement IS the speculative acceptance
objective (the verifier accepts a draft token iff it equals the main
model's greedy pick) — and the optimizer is hand-rolled Adam under a
warmup+cosine schedule, all inside one jitted train step.  Runs on CPU
at tier-1 test scale (tiny-test: 30 steps in seconds) and on TPU
unchanged for real drafts.

Checkpoints go through engine/weights.py's NATIVE format (config.json
with the architecture + model.safetensors in the engine's own pytree
layout), so ``--spec-decode draft --spec-draft-path <out>`` loads the
result end-to-end with no registry entry.

CLI: ``crowdllama-tpu distill-draft`` (cli/main.py).
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field, replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from crowdllama_tpu.models import transformer as T
from crowdllama_tpu.models.config import ModelConfig

log = logging.getLogger("crowdllama.train.distill")


@dataclass
class DistillConfig:
    teacher: str = "tiny-test"   # registry name of the main model
    teacher_path: str = ""       # its checkpoint ("" = random init, the
    #                              tier-1 teacher: seed-0 init is
    #                              exactly what the test engine serves)
    draft_layers: int = 2
    steps: int = 1200
    batch: int = 16
    seq_len: int = 64
    corpus_seqs: int = 256       # teacher-rollout sequences to synthesize
    corpus_path: str = ""        # optional text file: its token windows
    #                              seed 3/4 of the rollout prefixes (the
    #                              prompt distribution) and its raw chunks
    #                              join the corpus
    max_prefix: int = 32         # longest rollout prefix (see rollout_corpus)
    sample_temperature: float = 0.0  # rollout sampling temp, 0 = greedy.
    #                              Greedy is the right default: the
    #                              verifier accepts drafts ALONG GREEDY
    #                              trajectories, and measured held-out
    #                              agreement on greedy rollouts doubles
    #                              when the corpus is greedy rollouts
    #                              (diverse random starts supply coverage)
    #                              vs temperature-sampled ones
    # Initialize embed/lm_head/final_norm FROM the teacher (copied, then
    # fine-tuned): sharing the logit geometry is worth ~+0.1 held-out
    # greedy agreement at tiny scale and is standard draft practice.
    tie_embeddings: bool = True
    lr: float = 3e-3
    warmup_frac: float = 0.1
    kl_weight: float = 0.5       # loss = w*KL + (1-w)*CE(teacher argmax)
    kl_temperature: float = 2.0
    seed: int = 0
    out: str = ""                # checkpoint dir ("" = don't save)
    log_every: int = 50
    extra_meta: dict = field(default_factory=dict)


# --------------------------------------------------------------- corpus


def rollout_corpus(cfg: ModelConfig, params, key, num_seqs: int,
                   seq_len: int, temperature: float,
                   prefix_pool: np.ndarray | None = None,
                   max_prefix: int = 32) -> np.ndarray:
    """Sample ``num_seqs`` sequences of ``seq_len`` tokens: a random-length
    PREFIX followed by the teacher's own continuation (greedy at
    ``temperature`` 0, else sampled).

    The prefix matters as much as the continuation: speculative acceptance
    is measured on states of the form "arbitrary user prompt + the main
    model's greedy continuation", so the corpus must visit that state
    family.  ``prefix_pool`` (a 1-D token array, e.g. tokenized text)
    draws prefixes from the deployment's prompt distribution; ``None``
    falls back to uniform-random prefixes.  Single-token starts are NOT
    enough — a student trained on them never sees long-foreign-prefix
    states and its measured text-prompt acceptance collapses to ~0."""
    b = num_seqs
    s = seq_len
    max_prefix = max(2, min(max_prefix, seq_len))
    dh = cfg.resolved_head_dim()
    k_pref, k_len, k_samp = jax.random.split(key, 3)
    if prefix_pool is not None and len(prefix_pool) > max_prefix:
        starts = np.asarray(jax.random.randint(
            k_pref, (b,), 0, len(prefix_pool) - max_prefix))
        prefix = jnp.asarray(
            np.stack([np.asarray(prefix_pool[st:st + max_prefix])
                      for st in starts]), jnp.int32)
    else:
        prefix = jax.random.randint(k_pref, (b, max_prefix), 0,
                                    cfg.vocab_size, jnp.int32)
    plens = jax.random.randint(k_len, (b,), min(4, max_prefix),
                               max_prefix + 1)
    kc = jnp.zeros((cfg.num_layers, b, cfg.num_kv_heads, s, dh),
                   jnp.float32)
    vc = jnp.zeros_like(kc)

    def step(carry, i):
        tok, kc, vc, key = carry
        pos = jnp.full((b,), 0, jnp.int32) + i
        logits, kc, vc = T.decode_step(params, cfg, tok, pos, kc, vc,
                                       pos + 1)
        if temperature > 0:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(
                sub, logits.astype(jnp.float32) / temperature, axis=-1
            ).astype(jnp.int32)
        else:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # While inside the prefix, the "continuation" is the prefix itself.
        inside = (i + 1) < plens
        nxt = jnp.where(inside,
                        prefix[:, jnp.minimum(i + 1, max_prefix - 1)], nxt)
        return (nxt, kc, vc, key), tok

    init = (prefix[:, 0], kc, vc, k_samp)
    _, toks = jax.lax.scan(step, init, jnp.arange(s))  # [S, B]
    return np.asarray(toks.T)  # [B, S]


def corpus_from_text(path: str, vocab_size: int, seq_len: int) -> np.ndarray:
    """Byte-level tokenization of a text file (bytes mod vocab), chunked
    into [N, S]."""
    data = np.frombuffer(open(path, "rb").read(), np.uint8).astype(np.int32)
    data = data % vocab_size
    n = len(data) // seq_len
    if n == 0:
        raise ValueError(f"{path}: too short for even one {seq_len}-token "
                         "sequence")
    return data[: n * seq_len].reshape(n, seq_len)


# ----------------------------------------------------------------- loss


def distill_loss(draft_params, draft_cfg: ModelConfig, teacher_logits,
                 tokens, kl_weight: float, kl_temperature: float):
    """KL(teacher‖student, temperature τ, scaled τ²) mixed with CE to the
    teacher's argmax.  Positions 0..T-2 predict tokens 1..T-1 (causal
    next-token).  The CE term targets EXACTLY what the verifier checks
    (greedy agreement); the KL term keeps the full distribution close so
    agreement generalizes off the corpus."""
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    logits, _, _ = T.prefill(draft_params, draft_cfg, tokens, positions)
    s = logits[:, :-1].astype(jnp.float32)          # student [B, T-1, V]
    th = teacher_logits[:, :-1].astype(jnp.float32)  # teacher [B, T-1, V]

    tau = kl_temperature
    p = jax.nn.softmax(th / tau, axis=-1)
    logq = jax.nn.log_softmax(s / tau, axis=-1)
    logp = jax.nn.log_softmax(th / tau, axis=-1)
    kl = jnp.sum(p * (logp - logq), axis=-1) * (tau * tau)  # [B, T-1]

    hard = jnp.argmax(th, axis=-1)                           # [B, T-1]
    ce = -jnp.take_along_axis(jax.nn.log_softmax(s, axis=-1),
                              hard[..., None], axis=-1)[..., 0]

    loss = kl_weight * jnp.mean(kl) + (1.0 - kl_weight) * jnp.mean(ce)
    agree = jnp.mean(jnp.argmax(s, axis=-1) == hard)
    return loss, (jnp.mean(kl), jnp.mean(ce), agree)


# ------------------------------------------------------------ optimizer
# Hand-rolled Adam + warmup/cosine — the whole dependency surface of this
# trainer is jax itself (the serving image carries no optimizer library
# on every target).


def _adam_init(params):
    z = lambda p: jax.tree_util.tree_map(
        lambda x: jnp.zeros_like(x, jnp.float32), p)
    return {"m": z(params), "v": z(params), "t": jnp.zeros((), jnp.int32)}


def _adam_update(grads, opt, params, lr, b1=0.9, b2=0.999, eps=1e-8):
    t = opt["t"] + 1
    m = jax.tree_util.tree_map(
        lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32),
        opt["m"], grads)
    v = jax.tree_util.tree_map(
        lambda v, g: b2 * v + (1 - b2) * jnp.square(g.astype(jnp.float32)),
        opt["v"], grads)
    tf = t.astype(jnp.float32)
    scale = lr * jnp.sqrt(1 - b2 ** tf) / (1 - b1 ** tf)
    params = jax.tree_util.tree_map(
        lambda p, m, v: (p.astype(jnp.float32)
                         - scale * m / (jnp.sqrt(v) + eps)).astype(p.dtype),
        params, m, v)
    return params, {"m": m, "v": v, "t": t}


def _lr_at(step, total: int, base: float, warmup_frac: float):
    warm = jnp.maximum(1.0, warmup_frac * total)
    s = step.astype(jnp.float32)
    ramp = jnp.minimum(s / warm, 1.0)
    prog = jnp.clip((s - warm) / jnp.maximum(1.0, total - warm), 0.0, 1.0)
    return base * ramp * 0.5 * (1.0 + jnp.cos(jnp.pi * prog))


# ------------------------------------------------------------ the loop


@partial(jax.jit, static_argnums=(2, 5, 6, 7, 8), donate_argnums=(0, 1))
def _train_step(draft_params, opt, draft_cfg, teacher_logits, tokens,
                steps: int, lr: float, warmup_frac: float,
                kl_weight: float, kl_temperature: float = 2.0):
    (loss, aux), grads = jax.value_and_grad(
        distill_loss, has_aux=True)(draft_params, draft_cfg,
                                    teacher_logits, tokens,
                                    kl_weight, kl_temperature)
    lr_t = _lr_at(opt["t"], steps, lr, warmup_frac)
    draft_params, opt = _adam_update(grads, opt, draft_params, lr_t)
    return draft_params, opt, loss, aux


@partial(jax.jit, static_argnums=(1,))
def _teacher_logits(teacher_params, teacher_cfg: ModelConfig, tokens):
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    logits, _, _ = T.prefill(teacher_params, teacher_cfg, tokens, positions)
    return logits.astype(jnp.float32)


def draft_config_for(teacher_cfg: ModelConfig, draft_layers: int,
                     max_context_length: int = 0) -> ModelConfig:
    """The distilled draft's architecture: the teacher's shape truncated
    to ``draft_layers`` layers (same vocab by construction — verification
    compares token ids)."""
    return replace(
        teacher_cfg,
        name=f"{teacher_cfg.name}-draft{draft_layers}l",
        num_layers=draft_layers,
        max_context_length=(max_context_length
                            or teacher_cfg.max_context_length))


def distill_draft(dc: DistillConfig, teacher_cfg: ModelConfig | None = None,
                  teacher_params=None) -> dict:
    """Run the distillation; returns ``{"losses", "agreement",
    "draft_config", "draft_params", "checkpoint"}``.  ``teacher_cfg`` /
    ``teacher_params`` override the registry/checkpoint resolution (tests
    pass the exact params their engine serves)."""
    from crowdllama_tpu.engine.weights import (
        load_or_init_params,
        resolve_model_config,
        save_params,
    )

    if teacher_cfg is None:
        teacher_cfg = resolve_model_config(dc.teacher, dc.teacher_path)
    if teacher_params is None:
        # float32 teacher: sharper logit targets than the serving bf16
        # cast, same argmax nearly everywhere.
        teacher_params = load_or_init_params(teacher_cfg, dc.teacher_path,
                                             dtype=jnp.float32)
    draft_cfg = draft_config_for(teacher_cfg, dc.draft_layers)

    key = jax.random.PRNGKey(dc.seed)
    key, k_text, k_rand, k_init = jax.random.split(key, 4)
    t0 = time.monotonic()
    parts = []
    if dc.corpus_path:
        # Text-seeded rollouts dominate (3:1): acceptance is measured on
        # "text prompt + greedy continuation" trajectories, and prefixes
        # drawn from the actual prompt distribution are what make held-out
        # text-trajectory agreement land ~0.5 instead of ~0.1 (uniform
        # prefixes) or ~0 (single-token starts).
        pool = np.frombuffer(open(dc.corpus_path, "rb").read(),
                             np.uint8).astype(np.int32) % teacher_cfg.vocab_size
        n_text = (dc.corpus_seqs * 3) // 4
        parts.append(rollout_corpus(
            teacher_cfg, teacher_params, k_text, n_text, dc.seq_len,
            dc.sample_temperature, prefix_pool=pool,
            max_prefix=dc.max_prefix))
        parts.append(rollout_corpus(
            teacher_cfg, teacher_params, k_rand,
            dc.corpus_seqs - n_text, dc.seq_len, dc.sample_temperature,
            max_prefix=dc.max_prefix))
        parts.append(corpus_from_text(dc.corpus_path,
                                      teacher_cfg.vocab_size, dc.seq_len))
    else:
        parts.append(rollout_corpus(
            teacher_cfg, teacher_params, k_rand, dc.corpus_seqs,
            dc.seq_len, dc.sample_temperature, max_prefix=dc.max_prefix))
    corpus = np.concatenate(parts, axis=0)
    log.info("corpus: %d sequences of %d tokens (%.1fs)",
             corpus.shape[0], corpus.shape[1], time.monotonic() - t0)

    draft_params = T.init_params(draft_cfg, k_init, dtype=jnp.float32)
    if dc.tie_embeddings:
        for k in ("embed", "lm_head", "final_norm"):
            if k in draft_params and k in teacher_params:
                # jnp.array COPIES: the train step donates student
                # buffers, and donating an aliased teacher buffer would
                # delete the teacher mid-run.
                draft_params[k] = jnp.array(
                    teacher_params[k], jnp.float32)
    opt = _adam_init(draft_params)
    rng = np.random.default_rng(dc.seed)

    losses: list[float] = []
    agreement = 0.0
    t0 = time.monotonic()
    for step in range(dc.steps):
        rows = rng.choice(corpus.shape[0], size=dc.batch,
                          replace=corpus.shape[0] < dc.batch)
        tokens = jnp.asarray(corpus[rows])
        tl = _teacher_logits(teacher_params, teacher_cfg, tokens)
        draft_params, opt, loss, (kl, ce, agree) = _train_step(
            draft_params, opt, draft_cfg, tl, tokens,
            dc.steps, dc.lr, dc.warmup_frac, dc.kl_weight,
            dc.kl_temperature)
        losses.append(float(loss))
        agreement = float(agree)
        if dc.log_every and (step % dc.log_every == 0
                             or step == dc.steps - 1):
            log.info("step %4d  loss %.4f  kl %.4f  ce %.4f  agree %.3f",
                     step, float(loss), float(kl), float(ce), agreement)
    log.info("distilled %d steps in %.1fs (final loss %.4f, greedy "
             "agreement %.3f)", dc.steps, time.monotonic() - t0,
             losses[-1], agreement)

    checkpoint = ""
    if dc.out:
        meta = {
            "teacher": teacher_cfg.name,
            "teacher_path": dc.teacher_path,
            "steps": dc.steps,
            "lr": dc.lr,
            "kl_weight": dc.kl_weight,
            "kl_temperature": dc.kl_temperature,
            "seq_len": dc.seq_len,
            "final_loss": losses[-1],
            "greedy_agreement": agreement,
            **dc.extra_meta,
        }
        checkpoint = str(save_params(draft_cfg, draft_params, dc.out,
                                     meta=meta))
        log.info("checkpoint: %s", checkpoint)
    return {
        "losses": losses,
        "agreement": agreement,
        "draft_config": draft_cfg,
        "draft_params": draft_params,
        "checkpoint": checkpoint,
    }
