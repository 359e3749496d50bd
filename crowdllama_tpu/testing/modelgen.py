"""Synthetic test-scale checkpoints with engineered decode behaviour.

Random-init tiny models have ulp-scale logit gaps, so greedy argmax flips
between numerically distinct-but-equivalent paths (prefill vs decode_step
vs the paged verify program) — any harness asserting byte-identity or
acceptance rates across paths turns into a numeric lottery.  The
generators here build weights whose margins are O(1) by construction, so
path-stable greedy decode is a property of the checkpoint, not luck.

Used by the speculative-pipeline chaos tests; jax is imported lazily so the module stays importable from
accelerator-free test collection.
"""

from __future__ import annotations


def permutation_params(mcfg) -> dict:
    """Test-scale weights implementing a confident next-token permutation.

    Attention and MLP block outputs are zeroed (wo = w_down = 0), so the
    residual stream is exactly the input token's embedding; the
    unembedding column for pi(t) is the unit embedding of t, making
    greedy decode walk a fixed permutation cycle over the non-special
    vocabulary with O(1) logit margins — immune to cross-path argmax
    flips, never emitting EOS.  pi is verified dominant before returning.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from crowdllama_tpu.engine.tokenizer import get_tokenizer
    from crowdllama_tpu.models import transformer as T

    params = T.init_params(mcfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    dim, vocab = mcfg.hidden_size, mcfg.vocab_size
    rng = np.random.default_rng(0)
    emb = rng.standard_normal((vocab, dim)).astype(np.float32)
    emb /= np.linalg.norm(emb, axis=1, keepdims=True)

    tok = get_tokenizer("")
    specials = sorted({tok.pad_id, tok.bos_id, tok.eos_id} - {-1})
    allowed = [t for t in range(vocab) if t not in specials]
    nxt = {t: allowed[(i + 1) % len(allowed)]
           for i, t in enumerate(allowed)}
    # Specials stay unmapped: BOS/PAD rows never drive an emitted
    # prediction (prompts end in a regular byte), and single-contributor
    # unembedding columns keep every margin wide.
    lm = np.zeros((dim, vocab), np.float32)
    for t in allowed:
        lm[:, nxt[t]] += emb[t]
    # Margin check: RMSNorm(emb[t]) @ lm must argmax at pi(t) for every
    # token that can appear in a generated sequence.
    h = emb * np.sqrt(dim)  # rows are unit vectors -> rms = 1/sqrt(dim)
    logits = h[allowed] @ lm
    assert (logits.argmax(axis=1) == np.array(
        [nxt[t] for t in allowed])).all(), "permutation not dominant"

    params["embed"] = jnp.asarray(emb)
    params["lm_head"] = jnp.asarray(lm)
    params["final_norm"] = jnp.ones((dim,), jnp.float32)
    params["layers"]["wo"] = jnp.zeros_like(params["layers"]["wo"])
    params["layers"]["w_down"] = jnp.zeros_like(params["layers"]["w_down"])
    return params


def permutation_checkpoint(model: str, out_dir, max_context: int = 256):
    """Write a native checkpoint of :func:`permutation_params` for
    ``model`` into ``out_dir`` and return its path as a string."""
    from crowdllama_tpu.engine.weights import save_params
    from crowdllama_tpu.models.config import get_config

    mcfg = get_config(model, max_context_length=max_context)
    save_params(mcfg, permutation_params(mcfg), out_dir,
                {"note": "permutation test model (testing/modelgen.py)"})
    return str(out_dir)
