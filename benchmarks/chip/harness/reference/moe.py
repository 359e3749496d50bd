"""Plain reference of the Mixtral-8x7B decoder (Jiang et al. 2024,
arXiv:2401.04088): the Mistral block of dense.py with the feed-forward
replaced by a sparse mixture of experts — a linear router over the experts,
the top two taken per token, their router logits softmaxed (which equals
softmax over all experts, top-2, renormalised), and the token's output the
weighted sum of those two experts' SwiGLU outputs.  No sliding window.

Every expert is computed for every token and masked by its router weight:
straightforward, and four times the system's work, which is why the check
runs on a sample.  One expert is dequantized to float32 at a time, so the
largest temporary is one expert (0.7 GB at Mixtral widths), not a layer's
bank of eight (5.6 GB).  Tolerance: see dense.py and tolerance.json.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import dense

F32 = jnp.float32


def router_weights(x, router, top_k: int):
    """[T, E] weights: softmax over each token's top-k router logits, zero
    for the experts it does not use."""
    logits = x @ router
    top, idx = jax.lax.top_k(logits, top_k)
    w = jax.nn.softmax(top, -1)
    return jnp.sum(jax.nn.one_hot(idx, logits.shape[-1], dtype=F32)
                   * w[..., None], axis=-2)


@jax.jit
def _expert(x, gate, up, down, weight):
    return weight[:, None] * dense.swiglu(
        x, dense.dequant(gate), dense.dequant(up), dense.dequant(down))


def mixture(x, w, hp):
    weights = router_weights(x, dense.dequant(w["router"]), hp["top_k"])
    take = lambda bank, e: jax.tree_util.tree_map(lambda a: a[e], bank)
    out = jnp.zeros_like(x)
    for e in range(hp["experts"]):
        out = out + _expert(x, take(w["w_gate"], e), take(w["w_up"], e),
                            take(w["w_down"], e), weights[:, e])
    return out


def forward(weights: dict, hf: dict, ids, positions):
    hp = dense.hyper(hf)
    attn = jax.jit(lambda x, w: x + dense.attention(
        dense.rms_norm(x, dense.dequant(w["ln1"]), hp["eps"]), w, hp))
    norm2 = jax.jit(lambda x, g: dense.rms_norm(x, dense.dequant(g),
                                                hp["eps"]))

    def layer_fn(x, w):
        x = attn(x, {k: w[k] for k in ("ln1", "wq", "wk", "wv", "wo")})
        return x + mixture(norm2(x, w["ln2"]), w, hp)

    return dense.forward(weights, hf, ids, positions, layer_fn=layer_fn)
