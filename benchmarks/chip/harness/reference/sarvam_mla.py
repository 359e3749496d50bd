"""Plain reference of the Sarvam decoder (``model_type: sarvam_mla``;
https://huggingface.co/sarvamai/sarvam-105b ``config.json`` and its
``described_as``: DeepSeek-V2/V3 latent attention WITHOUT a query low-rank
stage, a decoupled rotary part under a ``deepseek_yarn`` scaling, in every
layer; one leading dense SwiGLU layer, then a 128-expert top-8
sigmoid-routed SwiGLU mixture of experts with a selection bias and one
shared expert).

THE LAYERS.  Residual stream ``x`` of width d; layer ``l`` (0-indexed),
token ``t`` at position ``p_t``, H heads:

    h = RMSNorm(x)
    q = W_q h                  H heads of [q_n (qk_nope) ; q_r (qk_rope)]
    [c' ; k_r] = W_kva h       c' kv_lora_rank wide, k_r ONE vector for all heads
    c = RMSNorm_kv(c')         (``use_qk_norm``: the norm of the compressed stream)
    [k_n ; v] = W_kvb c        a head: qk_nope and v_head_dim wide
    q_r, k_r rotated at p_t over INTERLEAVED pairs (2i, 2i+1), angle p_t f_i
    scores = (q_n . k_n + q_r . k_r) * scale,  causal softmax in float32
    x = x + W_o [softmax . v]_heads
    m = RMSNorm(x)
    f = SwiGLU(m)                                  l < first_k_dense_replace
    f = SwiGLU_shared(m) + sum_{e in top k} w_e SwiGLU_e(m)        otherwise
    x = x + f

YaRN (``rope_scaling.type: deepseek_yarn``; D = qk_rope, theta, factor s,
original length L): ``f_i = (1 - m_i) theta^(-2i/D) / s + m_i
theta^(-2i/D)`` with ``m_i = 1 - clip((i - low) / (high - low), 0, 1)``,
``low = floor(d(beta_fast))``, ``high = ceil(d(beta_slow))``, ``d(r) = D
ln(L / (2 pi r)) / (2 ln theta)`` — for the published keys low 10, high 23.
cos and sin are multiplied by ``ymscale(s, mscale) / ymscale(s,
mscale_all_dim)``, ``ymscale(s, m) = 0.1 m ln s + 1``, and ``scale =
(qk_nope + qk_rope)^-1/2 ymscale(s, mscale_all_dim)^2`` — 0.135234 for the
published keys.

The router: ``s = sigmoid(m W_r)`` in float32 over ALL experts; the top k
of ``s + b`` (``b``: the selection bias, ``moe_router_enable_expert_bias``;
no ``n_group`` key: a plain top-k); ``w_e = routed_scaling_factor * s_e /
(sum_chosen s + 1e-20)``.  After the last layer RMSNorm, then the untied
head.  Written UNABSORBED: K and V are expanded a head (the program folds
``W_kvb`` into the query and the output and attends over the cached row
``[c ; k_r]``), so that program and reference are two algebraic forms of
one equation.

LAYOUT OF THE WEIGHTS (the program's: ``models/hybrid.py`` ``_shapes``):
``w_in = [W_q | W_kva]``, ``w_kvb`` a head ``[k_nope | v]``, SwiGLU ``w_gu
= [W_gate | W_up]``, the expert banks with the held experts leading.  The
program rotates two HALVES of the rotary part against each other
(``ops/rope.py`` ``rotate_half``), so its rotary columns lie ``[evens |
odds]`` of the published order (``models/convert.py``
``rotary_halves_from_interleaved`` is the permutation a checkpoint's
converter applies); :func:`interleaved` puts them back, and the rotation
here is the published one, over pairs ``(2i, 2i+1)``.

DEPARTURES, each on purpose:

* THE SHARE.  ``num_experts`` counts the experts held HERE, the contiguous
  block ``expert_parallel_rank`` of ``num_experts_published`` (absent: all
  are held).  The router keeps its published width, its k and its weights;
  every held expert is computed for every token and masked by its weight;
  what the absent experts would have added is left out — here as in the
  program — and the shared expert is computed whole.  ``vocab_size`` is
  this chip's rows: a smaller vocabulary.
* ``use_qk_norm`` is read as the family's norm of the compressed kv stream
  (``kv_norm``), not a per-head norm of the decompressed q and k
  (``bench.assumed`` has why).
* Attention is computed a BLOCK of queries at a time (``lax.map``), each
  block against all keys: 5,120 tokens by 64 heads by 5,120 keys in float32
  would not fit the chip at once.  The sums are the same.

float32 throughout, ``jax.default_matmul_precision("highest")`` set by the
caller, one sequence at a time, no cache, no batching, no sorted dispatch,
one expert dequantized at a time.  TOLERANCE: see dense.py (the emitted
token's deficit) and ``sarvam_mla.tolerance.json``; the CPU tests compare
logits (tests/test_sarvam_mla.py).

``controls`` names deliberate faults (the tests and the limits file read
how far each moves the result): ``no_k_rope`` (``k_r`` cached as
projected, the queries rotated), ``no_rope`` (nothing rotates),
``plain_frequencies`` (no YaRN blend: ``theta^(-2i/D)`` throughout),
``no_mscale`` (the scale without ``ymscale^2``), ``halves`` (the rotary
part rotated as two halves of the PUBLISHED order), ``no_kv_norm``,
``no_correction_bias``, ``no_scaling``; and two of PRECISION, each the
nearest below what the configuration states: ``bf16_router`` (the router's
input, product and scores in bf16 where they are stated float32) and
``int4_weights`` (every int8 matrix rounded on to 4 bits with one scale a
group of 128 inputs, the next step down this program's own ladder
``--quantize int8 | int4``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .afmoe import routed_part, swiglu, weight
from .dense import dequant, rms_norm

F32 = jnp.float32
STACK = {"R": "mla", "D": "mlp", "S": "smoe"}
#: queries a block of the attention
QUERY_BLOCK = 256


def ymscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def hyper(hf: dict) -> dict:
    """The sizes the equations need, from the ``config.json``."""
    held = hf["num_experts"]
    dense = hf.get("first_k_dense_replace", 0)
    return {
        "pattern": "".join("R" + ("D" if l < dense else "S")
                           for l in range(hf["num_hidden_layers"])),
        "eps": float(hf.get("rms_norm_eps", 1e-6)),
        "heads": hf["num_attention_heads"],
        "rank": hf["kv_lora_rank"], "nope": hf["qk_nope_head_dim"],
        "rope": hf["qk_rope_head_dim"], "v_dim": hf["v_head_dim"],
        "theta": float(hf.get("rope_theta", 10000.0)),
        "yarn": hf.get("rope_scaling") or None,
        "experts": hf.get("num_experts_published", held),
        "held": held, "first": hf.get("expert_parallel_rank", 0) * held,
        "top_k": hf["num_experts_per_tok"],
        "scaling": float(hf.get("routed_scaling_factor", 1.0)),
        "norm_topk": True,
    }


def correction_range(hp) -> tuple[int, int]:
    """(low, high) of the published keys' YaRN blend."""
    y, dim = hp["yarn"], hp["rope"]

    def d(rotations):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (2.0 * math.pi * rotations))
                / (2.0 * math.log(hp["theta"])))

    return (max(math.floor(d(y.get("beta_fast", 32))), 0),
            min(math.ceil(d(y.get("beta_slow", 1))), dim - 1))


def frequencies(hp, controls=()):
    """``f_i [rope/2]`` and what cos and sin are multiplied by."""
    dim, y = hp["rope"], hp["yarn"]
    plain = hp["theta"] ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
    if y is None or "plain_frequencies" in controls:
        return plain, 1.0
    low, high = correction_range(hp)
    keep = 1.0 - jnp.clip((jnp.arange(dim // 2, dtype=F32) - low)
                          / max(high - low, 1e-3), 0.0, 1.0)
    magnitude = (ymscale(y["factor"], y.get("mscale", 1.0))
                 / ymscale(y["factor"], y.get("mscale_all_dim", 0.0)))
    return (1.0 - keep) * plain / y["factor"] + keep * plain, magnitude


def score_scale(hp, controls=()) -> float:
    scale = (hp["nope"] + hp["rope"]) ** -0.5
    y = hp["yarn"]
    if y is None or "no_mscale" in controls:
        return scale
    return scale * ymscale(y["factor"], y.get("mscale_all_dim", 0.0)) ** 2


def interleaved(x):
    """The rotary part ``[..., rope]`` in the published order, from the
    program's ``[evens | odds]``."""
    half = x.shape[-1] // 2
    return jnp.stack([x[..., :half], x[..., half:]], -1).reshape(x.shape)


def rotate_pairs(x, positions, hp, controls=()):
    """``x [T, ..., rope]`` (published order) rotated at ``positions [T]``
    over the pairs ``(2i, 2i+1)``."""
    freqs, magnitude = frequencies(hp, controls)
    ang = positions.astype(F32)[:, None] * freqs[None, :]        # [T, rope/2]
    ang = ang.reshape(ang.shape[0], *(1,) * (x.ndim - 2), -1)
    cos, sin = jnp.cos(ang) * magnitude, jnp.sin(ang) * magnitude
    if "halves" in controls:
        a, b = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def mla(h, w, hp, controls=()):
    """Latent attention over one sequence ``h [T, d]`` at positions
    0..T-1, unabsorbed, the decoupled part rotated."""
    t = h.shape[0]
    nh, r, dn, dr = hp["heads"], hp["rank"], hp["nope"], hp["rope"]
    zin = h @ weight(w["w_in"], controls)
    q = zin[:, :nh * (dn + dr)].reshape(t, nh, dn + dr)
    c = zin[:, nh * (dn + dr):nh * (dn + dr) + r]
    if "no_kv_norm" not in controls:
        c = rms_norm(c, dequant(w["kv_norm"]), hp["eps"])
    q_n, q_r, k_r = q[..., :dn], interleaved(q[..., dn:]), interleaved(
        zin[:, -dr:])
    pos = jnp.arange(t)
    if "no_rope" not in controls:
        q_r = rotate_pairs(q_r, pos, hp, controls)
        if "no_k_rope" not in controls:
            k_r = rotate_pairs(k_r, pos, hp, controls)
    kv = (c @ weight(w["w_kvb"], controls)).reshape(t, nh, dn + hp["v_dim"])
    k_n, v = kv[..., :dn], kv[..., dn:]
    scale = score_scale(hp, controls)
    qb = min(QUERY_BLOCK, t)
    blocks = -(-t // qb)

    def padded(a):
        return jnp.pad(a, ((0, blocks * qb - t), (0, 0), (0, 0))).reshape(
            blocks, qb, *a.shape[1:])

    ki = jnp.arange(t)[None, :]

    def block(args):
        qn, qr, start = args
        seen = ki <= start + jnp.arange(qb)[:, None]
        scores = (jnp.einsum("qhd,khd->hqk", qn, k_n)
                  + jnp.einsum("qhd,kd->hqk", qr, k_r)) * scale
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    o = jax.lax.map(block, (padded(q_n), padded(q_r),
                            jnp.arange(blocks) * qb))
    return o.reshape(blocks * qb, -1)[:t] @ weight(w["wo"], controls)


def mixer(kind, h, w, hp, controls=()):
    if kind == "R":
        return mla(h, w, hp, controls)
    if kind == "D":
        return swiglu(h, w["w_gu"], w["w_down"], controls)
    return routed_part(h, w, hp, controls) + swiglu(
        h, w["ws_gu"], w["ws_down"], controls)


def forward(weights: dict, hf: dict, ids, positions, controls=()):
    """Logits [len(positions), vocab] of the sequence ``ids`` at the given
    positions.  ``hf["reference_controls"]`` names controls too, so that a
    control is read THROUGH ``check.py``: a run's ``check_input.json`` with
    that key added to its ``config`` has to come out as not correct."""
    controls = (*controls, *hf.get("reference_controls", ()))
    hp = hyper(hf)
    layer = {kind: jax.jit(lambda x, w, kind=kind: x + mixer(
        kind, rms_norm(x, dequant(w["norm"]), hp["eps"]), w, hp, controls))
        for kind in STACK}
    x = dequant(weights["embed"][jnp.asarray(ids)])
    seen = dict.fromkeys(STACK, 0)
    for kind in hp["pattern"]:
        x = layer[kind](x, weights["layers"][STACK[kind]][seen[kind]])
        seen[kind] += 1
    x = rms_norm(x[jnp.asarray(positions)], dequant(weights["final_norm"]),
                 hp["eps"])
    return x @ weight(weights["lm_head"], controls)
