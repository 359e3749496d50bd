"""Kimi Delta Attention (KDA) state primitives: the gated delta rule with a
decay per CHANNEL of a head, as one step (decode) and in chunks (prefill,
ragged rows).

The recurrence, per head with state ``S [dk, dv]`` (zero before the first
token), decay ``a_t = exp(g_t)`` in (0, 1] per key channel, step size
``beta_t`` in (0, 1):

    S' = Diag(a_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T            o_t = S_t^T q_t

:func:`kda_update` is one step of it.  :func:`kda_chunk_scan` computes the
same thing in chunks of ``chunk`` tokens (Kimi Linear report, sec. 3; the
WY representation with the UT transform of ``fla``'s ``chunk_kda``): with
``G_t`` the running sum of ``g`` inside a chunk and ``u_t = beta_t (v_t -
S'^T k_t)`` the pseudo-value every token adds as ``k_t u_t^T``,

    (I + Diag(beta) A) U = Diag(beta) (V - (exp(G) . K) S_0)
    A[t, s] = sum_d k_t[d] k_s[d] exp(G_t[d] - G_s[d])         (s <  t)
    O = (exp(G) . Q) S_0 + P U,   P[t, s] = the same with q_t  (s <= t)
    S_C = Diag(exp(G_C)) S_0 + (K . exp(G_C - G))^T U

The pairwise decay is taken as ``exp(G_t - G_s)`` with ``s <= t``, never
as ``exp(G_t) * exp(-G_s)``: a channel that forgets fast overflows the
second within a chunk.  The unit lower-triangular ``I + N`` (``N`` strictly
lower, so ``N^C = 0``) is inverted by doubling, ``(I - N)(I + N^2)(I +
N^4)...``: log2(C) batched matmuls where forward substitution is C
dependent steps.  Both forms leave the state untouched for a token whose
``g`` is 0 and ``beta`` 0 — how padding rows and idle slots are masked.

Everything is float32 (the state is carried over thousands of steps) and
every contraction is either elementwise-then-sum or a matmul at
``HIGHEST``: on a TPU a default float32 matmul is one bf16 pass.

This file is plain XLA.  The decode step's update of the CARRIED stack
``[L_K, S, H, dk, dv]`` goes through :func:`kda_update_at`, which on one
TPU device hands the stack to the Pallas kernel ``kda_update``
(``ops/pallas/kda.py``: one pass over the state, written in place) and
elsewhere runs :func:`kda_update` on the layer's slice; the kernels of this
family are named ``kda_...`` (benchmarks/chip/TRACING.kimi_linear.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST


def l2norm(x, eps: float = 1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis, float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


@jax.named_scope("kda_update")
def kda_update(q, k, v, g, beta, state):
    """One step of the recurrence for S sequences.

    q, k, g ``[S, H, dk]`` (g the log decay, <= 0; 0 = keep), v ``[S, H,
    dv]``, beta ``[S, H]`` (0 = add nothing), state ``[S, H, dk, dv]``
    float32.  Returns (o ``[S, H, dv]``, new state)."""
    q, k, v, g, beta = (m.astype(F32) for m in (q, k, v, g, beta))
    state = state * jnp.exp(g)[..., None]
    u = jnp.sum(state * k[..., None], axis=-2)
    delta = beta[..., None] * (v - u)
    state = state + k[..., None] * delta[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


def kda_update_path(state_shape: tuple[int, ...]) -> tuple[str, str]:
    """(path, why not the kernel) of :func:`kda_update_at` for a state
    ``[.., H, dk, dv]``, from the backend and the shape — the names
    ``crowdllama_kda_update_path`` exports: ``pallas`` or ``xla``."""
    from crowdllama_tpu.ops.pallas.kda import kda_update_refusal

    why = kda_update_refusal(tuple(state_shape))
    return ("xla" if why else "pallas"), why


def kda_update_at(q, k, v, g, beta, stack, layer):
    """:func:`kda_update` on layer ``layer``'s slab of the carried stack
    ``[L_K, S, H, dk, dv]``; returns (o, the stack with that slab updated).

    On one TPU device (or in forced interpret mode) with a key dim of whole
    sublanes and a value dim of whole lanes: the Pallas ``kda_update``,
    which takes the whole stack and writes the slab in place.  Elsewhere:
    :func:`kda_update` on the slice."""
    if kda_update_path(stack.shape)[0] == "pallas":
        from crowdllama_tpu.ops.pallas.kda import kda_update as kernel

        return kernel(q, k, v, g, beta, stack, layer)
    o, state = kda_update(q, k, v, g, beta, stack[layer])
    return o, stack.at[layer].set(state)


def _unit_lower_inverse(n):
    """``(I + n)^-1`` for ``n [..., C, C]`` strictly lower triangular."""
    c = n.shape[-1]
    eye = jnp.eye(c, dtype=F32)
    mm = lambda a, b: jnp.matmul(a, b, precision=_EXACT)
    inv, power, span = eye - n, n, 1        # power = n^span
    while 2 * span < c:
        power = mm(power, power)
        span *= 2
        inv = mm(inv, eye + power)
    return inv


@jax.named_scope("kda_scan")
def kda_chunk_scan(q, k, v, g, beta, state, chunk: int):
    """The recurrence over T tokens of S sequences, in chunks.

    q, k, g ``[S, T, H, dk]``, v ``[S, T, H, dv]``, beta ``[S, T, H]`` (g
    and beta 0 for rows that are not real), state ``[S, H, dk, dv]``.
    Returns (o ``[S, T, H, dv]`` float32, state after the last token)."""
    s, t, h, dk = q.shape
    c = min(chunk, t)
    pad = -t % c
    q, k, v, g, beta = (m.astype(F32) for m in (q, k, v, g, beta))
    if pad:     # g 0, beta 0: the padded rows neither decay nor add
        q, k, v, g, beta = (
            jnp.pad(m, ((0, 0), (0, pad)) + ((0, 0),) * (m.ndim - 2))
            for m in (q, k, v, g, beta))
    nc = (t + pad) // c
    # [nc, S, H, C, ..]: a chunk is a scan step, heads ahead of its rows
    q, k, v, g = (jnp.moveaxis(m.reshape(s, nc, c, h, -1), (1, 3), (0, 2))
                  for m in (q, k, v, g))
    beta = jnp.moveaxis(beta.reshape(s, nc, c, h), (1, 3), (0, 2))
    row, col = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    ein = lambda spec, a, b: jnp.einsum(spec, a, b, precision=_EXACT)

    def one_chunk(s0, xs):
        q, k, v, g, beta = xs               # [S, H, C, dk|dv], beta [S, H, C]
        cum = jnp.cumsum(g, axis=2)
        gap = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # [S,H,Ct,Cs,dk]
        decay = jnp.exp(jnp.where((col <= row)[..., None], gap, -jnp.inf))
        kd = k[:, :, None, :, :] * decay
        a = jnp.sum(k[:, :, :, None, :] * kd, -1) * (col < row)
        p = jnp.sum(q[:, :, :, None, :] * kd, -1)             # s <= t
        grow = jnp.exp(cum)
        rhs = beta[..., None] * (v - ein("shck,shkv->shcv", grow * k, s0))
        u = ein("shct,shtv->shcv",
                _unit_lower_inverse(beta[..., None] * a), rhs)
        o = ein("shck,shkv->shcv", grow * q, s0) + ein(
            "shct,shtv->shcv", p, u)
        to_end = jnp.exp(cum[:, :, -1:, :] - cum)
        s1 = grow[:, :, -1, :, None] * s0 + ein(
            "shck,shcv->shkv", k * to_end, u)
        return s1, o

    state, o = jax.lax.scan(one_chunk, state.astype(F32),
                            (q, k, v, g, beta))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(s, nc * c, h, -1)
    return o[:, :t], state
