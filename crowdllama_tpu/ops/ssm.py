"""Mamba-2 state-space primitives: the causal depthwise convolution with a
carried tail, the chunked SSD scan (prefill) and the one-step state update
(decode).

The recurrence, per head ``h`` with state ``S_h [P, N]`` (head dim x state
size), group ``g = h // (H / G)``:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t + D x_t

:func:`ssd_scan` computes it in chunks of ``chunk`` tokens (Dao & Gu 2024,
"Transformers are SSMs", the SSD algorithm: a masked quadratic form inside
a chunk, a short scan of chunk states between chunks); :func:`ssm_update`
is one step of it.  Both start from a given state, so a prompt admitted in
pieces continues where it stopped, and both leave the state untouched for
a token whose ``dt`` is 0 — which is how padding rows and idle slots are
masked (``exp(0) = 1``, ``0 * x (x) B = 0``).

Everything here is float32: the state is carried over thousands of steps
and is the one place of this model where bf16 rounding accumulates.

This file is plain XLA.  The decode step's update of the CARRIED stack goes
through :func:`ssm_update_at`, which on one TPU device hands the stack to
the Pallas kernel ``ssm_update`` (``ops/pallas/ssm.py``: one pass over the
state, where XLA makes two fusions of :func:`ssm_update`'s two expressions
and reads the state twice) and elsewhere runs :func:`ssm_update` on the
layer's slice; the kernels of this family are named ``ssm_...``
(benchmarks/chip/TRACING.nemotron_h.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _conv_rows(x, tail, w, b):
    """(out ``[S, T, C]``, ``[tail ; x]`` ``[S, K-1+T, C]``), float32, of
    the convolution continuing from ``tail`` ``[S, K-1, C]``, oldest
    first."""
    k = w.shape[1]
    t = x.shape[1]
    full = jnp.concatenate([tail.astype(F32), x.astype(F32)], axis=1)
    out = sum(full[:, j:j + t] * w[:, j].astype(F32) for j in range(k))
    if b is not None:
        out = b.astype(F32) + out
    return out, full


def _last_rows(full, valid, k: int):
    """The ``K-1`` rows of ``full`` before row ``valid`` of its T new ones,
    a sequence each."""
    return jax.vmap(lambda f, n: jax.lax.dynamic_slice_in_dim(
        f, n, k - 1, axis=0))(full, valid)


@jax.named_scope("ssm_conv")
def causal_conv(x, tail, w, b, valid):
    """Depthwise causal convolution over ``[S, T, C]`` continuing from the
    last ``K-1`` inputs of each sequence.

    x ``[S, T, C]``; tail ``[S, C, K-1]`` (oldest first); w ``[C, K]``
    (``w[:, K-1]`` weighs the current token); b ``[C]`` or None; valid
    ``[S]`` — how many of the T rows are real.  Returns (out ``[S, T, C]``
    float32, new tail ``[S, C, K-1]`` in the tail's dtype: the last ``K-1``
    inputs before row ``valid``, so 0 valid rows hand the tail back
    unchanged).
    """
    out, full = _conv_rows(x, jnp.swapaxes(tail, 1, 2), w, b)
    new_tail = _last_rows(full, valid, w.shape[1])
    return out, jnp.swapaxes(new_tail, 1, 2).astype(tail.dtype)


@jax.named_scope("ssm_conv")
def causal_conv_rows(x, tail, w, b, valid):
    """:func:`causal_conv` with the tail kept as ROWS, ``[S, K-1, C]``
    (channels on the lanes), and a decode step's new tail chosen, not
    gathered: with one new row a sequence it is ``full[1:]`` or ``full[:-1]``
    (the sequence moved or did not).  The per-sequence slice of
    :func:`causal_conv` became 32 dynamic-update-slices a layer on the chip,
    5.8 us each: 3.7 ms of a 24.6 ms step at Kimi's twenty layers (my chip
    run, PR 37)."""
    k = w.shape[1]
    out, full = _conv_rows(x, tail, w, b)
    if x.shape[1] == 1:
        new_tail = jnp.where((valid > 0)[:, None, None], full[:, 1:],
                             full[:, :k - 1])
    else:
        new_tail = _last_rows(full, valid, k)
    return out, new_tail.astype(tail.dtype)


def _heads_of_groups(m, heads: int):
    """``[..., G, N]`` -> ``[..., H, N]``: head h reads group h // (H/G)."""
    return jnp.repeat(m, heads // m.shape[-2], axis=-2)


@jax.named_scope("ssm_update")
def ssm_update(x, dt, a, b, c, d, state):
    """One step of the recurrence for S sequences.

    x ``[S, H, P]``, dt ``[S, H]`` (after softplus; 0 = leave the state),
    a ``[H]`` (negative), b, c ``[S, G, N]``, d ``[H]``, state
    ``[S, H, P, N]`` float32.  Returns (y ``[S, H, P]``, new state)."""
    h = x.shape[1]
    x, dt = x.astype(F32), dt.astype(F32)
    bh = _heads_of_groups(b.astype(F32), h)
    ch = _heads_of_groups(c.astype(F32), h)
    decay = jnp.exp(dt * a)[..., None, None]
    state = state * decay + (dt[..., None] * x)[..., None] * bh[:, :, None, :]
    y = jnp.einsum("shpn,shn->shp", state, ch) + d[:, None] * x
    return y, state


def ssm_update_path(state_shape: tuple[int, ...]) -> tuple[str, str]:
    """(path, why not the kernel) of :func:`ssm_update_at` for a state
    ``[.., H, P, N]``, from the backend and the shape — the names
    ``crowdllama_ssm_update_path`` exports: ``pallas`` or ``xla``."""
    from crowdllama_tpu.ops.pallas.ssm import ssm_update_refusal

    why = ssm_update_refusal(tuple(state_shape))
    return ("xla" if why else "pallas"), why


def ssm_update_at(x, dt, a, b, c, d, stack, layer):
    """:func:`ssm_update` on layer ``layer``'s slab of the carried stack
    ``[L_M, S, H, P, N]``; returns (y, the stack with that slab updated).

    On one TPU device (or in forced interpret mode) with a head dim of
    whole sublanes and a state size of whole lanes: the Pallas
    ``ssm_update``, which takes the whole stack and writes the slab in
    place.  Elsewhere: :func:`ssm_update` on the slice."""
    if ssm_update_path(stack.shape)[0] == "pallas":
        from crowdllama_tpu.ops.pallas.ssm import ssm_update as kernel

        return kernel(x, dt, a, b, c, d, stack, layer)
    y, state = ssm_update(x, dt, a, b, c, d, stack[layer])
    return y, stack.at[layer].set(state)


@jax.named_scope("ssm_scan")
def ssd_scan(x, dt, a, b, c, d, state, chunk: int):
    """The recurrence over T tokens of S sequences, in chunks.

    x ``[S, T, H, P]``, dt ``[S, T, H]`` (0 for rows that are not real),
    a ``[H]``, b, c ``[S, T, G, N]``, d ``[H]``, state ``[S, H, P, N]``.
    Returns (y ``[S, T, H, P]`` float32, state after the last token)."""
    s, t, h, p = x.shape
    q = min(chunk, t)
    pad = -t % q
    x, dt = x.astype(F32), dt.astype(F32)
    bh = _heads_of_groups(b.astype(F32), h)
    ch = _heads_of_groups(c.astype(F32), h)
    if pad:    # dt 0: the padded rows neither decay nor feed the state
        x, dt, bh, ch = (jnp.pad(m, ((0, 0), (0, pad)) + ((0, 0),) * (m.ndim - 2))
                         for m in (x, dt, bh, ch))
    nc = (t + pad) // q
    x, dt, bh, ch = (m.reshape(s, nc, q, *m.shape[2:]) for m in (x, dt, bh, ch))
    cum = jnp.cumsum(dt * a, axis=2)                       # [S, nc, Q, H]
    xdt = x * dt[..., None]
    # inside a chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j
    i, j = jnp.arange(q)[:, None], jnp.arange(q)[None, :]
    gap = cum[:, :, :, None, :] - cum[:, :, None, :, :]    # [S, nc, Qi, Qj, H]
    decay = jnp.exp(jnp.where((j <= i)[None, None, :, :, None], gap, -jnp.inf))
    scores = jnp.einsum("sciHn,scjHn->scijH", ch, bh) * decay
    y = jnp.einsum("scijH,scjHp->sciHp", scores, xdt)
    # what each chunk adds to the state, and how much of the old it keeps
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)              # [S, nc, Q, H]
    adds = jnp.einsum("scjH,scjHp,scjHn->scHpn", to_end, xdt, bh)
    keeps = jnp.exp(cum[:, :, -1, :])                      # [S, nc, H]

    def between(st, xs):
        add, keep = xs
        return st * keep[..., None, None] + add, st        # ys: state at entry

    state, entry = jax.lax.scan(
        between, state.astype(F32),
        (jnp.moveaxis(adds, 1, 0), jnp.moveaxis(keeps, 1, 0)))
    entry = jnp.moveaxis(entry, 0, 1)                      # [S, nc, H, P, N]
    y = y + jnp.einsum("sciH,scHpn,sciHn->sciHp", jnp.exp(cum), entry, ch)
    y = y + d[:, None] * x
    return y.reshape(s, nc * q, h, p)[:, :t], state
