"""Rotary position embeddings (half-rotation layout, HF-compatible)."""

from __future__ import annotations

import math

import jax.numpy as jnp


def yarn_correction_range(scaling, head_dim: int,
                          theta: float) -> tuple[int, int]:
    """(low, high): the frequency indices between which a yarn scaling
    blends.  ``d(r) = head_dim ln(L / (2 pi r)) / (2 ln theta)`` is the
    index whose wave turns ``r`` times over the original length ``L``; low
    is ``floor(d(beta_fast))``, high ``ceil(d(beta_slow))``, both inside
    the table."""
    def dim_of(rotations: float) -> float:
        return (head_dim * math.log(scaling.original_max_position_embeddings
                                    / (rotations * 2.0 * math.pi))
                / (2.0 * math.log(theta)))

    return (max(math.floor(dim_of(scaling.beta_fast)), 0),
            min(math.ceil(dim_of(scaling.beta_slow)), head_dim - 1))


def _inv_freq(head_dim: int, theta: float, scaling) -> jnp.ndarray:
    """The rotation frequencies ``[head_dim//2]``, float32, under
    ``scaling`` (a ``models.config.RopeScaling`` or None): "llama3" applies
    the Llama-3.1 frequency-dependent long-context scaling (low frequencies
    divided by ``factor``, high frequencies untouched, a smooth ramp between
    — matching HF's _compute_llama3_parameters so converted Llama-3.1/3.2
    checkpoints are bit-compatible); "linear" divides every frequency
    (position interpolation); "yarn" keeps the frequencies below
    :func:`yarn_correction_range`'s low index, divides those above its high
    one by ``factor`` and blends linearly between."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling is not None:
        if scaling.rope_type == "linear":
            inv_freq = inv_freq / scaling.factor
        elif scaling.rope_type == "llama3":
            old_len = float(scaling.original_max_position_embeddings)
            low_wavelen = old_len / scaling.low_freq_factor
            high_wavelen = old_len / scaling.high_freq_factor
            wavelen = 2.0 * math.pi / inv_freq
            smooth = ((old_len / wavelen - scaling.low_freq_factor)
                      / (scaling.high_freq_factor - scaling.low_freq_factor))
            smoothed = ((1.0 - smooth) * inv_freq / scaling.factor
                        + smooth * inv_freq)
            inv_freq = jnp.where(
                wavelen > low_wavelen, inv_freq / scaling.factor,
                jnp.where(wavelen < high_wavelen, inv_freq, smoothed))
        elif scaling.rope_type == "yarn":
            low, high = yarn_correction_range(scaling, head_dim, theta)
            ramp = jnp.clip(
                (jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                / max(high - low, 1e-3), 0.0, 1.0)
            inv_freq = (ramp * inv_freq / scaling.factor
                        + (1.0 - ramp) * inv_freq)
        else:  # pragma: no cover - rejected upstream at config parse
            raise ValueError(f"unknown rope scaling {scaling.rope_type!r}")
    return inv_freq


def _magnitude(scaling) -> float:
    """What cos and sin are multiplied by: a yarn scaling's ``mscale`` over
    its ``mscale_all_dim`` correction, 1 for every other."""
    if scaling is None or scaling.rope_type != "yarn":
        return 1.0
    return (scaling.yarn_mscale(scaling.mscale)
            / scaling.yarn_mscale(scaling.mscale_all_dim))


def rope_table(max_len: int, head_dim: int, theta: float,
               scaling=None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Precompute (cos, sin) tables of shape [max_len, head_dim//2], fp32,
    at :func:`_inv_freq`'s frequencies."""
    inv_freq = _inv_freq(head_dim, theta, scaling)
    pos = jnp.arange(max_len, dtype=jnp.float32)
    return _cos_sin(jnp.outer(pos, inv_freq), scaling)


def rope_angles(positions: jnp.ndarray, head_dim: int, theta: float,
                scaling=None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(cos, sin) ``[..., Dh/2]`` of ``positions [...]`` themselves: what
    :func:`rope_table` holds at those rows, without the table."""
    inv_freq = _inv_freq(head_dim, theta, scaling)
    return _cos_sin(positions.astype(jnp.float32)[..., None] * inv_freq,
                    scaling)


def _cos_sin(angles, scaling):
    m = _magnitude(scaling)
    if m == 1.0:
        return jnp.cos(angles), jnp.sin(angles)
    return jnp.cos(angles) * m, jnp.sin(angles) * m


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate ``x`` [..., T, H, Dh] by per-token ``positions`` [..., T].

    Uses the 'rotate_half' convention (x split into two halves), matching the
    HF Llama implementation so converted checkpoints are bit-compatible.
    """
    return rotate_half(x, cos[positions], sin[positions])


def rotate_half(x: jnp.ndarray, c: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """``x`` [..., T, H, Dh] rotated by each token's own angles: ``c``, ``s``
    [..., T, Dh/2] their cosines and sines."""
    dtype = x.dtype
    c = jnp.expand_dims(c, axis=-2)  # broadcast over heads: [..., T, 1, Dh/2]
    s = jnp.expand_dims(s, axis=-2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(dtype)
