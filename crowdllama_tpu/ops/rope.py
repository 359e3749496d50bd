"""Rotary position embeddings (half-rotation layout, HF-compatible)."""

from __future__ import annotations

import math

import jax.numpy as jnp


def rope_table(max_len: int, head_dim: int, theta: float,
               scaling=None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Precompute (cos, sin) tables of shape [max_len, head_dim//2], fp32.

    ``scaling`` is a ``models.config.RopeScaling`` (or None): "llama3"
    applies the Llama-3.1 frequency-dependent long-context scaling (low
    frequencies divided by ``factor``, high frequencies untouched, a
    smooth ramp between — matching HF's _compute_llama3_parameters so
    converted Llama-3.1/3.2 checkpoints are bit-compatible); "linear"
    divides every frequency (position interpolation).
    """
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
        else:  # pragma: no cover - rejected upstream at config parse
            raise ValueError(f"unknown rope scaling {scaling.rope_type!r}")
    pos = jnp.arange(max_len, dtype=jnp.float32)
    angles = jnp.outer(pos, inv_freq)  # [T, Dh/2]
    return jnp.cos(angles), jnp.sin(angles)


def rope_angles(positions: jnp.ndarray, head_dim: int,
                theta: float) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(cos, sin) ``[..., Dh/2]`` of ``positions [...]`` themselves: what
    :func:`rope_table` holds at those rows, without the table."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


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
