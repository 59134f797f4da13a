"""Rotary position embeddings (non-interleaved / HF "rotate_half" layout)."""

from __future__ import annotations

import math

import jax.numpy as jnp


def rope_table(positions: jnp.ndarray, head_dim: int, theta: float,
               scale: float = 1.0):
    """cos/sin tables for integer positions.

    positions: [...], returns (cos, sin) each [..., head_dim].
    ``scale`` > 1 is HF linear rope_scaling (positions divided by factor —
    Gemma-3's global-rope long-context stretch).
    """
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = (
        positions.astype(jnp.float32)[..., None] / scale
    ) * freqs  # [..., half]
    cos = jnp.cos(angles)
    sin = jnp.sin(angles)
    # rotate_half layout: duplicate for both halves
    return (
        jnp.concatenate([cos, cos], axis=-1),
        jnp.concatenate([sin, sin], axis=-1),
    )


def rope_freqs(law) -> jnp.ndarray:
    """The frequency vector [rotary_dim // 2] of a rotary law (a
    ``models.config.RopeLaw``): ``theta ** (-2i / rotary_dim)``, and under
    YaRN the per-frequency blend of that and itself over ``factor`` by the
    linear ramp between the correction dims of ``beta_fast`` and
    ``beta_slow`` rotations over the original positions (the published
    ``_compute_yarn_parameters``, with truncation)."""
    dim = law.rotary_dim
    pos_freqs = law.theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inv = 1.0 / pos_freqs
    if law.yarn is None:
        return inv
    factor, original, beta_fast, beta_slow = law.yarn

    def correction_dim(rotations: float) -> float:
        return dim * math.log(original / (rotations * 2 * math.pi)) / (
            2 * math.log(law.theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    # ramp 0: the frequency as it is (extrapolated); 1: over ``factor``.
    return inv / factor * ramp + inv * (1.0 - ramp)


def rope_table_for(positions: jnp.ndarray, law):
    """(cos, sin), each [..., rotary_dim], of a ``RopeLaw``: the angles of
    ``rope_freqs`` in the rotate_half layout, times the law's attention
    factor. ``apply_rope`` rotates the first ``rotary_dim`` lanes of a head
    with it and passes the rest through."""
    angles = positions.astype(jnp.float32)[..., None] * rope_freqs(law)
    cos = jnp.cos(angles) * law.attention_factor
    sin = jnp.sin(angles) * law.attention_factor
    return (
        jnp.concatenate([cos, cos], axis=-1),
        jnp.concatenate([sin, sin], axis=-1),
    )


def _rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: [..., n_heads, head_dim]; cos/sin: [..., rotary] (broadcast over
    heads). A table narrower than the head rotates the head's first
    ``rotary`` lanes and leaves the rest as they are (partial rotary)."""
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    rotary = cos.shape[-1]
    if rotary < x.shape[-1]:
        head = apply_rope(x[..., :rotary], cos[..., 0, :], sin[..., 0, :])
        return jnp.concatenate([head, x[..., rotary:]], axis=-1)
    out = x.astype(jnp.float32) * cos + _rotate_half(x.astype(jnp.float32)) * sin
    return out.astype(x.dtype)
