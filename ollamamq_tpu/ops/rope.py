"""Rotary position embeddings (HF-Llama rotate-half convention)."""

from __future__ import annotations

import math

import jax.numpy as jnp


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies [head_dim//2], float32. (`theta` as a float: a
    configuration file's 100000000000 is an int no int32 holds.)"""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (float(theta) ** exponents)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               rotary_dim=None) -> jnp.ndarray:
    """Apply RoPE.

    x: [..., T, H, head_dim] (positions broadcast over leading dims)
    positions: [..., T] int32
    rotary_dim: rotate the FIRST that many lanes of a head only (a partial
    rotary embedding: frequencies over rotary_dim, the other lanes pass);
    None or head_dim: the whole head.
    """
    head_dim = x.shape[-1]
    if rotary_dim is not None and rotary_dim != head_dim:
        return jnp.concatenate(
            [apply_rope(x[..., :rotary_dim], positions, theta),
             x[..., rotary_dim:]], axis=-1)
    inv_freq = rope_freqs(head_dim, theta)  # [hd/2]
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [..., T, hd/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., T, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_freqs(dim: int, theta: float, yarn: dict) -> jnp.ndarray:
    """YaRN inverse frequencies [dim//2], float32, of a published
    `rope_scaling` group of type "yarn": a frequency whose wavelength fits
    the original context `beta_fast` times or more is kept, one that fits it
    `beta_slow` times or fewer is divided by `factor`, a linear ramp between
    (the family's inference code: find_correction_range, linear_ramp)."""
    factor = float(yarn["factor"])
    orig = float(yarn["original_max_position_embeddings"])
    fast, slow = float(yarn.get("beta_fast", 32)), float(yarn.get("beta_slow", 1))
    base = rope_freqs(dim, theta)

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(fast)), 0)
    high = min(math.ceil(correction_dim(slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return base / factor * ramp + base * (1.0 - ramp)


def yarn_cos_scale(yarn: dict) -> float:
    """What YaRN multiplies cos and sin by: mscale(factor, `mscale`) /
    mscale(factor, `mscale_all_dim`) — 1 where the two are equal."""
    def m(scale):
        return 0.1 * scale * math.log(yarn["factor"]) + 1.0 \
            if yarn["factor"] > 1 else 1.0

    return m(yarn.get("mscale", 1)) / m(yarn.get("mscale_all_dim", 0))


def apply_rope_freqs(x: jnp.ndarray, positions: jnp.ndarray,
                     inv_freq: jnp.ndarray, scale: float = 1.0) -> jnp.ndarray:
    """`apply_rope` (rotate-half) with given inverse frequencies [d/2] over
    the whole last axis of x [..., T, H, d]; cos and sin times `scale`."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos = (jnp.cos(angles) * scale)[..., None, :]
    sin = (jnp.sin(angles) * scale)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
