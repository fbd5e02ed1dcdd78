"""Rotary position embeddings (full and partial-rotary, optionally YaRN)."""
from __future__ import annotations

import math
from typing import Optional

import jax.numpy as jnp

from repro.configs.base import RopeScaling


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention-temperature factor 0.1·mscale·ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_dim(rotations: float, dim: int, theta: float, orig: int) -> float:
    """The rotary dim whose wavelength fits ``rotations`` turns into the
    original context."""
    return dim * math.log(orig / (rotations * 2 * math.pi)) \
        / (2 * math.log(theta))


def rope_freqs(head_dim: int, theta: float, partial: float = 1.0,
               scaling: Optional[RopeScaling] = None) -> jnp.ndarray:
    """Inverse frequencies for the rotary dims (rot_dim = head_dim*partial).

    With YaRN ``scaling``, dims below the β_fast correction dim keep
    their frequency, dims above the β_slow one are divided by the factor,
    and a linear ramp blends the two in between."""
    rot = int(head_dim * partial)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    if scaling is None:
        return inv
    s = scaling
    orig = s.original_max_position_embeddings
    low = max(math.floor(_yarn_dim(s.beta_fast, rot, theta, orig)), 0)
    high = min(math.ceil(_yarn_dim(s.beta_slow, rot, theta, orig)), rot - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inv / s.factor * ramp + inv * (1.0 - ramp)


def rope_mscale(scaling: Optional[RopeScaling]) -> float:
    """Factor on cos and sin: mscale over mscale_all_dim's (1 when the two
    are equal, as in DeepSeek-V2)."""
    if scaling is None:
        return 1.0
    return (yarn_mscale(scaling.factor, scaling.mscale)
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               partial: float = 1.0,
               scaling: Optional[RopeScaling] = None) -> jnp.ndarray:
    """Apply RoPE.

    x: (..., S, H, head_dim) — positions: broadcastable to (..., S).
    Uses the half-split convention (rotate_half), matching Llama/Qwen.
    """
    head_dim = x.shape[-1]
    inv = rope_freqs(head_dim, theta, partial, scaling)
    rot = inv.shape[0] * 2
    angles = positions[..., None].astype(jnp.float32) * inv  # (..., S, rot/2)
    cos = jnp.cos(angles)[..., None, :]                      # (..., S, 1, r/2)
    sin = jnp.sin(angles)[..., None, :]
    m = rope_mscale(scaling)
    if m != 1.0:
        cos, sin = cos * m, sin * m
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = x_rot[..., : rot // 2], x_rot[..., rot // 2:]
    xf1 = x1.astype(jnp.float32)
    xf2 = x2.astype(jnp.float32)
    out1 = xf1 * cos - xf2 * sin
    out2 = xf2 * cos + xf1 * sin
    out = jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)
    if x_pass.shape[-1]:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out
