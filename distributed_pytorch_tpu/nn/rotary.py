"""Rotary position embeddings (RoPE).

Positions enter attention by rotating each (q, k) head vector in 2-D
planes — relative offsets then appear as phase differences inside the
q.k dot product, so no positional parameters exist and the scheme
extrapolates by construction. This is the modern replacement for the
learned absolute table (``TransformerLM(pos="rope")``); the reference
repo has no positional scheme at all (its model is an MLP over scalar
indices, reference ``min_DDP.py:44-48``).

TPU notes: the rotation is a pure elementwise map (two multiplies, one
shuffle) that XLA fuses into the surrounding qkv projection; it composes
with the flash/ring kernels untouched because it runs BEFORE attention.
The half-split ("rotate_half", NeoX/Llama) layout is used: dims [0, d/2)
pair with [d/2, d), which keeps the shuffle a single concat instead of a
stride-2 gather (strided lane moves are slow on the VPU).
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_angles(positions, head_dim: int, base: float = 10000.0,
                dtype=jnp.float32):
    """(cos, sin) tables for ``positions`` (any shape P), each
    (P..., head_dim/2): angle(p, i) = p * base^(-2i/d)."""
    if head_dim % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {head_dim}")
    half = head_dim // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rope(x, positions, base: float = 10000.0):
    """Rotate head vectors: x (..., H, S, Dh), positions (S,) int.

    Returns x with each head vector rotated by its position's angles in
    the half-split pairing; dtype preserved (angles computed in f32)."""
    dh = x.shape[-1]
    cos, sin = rope_angles(positions, dh, base, dtype=jnp.float32)
    # broadcast (S, Dh/2) over leading (..., H) axes
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def yarn_inv_freq(rot_dim: int, base: float, *, factor: float,
                  original_max: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0):
    """YaRN's frequencies (Peng et al. 2023) for a rotary part of
    ``rot_dim``: frequency ``i`` of ``rot_dim / 2`` is the plain
    ``base^(-2i/rot_dim)`` where it turns more than ``beta_fast`` times
    inside the original context, that divided by ``factor`` where it
    turns less than ``beta_slow`` times, and a linear blend between.
    The ramp's ends are the floor and ceiling of the dimension at which a
    frequency makes exactly ``beta`` turns, clamped to the part."""
    import math

    half = rot_dim // 2
    f = base ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot_dim)

    def dim_of(turns):
        return rot_dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    lo = max(math.floor(dim_of(beta_fast)), 0)
    hi = min(math.ceil(dim_of(beta_slow)), rot_dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - lo)
                    / max(hi - lo, 1e-3), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float) -> float:
    """``0.1 mscale ln(factor) + 1`` (1 where nothing is stretched)."""
    import math

    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotate_interleaved(x, positions, inv_freq, mult: float = 1.0):
    """Rotate the last axis of ``x`` in interleaved pairs (2i, 2i + 1)
    by ``positions * inv_freq``. ``positions`` broadcasts against the
    axes of ``x`` before the last (give it a trailing axis per head axis
    it has to span); angles in float32, dtype preserved."""
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(ang) * mult, jnp.sin(ang) * mult
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)
