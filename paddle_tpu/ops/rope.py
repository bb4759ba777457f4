"""Rotary position embedding with the YaRN frequency blend
(arXiv:2309.00071), as DeepSeek-V3's published code applies it: to a
`dim`-wide slice of each head, in float32.

    f_i = theta^(-2i / dim),  i in [0, dim / 2)
    served f_i' = f_i / factor blended with f_i by a linear ramp over i,
    0 below the dimension where `original` positions make `beta_fast`
    rotations (floored) and 1 above the one where they make `beta_slow`
    (ceiled): rarely-turning dimensions are interpolated, fast ones kept.

Pairs are (2i, 2i + 1) of the vector as projected. `rotate` returns them
de-interleaved, the rotated first members in the first half and the second
members in the second, as the published code does before its
`rotate_half`: queries and keys go through the same permutation, so their
dot products are those of the interleaved rotation.
"""
from __future__ import annotations

import math


def yarn_correction_range(dim, theta, original, beta_fast, beta_slow):
    """(low, high) dimensions of the ramp (`yarn_find_correction_range`)."""

    def dim_of(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    return (max(math.floor(dim_of(beta_fast)), 0),
            min(math.ceil(dim_of(beta_slow)), dim - 1))


def yarn_inv_freq(dim, theta=10000.0, factor=1.0, original=4096,
                  beta_fast=32, beta_slow=1):
    """[dim / 2] float32 numpy frequencies; `factor` 1 is plain RoPE."""
    import numpy as np

    f = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor == 1:
        return f.astype(np.float32)
    low, high = yarn_correction_range(dim, theta, original, beta_fast,
                                      beta_slow)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (f / factor * ramp + f * (1.0 - ramp)).astype(np.float32)


def yarn_mscale(factor, mscale=1.0):
    """0.1 mscale ln(factor) + 1 (1 where nothing is scaled)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def table(positions, inv_freq):
    """positions [...] int -> (cos, sin) [..., dim / 2] float32."""
    import jax.numpy as jnp

    ang = jnp.asarray(positions, jnp.float32)[..., None] \
        * jnp.asarray(inv_freq, jnp.float32)
    return jnp.cos(ang), jnp.sin(ang)


def rotate(x, cos, sin):
    """x [..., dim] with pairs (2i, 2i + 1); cos, sin broadcastable to
    [..., dim / 2]. -> [..., dim] in x's dtype, de-interleaved."""
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    a, b = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)
