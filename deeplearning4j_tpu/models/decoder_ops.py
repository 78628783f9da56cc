"""The two small pieces the served decoder blocks share
(``models/lfm2_moe.py``, ``models/exaone_moe.py``): RMSNorm with its
statistics in float32, and rotate-half rotary positions in float32."""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def rms_norm(x, g, eps):
    """``x * rsqrt(mean(x^2) + eps) * g`` over the last axis: the
    statistics in float32, the result in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    r = lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (xf * r).astype(x.dtype) * g


def rope(x, pos, theta):
    """Rotate-half RoPE of ``x [n, t, heads, hd]`` at ``pos [n, t]``,
    in float32."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    xf = x.astype(jnp.float32)
    rot = jnp.concatenate([-xf[..., hd // 2:], xf[..., :hd // 2]], -1)
    return (xf * cos + rot * sin).astype(x.dtype)


__all__ = ["rms_norm", "rope"]
