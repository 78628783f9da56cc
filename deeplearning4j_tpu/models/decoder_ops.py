"""The small pieces the served decoder blocks share
(``models/lfm2_moe.py``, ``models/exaone_moe.py``,
``models/glm_moe_dsa.py``): RMSNorm with its statistics in float32, and
rotary positions in float32, rotate-half or over interleaved pairs."""

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


def rope_interleaved(x, pos, theta):
    """RoPE over INTERLEAVED pairs of ``x [n, t, heads, hd]`` at ``pos
    [n, t]``, in float32: lanes ``(2i, 2i + 1)`` turn by ``pos *
    theta^(-2i / hd)`` (``rope`` pairs lane ``i`` with ``i + hd / 2``)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[..., None] * inv        # [n, t, hd / 2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], hd // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


__all__ = ["rms_norm", "rope", "rope_interleaved"]
