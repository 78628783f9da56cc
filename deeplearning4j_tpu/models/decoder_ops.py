"""The small pieces the served decoder blocks share
(``models/lfm2_moe.py``, ``models/exaone_moe.py``,
``models/glm_moe_dsa.py``, ``models/mellum.py``): RMSNorm with its
statistics in float32, and rotary positions in float32, rotate-half or
over interleaved pairs, at the frequencies of a ``rope_parameters``
entry (``default`` or ``yarn``)."""

from __future__ import annotations

import math

import jax.numpy as jnp
from jax import lax


def rms_norm(x, g, eps):
    """``x * rsqrt(mean(x^2) + eps) * g`` over the last axis: the
    statistics in float32, the result in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    r = lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (xf * r).astype(x.dtype) * g


def rope_frequencies(entry, hd):
    """One ``rope_parameters`` entry -> (inverse frequencies ``[hd /
    2]`` float32, the factor cos and sin are scaled by).

    ``default``: ``theta^(-2i / hd)``. ``yarn`` (Hugging Face's
    ``_compute_yarn_parameters``, ``truncate`` on): the frequencies
    divided by ``factor`` (interpolated) and as they are (extrapolated),
    blended by a linear ramp over the frequency index between the
    correction dims of ``beta_fast`` and ``beta_slow`` at
    ``original_max_position_embeddings``; cos and sin scaled by
    ``attention_factor`` (``0.1 ln(factor) + 1`` where the entry has
    none), so that scores scale by its square."""
    theta = float(entry["rope_theta"])
    kind = entry.get("rope_type", "default")
    pos_freqs = theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    if kind == "default":
        return 1.0 / pos_freqs, 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r} is not implemented "
                         "(only 'default' and 'yarn')")
    factor = float(entry["factor"])
    original = float(entry["original_max_position_embeddings"])
    scale = entry.get("attention_factor")
    scale = 0.1 * math.log(factor) + 1.0 if scale is None else float(scale)

    def correction_dim(rotations):
        return hd * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(entry.get("beta_fast", 32)))),
              0)
    high = min(math.ceil(correction_dim(float(entry.get("beta_slow", 1)))),
               hd - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    extrapolated = 1.0 - ramp
    inv = (1.0 / (factor * pos_freqs)) * (1.0 - extrapolated) \
        + (1.0 / pos_freqs) * extrapolated
    return inv, scale


def rope(x, pos, theta):
    """Rotate-half RoPE of ``x [n, t, heads, hd]`` at ``pos [n, t]``,
    in float32. ``theta`` is the base, or a whole ``rope_parameters``
    entry (``rope_frequencies``)."""
    hd = x.shape[-1]
    entry = theta if isinstance(theta, dict) else {"rope_theta": theta}
    inv, scale = rope_frequencies(entry, hd)
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
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


__all__ = ["rms_norm", "rope", "rope_frequencies", "rope_interleaved"]
