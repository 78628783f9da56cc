"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has NO sequence parallelism of any kind — long sequences
are handled only by truncated BPTT and masking (SURVEY.md §5
"Long-context/sequence parallelism: none"). This module is the
capability the TPU rebuild adds as first-class: sequence length scales
past one chip's HBM by sharding the token axis over an 'sp' mesh axis.

Two interchangeable strategies, both pure per-shard functions intended
to run inside ``shard_map`` over a Mesh with an ``sp`` axis:

- ``ring_attention``: blockwise attention with an online (streaming)
  softmax. Each device holds Q/K/V shards ``[B, H, T/sp, D]``; K/V
  blocks rotate around the ring via ``lax.ppermute`` while each device
  accumulates its queries' output with the numerically-stable running
  (max, sum, out) triple. Communication rides ICI neighbor links —
  bandwidth-optimal, memory O(T/sp) per device.
- ``ulysses_attention``: all-to-all swaps the shard axis from tokens to
  heads (``lax.all_to_all``), runs dense local attention on full-length
  sequences for H/sp heads, and swaps back. Cheaper at moderate T,
  requires sp | H.

Both compute the exact same math as dense attention (verified in
tests/test_ring_attention.py against a single-device reference), and
both are differentiable — ``ppermute``/``all_to_all`` transpose
correctly under ``jax.grad`` inside ``shard_map``, so the backward pass
is itself a ring pass.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _online_block(carry, k, v, bias):
    """Fold one K/V block into the streaming-softmax state.

    carry = (o, m, l): accumulated unnormalised output [B,H,Tq,D] (f32),
    running row max m [B,H,Tq,1], running row sum l [B,H,Tq,1].
    bias: additive logit bias for this block ([B,H,Tq,Tk] or None).
    """
    o, m, l, q, scale = carry
    logits = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k,
        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        logits = logits + bias
    m_blk = jnp.max(logits, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_blk)
    # guard -inf rows (fully-masked block): exp(-inf - -inf) -> use where
    corr = jnp.exp(jnp.minimum(m - m_new, 0.0))
    p = jnp.exp(logits - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o * corr + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return o_new, m_new, l_new, q, scale


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                   kv_mask: Optional[jax.Array] = None):
    """Exact blockwise ring attention; call inside shard_map.

    q, k, v: per-shard ``[B, H, T_local, D]`` (token axis sharded over
    ``axis_name``). kv_mask: per-shard ``[B, T_local]``, 1.0 = valid
    key (travels around the ring with its K/V block). Returns
    ``[B, H, T_local, D]`` in q's dtype.
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, h, tq, d = q.shape
    tk = k.shape[2]
    scale = jnp.float32(1.0) / jnp.sqrt(jnp.float32(d))
    qf = q.astype(jnp.float32)

    neg = jnp.float32(-1e30)
    q_pos = my * tq + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)

    def bias_for(src_idx, mask_blk):
        bias = None
        if causal:
            k_pos = src_idx * tk + lax.broadcasted_iota(
                jnp.int32, (tq, tk), 1)
            bias = jnp.where(k_pos <= q_pos, 0.0, neg)[None, None]
        if mask_blk is not None:
            mb = jnp.where(mask_blk.astype(bool), 0.0, neg)
            mb = mb[:, None, None, :]  # [B,1,1,Tk]
            bias = mb if bias is None else bias + mb
        return bias

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(s, state):
        o, m, l, kk, vv, mask_blk = state
        src = (my - s) % n  # who this K/V block originally belonged to
        carry = _online_block(
            (o, m, l, qf, scale), kk.astype(jnp.float32),
            vv, bias_for(src, mask_blk))
        o, m, l = carry[0], carry[1], carry[2]
        # rotate K/V (and its mask) to the next device; skip after last
        if s < n - 1:
            kk, vv = lax.ppermute((kk, vv), axis_name, perm)
            if mask_blk is not None:
                mask_blk = lax.ppermute(mask_blk, axis_name, perm)
        return o, m, l, kk, vv, mask_blk

    o = jnp.zeros((b, h, tq, d), jnp.float32)
    m = jnp.full((b, h, tq, 1), neg, jnp.float32)
    l = jnp.zeros((b, h, tq, 1), jnp.float32)
    state = (o, m, l, k, v, kv_mask)
    # python loop: n is static; unrolled ring lets XLA overlap the
    # ppermute of step s+1's block with step s's matmuls
    for s in range(n):
        state = step(s, state)
    o, m, l = state[0], state[1], state[2]
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str = "sp",
                      causal: bool = False,
                      kv_mask: Optional[jax.Array] = None):
    """Ulysses-style context parallelism; call inside shard_map.

    All-to-all re-shards [B, H, T/sp, D] (tokens sharded) into
    [B, H/sp, T, D] (heads sharded), runs dense attention on the full
    sequence locally, and swaps back. Requires sp | H.
    """
    n = lax.axis_size(axis_name)
    b, h, t_loc, d = q.shape
    if h % n != 0:
        raise ValueError(f"ulysses needs sp|heads: {n} heads {h}")

    def a2a_fwd(x):  # [B,H,Tl,D] -> [B,H/n,T,D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def a2a_bwd(x):  # [B,H/n,T,D] -> [B,H,Tl,D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qg, kg, vg = a2a_fwd(q), a2a_fwd(k), a2a_fwd(v)
    t = qg.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    logits = jnp.einsum("bhqd,bhkd->bhqk", qg.astype(jnp.float32),
                        kg.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * scale
    neg = jnp.float32(-1e30)
    if causal:
        qp = lax.broadcasted_iota(jnp.int32, (t, t), 0)
        kp = lax.broadcasted_iota(jnp.int32, (t, t), 1)
        logits = logits + jnp.where(kp <= qp, 0.0, neg)[None, None]
    if kv_mask is not None:
        full_mask = lax.all_gather(kv_mask, axis_name, axis=1, tiled=True)
        logits = logits + jnp.where(full_mask.astype(bool), 0.0,
                                    neg)[:, None, None, :]
    w = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", w, vg.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return a2a_bwd(ctx.astype(q.dtype))


def dense_attention(q, k, v, causal: bool = False,
                    kv_mask: Optional[jax.Array] = None):
    """Single-device reference used by tests and the unsharded path."""
    d = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * scale
    neg = jnp.float32(-1e30)
    t, tk = logits.shape[-2], logits.shape[-1]
    if causal:
        qp = lax.broadcasted_iota(jnp.int32, (t, tk), 0)
        kp = lax.broadcasted_iota(jnp.int32, (t, tk), 1)
        logits = logits + jnp.where(kp <= qp, 0.0, neg)[None, None]
    if kv_mask is not None:
        logits = logits + jnp.where(kv_mask.astype(bool), 0.0,
                                    neg)[:, None, None, :]
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w,
                      v.astype(jnp.float32)).astype(q.dtype)
