"""Flash / memory-efficient attention for the transformer hot path.

Reference: the reference's attention is the vanilla O(L^2)-memory
multiHeadDotProductAttention op (SURVEY.md §5 long-context: "vanilla
O(L²)"); it has no flash path at all. This module is the TPU-native
upgrade that slots under the same seam (TransformerEncoder attn_fn,
SelfAttentionLayer op):

Three implementations, one dispatcher:

1. `pallas_flash_forward` — in-repo Pallas TPU kernel: online-softmax
   streaming over K/V blocks, one grid step per (batch*head, q-block),
   K/V resident in VMEM, logits never materialized in HBM. Used via
   `flash_attention` (custom_vjp) whose backward recomputes with the
   blockwise path (O(T) memory both directions).
2. `blockwise_attention` — pure-jax lax.scan online softmax. Same
   memory behavior (XLA keeps only one [bq, bk] logits tile live per
   step), runs on any backend; it is both the CPU fallback and the
   recompute backward.
3. jax's library Pallas kernel (jax.experimental.pallas.ops.tpu.
   flash_attention) — fwd AND bwd as tuned kernels; preferred on TPU
   when shapes meet its block constraints.

`attention(q, k, v, mask, impl="auto")` picks: library kernel on TPU
(aligned shapes) → in-repo flash → blockwise.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from deeplearning4j_tpu.ops.registry import register_op

_NEG_INF = -1e30  # large-but-finite: keeps masked softmax NaN-free


# ======================================================================
# 1. blockwise (pure jax) — fallback + recompute backward
# ======================================================================
def blockwise_attention(q, k, v, mask=None, causal: bool = False,
                        block_k: int = 256, scale: Optional[float] = None):
    """Online-softmax attention scanning K/V in blocks.

    q,k,v: [N,H,T,dh]; mask: [N,Tk] key-padding (1=valid) or
    broadcastable [N,1,1,Tk]. Returns [N,H,Tq,dh].
    """
    n, h, tq, dh = q.shape
    tk = k.shape[2]
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    orig_dtype = q.dtype
    qf = (q * scale).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    pad = (-tk) % block_k
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nblk = (tk + pad) // block_k

    if mask is not None and mask.ndim == 4:
        mask = mask[:, 0, 0, :]
    key_valid = jnp.ones((n, tk), jnp.float32) if mask is None \
        else mask.astype(jnp.float32)
    if pad:
        key_valid = jnp.pad(key_valid, ((0, 0), (0, pad)))

    kf = kf.reshape(n, h, nblk, block_k, dh).transpose(2, 0, 1, 3, 4)
    vf = vf.reshape(n, h, nblk, block_k, dh).transpose(2, 0, 1, 3, 4)
    key_valid = key_valid.reshape(n, nblk, block_k).transpose(1, 0, 2)

    q_pos = jnp.arange(tq)[:, None]

    def step(carry, blk):
        m, l, acc = carry
        kb, vb, valid, bi = blk
        s = jnp.einsum("nhqd,nhkd->nhqk", qf, kb)
        s = jnp.where(valid[:, None, None, :] > 0, s, _NEG_INF)
        if causal:
            k_pos = bi * block_k + jnp.arange(block_k)[None, :]
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum("nhqk,nhkd->nhqd", p, vb)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((n, h, tq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((n, h, tq, 1), jnp.float32)
    a0 = jnp.zeros((n, h, tq, dh), jnp.float32)
    (m, l, acc), _ = lax.scan(
        step, (m0, l0, a0),
        (kf, vf, key_valid, jnp.arange(nblk)))
    return (acc / jnp.maximum(l, 1e-30)).astype(orig_dtype)


# ======================================================================
# 2. in-repo Pallas forward kernel
# ======================================================================
def _flash_fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, *,
                      block_k: int, scale: float, causal: bool,
                      block_q: int):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # [bq, dh]
    tk = k_ref.shape[1]
    nblk = tk // block_k
    bq = q.shape[0]

    def body(i, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32)
        valid = mask_ref[0, pl.ds(i * block_k, block_k)] > 0
        s = jnp.where(valid[None, :], s, _NEG_INF)
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 0)
            k_pos = i * block_k + lax.broadcasted_iota(
                jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.dot(p, vb,
                                        preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    dh = q.shape[-1]
    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, dh), jnp.float32)
    m, l, acc = lax.fori_loop(0, nblk, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def pallas_flash_forward(q, k, v, mask=None, causal: bool = False,
                         block_q: int = 128, block_k: int = 128,
                         scale: Optional[float] = None,
                         interpret: bool = False):
    """Forward-only Pallas flash attention (see module docstring).

    Requires Tq % block_q == 0 and Tk % block_k == 0 (dispatcher pads);
    grid = (N*H, Tq/block_q); each step streams K/V of one (n,h) pair
    through VMEM in block_k chunks.
    """
    n, h, tq, dh = q.shape
    tk = k.shape[2]
    if scale is None:
        scale = 1.0 / (dh ** 0.5)
    if tq % block_q or tk % block_k:
        raise ValueError(f"shape not block-aligned: Tq={tq}, Tk={tk}")
    if mask is None:
        mask_arr = jnp.ones((n, tk), jnp.float32)
    else:
        mask_arr = (mask[:, 0, 0, :] if mask.ndim == 4 else mask) \
            .astype(jnp.float32)

    qr = q.reshape(n * h, tq, dh)
    kr = k.reshape(n * h, tk, dh)
    vr = v.reshape(n * h, tk, dh)

    kernel = functools.partial(_flash_fwd_kernel, block_k=block_k,
                               scale=scale, causal=causal, block_q=block_q)
    out = pl.pallas_call(
        kernel,
        grid=(n * h, tq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, tk, dh), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, tk, dh), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, tk), lambda b, i: (b // h, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n * h, tq, dh), q.dtype),
        interpret=interpret,
    )(qr, kr, vr, mask_arr)
    return out.reshape(n, h, tq, dh)


# custom_vjp: Pallas forward, blockwise recompute backward
@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flash(q, k, v, mask, causal):
    return pallas_flash_forward(q, k, v, mask, causal=causal)


def _flash_fwd(q, k, v, mask, causal):
    return pallas_flash_forward(q, k, v, mask, causal=causal), \
        (q, k, v, mask)


def _flash_bwd(causal, res, g):
    q, k, v, mask = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: blockwise_attention(q_, k_, v_, mask,
                                               causal=causal), q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


# ======================================================================
# 3. dispatcher
# ======================================================================
def _library_block_sizes(tq, tk):
    """Tuned block sizes for the library kernel. Its built-in defaults
    collapse at long sequence (measured on v5e, T=2048 batch 24:
    44.2 ms default vs 10.1 ms with these blocks vs 16.5 ms XLA naive
    — the default-blocks kernel LOSES 2.7x to XLA, the tuned one wins
    1.6x; BASELINE.md 'flash attention re-measured, round 5')."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
    )

    # the kernel requires seq_len % block == 0 — take the largest
    # power-of-two block that divides (T=1536 must get 512/256, not
    # crash on 1024)
    bq = next(b for b in (512, 256, 128) if tq % b == 0)
    bkv = next(b for b in (1024, 512, 256, 128) if tk % b == 0)
    return BlockSizes(
        block_q=bq, block_k_major=bkv, block_k=bkv, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bkv,
        block_k_dkv=bkv, block_q_dkv=bq,
        block_k_major_dq=bkv, block_k_dq=bkv, block_q_dq=bq)


def _library_flash(q, k, v, mask, causal):
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        SegmentIds, flash_attention as lib_flash,
    )
    dh = q.shape[-1]
    seg = None
    if mask is not None:
        m2 = (mask[:, 0, 0, :] if mask.ndim == 4 else mask)
        kv_seg = jnp.where(m2.astype(bool), 0, 1).astype(jnp.int32)
        q_seg = jnp.zeros((q.shape[0], q.shape[2]), jnp.int32)
        seg = SegmentIds(q=q_seg, kv=kv_seg)
    return lib_flash(q, k, v, segment_ids=seg, causal=causal,
                     sm_scale=1.0 / (dh ** 0.5),
                     block_sizes=_library_block_sizes(q.shape[2],
                                                      k.shape[2]))


def _xla_attention(q, k, v, mask, causal):
    """Plain fused-softmax attention — what XLA compiles best at short
    sequence (measured: beats every flash variant below ~1024 on v5e)."""
    dh = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, q.dtype))
    logits = jnp.einsum("nhqd,nhkd->nhqk", q, k) * scale
    neg = jnp.asarray(_NEG_INF, logits.dtype)
    if mask is not None:
        m4 = mask if mask.ndim == 4 else mask[:, None, None, :]
        logits = jnp.where(m4.astype(bool), logits, neg)
    if causal:
        tq, tk = q.shape[2], k.shape[2]
        cm = jnp.tril(jnp.ones((tq, tk), bool), tk - tq)
        logits = jnp.where(cm[None, None], logits, neg)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("nhqk,nhkd->nhqd", w, v)


@register_op("flash_attention")
def attention(q, k, v, mask=None, causal: bool = False,
              impl: str = "auto"):
    """Dispatching flash attention. q,k,v: [N,H,T,dh]; mask: [N,Tk]
    key-padding (1 = attend). impl: auto | library | pallas | blockwise.
    """
    n, h, tq, dh = q.shape
    tk = k.shape[2]
    on_tpu = jax.default_backend() == "tpu"

    if impl == "auto":
        aligned = tq % 128 == 0 and tk % 128 == 0 and dh >= 64
        if on_tpu and tk < 1024:
            # short-KV: XLA's fused softmax attention beats every
            # flash variant (v5e measurements, BASELINE.md round 5 —
            # T=512 fwd: 4.0 ms XLA vs 6.1 ms best-tuned flash), and
            # the O(tq*tk) logits stay small when the KV side is
            # short. Routing is on KV length: a short-QUERY call
            # against a long KV (cross-attention, cached decode) is
            # exactly where flash's no-materialization matters, so it
            # must NOT fall through to the einsum path.
            impl = "xla"
        elif on_tpu and aligned:
            impl = "library"
        elif on_tpu and tq % 128 == 0 and tk % 128 == 0:
            impl = "pallas"
        else:
            impl = "blockwise"
    if impl == "xla":
        return _xla_attention(q, k, v, mask, causal)
    if impl == "library":
        return _library_flash(q, k, v, mask, causal)
    if impl == "pallas":
        return _flash(q, k, v, mask, causal)
    if impl == "blockwise":
        return blockwise_attention(q, k, v, mask, causal=causal)
    raise ValueError(f"unknown impl {impl!r}")


__all__ = ["attention", "blockwise_attention", "pallas_flash_forward"]
