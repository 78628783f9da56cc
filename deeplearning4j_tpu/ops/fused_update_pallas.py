"""Fused master-update Pallas kernel: loss-scale unscale + global-norm
clip + Adam bias-corrected update in ONE pass over a contiguous
flattened fp32 master shard.

Reference role: the cuDNN fused-primitives playbook (Chetlur et al.,
arXiv:1410.0759) applied to the OPTIMIZER, as called for by
"Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (Xu et al., arXiv:2004.13336): once the weight update is
sharded across replicas, each replica touches a contiguous 1/N slice
of the fp32 masters + Adam moments, and the whole update —

    g_eff   = grad * inv_scale * clip_coef        (unscale + clip)
    m'      = b1*m + (1-b1)*g_eff                 (first moment)
    v'      = b2*v + (1-b2)*g_eff^2               (second moment)
    master' = master - alpha * m' / (sqrt(v')+eps)

with ``alpha = lr * sqrt(1-b2^t) / (1-b1^t)`` (the bias-corrected step
size, ``updaters._step_float`` semantics) — is a single elementwise
pass. XLA schedules it as several fusions that round-trip the four
vectors through HBM; this kernel reads grad/m/v/master ONCE and writes
m'/v'/master' ONCE.

Numerics contract: bit-for-float identical to composing
``precision.unscale_grads`` -> global-norm clip ->
``updaters.Adam.apply`` -> ``p - u`` on the same flat f32 vector (the
golden test in tests/test_update_sharding.py checks step 300, where a
half-precision bias-correction power would long since have decayed —
see ``updaters._step_float``).

Dispatch (``fused_update_mode()``):
- ``pallas``    — real TPU backend: the kernel above.
- ``interpret`` — forced via ``DL4J_TPU_FUSED_UPDATE=interpret``: the
  same kernel through the Pallas interpreter (CPU-testable path for
  the kernel + shard_map plumbing).
- ``xla``       — everything else (CPU/GPU, or
  ``DL4J_TPU_FUSED_UPDATE=xla``): the identical jnp formula; XLA fuses
  it well enough off-TPU and the numerics are the same by
  construction.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.learning.updaters import Adam, _step_float
from deeplearning4j_tpu.ops.registry import register_op

_LANE = 128


def fused_update_mode() -> str:
    """'pallas' | 'interpret' | 'xla' — see module docstring."""
    env = os.environ.get("DL4J_TPU_FUSED_UPDATE", "auto").strip().lower()
    if env in ("pallas", "interpret", "xla"):
        return env
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def adam_update_scalars(updater: Adam, step, inv_scale=None,
                        clip_norm=None, grad_norm=None):
    """The two per-step scalars the fused kernel consumes, as one (2,)
    f32 array ``[gscale, alpha]``:

    - ``gscale`` — the combined gradient multiplier: loss-scale
      unscale (``1/scale``) times the global-norm clip coefficient
      ``min(1, clip_norm / ||unscaled_grad||)``. Either factor defaults
      to 1 when its feature is off.
    - ``alpha`` — Adam's bias-corrected step size at ``step``
      (``updaters.Adam.apply`` formula, f32 powers per ``_step_float``).

    ``grad_norm`` is the norm of the (still-scaled) gradient vector;
    required when ``clip_norm`` is set.
    """
    gscale = jnp.asarray(1.0, jnp.float32)
    inv = None
    if inv_scale is not None:
        inv = jnp.asarray(inv_scale, jnp.float32)
        gscale = gscale * inv
    if clip_norm is not None:
        if grad_norm is None:
            raise ValueError("clip_norm requires grad_norm")
        unscaled = jnp.asarray(grad_norm, jnp.float32)
        if inv is not None:
            unscaled = unscaled * inv
        gscale = gscale * jnp.minimum(
            1.0, jnp.asarray(clip_norm, jnp.float32)
            / jnp.maximum(unscaled, 1e-12))
    lr = updater._lr(step)
    tf = _step_float(step + 1)
    bc1 = 1 - jnp.power(jnp.float32(updater.beta1), tf)
    bc2 = 1 - jnp.power(jnp.float32(updater.beta2), tf)
    alpha = jnp.asarray(lr, jnp.float32) * jnp.sqrt(bc2) / bc1
    return jnp.stack([gscale, alpha])


# ------------------------------------------------------------- formula
def _formula(master, m, v, grad, gscale, alpha, beta1, beta2, eps):
    """The reference jnp math (XLA fallback; also the kernel's spec)."""
    g = grad.astype(jnp.float32) * gscale
    m2 = beta1 * m + (1 - beta1) * g
    v2 = beta2 * v + (1 - beta2) * g * g
    upd = alpha * m2 / (jnp.sqrt(v2) + eps)
    return (master - upd.astype(master.dtype)), m2, v2


# -------------------------------------------------------------- kernel
def _k_fused(sc_ref, g_ref, m_ref, v_ref, p_ref, om_ref, ov_ref,
             op_ref, *, beta1, beta2, eps):
    gs = sc_ref[0]
    al = sc_ref[1]
    g = g_ref[...].astype(jnp.float32) * gs
    m = beta1 * m_ref[...] + (1 - beta1) * g
    v = beta2 * v_ref[...] + (1 - beta2) * g * g
    om_ref[...] = m
    ov_ref[...] = v
    op_ref[...] = (p_ref[...]
                   - (al * m / (jnp.sqrt(v) + eps)).astype(op_ref.dtype))


def _pallas_update(master, m, v, grad, scalars, beta1, beta2, eps,
                   interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = master.shape[0]
    rows = n // _LANE
    as2d = lambda a: a.reshape(rows, _LANE)
    # VMEM budget: 7 row-block buffers (4 in + 3 out) double-buffered
    # in f32 — cap each at ~512 KB so the working set stays well under
    # the ~16 MB VMEM even with pipelining. Mosaic wants the row block
    # a multiple of 8 or the whole array, so the block is the cap (or
    # all rows) and the last grid step may be partial: its
    # out-of-range rows are read as padding and never written back,
    # which an elementwise kernel does not notice.
    bm = min(rows, (512 * 1024) // (4 * _LANE))
    grid = (pl.cdiv(rows, bm),)
    row_spec = pl.BlockSpec((bm, _LANE), lambda i: (i, 0))
    sc_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    # out_shape order matches the kernel's out refs: (m', v', master')
    out_m, out_v, out_p = pl.pallas_call(
        functools.partial(_k_fused, beta1=beta1, beta2=beta2, eps=eps),
        grid=grid,
        in_specs=[sc_spec, row_spec, row_spec, row_spec, row_spec],
        out_specs=[row_spec, row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANE), jnp.float32),
                   jax.ShapeDtypeStruct((rows, _LANE), jnp.float32),
                   jax.ShapeDtypeStruct((rows, _LANE), master.dtype)],
        interpret=interpret,
    )(scalars.astype(jnp.float32), as2d(grad), as2d(m), as2d(v),
      as2d(master))
    return (out_p.reshape(n), out_m.reshape(n), out_v.reshape(n))


# ------------------------------------------------------------ dispatch
def adam_segment_update(master, m, v, grad, scalars, *, beta1, beta2,
                        eps, mode=None):
    """One fused update pass over a contiguous flat segment (the
    per-replica master shard). ``scalars`` is ``[gscale, alpha]`` from
    :func:`adam_update_scalars`. Returns ``(master', m', v')``.

    ``mode`` overrides :func:`fused_update_mode` (tests)."""
    mode = mode or fused_update_mode()
    if mode == "xla":
        return _formula(master, m, v, grad, scalars[0], scalars[1],
                        beta1, beta2, eps)
    n = master.shape[0]
    pad = (-n) % _LANE
    if pad:
        zpad = lambda a: jnp.concatenate(
            [a, jnp.zeros((pad,), a.dtype)])
        master, m, v, grad = map(zpad, (master, m, v, grad))
    out = _pallas_update(master, m, v, grad, scalars, beta1, beta2,
                         eps, interpret=(mode == "interpret"))
    if pad:
        out = tuple(a[:n] for a in out)
    return out


def fused_master_update(master, m, v, grad, step, updater: Adam,
                        inv_scale=None, clip_norm=None, grad_norm=None,
                        mode=None):
    """Convenience entry: scalars + segment update in one call (the
    golden-test surface; the sharded trainer composes the two pieces
    itself so the scalar math runs once per step, not per shard)."""
    if type(updater) is not Adam:
        raise TypeError(
            f"fused_master_update implements the Adam formula; got "
            f"{type(updater).__name__} (use the generic flat-updater "
            "path)")
    if clip_norm is not None and grad_norm is None:
        grad_norm = jnp.sqrt(
            jnp.sum(grad.astype(jnp.float32) ** 2))
    sc = adam_update_scalars(updater, step, inv_scale=inv_scale,
                             clip_norm=clip_norm, grad_norm=grad_norm)
    return adam_segment_update(master, m, v, grad, sc,
                               beta1=updater.beta1, beta2=updater.beta2,
                               eps=updater.epsilon, mode=mode)


@register_op("fused_adam_master_update")
def _op(master, m, v, grad, step, updater, inv_scale=None,
        clip_norm=None, grad_norm=None, mode=None):
    return fused_master_update(master, m, v, grad, step, updater,
                               inv_scale=inv_scale, clip_norm=clip_norm,
                               grad_norm=grad_norm, mode=mode)
