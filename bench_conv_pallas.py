"""Interleaved A/B: Pallas conv+BN-stats fused kernels vs XLA
conv -> batch-norm stats, on the real ResNet-50 shapes (batch 256,
bf16). Methodology per BASELINE.md: both variants compiled in ONE
process, alternated across repeats, min-of-k windows, device-resident
inputs, a device->host read closing every window.

Run: python bench_conv_pallas.py   (needs the TPU; run alone)
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops.conv_pallas import (conv1x1_bn_stats,
                                                conv3x3_bn_stats)

# (kind, N, H, W, Cin, Cout) — every stride-1 conv class in the
# ResNet-50 bottleneck stacks at batch 256
SHAPES = [
    ("1x1", 256, 56, 56, 64, 64),
    ("1x1", 256, 56, 56, 64, 256),
    ("1x1", 256, 56, 56, 256, 64),
    ("1x1", 256, 28, 28, 512, 128),
    ("1x1", 256, 28, 28, 128, 512),
    ("1x1", 256, 14, 14, 1024, 256),
    ("1x1", 256, 14, 14, 256, 1024),
    ("1x1", 256, 7, 7, 2048, 512),
    ("1x1", 256, 7, 7, 512, 2048),
    ("3x3", 256, 56, 56, 64, 64),
    ("3x3", 256, 28, 28, 128, 128),
    ("3x3", 256, 14, 14, 256, 256),
    ("3x3", 256, 7, 7, 512, 512),
]

REPS = 4
ITERS = 100   # in-jit scan iterations: amortize per-call dispatch


def _xla_1x1(x, w):
    y = jnp.einsum("nhwc,cd->nhwd", x, w)
    yf = y.astype(jnp.float32)
    return y, yf.mean((0, 1, 2)), yf.var((0, 1, 2))


def _xla_3x3(x, w):
    y = jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    yf = y.astype(jnp.float32)
    return y, yf.mean((0, 1, 2)), yf.var((0, 1, 2))


def _xla_conv_only_1x1(x, w):
    y = jnp.einsum("nhwc,cd->nhwd", x, w)
    return y, jnp.zeros(w.shape[-1]), jnp.zeros(w.shape[-1])


def _xla_conv_only_3x3(x, w):
    y = jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y, jnp.zeros(w.shape[-1]), jnp.zeros(w.shape[-1])


def _looped(fn):
    """scan(ITERS) with a structural data dependency: the (small)
    weight is perturbed by a tiny carry derived from the previous
    iteration's outputs, so XLA can neither hoist the body (LICM) nor
    collapse iterations; an optimization_barrier forces the full conv
    output tensor to materialize each step, matching the real network
    (the BN-apply consumes it)."""

    @jax.jit
    def run(x, w):
        def body(c, _):
            y, m, v = fn(x, w + c)
            y = jax.lax.optimization_barrier(y)
            t = (y.reshape(-1)[0].astype(jnp.float32)
                 + jnp.sum(m) + jnp.sum(v))
            return (t * 1e-30).astype(w.dtype), None

        c, _ = jax.lax.scan(body, jnp.zeros((), w.dtype), None,
                            length=ITERS)
        return c.astype(jnp.float32)

    return run


def _time(run, x, w):
    float(run(x, w))   # compile + sync (device->host read)
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        float(run(x, w))
        best = min(best, (time.perf_counter() - t0) / ITERS)
    return best * 1e3


def main():
    rs = np.random.RandomState(0)
    results = []
    for kind, n, h, wd, cin, cout in SHAPES:
        x = jax.device_put(jnp.asarray(
            rs.randn(n, h, wd, cin) * 0.5, jnp.bfloat16))
        if kind == "1x1":
            w = jax.device_put(jnp.asarray(
                rs.randn(cin, cout) * 0.05, jnp.bfloat16))
            pal, ref, conv_only = (conv1x1_bn_stats, _xla_1x1,
                                   _xla_conv_only_1x1)
        else:
            w = jax.device_put(jnp.asarray(
                rs.randn(3, 3, cin, cout) * 0.05, jnp.bfloat16))
            pal, ref, conv_only = (conv3x3_bn_stats, _xla_3x3,
                                   _xla_conv_only_3x3)
        yp, mp, vp = pal(x, w)
        yr, mr, vr = jax.jit(ref)(x, w)
        jax.block_until_ready(vr)
        err = float(jnp.abs(mp - mr).max() + jnp.abs(vp - vr).max())
        run_p, run_x, run_c = (_looped(pal), _looped(ref),
                               _looped(conv_only))
        # interleave: p, x, c, p, x, c
        t_p = _time(run_p, x, w)
        t_x = _time(run_x, x, w)
        t_c = _time(run_c, x, w)
        t_p = min(t_p, _time(run_p, x, w))
        t_x = min(t_x, _time(run_x, x, w))
        t_c = min(t_c, _time(run_c, x, w))
        r = {"kind": kind, "shape": [n, h, wd, cin, cout],
             "pallas_fused_ms": round(t_p, 4),
             "xla_conv_stats_ms": round(t_x, 4),
             "xla_conv_only_ms": round(t_c, 4),
             "stats_cost_ms": round(t_x - t_c, 4),
             "speedup_vs_xla": round(t_x / t_p, 3),
             "stats_err": round(err, 5)}
        results.append(r)
        print(json.dumps(r))
    tot_p = sum(r["pallas_fused_ms"] for r in results)
    tot_x = sum(r["xla_conv_stats_ms"] for r in results)
    tot_c = sum(r["xla_conv_only_ms"] for r in results)
    print(json.dumps({"total_pallas_ms": round(tot_p, 3),
                      "total_xla_conv_stats_ms": round(tot_x, 3),
                      "total_xla_conv_only_ms": round(tot_c, 3),
                      "overall_speedup": round(tot_x / tot_p, 3)}))


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    main()
