"""Interleaved A/B: Pallas residual-tail kernel (BN-apply+ReLU+add in
one pass) vs XLA's own scheduling of the same tail after a real conv —
the round-5 probe VERDICT r4 #1(b) named (the 11 ms residual-add ledger
category + share of the 17.4 ms mask traffic).

Both sides run `conv1x1 -> tail` so the conv/tail fusion BOUNDARY
matches the real network (in one bare elementwise jit XLA trivially
fuses the whole tail and there is nothing to measure). Forward AND
train (value_and_grad) variants; methodology per BASELINE.md /
bench_conv_pallas.py: one process, in-jit scan with a structural
carry->weight dependency (LICM-proof), optimization_barrier after the
conv, device->host read closing every window, alternated min-of-k.

Run: python bench_residual_tail.py   (needs the TPU; run alone)
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops.residual_tail_pallas import (
    _ref_formula, bn_relu_residual,
)

# (N, H, W, C) — the four residual-join shapes of batch-256 ResNet-50
SHAPES = [
    (256, 56, 56, 256),
    (256, 28, 28, 512),
    (256, 14, 14, 1024),
    (256, 7, 7, 2048),
]

REPS = 6
ITERS = 50


def _make_sides(c):
    def conv(x, w):
        return jnp.einsum("nhwc,cd->nhwd", x, w)

    def xla_side(x, res, w, mean, var, gamma, beta):
        y = jax.lax.optimization_barrier(conv(x, w))
        return _ref_formula(y, res, mean, var, gamma, beta, 1e-5)

    def pal_side(x, res, w, mean, var, gamma, beta):
        y = jax.lax.optimization_barrier(conv(x, w))
        return bn_relu_residual(y, res, mean, var, gamma, beta)

    return xla_side, pal_side


def _looped_fwd(fn):
    @jax.jit
    def run(x, res, w, args):
        def body(c, _):
            out = fn(x, res, w + c, *args)
            t = out.reshape(-1)[0].astype(jnp.float32)
            return (t * 1e-30).astype(w.dtype), None

        c, _ = jax.lax.scan(body, jnp.zeros((), w.dtype), None,
                            length=ITERS)
        return c.astype(jnp.float32)

    return run


def _looped_train(fn):
    @jax.jit
    def run(x, res, w, args):
        def loss(w_):
            out = fn(x, res, w_, *args)
            return jnp.sum(out.astype(jnp.float32) ** 2) * 1e-6

        def body(c, _):
            v, g = jax.value_and_grad(loss)(w + c)
            t = v + g.reshape(-1)[0].astype(jnp.float32)
            return (t * 1e-30).astype(w.dtype), None

        c, _ = jax.lax.scan(body, jnp.zeros((), w.dtype), None,
                            length=ITERS)
        return c.astype(jnp.float32)

    return run


def _time(run, *a):
    float(run(*a))   # compile + sync (device->host read)
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        float(run(*a))
        best = min(best, (time.perf_counter() - t0) / ITERS)
    return best * 1e3


def main():
    rs = np.random.RandomState(0)
    results = []
    for n, h, wd, c in SHAPES:
        x = jax.device_put(jnp.asarray(
            rs.randn(n, h, wd, c) * 0.5, jnp.bfloat16))
        res = jax.device_put(jnp.asarray(
            rs.randn(n, h, wd, c) * 0.5, jnp.bfloat16))
        w = jax.device_put(jnp.asarray(
            rs.randn(c, c) * 0.05, jnp.bfloat16))
        args = tuple(jax.device_put(jnp.asarray(v, jnp.float32)) for v in
                     (rs.randn(c) * 0.1, rs.rand(c) + 0.5,
                      rs.rand(c) + 0.5, rs.randn(c) * 0.1))
        xla_side, pal_side = _make_sides(c)
        # numerics pin before timing
        a = np.asarray(jax.jit(xla_side)(x, res, w, *args),
                       np.float32)
        b = np.asarray(jax.jit(pal_side)(x, res, w, *args),
                       np.float32)
        err = float(np.abs(a - b).max())
        row = {"shape": [n, h, wd, c], "max_err": round(err, 5)}
        for nm, loop in (("fwd", _looped_fwd), ("train", _looped_train)):
            rx, rp = loop(xla_side), loop(pal_side)
            t_x = _time(rx, x, res, w, args)
            t_p = _time(rp, x, res, w, args)
            t_x = min(t_x, _time(rx, x, res, w, args))
            t_p = min(t_p, _time(rp, x, res, w, args))
            row[f"xla_{nm}_ms"] = round(t_x, 4)
            row[f"pallas_{nm}_ms"] = round(t_p, 4)
            row[f"{nm}_speedup"] = round(t_x / t_p, 3)
        results.append(row)
        print(json.dumps(row))
    for nm in ("fwd", "train"):
        tx = sum(r[f"xla_{nm}_ms"] for r in results)
        tp = sum(r[f"pallas_{nm}_ms"] for r in results)
        print(json.dumps({f"total_xla_{nm}_ms": round(tx, 3),
                          f"total_pallas_{nm}_ms": round(tp, 3),
                          f"overall_{nm}_speedup": round(tx / tp, 3)}))


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    main()
