"""Interleaved A/B: TRAINING step through the Pallas LSTM recurrence
(custom VJP, reverse-time recompute scan) vs the lax.scan path.

VERDICT r3 item #6: the round-3 kernel was forward-only, so the one
config class where it wins (H>=512) couldn't use it for training — the
CudnnLSTMHelper role (SURVEY.md §2.9) it exists to fill. This measures
value_and_grad + SGD through ``lstm_layer(impl=...)`` at the round-3
A/B shapes, same methodology (one process, alternated repeats,
min-of-k windows, in-jit scan iterations to amortize per-call
dispatch, device->host read closing each window).

Run: python bench_lstm_train_ab.py   (needs the TPU; run alone)
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops.nn import lstm_layer

# (N, T, H) — the round-3 forward A/B shapes (BASELINE.md)
SHAPES = [
    (256, 200, 256),
    (512, 200, 512),
    (256, 200, 1024),
]
REPS = 6
ITERS = 20


def make_step(impl, n, t, h, dtype):
    def loss_fn(params, x, tgt):
        w_ih, w_hh, b = params
        ys, (hT, cT) = lstm_layer(x, w_ih, w_hh, b, impl=impl)
        return jnp.mean((ys.astype(jnp.float32)
                         - tgt.astype(jnp.float32)) ** 2)

    grad_fn = jax.value_and_grad(loss_fn)

    @jax.jit
    def run(params, x, tgt):
        def body(p, _):
            loss, g = grad_fn(p, x, tgt)
            p2 = jax.tree.map(lambda a, b: a - 1e-3 * b.astype(a.dtype),
                              p, g)
            return p2, loss

        params2, losses = jax.lax.scan(body, params,
                                       jnp.arange(ITERS))
        return params2, losses[-1]

    return run


def main():
    dtype = jnp.bfloat16
    rng = np.random.default_rng(0)
    print(f"# devices: {jax.devices()}")
    rows = []
    for n, t, h in SHAPES:
        x = jax.device_put(jnp.asarray(
            rng.normal(0, 0.5, (n, t, h)), dtype))
        tgt = jax.device_put(jnp.asarray(
            rng.normal(0, 0.5, (n, t, h)), dtype))
        params = tuple(jax.device_put(v) for v in (
            jnp.asarray(rng.normal(0, 0.05, (h, 4 * h)), dtype),
            jnp.asarray(rng.normal(0, 0.05, (h, 4 * h)), dtype),
            jnp.zeros((4 * h,), dtype)))
        steps = {k: make_step(k, n, t, h, dtype)
                 for k in ("scan", "pallas")}
        # compile + numerics pin
        outs = {}
        for k, fn in steps.items():
            p2, loss = fn(params, x, tgt)
            jax.block_until_ready(p2)
            outs[k] = float(loss)
        rel = abs(outs["scan"] - outs["pallas"]) / max(
            abs(outs["scan"]), 1e-9)
        best = {"scan": float("inf"), "pallas": float("inf")}
        for _ in range(REPS):
            for k in ("scan", "pallas"):
                t0 = time.perf_counter()
                p2, loss = steps[k](params, x, tgt)
                jax.block_until_ready(p2)
                dt = (time.perf_counter() - t0) / ITERS
                best[k] = min(best[k], dt)
        row = {"shape": f"{n}x{t}x{h}",
               "scan_ms": round(best["scan"] * 1e3, 2),
               "pallas_ms": round(best["pallas"] * 1e3, 2),
               "speedup": round(best["scan"] / best["pallas"], 3),
               "loss_rel_diff": f"{rel:.2e}"}
        rows.append(row)
        print(json.dumps(row))
    print(json.dumps({"metric": "lstm_train_ab", "rows": rows}))


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    main()
