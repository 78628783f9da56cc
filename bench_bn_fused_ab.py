"""Interleaved A/B: fused BN+ReLU backward (custom_vjp, mask recomputed
in-fusion) vs XLA autodiff, on the FULL ResNet-50 train step.

Round-4 attack on the byte ledger's backward-traffic categories
(BASELINE.md): autodiff emits relu-bwd (read y, read g, write g'),
then BN reductions (read g', read x), then dx (read g', read x,
write dx) — the masked gradient g' round-trips HBM twice. The fused
backward (ops/nn.py batch_norm_relu_train) recomputes the mask and
x-hat inline in both backward fusions, so g' is never materialized:
~10 B/elem instead of ~16 B/elem for every conv->BN->ReLU block
(33 of ResNet-50's 49 ReLUs; the post-residual ReLUs keep autodiff
because their masked gradient fans out to two consumers and must
materialize anyway).

Methodology: one process, two compiled steps (module flag flipped at
trace time), identical seed/params/batch, alternated windows of
in-graph steps, min-of-k, every window closed by a device->host loss
read; plus cost_analysis() bytes/flops for both executables — the
byte delta is the noise-free half of the evidence.

Run: python bench_bn_fused_ab.py   (needs the TPU; run alone)
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

import deeplearning4j_tpu.ops.nn as nnops
from bench_resnet import build, _cost_analysis_flops


def _cost_analysis_bytes(compiled):
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    b = ca.get("bytes accessed")
    return float(b) if b else None


def make_side(fused: bool, batch: int, classes: int, dtype: str):
    nnops.FUSED_BN_RELU_BWD = fused
    net = build(classes, dtype, False, False)
    dt = net._dtype
    rng = np.random.default_rng(0)
    x = jax.device_put(jnp.asarray(
        rng.normal(0, 1, (batch, 224, 224, 3)), dt))
    y = jax.device_put(jnp.asarray(
        np.eye(classes, dtype=np.float32)[
            rng.integers(0, classes, batch)], dt))
    conf = net.conf
    inputs = {conf.network_inputs[0]: x}
    labels = {conf.network_outputs[0]: y}
    step = net._get_train_step()
    low = step.lower(net.params_map, net.states_map, net.opt_states,
                     jnp.asarray(0), jnp.asarray(0), inputs, labels,
                     {}, {}, jax.random.key(0))
    comp = low.compile()
    state = (net.params_map, net.states_map, net.opt_states)

    def run(state, i):
        p, s, o, loss = step(state[0], state[1], state[2],
                             jnp.asarray(i), jnp.asarray(0), inputs,
                             labels, {}, {}, jax.random.key(i))
        return (p, s, o), loss

    # CRITICAL: trace the jit dispatch cache NOW, while the module flag
    # still holds this side's value — jit traces lazily at first call,
    # and by warmup time the flag holds the LAST side's value (a first
    # version of this bench timed fused-vs-fused because of exactly
    # this; the AOT .lower().compile() above does not seed the cache).
    state, loss = run(state, 0)
    float(jnp.mean(loss))

    return {"run": run, "state": state, "compiled": comp}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--classes", type=int, default=1000)
    ap.add_argument("--dtype", default="bf16")
    args = ap.parse_args()

    sides = {}
    for name, fused in (("autodiff", False), ("fused", True)):
        sides[name] = make_side(fused, args.batch, args.classes,
                                args.dtype)
        c = sides[name]["compiled"]
        sides[name]["bytes"] = _cost_analysis_bytes(c)
        sides[name]["flops"] = _cost_analysis_flops(c)

    # warmup + loss-trajectory sanity (same seed/params both sides)
    losses = {}
    for name, s in sides.items():
        st, loss = s["run"](s["state"], 0)
        for i in range(1, 6):
            st, loss = s["run"](st, i)
        losses[name] = float(jnp.mean(loss))
        s["state"] = st
    rel = abs(losses["autodiff"] - losses["fused"]) / max(
        abs(losses["autodiff"]), 1e-9)

    best = {k: float("inf") for k in sides}
    for _ in range(args.reps):
        for name, s in sides.items():
            st = s["state"]
            t0 = time.perf_counter()
            for i in range(args.steps):
                st, loss = s["run"](st, i + 1)
            float(jnp.mean(loss))
            best[name] = min(best[name], time.perf_counter() - t0)
            s["state"] = st

    out = {"metric": "bn_fused_bwd_ab", "batch": args.batch,
           "autodiff_ms_per_step": round(best["autodiff"] / args.steps
                                         * 1e3, 2),
           "fused_ms_per_step": round(best["fused"] / args.steps * 1e3,
                                      2),
           "speedup": round(best["autodiff"] / best["fused"], 4),
           "img_per_sec_fused": round(
               args.batch * args.steps / best["fused"], 1),
           "img_per_sec_autodiff": round(
               args.batch * args.steps / best["autodiff"], 1),
           "loss_rel_diff_after_6_steps": f"{rel:.2e}",
           "bytes_autodiff": sides["autodiff"]["bytes"],
           "bytes_fused": sides["fused"]["bytes"],
           "flops_autodiff": sides["autodiff"]["flops"],
           "flops_fused": sides["fused"]["flops"]}
    if out["bytes_autodiff"] and out["bytes_fused"]:
        out["bytes_saved_pct"] = round(
            100 * (1 - out["bytes_fused"] / out["bytes_autodiff"]), 2)
    print(json.dumps(out))


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    main()
