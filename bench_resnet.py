"""ResNet-50 train-step benchmark + ablation harness (single chip).

North-star config from BASELINE.json: ResNet-50, ComputationGraph,
images/sec/chip and MFU. Methodology matches bench.py (v3): device-
resident inputs, best-of-3 timing windows, every window ends with a
device->host loss read.

MFU accounting: ResNet-50 fwd ~= 4.09 GFLOP/img at 224x224 (counting
MAC=2); train step ~= 3x fwd. Peak: 197 TFLOPS bf16 on TPU v5 lite.

Usage: python bench_resnet.py [--batch 256] [--dtype bf16]
       [--mode train|fwd] [--no-bn] [--no-l2] [--steps 10]
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

# Fallback XLA cost-analysis numbers for the DEFAULT config only
# (batch 256, 1000 classes, BN on, L2 on — BASELINE.md round-2
# accounting): fwd 7.46 GFLOP/img, full train step 22.3 GFLOP/img.
# Any other config derives flops from compiled.cost_analysis() live;
# if that fails for a non-default config, no mfu/tflops is emitted
# rather than reporting numbers for a program we didn't measure.
FWD_FLOPS_PER_IMG = 7.46e9
TRAIN_FLOPS_PER_IMG = 22.3e9


def _cost_analysis_flops(compiled):
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    f = ca.get("flops")
    return float(f) if f and f > 0 else None


def build(num_classes=1000, dtype="bf16", no_bn=False, no_l2=False):
    from deeplearning4j_tpu.learning import Nesterovs
    from deeplearning4j_tpu.zoo.resnet50 import ResNet50

    model = ResNet50(num_classes=num_classes,
                     updater=Nesterovs(learning_rate=1e-1, momentum=0.9))
    conf = model.conf()
    if no_l2:
        for node in conf.nodes:
            lay = getattr(node.vertex, "layer", None)
            if lay is not None:
                lay.l2 = 0.0
        conf.l2 = 0.0
    if no_bn:
        from deeplearning4j_tpu.nn.conf import ActivationLayer
        from deeplearning4j_tpu.nn.graph.graph import LayerVertex
        for node in conf.nodes:
            lay = getattr(node.vertex, "layer", None)
            if lay is not None and type(lay).__name__ == "BatchNormalization":
                node.vertex = LayerVertex(
                    ActivationLayer(activation=lay.activation or "identity"))
    conf.dtype = {"bf16": "bfloat16", "f32": "float32"}[dtype]
    from deeplearning4j_tpu.nn.graph.graph import ComputationGraph
    return ComputationGraph(conf).init()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--dtype", default="bf16", choices=["bf16", "f32"])
    ap.add_argument("--mode", default="train", choices=["train", "fwd"])
    ap.add_argument("--no-bn", action="store_true")
    ap.add_argument("--no-l2", action="store_true")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--classes", type=int, default=1000)
    ap.add_argument("--hlo", action="store_true",
                    help="dump optimized HLO to /tmp/resnet_step.hlo")
    ap.add_argument("--precision-ab", action="store_true",
                    help="run the precision A/B/C (f32 vs "
                         "mixed_bfloat16 policy vs naive full-bf16) "
                         "and report mixed/naive speedups vs f32")
    ap.add_argument("--pipeline-ab", action="store_true",
                    help="also A/B the device input pipeline (async "
                         "prefetch + double-buffered transfers) over a "
                         "host-resident image stream: reports "
                         "pipeline_speedup (pure transfer overlap — "
                         "shapes are fixed, no recompiles involved)")
    ap.add_argument("--pipeline-batches", type=int, default=8,
                    help="minibatches per epoch in the pipeline A/B")
    ap.add_argument("--zero-ab", action="store_true",
                    help="interleaved A/B of the data-parallel sharing "
                         "step: replicated vs ZeRO-style update "
                         "sharding (step time + per-device master/opt "
                         "byte gauges; recorded into MULTICHIP rounds)")
    args = ap.parse_args()

    if args.zero_ab:
        from bench_common import zero_ab

        print(json.dumps(zero_ab("resnet", steps=args.steps,
                                 batch=args.batch,
                                 classes=args.classes)))
        return

    if args.precision_ab:
        from bench_common import precision_ab

        print(json.dumps(precision_ab(
            "resnet", steps=args.steps, batch=args.batch,
            classes=args.classes)))
        return

    net = build(args.classes, args.dtype, args.no_bn, args.no_l2)
    dt = net._dtype

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (args.batch, 224, 224, 3)), dt)
    y = jnp.asarray(
        np.eye(args.classes, dtype=np.float32)[
            rng.integers(0, args.classes, args.batch)], dt)
    x, y = jax.device_put(x), jax.device_put(y)

    conf = net.conf
    inputs = {conf.network_inputs[0]: x}
    labels = {conf.network_outputs[0]: y}

    if args.mode == "train":
        step = net._get_train_step()
        state = (net.params_map, net.states_map, net.opt_states)

        def run(state, i):
            p, s, o, loss = step(state[0], state[1], state[2],
                                 jnp.asarray(i), jnp.asarray(0), inputs,
                                 labels, {}, {}, jax.random.key(i))
            return (p, s, o), loss
    else:
        fwd = jax.jit(lambda pm, sm: net._forward_all(
            pm, sm, inputs, False, None)[0][conf.network_outputs[0]])
        state = (net.params_map, net.states_map)

        def run(state, i):
            out = fwd(state[0], state[1])
            return state, out

    # Lower+compile once up front: the XLA compile cache makes the
    # jitted call below hit the same executable, and cost_analysis()
    # gives per-step flops for THE ACTUAL CONFIG (batch/classes/bn/l2
    # ablations change the program, so constants don't transfer).
    jitted = step if args.mode == "train" else fwd
    if args.mode == "train":
        largs = (net.params_map, net.states_map, net.opt_states,
                 jnp.asarray(0), jnp.asarray(0), inputs, labels, {},
                 {}, jax.random.key(0))
    else:
        largs = (net.params_map, net.states_map)
    comp = jitted.lower(*largs).compile()
    # register the compiled step in the roofline program registry so
    # the aggregate line carries its verdict row (memory- vs compute-
    # bound + achieved rates once the timed window is fed back in)
    from deeplearning4j_tpu.profiler import programs
    from deeplearning4j_tpu.profiler.telemetry import _arg_signature

    programs.set_enabled(True)
    programs.get_default().reset()
    programs.get_default().register(
        "bench_resnet_step", _arg_signature(largs, {}), comp,
        source="bench")
    try:
        measured_step_flops = _cost_analysis_flops(comp)
    except Exception as e:
        print("cost_analysis unavailable:", e)
        measured_step_flops = None
    if args.hlo:
        with open("/tmp/resnet_step.hlo", "w") as f:
            f.write(comp.as_text())
        print("cost_analysis flops:", measured_step_flops)
        print("HLO dumped to /tmp/resnet_step.hlo")

    # warmup/compile
    t0 = time.perf_counter()
    state, loss = run(state, 0)
    lv = float(jnp.mean(loss))
    print(f"compile+first step: {time.perf_counter()-t0:.1f}s loss={lv:.3f}")

    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for i in range(args.steps):
            state, loss = run(state, i + 1)
        float(jnp.mean(loss))
        best = min(best, time.perf_counter() - t0)

    img_s = args.batch * args.steps / best
    is_default_cfg = (args.classes == 1000 and not args.no_bn
                      and not args.no_l2)
    if measured_step_flops is not None:
        per_img = measured_step_flops / args.batch
        flops_src = "cost_analysis"
    elif is_default_cfg:
        per_img = (TRAIN_FLOPS_PER_IMG if args.mode == "train"
                   else FWD_FLOPS_PER_IMG)
        flops_src = "baseline_const"
    else:
        per_img = None
        flops_src = None
    from bench_common import peak_flops
    peak = peak_flops(args.dtype)
    out = {"mode": args.mode, "dtype": args.dtype, "batch": args.batch,
           "no_bn": args.no_bn, "no_l2": args.no_l2,
           "img_per_sec": round(img_s, 1)}
    if per_img is not None:
        flops = img_s * per_img
        out["tflops"] = round(flops / 1e12, 1)
        out["flops_src"] = flops_src
        if peak:
            out["mfu_est"] = round(flops / peak, 4)
    from bench_common import roofline_row
    row = roofline_row("bench_resnet_step",
                       seconds_per_step=best / args.steps,
                       steps=args.steps)
    if row:
        out["roofline"] = row
    if args.pipeline_ab and args.mode == "train":
        from bench_common import pipeline_ab_fixed
        from deeplearning4j_tpu.datasets import ArrayDataSetIterator

        n_img = args.batch * args.pipeline_batches
        xs = np.asarray(rng.normal(0, 1, (n_img, 224, 224, 3)),
                        np.float32)
        ys = np.eye(args.classes, dtype=np.float32)[
            rng.integers(0, args.classes, n_img)]
        # fresh net: the timed loop above DONATED the original net's
        # param buffers into the manual step calls
        ab_net = build(args.classes, args.dtype, args.no_bn, args.no_l2)
        # host-resident stream: the 'off' side pays a synchronous
        # ~150MB/batch host->device copy per step at batch 256
        out.update(pipeline_ab_fixed(
            ab_net, lambda: ArrayDataSetIterator(xs, ys, args.batch)))
    print(json.dumps(out))


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    main()
