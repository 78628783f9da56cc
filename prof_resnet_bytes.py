"""Per-fusion HBM byte ledger for the ResNet-50 train step.

Parses the optimized HLO of the compiled step and charges each
top-level instruction its operand+result bytes (the HBM traffic a
fusion pays, ignoring VMEM reuse inside the fusion — an upper bound
per fusion, but relative weights are what the ledger is for).
Buckets by fusion content: convolution, reduce (BN stats), select
(relu masks), scatter, elementwise, copy/transpose, allreduce.

Usage: python prof_resnet_bytes.py [--batch 256] [--top 25]
"""

from __future__ import annotations

import argparse
import json
import re
from collections import defaultdict

_DT_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
             "pred": 1, "s8": 1, "u8": 1, "f64": 8, "s64": 8, "u64": 8,
             "s16": 2, "u16": 2}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def shape_bytes(shape_str: str) -> int:
    """Bytes of one shape or a tuple '(f32[..], bf16[..])'."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DT_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DT_BYTES[dt]
    return total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--hlo", default=None,
                    help="parse an existing HLO dump instead of compiling")
    args = ap.parse_args()

    if args.hlo:
        text = open(args.hlo).read()
    else:
        import jax
        import jax.numpy as jnp
        import numpy as np
        from bench_resnet import build

        net = build(1000, "bf16", False, False)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(0, 1, (args.batch, 224, 224, 3)),
                        net._dtype)
        y = jnp.asarray(np.eye(1000, dtype=np.float32)[
            rng.integers(0, 1000, args.batch)], net._dtype)
        conf = net.conf
        inputs = {conf.network_inputs[0]: x}
        labels = {conf.network_outputs[0]: y}
        step = net._get_train_step()
        text = step.lower(net.params_map, net.states_map, net.opt_states,
                          jnp.asarray(0), jnp.asarray(0), inputs, labels,
                          {}, {}, jax.random.key(0)).compile().as_text()

    # find ENTRY computation body
    m = re.search(r"ENTRY [^{]+\{(.*?)\n\}", text, re.S)
    body = m.group(1) if m else text

    # shape table for every instruction in the whole module
    inst_shape = {}
    for mm in re.finditer(
            r"%?([\w\.\-]+) = (\([^)]*\)|\w+\[[\d,]*\]\S*)", text):
        inst_shape[mm.group(1)] = mm.group(2)

    # fused-computation bodies (span until the brace at line start —
    # a body's FIRST '}' is usually a layout annotation like {3,2,1,0})
    comp_bodies = dict(
        (mm.group(1), mm.group(2))
        for mm in re.finditer(
            r"%([\w\.\-]+)\s*\([^)]*\)\s*->\s*[^{]*\{(.*?)\n\}",
            text, re.S))

    def classify(line: str) -> str:
        call = re.search(r"calls=%?([\w\.\-]+)", line)
        inner = comp_bodies.get(call.group(1), "") if call else ""
        blob = line + inner
        if "convolution" in blob:
            return "conv"
        if "scatter" in blob or "select-and-scatter" in blob:
            return "pool-scatter"
        if "all-reduce" in blob:
            return "collective"
        if "reduce(" in blob or "reduce-window" in blob:
            return "reduce(BN-stats/loss)"
        if "compare" in blob or "select(" in blob:
            return "select(relu-mask)"
        if "copy" in blob or "transpose" in blob:
            return "copy/transpose"
        if "dot(" in blob:
            return "matmul"
        return "elementwise"

    buckets = defaultdict(lambda: [0, 0])   # cat -> [bytes, count]
    rows = []
    for line in body.splitlines():
        line = line.strip()
        mm = re.match(
            r"%?([\w\.\-]+) = (\([^)]*\)|\w+\[[\d,]*\]\S*) (\w[\w\-]*)",
            line)
        if not mm:
            continue
        name, shape_s, opcode = mm.groups()
        if opcode in ("parameter", "constant", "tuple",
                      "get-tuple-element", "bitcast"):
            continue
        out_b = shape_bytes(shape_s)
        opnd_b = 0
        # operands are the paren group attached to the OPCODE TOKEN —
        # a plain substring split would cut inside the instruction's
        # own name ('%fusion.42'), and the whole-line first paren group
        # is the tuple RESULT shape for multi-output fusions
        argm = re.search(r"\s" + re.escape(opcode) + r"\((.*?)\)", line)
        if argm:
            for op_name in re.findall(r"%([\w\.\-]+)", argm.group(1)):
                s = inst_shape.get(op_name)
                if s:
                    opnd_b += shape_bytes(s)
        total = out_b + opnd_b
        cat = classify(line) if opcode == "fusion" else (
            "conv" if opcode == "convolution" else
            "collective" if "all-reduce" in opcode else
            "pool-scatter" if "scatter" in opcode else
            "copy/transpose" if opcode in ("copy", "transpose") else
            opcode)
        buckets[cat][0] += total
        buckets[cat][1] += 1
        rows.append((total, name, cat, shape_s[:40]))

    grand = sum(b for b, _ in buckets.values())
    print(f"total charged HBM bytes/step: {grand/1e9:.1f} GB")
    for cat, (b, c) in sorted(buckets.items(), key=lambda kv: -kv[1][0]):
        print(f"  {cat:<22} {b/1e9:7.2f} GB  ({c} ops, "
              f"{100*b/grand:.1f}%)")
    print(f"\ntop {args.top} single instructions by bytes:")
    for total, name, cat, shape_s in sorted(rows, reverse=True)[:args.top]:
        print(f"  {total/1e6:9.1f} MB  {cat:<20} {name[:60]}")
    json.dump({k: v[0] for k, v in buckets.items()},
              open("/tmp/resnet_bytes.json", "w"))


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    main()
