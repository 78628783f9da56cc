"""v5e-16 feasibility artifact for the ResNet-50 DP north star.

The BASELINE.json target ("ParallelWrapper GradientSharing DP ResNet-50
on v5e-16, >=45% MFU") is defined on 16 chips this environment does not
have. This script makes the scaling argument concrete WITHOUT hardware:
`jax.experimental.topologies.get_topology_desc("v5e:4x4")` builds a
device-less v5e-16 topology, and the REAL ComputationGraph train step
(the same one bench_resnet.py times on the single real chip) is
AOT-lowered and compiled against it with data-parallel shardings
(params/opt replicated, batch sharded 16-way — GSPMD inserts the
gradient all-reduces). From the compiled executable we extract:

- per-chip FLOPs per step (cost_analysis),
- the gradient-sync collective bytes XLA actually scheduled
  (all-reduce/reduce-scatter/all-gather instruction shapes in the
  optimized HLO),
- per-chip memory,
- expected ICI all-reduce time under stated bandwidth assumptions, and
  the resulting step-time/MFU projection from the measured single-chip
  compute time.

Run (CPU client is enough — compilation only, no execution):
  JAX_PLATFORMS=cpu python prof_resnet_v5e16.py
"""

from __future__ import annotations

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import bench_resnet

PER_CHIP_BATCH = 256
N_CHIPS = 16
# public v5e numbers: 197 TFLOP/s bf16 peak per chip; ICI 2D torus with
# ~400 GB/s aggregate per-chip ICI bandwidth (v5e spec sheet). The
# effective ring-all-reduce bandwidth is lower; we report a range.
PEAK_BF16 = 197e12
ICI_EFFECTIVE_GBPS = (100e9, 200e9)   # conservative .. optimistic

_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "u8": 1, "s8": 1,
          "u16": 2, "s16": 2, "u32": 4, "s32": 4, "u64": 8, "s64": 8,
          "pred": 1}


def _group_size(line):
    """Communicating-group size from replica_groups: explicit
    {{0,1,...}} lists or iota [g_size,n_groups]<=[...] notation."""
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        return m.group(1).count(",") + 1
    # iota notation: [num_groups, devices_per_group]<=[N]
    m = re.search(r"replica_groups=\[\d+,(\d+)\]<=", line)
    if m:
        return int(m.group(1))
    return None


def _collective_bytes(hlo_text):
    """Sum result bytes of cross-chip collectives in optimized HLO
    (degenerate single-member groups excluded — they move no data)."""
    kinds = ("all-reduce", "reduce-scatter", "all-gather",
             "collective-permute")
    out = {}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"%[\w.-]+ = (.*)$", ls)
        if m is None:
            continue
        kind = next((k for k in kinds
                     if f" {k}(" in ls or f" {k}-start(" in ls), None)
        if kind is None:
            continue
        gs = _group_size(ls)
        if gs is not None and gs <= 1:
            continue
        type_part = ls.split(f" {kind}(")[0].split(f" {kind}-start(")[0]
        size = 0
        for dt, dims in re.findall(r"([a-z][a-z0-9]*)\[([0-9,]*)\]",
                                   type_part):
            if dt not in _BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            size += n * _BYTES[dt]
        out.setdefault(kind, [0, 0])
        out[kind][0] += 1
        out[kind][1] += size
    return out


def main():
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:4x4")
    devs = np.array(topo.devices)
    assert devs.size == N_CHIPS
    mesh = Mesh(devs.reshape(N_CHIPS), ("data",))

    net = bench_resnet.build(1000, "bf16")
    step = net._get_train_step()
    conf = net.conf
    B = PER_CHIP_BATCH * N_CHIPS

    def sds(tree, spec):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                jnp.shape(a), jnp.asarray(a).dtype,
                sharding=NamedSharding(mesh, spec)), tree)

    x_s = {conf.network_inputs[0]: jax.ShapeDtypeStruct(
        (B, 224, 224, 3), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("data")))}
    y_s = {conf.network_outputs[0]: jax.ShapeDtypeStruct(
        (B, 1000), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("data")))}
    i_s = jax.ShapeDtypeStruct((), jnp.int32)
    k_aval = jax.eval_shape(lambda: jax.random.key(0))
    k_s = jax.ShapeDtypeStruct(k_aval.shape, k_aval.dtype,
                               sharding=NamedSharding(mesh, P()))

    low = step.lower(sds(net.params_map, P()), sds(net.states_map, P()),
                     sds(net.opt_states, P()), i_s, i_s, x_s, y_s,
                     {}, {}, k_s)
    comp = low.compile()

    ca = comp.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    # cost_analysis reports the PARTITIONED (per-chip) program: with
    # batch sharded 16-way this matches the single-chip batch-256 step
    # (~22.6 GFLOP/img), which is the consistency check.
    per_chip_flops = float(ca.get("flops", 0.0))
    total_flops = per_chip_flops * N_CHIPS
    colls = _collective_bytes(comp.as_text())
    # ring all-reduce moves 2*(N-1)/N * payload per chip
    ar_payload = colls.get("all-reduce", [0, 0])[1]
    ring_factor = 2.0 * (N_CHIPS - 1) / N_CHIPS
    ici_bytes_per_chip = ar_payload * ring_factor
    mem = comp.memory_analysis()

    out = {
        "topology": "v5e:4x4 (16 chips, AOT — no hardware attached)",
        "global_batch": B,
        "per_chip_batch": PER_CHIP_BATCH,
        "step_flops_total": total_flops,
        "step_gflops_per_chip": round(per_chip_flops / 1e9, 2),
        "per_img_gflops": round(per_chip_flops / PER_CHIP_BATCH / 1e9,
                                3),
        "collectives": {k: {"count": v[0], "payload_mb":
                            round(v[1] / 1e6, 2)}
                        for k, v in colls.items()},
        "grad_allreduce_payload_mb": round(ar_payload / 1e6, 2),
        "ici_bytes_per_chip_mb": round(ici_bytes_per_chip / 1e6, 2),
        "ici_time_ms_range": [
            round(ici_bytes_per_chip / bw * 1e3, 3)
            for bw in reversed(ICI_EFFECTIVE_GBPS)],
        "per_chip_hbm_bytes": getattr(mem, "temp_size_in_bytes", None),
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
    }
    # projection: measured single-chip step time (BENCH_r03: 2151.9
    # img/s at batch 256 -> 119.0 ms/step) + ICI time if NOT overlapped
    single_chip_ms = PER_CHIP_BATCH / 2151.9 * 1e3
    out["projection"] = {
        "measured_single_chip_step_ms": round(single_chip_ms, 2),
        "projected_step_ms_no_overlap": [
            round(single_chip_ms + t, 2)
            for t in out["ici_time_ms_range"]],
        "projected_mfu": [
            round(per_chip_flops / ((single_chip_ms + t) / 1e3)
                  / PEAK_BF16, 4)
            for t in out["ici_time_ms_range"]],
        "note": ("grad all-reduce overlaps with the backward pass in "
                 "practice; the no-overlap projection is the floor. "
                 "DP scaling is compute-bound: the binding constraint "
                 "on the 45% target remains single-chip MFU, not ICI."),
    }
    print(json.dumps(out, indent=2))
    return out


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    main()
