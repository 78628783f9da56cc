"""Capture a jax.profiler trace of the ResNet-50 train step and print a
per-category device-time breakdown (SURVEY.md §5 tracing; the
OpProfiler/GraphProfile role for the CNN flagship).

Usage:  python prof_resnet.py [trace_dir]
Then the xplane under <trace_dir>/plugins/profile/*/ is parsed directly
(the tensorboard-plugin converter in this image has a proto version
clash, so we read the XSpace proto ourselves).
"""

from __future__ import annotations

import glob
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np

import bench_resnet as br


def capture(trace_dir: str) -> None:
    net = br.build(1000, "bf16")
    conf = net.conf
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, (256, 224, 224, 3)), net._dtype)
    y = jnp.asarray(
        np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, 256)],
        net._dtype)
    inputs = {conf.network_inputs[0]: x}
    labels = {conf.network_outputs[0]: y}
    step = net._get_train_step()
    state = (net.params_map, net.states_map, net.opt_states)

    def run(state, i):
        p, s, o, loss = step(state[0], state[1], state[2], jnp.asarray(i),
                             jnp.asarray(0), inputs, labels, {}, {},
                             jax.random.key(i))
        return (p, s, o), loss

    state, loss = run(state, 0)
    float(jnp.mean(loss))
    with jax.profiler.trace(trace_dir):
        for i in range(3):
            state, loss = run(state, i + 1)
        float(jnp.mean(loss))


def report(trace_dir: str) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    files = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    if not files:
        raise SystemExit(f"no xplane under {trace_dir}")
    xs = xplane_pb2.XSpace()
    with open(sorted(files)[-1], "rb") as f:
        xs.ParseFromString(f.read())
    for plane in xs.planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        ev_names = {i: m.name for i, m in plane.event_metadata.items()}
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            cat: dict = {}
            for ev in line.events:
                name = ev_names.get(ev.metadata_id, "?")
                m = re.match(r"%?([a-zA-Z_\-]+)", name.split(" = ")[0])
                c = m.group(1) if m else "?"
                cat[c] = cat.get(c, 0) + ev.duration_ps
            total = sum(cat.values())
            print(f"{plane.name}: {total/3e9:.1f} ms/step over 3 steps")
            for c, d in sorted(cat.items(), key=lambda kv: -kv[1])[:15]:
                print(f"  {d/3e9:8.2f} ms/step {100*d/total:5.1f}%  {c}")


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    td = sys.argv[1] if len(sys.argv) > 1 else "/tmp/jaxprof"
    capture(td)
    report(td)
