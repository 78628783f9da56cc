#!/bin/bash
# Canonical test entry point.
#
# Tests run on a virtual 8-device CPU mesh (tests/conftest.py sets
# JAX_PLATFORMS=cpu and the host device count); the gates below say
# JAX_PLATFORMS=cpu themselves.
#
# DL4J_TPU_TELEMETRY=1 pins telemetry ON for the telemetry tests
# regardless of ambient env (it defaults on; =0 would silently skip
# the recompile-detector and step-phase assertions).
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    python -m pytest tests/ "$@"
rc=$?
# signal death (Ctrl-C = 130, kill = 137+): propagate immediately,
# don't run the smoke step on an interrupted suite
if [ $rc -ge 128 ]; then
    exit $rc
fi

# /metrics smoke check: the telemetry endpoint must serve Prometheus
# text with the compile counter after a two-shape fit. A regression
# here fails the run loudly even if no test exercised the endpoint.
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    python - <<'EOF'
import sys
import urllib.request

import numpy as np

from deeplearning4j_tpu.learning.updaters import Sgd
from deeplearning4j_tpu.nn.conf import (
    DenseLayer, InputType, NeuralNetConfiguration, OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer.network import MultiLayerNetwork
from deeplearning4j_tpu.ui.server import UIServer

conf = (NeuralNetConfiguration.builder().updater(Sgd(1e-2)).list()
        .layer(DenseLayer(n_out=4, activation="relu"))
        .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
        .setInputType(InputType.feedForward(3)).build())
net = MultiLayerNetwork(conf).init()
rs = np.random.RandomState(0)
for n in (8, 16):   # two batch shapes -> two compiles
    net.fit(rs.randn(n, 3).astype(np.float32),
            np.eye(2, dtype=np.float32)[rs.randint(0, 2, n)])
ui = UIServer()
port = ui.start(port=0)
try:
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
finally:
    ui.stop()
ok = ('dl4j_tpu_jit_compiles_total{site="mln_step"} 2' in text
      and "dl4j_tpu_step_phase_seconds" in text)
if not ok:
    sys.stderr.write("=== /metrics smoke check FAILED ===\n" + text)
    sys.exit(1)
print("/metrics smoke check OK")
EOF
smoke=$?
if [ $smoke -ne 0 ]; then
    echo "FATAL: telemetry /metrics smoke check regressed" >&2
    exit 1
fi

# Device-prefetch CPU fallback smoke: depth>0 on a CPU-only backend
# must still deliver every batch in order (transfers degrade to cheap
# host copies), and BOTH pipeline threads must be joined afterwards —
# the thread-leak gate inside conftest covers the suite, this covers
# the standalone-interpreter path.
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    python - <<'EOF'
import sys
import threading

import numpy as np

before = {t for t in threading.enumerate() if t.is_alive()}
from deeplearning4j_tpu.datasets import (
    ArrayDataSetIterator, BatchShapePolicy, DevicePrefetchIterator,
)

x = np.arange(120, dtype=np.float32).reshape(30, 4)
y = np.zeros((30, 2), np.float32)
with DevicePrefetchIterator(
        ArrayDataSetIterator(x, y, 8), depth=2,
        policy=BatchShapePolicy("pad_last", batch_size=8)) as pf:
    feats = [np.asarray(ds.features) for ds in pf]
ok = (len(feats) == 4 and all(f.shape == (8, 4) for f in feats)
      and np.array_equal(feats[0][:8, 0], x[:8, 0]))
leaked = {t for t in threading.enumerate() if t.is_alive()} - before
if leaked or not ok:
    sys.stderr.write(
        f"prefetch CPU fallback smoke FAILED: ok={ok} leaked={leaked}\n")
    sys.exit(1)
print("device-prefetch CPU fallback smoke OK (depth=2, no leaked threads)")
EOF
pfsmoke=$?
if [ $pfsmoke -ne 0 ]; then
    echo "FATAL: device-prefetch CPU fallback smoke regressed" >&2
    exit 1
fi

# Precision-matrix smoke gate: one tiny MLN fit per policy. Asserts
# (a) finite loss under every policy, (b) NO dtype leak — master
# params and updater state stay fp32 under the mixed policies, and
# (c) mixed final loss within 2% of the f32 run (same seed/steps).
# A cast placed on the wrong side of value_and_grad, or an updater
# quietly downcasting its moments, fails here before any TPU run.
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    python - <<'EOF'
import sys

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.learning.updaters import Adam
from deeplearning4j_tpu.nn.conf import (
    DenseLayer, InputType, NeuralNetConfiguration, OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer.network import MultiLayerNetwork

rs = np.random.RandomState(0)
x = rs.randn(32, 8).astype(np.float32)
y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 32)]


def fit(policy):
    conf = (NeuralNetConfiguration.builder().seed(11)
            .updater(Adam(1e-2)).precision(policy).list()
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .setInputType(InputType.feedForward(8)).build())
    net = MultiLayerNetwork(conf).init()
    for _ in range(25):
        net.fit(x, y)
    dts = {str(l.dtype)
           for t in (net.params_list, net.opt_states)
           for l in jax.tree_util.tree_leaves(t)
           if jnp.issubdtype(l.dtype, jnp.floating)}
    return net.score(), dts


losses = {}
fail = []
for pol in ("float32", "mixed_bfloat16", "mixed_float16"):
    loss, dts = fit(pol)
    losses[pol] = loss
    if not np.isfinite(loss):
        fail.append(f"{pol}: non-finite loss {loss}")
    if dts != {"float32"}:
        fail.append(f"{pol}: dtype leak — master/opt dtypes {dts}")
for pol in ("mixed_bfloat16", "mixed_float16"):
    rel = abs(losses[pol] - losses["float32"]) / abs(losses["float32"])
    if rel > 0.02:
        fail.append(f"{pol}: final loss {losses[pol]:.5f} deviates "
                    f"{rel:.1%} from f32 {losses['float32']:.5f} "
                    "(tolerance 2%)")
if fail:
    sys.stderr.write("precision-matrix smoke FAILED:\n  "
                     + "\n  ".join(fail) + "\n")
    sys.exit(1)
print("precision-matrix smoke OK "
      + " ".join(f"{k}={v:.5f}" for k, v in losses.items()))
EOF
precsmoke=$?
if [ $precsmoke -ne 0 ]; then
    echo "FATAL: precision-matrix smoke gate regressed" >&2
    exit 1
fi

# Model-health smoke gate (docs/OBSERVABILITY.md "Model health"): a
# CPU fit with HealthMonitor(frequency=2) must (a) populate the
# per-layer grad-norm gauges, (b) cost exactly ONE extra compile at
# the mln_step site with one health fetch per sampled step and no
# second backward, and (c) leave off-mode training bit-identical to a
# never-monitored run (attach->detach lands on the legacy executable).
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    python - <<'EOF'
import sys

import jax
import numpy as np

from deeplearning4j_tpu.learning.updaters import Adam
from deeplearning4j_tpu.nn.conf import (
    DenseLayer, InputType, NeuralNetConfiguration, OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer.network import MultiLayerNetwork
from deeplearning4j_tpu.profiler import HealthMonitor, telemetry

rs = np.random.RandomState(0)
x = rs.randn(16, 4).astype(np.float32)
y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, 16)]


def make():
    conf = (NeuralNetConfiguration.builder().seed(11).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))
            .setInputType(InputType.feedForward(4)).build())
    return MultiLayerNetwork(conf).init()


fail = []
reg = telemetry.MetricsRegistry.get_default()
compiles = lambda: reg.counter(telemetry.JIT_COMPILES).value(
    site="mln_step")

# monitored run: gauges + cost contract
net = make()
hm = HealthMonitor(frequency=2)
net.setHealthMonitor(hm)
c0 = compiles()
for _ in range(6):
    net.fit(x, y)
if compiles() - c0 != 1:
    fail.append(f"monitored fit compiled {compiles() - c0}x at "
                "mln_step, expected exactly 1")
if hm.fetches != 3:
    fail.append(f"{hm.fetches} health fetches for 6 steps at "
                "frequency=2, expected 3 (one per sampled step)")
gn = reg.gauge(telemetry.LAYER_GRAD_NORM)
for layer in ("0:DenseLayer", "1:OutputLayer"):
    if not gn.value(layer=layer, site="mln") > 0:
        fail.append(f"layer grad-norm gauge missing/zero for {layer}")
if hm.last["nonfinite_first_layer"] != -1:
    fail.append("clean fit reported a non-finite layer")
# toggling the monitor must cost exactly one more compile (off-mode
# executable), then reuse both cached executables
net.setHealthMonitor(None)
net.fit(x, y)
if compiles() - c0 != 2:
    fail.append(f"detach cost {compiles() - c0 - 1} extra compiles, "
                "expected exactly 1")

# off-mode bit-equality: attach->detach vs never monitored
a = make()
b = make()
b.setHealthMonitor(HealthMonitor(frequency=2))
b.setHealthMonitor(None)
for _ in range(5):
    a.fit(x, y)
    b.fit(x, y)
for la, lb in zip(jax.tree_util.tree_leaves((a.params_list,
                                             a.opt_states)),
                  jax.tree_util.tree_leaves((b.params_list,
                                             b.opt_states))):
    if not np.array_equal(np.asarray(la), np.asarray(lb)):
        fail.append("off-mode run is NOT bit-identical to a "
                    "never-monitored run")
        break

if fail:
    sys.stderr.write("model-health smoke FAILED:\n  "
                     + "\n  ".join(fail) + "\n")
    sys.exit(1)
print("model-health smoke OK: 1 extra compile, "
      f"{hm.fetches} fetches/6 steps, gauges live, off-mode "
      "bit-identical")
EOF
mhsmoke=$?
if [ $mhsmoke -ne 0 ]; then
    echo "FATAL: model-health smoke gate regressed" >&2
    exit 1
fi

# Chaos smoke gate (docs/FAULT_TOLERANCE.md): three phases sharing one
# checkpoint dir. A: clean baseline + identity check (a FaultTolerance
# with every guard off must be bit-identical to the legacy fit loop).
# B: env-gated chaos — NaN batch + transient transfer errors + a real
# SIGTERM mid-run — must roll back, retry, and exit cleanly with a
# resumable bundle. C: auto-resume under continued transfer errors
# must finish on the NEXT batch with a finite loss within tolerance of
# the clean run. Any silent regression in the recovery paths fails CI.
CHAOS_DIR=$(mktemp -d /tmp/dl4j_chaos_gate.XXXXXX)
export DL4J_TPU_CHAOS_GATE_DIR="$CHAOS_DIR"
# shared fixture for the three phases: phase C's exact iteration count
# and loss-tolerance comparison are only meaningful if every phase
# builds the IDENTICAL model and batch stream — one module, imported by
# each subprocess, instead of three drift-prone copies
cat > "$CHAOS_DIR/chaos_gate_common.py" <<'EOF'
import numpy as np

from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.datasets import ArrayDataSetIterator
from deeplearning4j_tpu.nn.conf import (
    DenseLayer, InputType, NeuralNetConfiguration, OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

rng = np.random.default_rng(0)
x = rng.normal(size=(48, 4)).astype(np.float32)
y = np.eye(2, dtype=np.float32)[(x.sum(1) > 0).astype(int)]


def make():
    return MultiLayerNetwork(
        (NeuralNetConfiguration.builder().seed(11)
         .updater(Adam(learning_rate=0.01)).list()
         .layer(DenseLayer(n_out=8, activation="tanh"))
         .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
         .setInputType(InputType.feedForward(4)).build())).init()


def it():
    return ArrayDataSetIterator(x, y, 8, shuffle=True, seed=5)
EOF
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    PYTHONPATH="$CHAOS_DIR" python - <<'EOF'
# phase A: clean baseline + identity-policy bit-equality
import json
import os
import sys

import jax
import numpy as np

from chaos_gate_common import it, make, x, y
from deeplearning4j_tpu.datasets import DataSet
from deeplearning4j_tpu.util import FaultTolerance

d = os.environ["DL4J_TPU_CHAOS_GATE_DIR"]
clean = make()
clean.fit(it(), epochs=3)
clean_loss = clean.score(DataSet(x, y))
ident = make()
ident.fit(it(), epochs=3,
          fault_tolerance=FaultTolerance(divergence_window=0))
for a, b in zip(jax.tree_util.tree_leaves((clean.params_list,
                                           clean.opt_states)),
                jax.tree_util.tree_leaves((ident.params_list,
                                           ident.opt_states))):
    if not np.array_equal(np.asarray(a), np.asarray(b)):
        sys.stderr.write("chaos gate A: identity FaultTolerance is NOT "
                         "bit-identical to the legacy fit loop\n")
        sys.exit(1)
with open(os.path.join(d, "clean.json"), "w") as f:
    json.dump({"loss": float(clean_loss)}, f)
print(f"chaos gate A OK: clean loss {clean_loss:.5f}, identity policy "
      "bit-identical")
EOF
gateA=$?
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    DL4J_TPU_CHAOS=1 DL4J_TPU_CHAOS_NAN_STEPS=4 \
    DL4J_TPU_CHAOS_TRANSFER_P=0.2 DL4J_TPU_CHAOS_PREEMPT_AT=10 \
    DL4J_TPU_CHAOS_SEED=7 \
    PYTHONPATH="$CHAOS_DIR" python - <<'EOF'
# phase B: NaN batch + flaky transfers + SIGTERM -> clean bundle
import os
import sys

from chaos_gate_common import it, make
from deeplearning4j_tpu.datasets import DevicePrefetchIterator
from deeplearning4j_tpu.profiler import telemetry
from deeplearning4j_tpu.util import FaultTolerance
from deeplearning4j_tpu.util.resilience import latest_valid_bundle

d = os.environ["DL4J_TPU_CHAOS_GATE_DIR"]
net = make()
ft = FaultTolerance(checkpoint_dir=d, divergence_window=8,
                    snapshot_every=2, transfer_backoff=0.005)
with DevicePrefetchIterator(it(), depth=2) as pf:
    net.fit(pf, epochs=3, fault_tolerance=ft)   # SIGTERM fires inside
reg = telemetry.MetricsRegistry.get_default()
fail = []
if latest_valid_bundle(d) is None:
    fail.append("no valid resumable bundle after SIGTERM")
if reg.counter(telemetry.FT_PREEMPTION_CHECKPOINTS).total() != 1:
    fail.append("preemption checkpoint counter != 1")
if reg.counter(telemetry.FT_ROLLBACKS).total() < 1:
    fail.append("NaN batch did not trigger a rollback")
if reg.counter(telemetry.TRANSFER_RETRIES).total() < 1:
    fail.append("transfer errors did not trigger retries")
if reg.counter(telemetry.TRANSFER_QUARANTINES).total() != 0:
    fail.append("transient errors escalated to quarantine")
if fail:
    sys.stderr.write("chaos gate B FAILED:\n  " + "\n  ".join(fail) + "\n")
    sys.exit(1)
print(f"chaos gate B OK: preempted at iteration "
      f"{net.getIterationCount()} with "
      f"{reg.counter(telemetry.FT_ROLLBACKS).total():.0f} rollback(s), "
      f"{reg.counter(telemetry.TRANSFER_RETRIES).total():.0f} "
      "transfer retry(ies), bundle written")
EOF
gateB=$?
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    DL4J_TPU_CHAOS=1 DL4J_TPU_CHAOS_TRANSFER_P=0.2 \
    DL4J_TPU_CHAOS_SEED=13 \
    PYTHONPATH="$CHAOS_DIR" python - <<'EOF'
# phase C: auto-resume -> next batch -> finite loss near the clean run
import json
import os
import sys

import numpy as np

from chaos_gate_common import it, make, x, y
from deeplearning4j_tpu.datasets import DataSet, DevicePrefetchIterator
from deeplearning4j_tpu.profiler import telemetry
from deeplearning4j_tpu.util import FaultTolerance

d = os.environ["DL4J_TPU_CHAOS_GATE_DIR"]
net = make()
ft = FaultTolerance(checkpoint_dir=d, divergence_window=8,
                    snapshot_every=2, transfer_backoff=0.005)
with DevicePrefetchIterator(it(), depth=2) as pf:
    net.fit(pf, epochs=3, fault_tolerance=ft)
reg = telemetry.MetricsRegistry.get_default()
final = net.score(DataSet(x, y))
clean = json.load(open(os.path.join(d, "clean.json")))["loss"]
fail = []
if reg.counter(telemetry.FT_AUTO_RESUMES).total() != 1:
    fail.append("run did not auto-resume from the bundle")
# 18 total steps across both incarnations, minus the one rolled-back
# NaN batch — a smaller count means resume repeated or skipped work.
# Exact-17 depends on NAN_STEPS=4 landing right ON a snapshot step
# (snapshot_every=2): the rollback then discards zero good steps. If
# either knob changes, re-derive this constant (see the rollback-
# granularity note in docs/FAULT_TOLERANCE.md).
if net.getIterationCount() != 17:
    fail.append(f"resumed run ended at iteration "
                f"{net.getIterationCount()}, expected 17")
if not np.isfinite(final):
    fail.append(f"non-finite final loss {final}")
# one skipped batch perturbs the trajectory; 'within tolerance' here
# means the chaos run still converged to the clean run's neighborhood
elif abs(final - clean) > max(0.5 * abs(clean), 0.05):
    fail.append(f"final loss {final:.5f} too far from clean run's "
                f"{clean:.5f}")
if fail:
    sys.stderr.write("chaos gate C FAILED:\n  " + "\n  ".join(fail) + "\n")
    sys.exit(1)
print(f"chaos gate C OK: auto-resumed, finished at iteration "
      f"{net.getIterationCount()}, loss {final:.5f} "
      f"(clean {clean:.5f})")
EOF
gateC=$?
rm -rf "$CHAOS_DIR"
if [ $gateA -ne 0 ] || [ $gateB -ne 0 ] || [ $gateC -ne 0 ]; then
    echo "FATAL: chaos smoke gate regressed (A=$gateA B=$gateB C=$gateC)" >&2
    exit 1
fi

# Update-sharding smoke gate (docs/SHARDING.md): on an 8-device CPU
# mesh, the ZeRO-style sharing step (update_sharding='zero') must
# (a) match the replicated sharing step's fit loss within tolerance,
# (b) actually shard the fp32 masters + Adam moments — placement
# asserted through the new per-device byte gauges AND the arrays'
# shardings — and (c) survive a REAL chaos SIGTERM mid-fit, then
# auto-resume on a DIFFERENT device count (8-way save -> 4-way resume)
# with bit-equal re-sharded moments and an exact total step count.
ZERO_DIR=$(mktemp -d /tmp/dl4j_zero_gate.XXXXXX)
export DL4J_TPU_ZERO_GATE_DIR="$ZERO_DIR"
cat > "$ZERO_DIR/zero_gate_common.py" <<'EOF'
import numpy as np

from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.datasets import ArrayDataSetIterator
from deeplearning4j_tpu.nn.conf import (
    DenseLayer, InputType, NeuralNetConfiguration, OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

rng = np.random.default_rng(0)
x = rng.normal(size=(64, 6)).astype(np.float32)
y = np.eye(2, dtype=np.float32)[(x.sum(1) > 0).astype(int)]


def make():
    return MultiLayerNetwork(
        (NeuralNetConfiguration.builder().seed(11)
         .updater(Adam(learning_rate=0.01)).list()
         .layer(DenseLayer(n_out=16, activation="tanh"))
         .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
         .setInputType(InputType.feedForward(6)).build()))


def it():
    return ArrayDataSetIterator(x, y, 8, shuffle=True, seed=5)
EOF
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    PYTHONPATH="$ZERO_DIR" python - <<'EOF'
# phase Z1: parity + sharded placement via the byte gauges
import sys

import jax
import numpy as np

from zero_gate_common import it, make
from deeplearning4j_tpu.parallel.mesh import build_mesh
from deeplearning4j_tpu.parallel.sharded import ShardedTrainer
from deeplearning4j_tpu.profiler import telemetry

mesh = build_mesh(num_data=8)
fail = []
a = make(); a.init()
ta = ShardedTrainer(a, mesh=mesh, mode="sharing")
b = make(); b.init()
tb = ShardedTrainer(b, mesh=mesh, mode="sharing", update_sharding="zero")
for _ in range(2):
    ta.fit(it(), epochs=1)
    tb.fit(it(), epochs=1)
la, lb = float(a.score()), float(b.score())
if not np.isfinite(lb) or abs(la - lb) / abs(la) > 1e-3:
    fail.append(f"zero loss {lb:.6f} deviates from replicated {la:.6f}")
reg = telemetry.MetricsRegistry.get_default()
mg = reg.gauge(telemetry.MASTER_PARAM_BYTES)
og = reg.gauge(telemetry.OPT_STATE_BYTES)
m_rep = mg.value(mode="replicated", site="sharded")
m_z = mg.value(mode="update_sharded", site="sharded")
o_rep = og.value(mode="replicated", site="sharded")
o_z = og.value(mode="update_sharded", site="sharded")
if not (m_rep > 0 and 0 < m_z < m_rep / 4):
    fail.append(f"master byte gauges not ~1/8: replicated={m_rep} "
                f"sharded={m_z}")
if not (o_rep > 0 and 0 < o_z < o_rep / 4):
    fail.append(f"opt byte gauges not ~1/8: replicated={o_rep} "
                f"sharded={o_z}")
flat = next(iter(tb._zero["masters"].values()))
if flat.addressable_shards[0].data.shape[0] != flat.shape[0] // 8:
    fail.append("flat masters are NOT sharded 1/8 per device: "
                f"{flat.sharding}")
if fail:
    sys.stderr.write("zero gate Z1 FAILED:\n  " + "\n  ".join(fail) + "\n")
    sys.exit(1)
print(f"zero gate Z1 OK: loss parity {la:.5f}/{lb:.5f}, master bytes "
      f"{m_rep:.0f}->{m_z:.0f}, opt bytes {o_rep:.0f}->{o_z:.0f}")
EOF
gateZ1=$?
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    DL4J_TPU_CHAOS=1 DL4J_TPU_CHAOS_PREEMPT_AT=7 DL4J_TPU_CHAOS_SEED=3 \
    PYTHONPATH="$ZERO_DIR" python - <<'EOF'
# phase Z2: chaos SIGTERM mid-fit on the 8-way zero trainer -> bundle
import json
import os
import sys

import jax
import numpy as np

from zero_gate_common import it, make
from deeplearning4j_tpu.parallel.mesh import build_mesh
from deeplearning4j_tpu.parallel.sharded import ShardedTrainer
from deeplearning4j_tpu.profiler import telemetry
from deeplearning4j_tpu.util import FaultTolerance
from deeplearning4j_tpu.util.resilience import latest_valid_bundle

d = os.environ["DL4J_TPU_ZERO_GATE_DIR"]
net = make(); net.init()
tr = ShardedTrainer(net, mesh=build_mesh(num_data=8), mode="sharing",
                    update_sharding="zero")
tr.fit(it(), epochs=3,
       fault_tolerance=FaultTolerance(checkpoint_dir=d,
                                      divergence_window=0))
bundle = latest_valid_bundle(d)
fail = []
if bundle is None:
    fail.append("no valid bundle after chaos SIGTERM")
else:
    man = json.load(open(os.path.join(bundle, "manifest.json")))
    if man.get("mesh", {}).get("data") != 8 \
            or man["mesh"].get("update_sharding") != "zero":
        fail.append(f"manifest mesh wrong: {man.get('mesh')}")
    if not any(m.startswith("zero_shards_p") for m in man["digests"]):
        fail.append("bundle carries no per-host zero shard file")
reg = telemetry.MetricsRegistry.get_default()
if reg.counter(telemetry.FT_PREEMPTION_CHECKPOINTS).total() != 1:
    fail.append("preemption checkpoint counter != 1")
if fail:
    sys.stderr.write("zero gate Z2 FAILED:\n  " + "\n  ".join(fail) + "\n")
    sys.exit(1)
with open(os.path.join(d, "z2.json"), "w") as f:
    json.dump({"iteration": net.getIterationCount()}, f)
print(f"zero gate Z2 OK: SIGTERM at iteration {net.getIterationCount()},"
      " shard-aware bundle written")
EOF
gateZ2=$?
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    PYTHONPATH="$ZERO_DIR" python - <<'EOF'
# phase Z3: auto-resume the preempted job on a DIFFERENT device count
import json
import os
import sys

import jax
import numpy as np

from zero_gate_common import it, make
from deeplearning4j_tpu.parallel.mesh import build_mesh
from deeplearning4j_tpu.parallel.sharded import ShardedTrainer
from deeplearning4j_tpu.profiler import telemetry
from deeplearning4j_tpu.util import FaultTolerance

d = os.environ["DL4J_TPU_ZERO_GATE_DIR"]
z2 = json.load(open(os.path.join(d, "z2.json")))
net = make(); net.init()
tr = ShardedTrainer(net, mesh=build_mesh(num_data=4,
                                         devices=jax.devices()[:4]),
                    mode="sharing", update_sharding="zero")
tr.fit(it(), epochs=3,
       fault_tolerance=FaultTolerance(checkpoint_dir=d,
                                      divergence_window=0))
fail = []
reg = telemetry.MetricsRegistry.get_default()
if reg.counter(telemetry.FT_AUTO_RESUMES).total() != 1:
    fail.append("run did not auto-resume from the bundle")
# 3 epochs x 8 batches = 24 total steps across both incarnations
if net.getIterationCount() != 24:
    fail.append(f"resumed run ended at iteration "
                f"{net.getIterationCount()}, expected 24")
if not np.isfinite(float(net.score())):
    fail.append(f"non-finite final loss {float(net.score())}")
if fail:
    sys.stderr.write("zero gate Z3 FAILED:\n  " + "\n  ".join(fail) + "\n")
    sys.exit(1)
print(f"zero gate Z3 OK: resumed from iteration {z2['iteration']} on a "
      f"4-way mesh, finished at {net.getIterationCount()}, loss "
      f"{float(net.score()):.5f}")
EOF
gateZ3=$?
rm -rf "$ZERO_DIR"
if [ $gateZ1 -ne 0 ] || [ $gateZ2 -ne 0 ] || [ $gateZ3 -ne 0 ]; then
    echo "FATAL: update-sharding smoke gate regressed (Z1=$gateZ1 Z2=$gateZ2 Z3=$gateZ3)" >&2
    exit 1
fi

# Serving smoke gate (docs/SERVING.md): the continuous-batching decode
# engine under JAX_PLATFORMS=cpu must (a) serve 16 mixed-length
# CONCURRENT requests with greedy outputs token-identical to solo
# generate() calls, (b) serve them entirely from the AOT warm pool —
# zero compiles at the serving_decode/serving_prefill jit sites after
# startup, (c) populate the occupancy/latency/TTFT/queue-depth/KV-page
# telemetry, and (d) shut down cleanly — no surviving ServingEngine
# thread (the suite-wide thread-leak gate in conftest.py also watches
# this name).
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    python - <<'EOF'
import sys
import threading
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.profiler import telemetry
from deeplearning4j_tpu.serving import DecodeEngine

cfg = tiny_config(vocab=17, max_len=48, d_model=32, n_layers=2,
                  n_heads=4, d_ff=64)
cfg.dropout = 0.0
m = CausalLM(cfg, compute_dtype=jnp.float32)
params = m.init_params(jax.random.key(1))
rng = np.random.default_rng(0)
specs = [(int(rng.integers(3, 14)), int(rng.integers(1, 13)))
         for _ in range(16)]
prompts = [rng.integers(0, 17, (t0,)).astype(np.int32)
           for t0, _ in specs]

reg = telemetry.MetricsRegistry.get_default()
compiles = lambda s: reg.counter(telemetry.JIT_COMPILES).value(site=s)
fail = []
eng = DecodeEngine(m, params, slots=4, page_size=8).start()
d0, p0 = compiles("serving_decode"), compiles("serving_prefill")
with ThreadPoolExecutor(max_workers=8) as ex:
    handles = list(ex.map(
        lambda pn: eng.submit(pn[0], pn[1]),
        zip(prompts, [n for _, n in specs])))
outs = [h.result(timeout=300) for h in handles]
for p, (_, new), got in zip(prompts, specs, outs):
    want = np.asarray(m.generate(
        params, jnp.asarray(p[None, :], jnp.int32), new))[0]
    if not np.array_equal(got, want):
        fail.append(f"greedy mismatch for prompt len {p.size} / "
                    f"new {new}: {got.tolist()} != {want.tolist()}")
        break
if compiles("serving_decode") != d0 or compiles("serving_prefill") != p0:
    fail.append("post-startup requests paid a trace/compile at a "
                "serving jit site (AOT warm pool regressed)")
st = eng.stats()
if st["warm_pool"]["misses"] != 0:
    fail.append(f"{st['warm_pool']['misses']} warm-pool misses for "
                "in-bucket traffic")
lat = reg.histogram(telemetry.SERVING_REQUEST_LATENCY)
eid = eng.engine_id        # SERVING_* series are engine-labelled now
if lat.count(reason="length", engine=eid) != 16:
    fail.append(f"latency histogram has "
                f"{lat.count(reason='length', engine=eid)} "
                "samples, expected 16")
pct = lat.percentiles(reason="length", engine=eid)
if not (pct["p50"] > 0 and pct["p99"] >= pct["p50"]):
    fail.append(f"latency percentiles not sane: {pct}")
if not 0 < st["avg_occupancy"] <= 1:
    fail.append(f"avg occupancy {st['avg_occupancy']} not in (0, 1]")
# SERVING_KV_* series carry the kv_dtype label now (fp8 KV PR); this
# engine runs the pool in its f32 compute dtype
if reg.gauge(telemetry.SERVING_KV_PAGE_UTILIZATION).value(
        engine=eid, kv_dtype="float32") != 0.0:
    fail.append("KV pages not all freed after completion")
if reg.gauge(telemetry.SERVING_KV_PAGE_BYTES).value(
        engine=eid, kv_dtype="float32") <= 0:
    fail.append("KV page-bytes gauge not published at pool allocation")
if reg.histogram(telemetry.SERVING_TTFT).count(engine=eid) != 16:
    fail.append("TTFT histogram incomplete")
eng.shutdown()
leaked = [t.name for t in threading.enumerate()
          if t.is_alive() and t.name.startswith("ServingEngine")]
if leaked:
    fail.append(f"ServingEngine thread(s) survived shutdown: {leaked}")
if fail:
    sys.stderr.write("serving smoke FAILED:\n  " + "\n  ".join(fail)
                     + "\n")
    sys.exit(1)
print(f"serving smoke OK: 16 mixed-length requests token-identical, "
      f"avg occupancy {st['avg_occupancy']:.2f}, p50 "
      f"{pct['p50']*1e3:.1f}ms p99 {pct['p99']*1e3:.1f}ms, 0 serving-"
      "site compiles post-startup, clean shutdown")
EOF
servsmoke=$?
if [ $servsmoke -ne 0 ]; then
    echo "FATAL: serving smoke gate regressed" >&2
    exit 1
fi

# KV-path smoke gate (docs/SERVING.md "KV precision and the attention
# kernel"): the Pallas paged-attention kernel under the INTERPRETER
# (the same kernel body the TPU compiles) must (a) produce greedy
# outputs TOKEN-IDENTICAL to the einsum engine at f32 across a
# 16-request mixed workload INCLUDING prefix-cache hits and a sticky-
# session resume, (b) with kv_dtype="fp8_e4m3" agree with the einsum
# engine on >= 99% of generated tokens, (c) pay zero serving-site
# compiles after startup in every mode, and (d) drain pools — and with
# them the fp8 scale planes — to zero at shutdown.
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    python - <<'EOF'
import sys

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.profiler import telemetry
from deeplearning4j_tpu.serving import DecodeEngine

cfg = tiny_config(vocab=17, max_len=48, d_model=32, n_layers=2,
                  n_heads=4, d_ff=64)
cfg.dropout = 0.0
m = CausalLM(cfg, compute_dtype=jnp.float32)
params = m.init_params(jax.random.key(1))
rng = np.random.default_rng(7)
shared = rng.integers(0, 17, (9,)).astype(np.int32)
jobs = []                      # (prompt, new, session_id)
for i in range(16):
    if i in (3, 11):           # session open + RESUME of the same id
        jobs.append((rng.integers(0, 17, (5,)).astype(np.int32),
                     4, "conv"))
    elif i % 4 == 0:           # prefix-cache traffic
        jobs.append((np.concatenate(
            [shared, rng.integers(0, 17, (3,)).astype(np.int32)]),
            int(rng.integers(3, 7)), None))
    else:
        jobs.append((rng.integers(0, 17,
                                  (int(rng.integers(3, 12)),)
                                  ).astype(np.int32),
                     int(rng.integers(2, 8)), None))

reg = telemetry.MetricsRegistry.get_default()
compiles = lambda s: reg.counter(telemetry.JIT_COMPILES).value(site=s)
SITES = ("serving_decode", "serving_prefill", "serving_prefix_prefill",
         "serving_adopt", "serving_cow_copy")
fail = []


def serve(attn_mode, kv_dtype):
    eng = DecodeEngine(m, params, slots=3, page_size=8,
                       max_context=32, max_chunk=4,
                       prefill_buckets=[8, 16], prefix_cache=True,
                       session_capacity=2, attn_mode=attn_mode,
                       kv_dtype=kv_dtype).start()
    base = {s: compiles(s) for s in SITES}
    outs = [np.asarray(eng.submit(p, n, session_id=sid)
                       .result(timeout=300)) for p, n, sid in jobs]
    delta = {s: compiles(s) - base[s] for s in SITES
             if compiles(s) != base[s]}
    if delta:
        fail.append(f"{attn_mode}/{kv_dtype}: post-startup compiles "
                    f"at serving sites: {delta}")
    if eng.stats()["warm_pool"]["misses"]:
        fail.append(f"{attn_mode}/{kv_dtype}: warm-pool misses")
    eng.shutdown()
    if eng.pool.allocated != 0:
        fail.append(f"{attn_mode}/{kv_dtype}: {eng.pool.allocated} "
                    "pages still allocated after shutdown (scale "
                    "planes leak with their pages)")
    return outs

ein = serve("xla", None)
ker = serve("interpret", None)
fp8 = serve("interpret", "fp8_e4m3")
for i, (a, b) in enumerate(zip(ein, ker)):
    if not np.array_equal(a, b):
        fail.append(f"kernel engine diverged from einsum engine on "
                    f"request {i}: {b.tolist()} != {a.tolist()}")
        break
tok_match = sum(int(np.sum(np.asarray(a) == np.asarray(b)))
                for a, b in zip(ein, fp8))
tok_total = sum(a.size for a in ein)
agree = tok_match / tok_total
if agree < 0.99:
    fail.append(f"fp8 token agreement {agree:.3f} < 0.99 "
                f"({tok_match}/{tok_total})")
if fail:
    sys.stderr.write("KV-path smoke FAILED:\n  " + "\n  ".join(fail)
                     + "\n")
    sys.exit(1)
print(f"KV-path smoke OK: interpret kernel token-identical to einsum "
      f"over {len(jobs)} requests (sessions + prefix hits), fp8 "
      f"agreement {agree:.3f}, 0 serving-site compiles post-start, "
      "pools drained")
EOF
kvsmoke=$?
if [ $kvsmoke -ne 0 ]; then
    echo "FATAL: KV-path (paged-attention / fp8) smoke gate regressed" >&2
    exit 1
fi

# Spec-decode smoke gate (docs/SERVING.md "Speculative decoding"):
# the draft-verify burst under JAX_PLATFORMS=cpu must (a) produce
# greedy outputs TOKEN-IDENTICAL to a spec-off engine across a
# 16-request mixed workload INCLUDING prefix-cache hits and a sticky-
# session resume (rejection sampling at T=0 is longest-prefix exact,
# so speculation may never change a token), (b) advance the proposed/
# accepted counters — the self-draft finds SOMETHING on 13-vocab
# traffic, (c) pay zero serving-site compiles after startup, the
# ("verify", K) program included, and (d) shut down clean: pools
# drained, no leaked engine threads.
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    python - <<'EOF'
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.profiler import telemetry
from deeplearning4j_tpu.serving import DecodeEngine

cfg = tiny_config(vocab=13, max_len=64, d_model=32, n_layers=2,
                  n_heads=4, d_ff=64)
cfg.dropout = 0.0
m = CausalLM(cfg, compute_dtype=jnp.float32)
params = m.init_params(jax.random.key(1))
rng = np.random.default_rng(11)
shared = rng.integers(0, 13, (9,)).astype(np.int32)
jobs = []                      # (prompt, new, session_id)
for i in range(16):
    if i in (3, 11):           # session open + RESUME of the same id
        jobs.append((rng.integers(0, 13, (5,)).astype(np.int32),
                     5, "conv"))
    elif i % 4 == 0:           # prefix-cache traffic
        jobs.append((np.concatenate(
            [shared, rng.integers(0, 13, (3,)).astype(np.int32)]),
            int(rng.integers(4, 8)), None))
    else:
        jobs.append((rng.integers(0, 13,
                                  (int(rng.integers(3, 12)),)
                                  ).astype(np.int32),
                     int(rng.integers(3, 9)), None))

reg = telemetry.MetricsRegistry.get_default()
compiles = lambda s: reg.counter(telemetry.JIT_COMPILES).value(site=s)
SITES = ("serving_decode", "serving_prefill", "serving_prefix_prefill",
         "serving_verify", "serving_adopt", "serving_cow_copy")
fail = []


def serve(spec):
    eng = DecodeEngine(m, params, slots=3, page_size=8,
                       max_context=48, max_chunk=4,
                       prefill_buckets=[8, 16], prefix_cache=True,
                       session_capacity=2, spec_decode=spec).start()
    base = {s: compiles(s) for s in SITES}
    outs = [np.asarray(eng.submit(p, n, session_id=sid)
                       .result(timeout=300)) for p, n, sid in jobs]
    delta = {s: compiles(s) - base[s] for s in SITES
             if compiles(s) != base[s]}
    if delta:
        fail.append(f"spec={spec}: post-startup compiles at serving "
                    f"sites: {delta}")
    if eng.stats()["warm_pool"]["misses"]:
        fail.append(f"spec={spec}: warm-pool misses")
    st = eng.stats()
    eng.shutdown()
    if eng.pool.allocated != 0:
        fail.append(f"spec={spec}: {eng.pool.allocated} pages still "
                    "allocated after shutdown")
    return outs, st

plain, _ = serve(None)
spec, st = serve(4)
for i, (a, b) in enumerate(zip(plain, spec)):
    if not np.array_equal(a, b):
        fail.append(f"spec engine diverged from plain engine on "
                    f"request {i}: {b.tolist()} != {a.tolist()}")
        break
sp = st.get("spec") or {}
if not sp.get("verify_dispatches"):
    fail.append("no verify dispatches recorded on the spec engine")
if not sp.get("proposed"):
    fail.append(f"spec proposed counter did not advance: {sp}")
if reg.counter(telemetry.SERVING_SPEC_PROPOSED).total() <= 0:
    fail.append("SERVING_SPEC_PROPOSED telemetry counter "
                "did not advance")
leaked = [t.name for t in threading.enumerate()
          if t.is_alive() and t.name.startswith("ServingEngine")]
if leaked:
    fail.append(f"ServingEngine thread(s) survived shutdown: {leaked}")
if fail:
    sys.stderr.write("spec-decode smoke FAILED:\n  "
                     + "\n  ".join(fail) + "\n")
    sys.exit(1)
print(f"spec-decode smoke OK: 16 mixed requests token-identical to "
      f"spec-off (sessions + prefix hits), {sp['verify_dispatches']} "
      f"verify dispatches, acceptance {sp['acceptance']:.2f}, "
      f"tokens/dispatch {sp['tokens_per_dispatch']:.2f}, 0 serving-"
      "site compiles post-start, clean shutdown")
EOF
specsmoke=$?
if [ $specsmoke -ne 0 ]; then
    echo "FATAL: spec-decode smoke gate regressed" >&2
    exit 1
fi

# Prefix-cache smoke gate (docs/SERVING.md "Prefix cache and
# sessions"): cross-request KV reuse under JAX_PLATFORMS=cpu must
# (a) produce warm-prefix greedy outputs TOKEN-IDENTICAL to both a
# cold prefill and a cache-off engine (which itself must stay
# identical to solo generate() — the pre-reuse contract), (b) advance
# the prefix hit counters / hit-token counters on warm traffic,
# (c) resume a two-turn sticky session token-identically with zero
# history re-prefill, and (d) drain COMPLETELY at shutdown — every
# refcount to zero, the pool fully free.
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    python - <<'EOF'
import sys

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.profiler import telemetry
from deeplearning4j_tpu.serving import DecodeEngine

cfg = tiny_config(vocab=17, max_len=64, d_model=32, n_layers=2,
                  n_heads=4, d_ff=64)
cfg.dropout = 0.0
m = CausalLM(cfg, compute_dtype=jnp.float32)
params = m.init_params(jax.random.key(1))
rng = np.random.default_rng(0)
sys_p = rng.integers(0, 17, (19,)).astype(np.int32)
prompts = [np.concatenate(
    [sys_p, rng.integers(0, 17, (n,)).astype(np.int32)])
    for n in (5, 7, 3, 9, 6)]
solo = lambda p, n: np.asarray(m.generate(
    params, jnp.asarray(np.asarray(p)[None, :], jnp.int32), n))[0]

fail = []
reg = telemetry.MetricsRegistry.get_default()
kw = dict(slots=2, page_size=8, prefill_buckets=[8, 16, 32],
          max_chunk=4)
# cache-off side: must be token-identical to solo generate()
off = DecodeEngine(m, params, **kw)
with off:
    off_outs = [off.generate(p, 8) for p in prompts]
for p, o in zip(prompts, off_outs):
    if not np.array_equal(o, solo(p, 8)):
        fail.append(f"cache-OFF engine diverged from solo generate() "
                    f"(prompt len {p.size})")
        break
# warm side: same prompts, prefix cache + sessions on
hit0 = reg.counter(telemetry.SERVING_PREFIX_HITS).total()
tok0 = reg.counter(telemetry.SERVING_PREFIX_HIT_TOKENS).total()
eng = DecodeEngine(m, params, prefix_cache=True, session_capacity=4,
                   **kw)
with eng:
    warm_reqs = [eng.submit(p, 8) for p in prompts]
    warm_outs = [r.result(timeout=300) for r in warm_reqs]
    hits = [r.cache_hit_tokens for r in warm_reqs]
    # two-turn sticky session: turn 2 extends turn 1's history
    t1 = prompts[0]
    r1 = eng.submit(t1, 6, session_id="conv")
    o1 = r1.result(timeout=300)
    t2 = np.concatenate([t1, o1,
                         rng.integers(0, 17, (4,)).astype(np.int32)])
    r2 = eng.submit(t2, 6, session_id="conv")
    o2 = r2.result(timeout=300)
    st = eng.prefix_stats()
for (p, o_off, o_warm) in zip(prompts, off_outs, warm_outs):
    if not np.array_equal(o_warm, o_off):
        fail.append(f"warm-prefix output diverged from cold "
                    f"(prompt len {p.size})")
        break
if not np.array_equal(o2, solo(t2, 6)):
    fail.append("session resume diverged from cold full-prompt decode")
if r2.cache_hit_tokens != t1.size + o1.size - 1:
    fail.append(f"session resume re-prefilled history "
                f"(hit {r2.cache_hit_tokens})")
if sum(1 for h in hits[1:] if h >= 16) != len(hits) - 1:
    fail.append(f"warm requests missed the shared prefix: hits={hits}")
if reg.counter(telemetry.SERVING_PREFIX_HITS).total() <= hit0:
    fail.append("prefix hit counter did not advance")
if reg.counter(telemetry.SERVING_PREFIX_HIT_TOKENS).total() \
        < tok0 + 4 * 16:
    fail.append("prefix hit-token counter did not advance")
if st["sessions"]["resumed_total"] != 1:
    fail.append(f"session stats wrong: {st['sessions']}")
if eng.pool.allocated != 0 or eng.pool.shared_pages() != 0:
    fail.append(f"pool did not drain at shutdown: "
                f"{eng.pool.allocated} pages, "
                f"{eng.pool.shared_pages()} shared")
if eng.stats()["warm_pool"]["misses"] != 0:
    fail.append("reuse programs missed the AOT warm pool")
if fail:
    sys.stderr.write("prefix-cache smoke FAILED:\n  "
                     + "\n  ".join(fail) + "\n")
    sys.exit(1)
print(f"prefix-cache smoke OK: {len(prompts)} shared-prefix requests "
      f"token-identical warm-vs-cold (hit tokens {hits}), 2-turn "
      f"session resumed at hit {r2.cache_hit_tokens}, pool drained, "
      "cache-off == solo generate()")
EOF
prefixsmoke=$?
if [ $prefixsmoke -ne 0 ]; then
    echo "FATAL: prefix-cache smoke gate regressed" >&2
    exit 1
fi

# Fleet smoke gate (docs/SERVING.md "Fleet"): the serving fleet under
# JAX_PLATFORMS=cpu must (a) serve 24 mixed-length requests through 2
# replicas + the disaggregated prefill lane with greedy outputs
# token-identical to solo generate() (and a 1-replica lane-less fleet
# identical too), (b) pay ZERO serving-site compiles after startup —
# replica 1 adopts replica 0's AOT warm pool, the lane and adopt
# programs are AOT too, (c) route a sticky session back to its pinned
# replica warm, (d) survive the kill-one-replica drill: queued and
# in-flight requests finish on the survivor token-identically (greedy
# replay), the flight recorder sees the death + re-route, sessions
# re-admit cold, and (e) drain every surviving pool to 0 at shutdown
# with no fleet thread leaked (conftest's gate also knows the
# ServingFleetRouter/ServingPrefillLane names).
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    DL4J_TPU_TRACING=1 python - <<'EOF'
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.profiler import flight_recorder, telemetry, tracing
from deeplearning4j_tpu.serving import ServingFleet

cfg = tiny_config(vocab=17, max_len=64, d_model=32, n_layers=2,
                  n_heads=4, d_ff=64)
cfg.dropout = 0.0
m = CausalLM(cfg, compute_dtype=jnp.float32)
params = m.init_params(jax.random.key(1))
solo = lambda p, n: np.asarray(m.generate(
    params, jnp.asarray(np.asarray(p)[None, :], jnp.int32), n))[0]
rng = np.random.default_rng(0)
specs = []
for i in range(24):
    t0 = int(rng.integers(20, 40)) if i % 3 == 0 \
        else int(rng.integers(3, 12))
    specs.append((rng.integers(0, 17, (t0,)).astype(np.int32),
                  int(rng.integers(2, 10))))

fail = []
reg = telemetry.MetricsRegistry.get_default()
compiles = lambda s: reg.counter(telemetry.JIT_COMPILES).value(site=s)
SITES = ("serving_decode", "serving_prefill", "serving_adopt",
         "serving_lane_prefill", "serving_prefix_prefill",
         "serving_cow_copy")

# (a) 1-replica lane-less fleet == solo generate()
one = ServingFleet(m, params, replicas=1, slots=4, page_size=8)
with one:
    for p, n in specs[:6]:
        if not np.array_equal(one.generate(p, n), solo(p, n)):
            fail.append(f"1-replica fleet diverged (prompt {p.size})")
            break

# 2 replicas + prefill lane, concurrent mixed traffic
fl = ServingFleet(m, params, replicas=2, slots=4, page_size=8,
                  prefill_threshold=16, prefix_cache=True,
                  session_capacity=8)
fl.start()
snap = {s: compiles(s) for s in SITES}
if fl.stats()["replicas"][1]["warm_pool"]["adopted"] == 0:
    fail.append("replica 1 did not adopt replica 0's AOT warm pool")
with ThreadPoolExecutor(max_workers=8) as ex:
    handles = list(ex.map(lambda pn: fl.submit(pn[0], pn[1]), specs))
outs = [h.result(timeout=300) for h in handles]
for (p, n), got in zip(specs, outs):
    if not np.array_equal(got, solo(p, n)):
        fail.append(f"fleet output diverged from solo generate() "
                    f"(prompt len {p.size} / new {n})")
        break
if fl._lane.stats()["prefills"] < 1:
    fail.append("no long prompt took the disaggregated prefill lane")

# (b) zero serving-site compiles after startup
for s in SITES:
    if compiles(s) != snap[s]:
        fail.append(f"post-startup compile at {s} "
                    f"({snap[s]} -> {compiles(s)})")

# (c) session affinity: turn 2 routes back to the pinned replica warm
t1 = rng.integers(0, 17, (9,)).astype(np.int32)
r1 = fl.submit(t1, 5, session_id="conv")
o1 = r1.result(120)
t2 = np.concatenate([t1, o1,
                     rng.integers(0, 17, (3,)).astype(np.int32)])
r2 = fl.submit(t2, 5, session_id="conv")
o2 = r2.result(120)
if r2.routing["reason"] != "affinity" \
        or r2.routing["replica"] != r1.routing["replica"]:
    fail.append(f"session did not route back warm: {r2.routing}")
if r2.cache_hit_tokens != t1.size + o1.size - 1:
    fail.append(f"session resume re-prefilled history "
                f"(hit {r2.cache_hit_tokens})")
if not np.array_equal(o2, solo(t2, 5)):
    fail.append("session resume diverged from solo generate()")

# (d) kill-one-replica drill: a long request mid-flight on the pinned
# replica + bystanders; everything must finish token-identically on
# the survivor, and the incident must be observable
doomed = r2.routing["replica"]
idx = next(i for i, r in enumerate(fl._replicas)
           if r.engine.engine_id == doomed)
long_p = rng.integers(0, 17, (4,)).astype(np.int32)
victim = fl.submit(long_p, 40, session_id="conv")   # affinity -> doomed
others = [fl.submit(rng.integers(0, 17, (6,)).astype(np.int32), 8)
          for _ in range(6)]
deadline = time.time() + 60
while len(victim.tokens) < 3 and time.time() < deadline:
    time.sleep(0.005)
fl.kill_replica(idx)
got = victim.result(timeout=300)
if not np.array_equal(got, solo(long_p, 40)):
    fail.append("victim request not replayed token-identically")
for h in others:
    h.result(timeout=300)
if fl.alive_replicas() != 1:
    fail.append(f"alive replicas {fl.alive_replicas()}, expected 1")
kinds = [e["kind"] for e in flight_recorder.get_default().events()]
if "fleet_replica_dead" not in kinds or "fleet_reroute" not in kinds:
    fail.append(f"flight recorder missed the drill: {sorted(set(kinds))}")
tl = tracing.timeline(victim.request_id)
if tl is None or tl["attrs"].get("engine") == doomed:
    fail.append("victim's trace not re-tagged to the survivor")
# sessions pinned on the dead replica re-admit cold
t3 = np.concatenate([t2, o2, rng.integers(0, 17, (2,)).astype(np.int32)])
r3 = fl.submit(t3, 4, session_id="conv")
o3 = r3.result(timeout=120)
if r3.routing["replica"] == doomed:
    fail.append("session still routed to the dead replica")
if not np.array_equal(o3, solo(t3, 4)):
    fail.append("cold re-admitted session diverged")

# (e) full drain at shutdown
reroutes = fl.n_reroutes
fl.shutdown()
for r in fl._replicas:
    if r.engine.pool.allocated != 0 or r.engine.pool.shared_pages():
        fail.append(f"replica {r.index} pool did not drain "
                    f"({r.engine.pool.allocated} pages)")
leaked = [t.name for t in threading.enumerate() if t.is_alive()
          and t.name.startswith(("ServingEngine", "ServingFleetRouter",
                                 "ServingPrefillLane"))]
if leaked:
    fail.append(f"fleet thread(s) survived shutdown: {leaked}")
if fail:
    sys.stderr.write("fleet smoke FAILED:\n  " + "\n  ".join(fail)
                     + "\n")
    sys.exit(1)
print(f"fleet smoke OK: 24 mixed requests token-identical across 2 "
      f"replicas + prefill lane "
      f"({fl._lane.stats()['prefills']} lane prefills), 0 post-start "
      f"compiles, warm session affinity, kill drill survived "
      f"({reroutes} reroutes), pools drained")
EOF
fleetsmoke=$?
if [ $fleetsmoke -ne 0 ]; then
    echo "FATAL: fleet smoke gate regressed" >&2
    exit 1
fi

# Tracing smoke gate (docs/OBSERVABILITY.md "Tracing one request"):
# (a) 8 mixed-length traced requests must each carry queue_wait /
# prefill / decode_burst / finish spans, retrievable programmatically
# AND over HTTP (/v1/serving/requests/<id>); responses and
# /v1/serving/stats join on request_id. (b) a forced flight-recorder
# dump must round-trip digest-valid through the JSONL loader. (c) with
# tracing+flight disabled, serving tokens and fit params are
# bit-identical to the enabled run, and the always-on instrumentation
# costs <5% serving p50 (min-of-3 windows, 2ms absolute slack).
TRACING_DIR=$(mktemp -d /tmp/dl4j_tracing_gate.XXXXXX)
export DL4J_TPU_TRACING_GATE_DIR="$TRACING_DIR"
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    DL4J_TPU_TRACING=1 python - <<'EOF'
import json
import os
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.learning.updaters import Adam
from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.nn.conf import (
    DenseLayer, InputType, NeuralNetConfiguration, OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer.network import MultiLayerNetwork
from deeplearning4j_tpu.profiler import flight_recorder, tracing
from deeplearning4j_tpu.remote.server import JsonModelServer
from deeplearning4j_tpu.serving import DecodeEngine

d = os.environ["DL4J_TPU_TRACING_GATE_DIR"]
flight_recorder.configure(directory=d)
fail = []

cfg = tiny_config(vocab=17, max_len=48, d_model=32, n_layers=2,
                  n_heads=4, d_ff=64)
cfg.dropout = 0.0
m = CausalLM(cfg, compute_dtype=jnp.float32)
params = m.init_params(jax.random.key(1))
rng = np.random.default_rng(0)
specs = [(int(rng.integers(3, 14)), int(rng.integers(2, 13)))
         for _ in range(8)]
prompts = [rng.integers(0, 17, (t0,)).astype(np.int32)
           for t0, _ in specs]
eng = DecodeEngine(m, params, slots=4, page_size=8).start()
srv = JsonModelServer(engine=eng)
port = srv.start()

# (a) every traced request carries the full span set, both paths
reqs = [eng.submit(p, n) for p, (_, n) in zip(prompts, specs)]
traced = [r.result(timeout=300) for r in reqs]
for r in reqs:
    tl = tracing.timeline(r.request_id)
    if tl is None:
        fail.append(f"request {r.request_id}: no timeline")
        continue
    names = [e["name"] for e in tl["events"]]
    for want in ("queue_wait", "prefill", "decode_burst", "finish"):
        if want not in names:
            fail.append(f"request {r.request_id}: missing {want} "
                        f"span (got {names})")
    http_tl = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/v1/serving/requests/{r.request_id}",
        timeout=10).read())
    if http_tl["trace_id"] != tl["trace_id"]:
        fail.append(f"request {r.request_id}: HTTP timeline mismatch")
recent = {x["request_id"]: x
          for x in eng.stats()["recent_requests"]}
if not all(r.request_id in recent
           and recent[r.request_id]["finish_reason"] == "length"
           for r in reqs):
    fail.append("stats recent_requests missing ids/finish reasons")
body = json.dumps({"prompt_ids": [1, 2, 3],
                   "max_new_tokens": 3}).encode()
out = json.loads(urllib.request.urlopen(urllib.request.Request(
    f"http://127.0.0.1:{port}/v1/serving/generate", data=body,
    headers={"Content-Type": "application/json"}),
    timeout=60).read())
if "request_id" not in out:
    fail.append("generate response missing request_id")

# (b) forced dump round-trips through the JSONL loader
flight_recorder.record("gate_marker", note=7)
p = flight_recorder.incident("forced_gate")
dump = flight_recorder.load_dump(p)
if not dump["valid"]:
    fail.append("forced dump digest-invalid")
elif dump["events"][-1]["kind"] != "forced_gate" \
        or not any(e["kind"] == "gate_marker" and e["note"] == 7
                   for e in dump["events"]):
    fail.append("forced dump did not round-trip its events")
elif not (dump["requests"]["recent"] or dump["requests"]["live"]):
    fail.append("forced dump carries no request timelines")

# (c) off-mode parity + p50 overhead, interleaved min-of-3 windows
def window():
    rs = [eng.submit(p, n) for p, (_, n) in zip(prompts, specs)]
    outs = [r.result(timeout=300) for r in rs]
    lats = sorted(r.latency_s for r in rs)
    return outs, lats[len(lats) // 2]

p50 = {"on": [], "off": []}
for rep in range(3):
    for mode in ("on", "off"):
        tracing.set_enabled(mode == "on")
        flight_recorder.configure(enabled=(mode == "on"))
        outs, med = window()
        p50[mode].append(med)
        if not all(np.array_equal(a, b)
                   for a, b in zip(traced, outs)):
            fail.append(f"{mode}-mode tokens differ from traced run")
on, off = min(p50["on"]), min(p50["off"])
if on > off * 1.05 + 0.002:
    fail.append(f"tracing+flight p50 overhead too high: "
                f"on={on*1e3:.2f}ms off={off*1e3:.2f}ms")
srv.stop()
eng.shutdown()

# fit bit-equality: instrumentation on vs fully off
def fit_once():
    conf = (NeuralNetConfiguration.builder().seed(11)
            .updater(Adam(1e-2)).list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))
            .setInputType(InputType.feedForward(4)).build())
    net = MultiLayerNetwork(conf).init()
    rs = np.random.RandomState(0)
    x = rs.randn(16, 4).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, 16)]
    for _ in range(5):
        net.fit(x, y)
    return net

tracing.set_enabled(True)
flight_recorder.configure(enabled=True)
a = fit_once()
tracing.set_enabled(False)
flight_recorder.configure(enabled=False)
b = fit_once()
for la, lb in zip(jax.tree_util.tree_leaves((a.params_list,
                                             a.opt_states)),
                  jax.tree_util.tree_leaves((b.params_list,
                                             b.opt_states))):
    if not np.array_equal(np.asarray(la), np.asarray(lb)):
        fail.append("fit with tracing+flight ON is not bit-identical "
                    "to OFF")
        break

if fail:
    sys.stderr.write("tracing smoke FAILED:\n  " + "\n  ".join(fail)
                     + "\n")
    sys.exit(1)
print(f"tracing smoke OK: 8 traced requests with full span sets, "
      f"request_id joins, dump round-trip, off-mode identical, p50 "
      f"on={on*1e3:.1f}ms off={off*1e3:.1f}ms")
EOF
tracesmoke=$?
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    DL4J_TPU_TRACING=1 python - <<'EOF'
# End-to-end incident drill: a chaos-injected watchdog stall during a
# traced serving+training run must leave a digest-valid flight dump
# holding (a) the last N train-step events, (b) the stall as its LAST
# event, and (c) the in-flight request timelines.
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets import ArrayDataSetIterator
from deeplearning4j_tpu.learning.updaters import Adam
from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.nn.conf import (
    DenseLayer, InputType, NeuralNetConfiguration, OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer.network import MultiLayerNetwork
from deeplearning4j_tpu.profiler import flight_recorder, telemetry
from deeplearning4j_tpu.serving import DecodeEngine
from deeplearning4j_tpu.util import FaultTolerance

inc = os.path.join(os.environ["DL4J_TPU_TRACING_GATE_DIR"], "drill")
flight_recorder.configure(directory=inc)
fail = []

cfg = tiny_config(vocab=17, max_len=64, d_model=32, n_layers=2,
                  n_heads=4, d_ff=64)
cfg.dropout = 0.0
gm = CausalLM(cfg, compute_dtype=jnp.float32)
gp = gm.init_params(jax.random.key(1))
eng = DecodeEngine(gm, gp, slots=2, page_size=8).start()
# a long request held in flight while training stalls
long_req = eng.submit(np.arange(4, dtype=np.int32), 56)

conf = (NeuralNetConfiguration.builder().seed(11).updater(Adam(1e-2))
        .list()
        .layer(DenseLayer(n_out=8, activation="tanh"))
        .layer(OutputLayer(n_out=2, activation="softmax",
                           loss="mcxent"))
        .setInputType(InputType.feedForward(4)).build())
net = MultiLayerNetwork(conf).init()
rs = np.random.RandomState(0)
x = rs.randn(16, 4).astype(np.float32)
y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, 16)]
net.fit(x, y)               # plain warm steps feed the ring
net.fit(x, y)
# guarded fit: batch shape changes -> first step recompiles, which
# always exceeds the 20ms watchdog deadline -> stall dump fires
net.fit(ArrayDataSetIterator(x, y, 8), epochs=1,
        fault_tolerance=FaultTolerance(divergence_window=0,
                                       step_deadline=0.02,
                                       flight_dir=inc))
long_req.result(timeout=300)
eng.shutdown()
deadline = time.time() + 10
dumps = []
while not dumps and time.time() < deadline:
    dumps = flight_recorder.list_dumps(inc)
    time.sleep(0.05)
if not dumps:
    fail.append("watchdog stall produced no incident dump")
else:
    out = flight_recorder.load_dump(dumps[0])
    if not out["valid"]:
        fail.append(f"dump {dumps[0]} digest-invalid")
    else:
        if out["events"][-1]["kind"] != "watchdog_stall":
            fail.append("dump's last event is not the stall: "
                        f"{out['events'][-1]}")
        if not any(e["kind"] == "train_step" for e in out["events"]):
            fail.append("dump carries no train_step events")
        tls = (out["requests"]["live"] + out["requests"]["recent"])
        if not any(t.get("request_id") == long_req.request_id
                   for t in tls):
            fail.append("in-flight request timeline missing from dump")
if telemetry.MetricsRegistry.get_default().counter(
        telemetry.WATCHDOG_STALLS).total() < 1:
    fail.append("watchdog stall counter not bumped")
if fail:
    sys.stderr.write("incident drill FAILED:\n  " + "\n  ".join(fail)
                     + "\n")
    sys.exit(1)
print(f"incident drill OK: stall dump {os.path.basename(dumps[0])} "
      f"with {len(flight_recorder.load_dump(dumps[0])['events'])} "
      "events incl. in-flight request timeline")
EOF
drill=$?
rm -rf "$TRACING_DIR"
if [ $tracesmoke -ne 0 ] || [ $drill -ne 0 ]; then
    echo "FATAL: tracing/incident smoke gate regressed (T=$tracesmoke D=$drill)" >&2
    exit 1
fi

# Control-plane chaos gate (docs/CONTROL_PLANE.md): one JobScheduler
# runs a 2x2-chip zero train job next to a 2-replica serving job on an
# 8-device CPU fleet; a whole worker is SIGKILL-equivalently killed
# mid-fit (no checkpoint at death). Asserts: the train job recovers
# its newest periodic bundle, MIGRATES onto the reduced topology
# (4-way -> 2-way, with re-sharded Adam moments BIT-EQUAL to the
# bundle), and finishes at the exact total step count with loss within
# tolerance of an uninterrupted 2-way run; concurrently a serving
# replica's worker dies and every request still completes (replays
# allowed, failures not; greedy outputs token-identical to solo
# generate()); the death is a digest-valid incident dump; and no
# scheduler/serving thread survives shutdown.
CTL_DIR=$(mktemp -d /tmp/dl4j_ctl_gate.XXXXXX)
export DL4J_TPU_CTL_GATE_DIR="$CTL_DIR"
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python - <<'EOF'
import json
import os
import shutil
import sys
import threading
import time

import jax
import numpy as np

from deeplearning4j_tpu import control
from deeplearning4j_tpu.datasets import ArrayDataSetIterator
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.nn.conf import (
    DenseLayer, InputType, NeuralNetConfiguration, OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.mesh import build_mesh
from deeplearning4j_tpu.parallel.sharded import ShardedTrainer
from deeplearning4j_tpu.profiler import flight_recorder, telemetry
from deeplearning4j_tpu.serving import ServingFleet
from deeplearning4j_tpu.util import FaultTolerance
from deeplearning4j_tpu.util.model_serializer import ModelSerializer
from deeplearning4j_tpu.util.resilience import latest_valid_bundle

GATE = os.environ["DL4J_TPU_CTL_GATE_DIR"]
CKPT = os.path.join(GATE, "ckpt")
FLIGHT = os.path.join(GATE, "incidents")
devs = jax.devices()
fail = []

rng = np.random.default_rng(0)
x = rng.normal(size=(64, 6)).astype(np.float32)
y = np.eye(2, dtype=np.float32)[(x.sum(1) > 0).astype(int)]


def make():
    return MultiLayerNetwork(
        (NeuralNetConfiguration.builder().seed(11)
         .updater(Adam(learning_rate=0.01)).list()
         .layer(DenseLayer(n_out=16, activation="tanh"))
         .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
         .setInputType(InputType.feedForward(6)).build()))


def make_iter():
    return ArrayDataSetIterator(x, y, 8, shuffle=True, seed=5)


class SlowIter(ArrayDataSetIterator):
    def next(self):
        time.sleep(0.1)
        return super().next()


VOCAB = 17
cfg = tiny_config(vocab=VOCAB, max_len=64, d_model=32, n_layers=2,
                  n_heads=4, d_ff=64)
cfg.dropout = 0.0
gpt = CausalLM(cfg, compute_dtype=jax.numpy.float32)
gparams = gpt.init_params(jax.random.key(1))


def solo(prompt, new):
    return np.asarray(gpt.generate(
        gparams, jax.numpy.asarray(np.asarray(prompt)[None, :],
                                   jax.numpy.int32), new))[0]


sched = control.JobScheduler(
    devices=devs[:6],
    workers={"w0": devs[:2], "w1": devs[2:4],
             "w2": [devs[4]], "w3": [devs[5]]},
    rebalance=False, flight_dir=FLIGHT)

# ---- serving job: 2 replicas on w2+w3 ------------------------------
def build_fleet(ctx):
    return ServingFleet(gpt, gparams, devices=ctx.devices, slots=2,
                        page_size=8, prefill_buckets=[8, 16, 40],
                        max_chunk=4)


serve = sched.submit(control.ServeJob(build_fleet, replicas=2,
                                      tenant="serve-tenant"))

# ---- train job: 4-chip zero, killed down to 2 chips ----------------
attempt_devices = []
nets = []


def run_train(ctx):
    attempt_devices.append(list(ctx.devices))
    net = make()
    net.init()
    nets.append(net)
    tr = ShardedTrainer(net, mesh=ctx.mesh(), mode="sharing",
                        update_sharding="zero")
    it = SlowIter(x, y, 8, shuffle=True, seed=5) \
        if ctx.attempt == 1 else make_iter()
    tr.fit(it, epochs=3, fault_tolerance=ctx.fault_tolerance)
    return float(net.score())


sched.wait(serve.job_id, timeout=600, states=("running",))
deadline = time.time() + 600
while serve.fleet is None and time.time() < deadline:
    time.sleep(0.05)
if serve.fleet is None:
    sys.stderr.write("control gate FAILED: fleet never came up\n")
    sys.exit(1)

# submit the train job only once the fleet serves: the drill needs
# traffic IN FLIGHT when the workers die, and on CPU the fleet's
# device-bound AOT warmup dwarfs the tiny zero fit
train = sched.submit(control.TrainJob(
    run_train, chips=4, tenant="train-tenant",
    checkpoint_dir=CKPT, backoff_s=2.0, max_retries=3,
    fault_tolerance=FaultTolerance(checkpoint_dir=CKPT,
                                   checkpoint_every=3,
                                   divergence_window=0)))

# ---- traffic: keeps flowing across the worker kill -----------------
requests = []
traffic_stop = threading.Event()
trng = np.random.default_rng(5)


SPECS = [(6, 4), (9, 12), (24, 6)]   # few shapes: solo() verification
#                                      pays one compile per shape


def traffic():
    i = 0
    while not traffic_stop.is_set():
        if len(requests) >= 250:     # bounded verification cost
            time.sleep(0.05)
            continue
        t0, n = SPECS[i % len(SPECS)]
        i += 1
        p = trng.integers(0, VOCAB, (t0,)).astype(np.int32)
        try:
            requests.append((p, n, serve.submit(p, n)))
        except Exception as e:      # capacity 429 would be a failure
            requests.append((p, n, e))
        time.sleep(0.2)


tt = threading.Thread(target=traffic, daemon=True)
tt.start()

# ---- the drill: kill the train worker + one serving worker ---------
deadline = time.time() + 600
while (not nets or nets[0].getIterationCount() < 5) \
        and time.time() < deadline:
    if train.state in control.TERMINAL:
        sys.stderr.write(f"control gate FAILED: train job died early: "
                         f"{train.status()}\n")
        sys.exit(1)
    time.sleep(0.02)
train_worker = "w0" if train.devices[0] in devs[:2] else "w1"
sched.kill_worker(train_worker)
sched.kill_worker("w3")            # one serving replica's chip dies
# snapshot the recovery bundle before the resumed attempt retires it
# (backoff_s=2.0 holds the relaunch long enough)
bundle = latest_valid_bundle(CKPT)
if bundle is None:
    fail.append("no digest-valid periodic bundle at the death")
else:
    shutil.copytree(bundle, os.path.join(GATE, "bundle_copy"))
    bundle = os.path.join(GATE, "bundle_copy")

time.sleep(1.0)                    # let some post-kill traffic route
traffic_stop.set()
tt.join(10)

sched.wait(train.job_id, timeout=600)

# ---- train-side assertions -----------------------------------------
if train.state != "completed":
    fail.append(f"train job ended {train.state}: {train.error}")
if train.attempts != 2 or train.retries_used != 1:
    fail.append(f"expected exactly one worker-lost retry, got "
                f"attempts={train.attempts} retries={train.retries_used}")
if len(attempt_devices) == 2:
    survivors = devs[2:4] if train_worker == "w0" else devs[:2]
    if len(attempt_devices[1]) != 2 \
            or set(attempt_devices[1]) != set(survivors):
        fail.append(f"resumed attempt not on the 2 surviving chips: "
                    f"{attempt_devices[1]}")
# exact total step count across both incarnations: 3 epochs x 8 batches
if nets and nets[-1].getIterationCount() != 24:
    fail.append(f"final iteration {nets[-1].getIterationCount()} != 24")
if telemetry.MetricsRegistry.get_default().counter(
        telemetry.FT_PERIODIC_CHECKPOINTS).total() < 1:
    fail.append("no periodic checkpoint was written")
if telemetry.MetricsRegistry.get_default().counter(
        telemetry.JOBS_MIGRATIONS).total() < 1:
    fail.append("migration counter not bumped")

# loss within tolerance of an uninterrupted 2-way run (same seed/data)
ref = make()
ref.init()
ShardedTrainer(ref, mesh=build_mesh(num_data=2,
                                    devices=attempt_devices[1]
                                    if len(attempt_devices) == 2
                                    else devs[:2]),
               mode="sharing", update_sharding="zero").fit(
    make_iter(), epochs=3)
if nets and not np.isclose(float(ref.score()), float(nets[-1].score()),
                           rtol=1e-3):
    fail.append(f"migrated loss {float(nets[-1].score()):.6f} deviates "
                f"from clean 2-way run {float(ref.score()):.6f}")

# bit-equal Adam moments through the 4->2 re-shard of the bundle
if bundle is not None:
    ref_net = make(); ref_net.init()
    ModelSerializer.loadInto(ref_net, os.path.join(bundle, "model.zip"))
    saved = [np.asarray(l) for l in jax.tree_util.tree_leaves(
        (ref_net.params_list, ref_net.opt_states))]
    net2 = make(); net2.init()
    ModelSerializer.loadInto(net2, os.path.join(bundle, "model.zip"))
    tr2 = ShardedTrainer(net2, mesh=build_mesh(num_data=2,
                                               devices=devs[:2]),
                         mode="sharing", update_sharding="zero")
    tr2._place_update_sharded()
    tr2._finish()
    got = [np.asarray(l) for l in jax.tree_util.tree_leaves(
        (net2.params_list, net2.opt_states))]
    for a, b in zip(saved, got):
        if not np.array_equal(a, b):
            fail.append("Adam moments NOT bit-equal after the 4->2 "
                        "re-shard")
            break
    man = json.load(open(os.path.join(bundle, "manifest.json")))
    if man.get("mesh", {}).get("data") != 4:
        fail.append(f"bundle not from the 4-way mesh: {man.get('mesh')}")

# ---- serving-side assertions ---------------------------------------
n_done = n_replayed = 0
for p, n, r in requests:
    if isinstance(r, Exception):
        fail.append(f"submit failed: {r}")
        continue
    try:
        out = r.result(timeout=120)
    except Exception as e:
        fail.append(f"request failed ({type(e).__name__}: {e})")
        continue
    n_done += 1
    n_replayed += int(r.attempts > 1)
    if not np.array_equal(out, solo(p, n)):
        fail.append("request output not token-identical to solo")
if n_done < 8:
    fail.append(f"too little traffic completed ({n_done})")
if serve.fleet is None or serve.fleet.alive_replicas() != 1:
    fail.append("serving fleet did not end on exactly the survivor")
if len(serve.devices) != 1 or serve.devices[0] != devs[4]:
    fail.append(f"serve job kept the dead chip: {serve.devices}")

# ---- incident dump for the death -----------------------------------
dumps = flight_recorder.list_dumps(FLIGHT)
worker_dumps = [d for d in dumps if "job_worker_lost" in d]
if not worker_dumps:
    fail.append(f"no job_worker_lost incident dump in {FLIGHT}")
else:
    loaded = flight_recorder.load_dump(worker_dumps[-1])
    if not loaded["valid"]:
        fail.append("worker-lost incident dump failed digest check")
    if loaded["events"] and loaded["events"][-1]["kind"] \
            != "job_worker_lost":
        fail.append("incident dump does not END on the worker death")

sched.shutdown()
time.sleep(1.0)
leaked = [t.name for t in threading.enumerate()
          if t.is_alive() and t.name.startswith(
              ("JobScheduler", "JobRunner", "ServingEngine",
               "ServingFleetRouter", "ServingPrefillLane"))]
if leaked:
    fail.append(f"threads survived shutdown: {leaked}")

if fail:
    sys.stderr.write("control-plane gate FAILED:\n  "
                     + "\n  ".join(fail) + "\n")
    sys.exit(1)
print(f"control-plane gate OK: worker {train_worker} killed mid-fit -> "
      f"train migrated 4->2 chips (attempt 2 on survivors), finished "
      f"at iteration 24 with bit-equal re-sharded moments; "
      f"{n_done} serving requests completed ({n_replayed} replayed, "
      f"0 failed); incident dump digest-valid")
EOF
ctlgate=$?
rm -rf "$CTL_DIR"
if [ $ctlgate -ne 0 ]; then
    echo "FATAL: control-plane chaos gate regressed" >&2
    exit 1
fi
# Control-plane PHASE-2 drill (docs/CONTROL_PLANE.md "Phase 2"): two
# REAL worker subprocesses under a WorkerSupervisor, bundles in a
# SharedFSBundleStore. Phase A: a fake maintenance notice lands
# mid-fit — the bundle must be digest-valid in the shared store
# BEFORE the deadline, the task must drain cleanly (outcome
# "preempted", zero failures), then migrate onto the survivor and
# finish at the exact step count with loss parity vs an uninterrupted
# run. Phase B: a worker process is SIGKILLed with NO notice — the
# survivor must discover the newest periodic bundle through the
# shared store and finish at the exact step count with loss parity.
# Workers respawn into capacity; no supervisor thread survives.
P2_DIR=$(mktemp -d /tmp/dl4j_p2_gate.XXXXXX)
export DL4J_TPU_P2_GATE_DIR="$P2_DIR"
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python - <<'EOF'
import os
import sys
import threading
import time

import numpy as np

GATE = os.environ["DL4J_TPU_P2_GATE_DIR"]
CTL = os.path.join(GATE, "ctl")
STORE = os.path.join(GATE, "store")
os.makedirs(CTL, exist_ok=True)
fail = []

# the drill's task module, dropped into the control dir (which rides
# every worker's sys.path)
with open(os.path.join(CTL, "p2_drill_task.py"), "w") as f:
    f.write('''
import time

import numpy as np


def build(seed=11):
    from deeplearning4j_tpu.learning import Adam
    from deeplearning4j_tpu.nn.conf import (
        DenseLayer, InputType, NeuralNetConfiguration, OutputLayer,
    )
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    return MultiLayerNetwork(
        (NeuralNetConfiguration.builder().seed(seed)
         .updater(Adam(learning_rate=0.01)).list()
         .layer(DenseLayer(n_out=8, activation="tanh"))
         .layer(OutputLayer(n_out=2, activation="softmax",
                            loss="mcxent"))
         .setInputType(InputType.feedForward(4)).build())).init()


def data(delay, ctx=None):
    from deeplearning4j_tpu.datasets import ArrayDataSetIterator

    rng = np.random.default_rng(0)
    x = rng.normal(size=(48, 4)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x.sum(1) > 0).astype(int)]

    class It(ArrayDataSetIterator):
        def next(self):
            time.sleep(delay)
            b = super().next()
            if ctx is not None:
                ctx.progress(ctx._step_seen + 1)
                ctx._step_seen += 1
            return b

    return It(x, y, 8, shuffle=True, seed=5)


def fit_task(ctx):
    net = build()
    ctx._step_seen = 0
    net.fit(data(float(ctx.params.get("delay", 0.1)), ctx), epochs=3,
            fault_tolerance=ctx.fault_tolerance)
    return {"iteration": int(net.getIterationCount()),
            "loss": float(net._score)}
''')

from deeplearning4j_tpu.control import WorkerSupervisor
from deeplearning4j_tpu.profiler import flight_recorder, telemetry
from deeplearning4j_tpu.util.resilience import SharedFSBundleStore

# the uninterrupted reference (same seed/data/arch, no delay)
sys.path.insert(0, CTL)
import p2_drill_task

ref = p2_drill_task.build()
ref.fit(p2_drill_task.data(0.0), epochs=3)
REF_LOSS = float(ref._score)
REF_ITERS = int(ref.getIterationCount())          # 18

sup = WorkerSupervisor(["w0", "w1"], control_dir=CTL,
                       heartbeat_s=0.1, lease_s=8.0,
                       restart_delay_s=0.2)
sup.start()


def wait_step(task, n, timeout=240):
    deadline = time.time() + timeout
    while time.time() < deadline:
        w = task.worker
        if task.state == "running" and w is not None \
                and (sup.workers_status()[w]["step"] or 0) >= n:
            return True
        time.sleep(0.05)
    return False


def run_phase(name, namespace, disrupt):
    ft = {"shared_root": STORE, "namespace": namespace,
          "checkpoint_every": 3, "divergence_window": 0}
    task = sup.submit_task("p2_drill_task:fit_task", {"delay": 0.1},
                           ft=ft)
    if not wait_step(task, 4):
        fail.append(f"{name}: task never reached step 4 "
                    f"({task.status()})")
        return None
    disrupt(task)
    try:
        task.wait(300)
    except TimeoutError:
        fail.append(f"{name}: task never finished ({task.status()})")
        return None
    if task.state != "completed":
        fail.append(f"{name}: task ended {task.state}: {task.error}")
        return None
    if task.migrations != 1:
        fail.append(f"{name}: expected exactly one migration, got "
                    f"{task.migrations}")
    if task.result["iteration"] != REF_ITERS:
        fail.append(f"{name}: finished at iteration "
                    f"{task.result['iteration']} != {REF_ITERS}")
    if not np.isclose(task.result["loss"], REF_LOSS, rtol=1e-4):
        fail.append(f"{name}: loss {task.result['loss']:.6f} deviates "
                    f"from clean run {REF_LOSS:.6f}")
    return task


# ---- phase A: maintenance notice -> checkpoint before deadline -----
def notice(task):
    store = SharedFSBundleStore(STORE, "pA")
    prev = store.latest_valid()        # periodic bundle from step 3
    t0 = time.monotonic()
    deadline_s = 15.0
    sup.preempt(task.worker, deadline_s=deadline_s)
    # the notice must produce a NEW preemption bundle (a later step
    # boundary than any periodic one) inside the grace window
    while store.latest_valid() == prev \
            and time.monotonic() - t0 < deadline_s:
        time.sleep(0.05)
    landed = time.monotonic() - t0
    if store.latest_valid() == prev:
        fail.append("phase A: no NEW digest-valid bundle landed in "
                    "the shared store before the notice deadline")
    else:
        print(f"phase A: preemption bundle landed {landed:.1f}s into "
              f"the {deadline_s:.0f}s notice window")


taskA = run_phase("phase A", "pA", notice)
if taskA is not None and taskA.error:
    fail.append(f"phase A: post-notice failure recorded: "
                f"{taskA.error}")
events = flight_recorder.get_default().events()
kinds = [e["kind"] for e in events]
for k in ("worker_preempt_notice", "worker_task_migrated"):
    if k not in kinds:
        fail.append(f"phase A: flight event {k} missing")
if not any(e["kind"] == "worker_task_migrated"
           and e.get("reason") == "preempt_notice" for e in events):
    fail.append("phase A: migration was not the notice-drain kind")

# ---- phase B: SIGKILL, no notice -> periodic-bundle recovery -------
def sigkill(task):
    sup.kill(task.worker)


# wait for the phase-A worker to respawn so phase B has 2 workers
deadline = time.time() + 120
while len(sup.alive()) < 2 and time.time() < deadline:
    time.sleep(0.1)
taskB = run_phase("phase B", "pB", sigkill)
if "worker_process_dead" not in [
        e["kind"] for e in flight_recorder.get_default().events()]:
    fail.append("phase B: no worker_process_dead flight event")

# ---- liveness gauges + clean shutdown ------------------------------
sup._publish_gauges(force=True)
g = telemetry.MetricsRegistry.get_default().gauge(
    telemetry.WORKER_PROCESSES)
alive_gauge = {dict(k).get("state"): v for k, v in g.values().items()}
if alive_gauge.get("alive", 0) < 1:
    fail.append(f"worker liveness gauge empty: {alive_gauge}")

procs = [h.proc for h in sup._handles.values() if h.proc is not None]
sup.shutdown()
if any(p.poll() is None for p in procs):
    fail.append("worker processes survived supervisor shutdown")
time.sleep(1.0)
leaked = [t.name for t in threading.enumerate()
          if t.is_alive() and t.name.startswith(
              ("WorkerSupervisor", "NoticePoller", "WorkerHeartbeat"))]
if leaked:
    fail.append(f"threads survived shutdown: {leaked}")

if fail:
    sys.stderr.write("phase-2 drill FAILED:\n  "
                     + "\n  ".join(fail) + "\n")
    sys.exit(1)
print(f"phase-2 drill OK: noticed worker checkpointed to the shared "
      f"store before its deadline and drained cleanly; SIGKILLed "
      f"worker's task migrated onto the survivor via the shared "
      f"store and finished at iteration {REF_ITERS} with loss parity "
      f"({REF_LOSS:.6f}); workers respawned; no leaked threads")
EOF
p2gate=$?
rm -rf "$P2_DIR"
if [ $p2gate -ne 0 ]; then
    echo "FATAL: control-plane phase-2 drill regressed" >&2
    exit 1
fi
# SLO smoke gate (docs/OBSERVABILITY.md "Alerting and SLOs"): the
# end-to-end alerting drill. A 2-replica serving fleet under a
# JobScheduler runs with the SLO engine's p99 burn-rate + queue-
# pressure rules; a chaos-injected latency spike (chaos.hang_replica)
# must drive the burn-rate alert pending -> firing -> resolved within
# its fast window, the firing transition must appear in /v1/alerts,
# the flight recorder, and dl4j_tpu_alerts_total{state="firing"}, the
# page severity must leave a digest-valid incident dump, a sustained
# queue-pressure alert must make the scheduler restart a drained
# replica (the alert-driven scale-up), and SLO-off serving must stay
# token-identical with zero evaluator threads.
SLO_DIR=$(mktemp -d /tmp/dl4j_slo_gate.XXXXXX)
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    DL4J_SLO_GATE_DIR="$SLO_DIR" \
    python - <<'EOF'
import json
import os
import sys
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import control
from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.profiler import (
    chaos, flight_recorder, slo, telemetry,
)
from deeplearning4j_tpu.serving import ServingFleet
from deeplearning4j_tpu.ui.server import UIServer

FLIGHT = os.environ["DL4J_SLO_GATE_DIR"]
fail = []

cfg = tiny_config(vocab=17, max_len=48, d_model=32, n_layers=2,
                  n_heads=4, d_ff=64)
cfg.dropout = 0.0
m = CausalLM(cfg, compute_dtype=jnp.float32)
params = m.init_params(jax.random.key(1))
rng = np.random.default_rng(0)
prompts = [rng.integers(0, 17, (int(rng.integers(3, 12)),)).astype(
    np.int32) for _ in range(6)]
solo = {i: np.asarray(m.generate(
    params, jnp.asarray(p[None, :], jnp.int32), 3))[0]
    for i, p in enumerate(prompts)}
devs = jax.devices()[:2]
reg = telemetry.MetricsRegistry.get_default()

TARGET = 0.25        # aligned to a DEFAULT_BUCKETS bound
eng = slo.SLOEngine(
    [slo.BurnRate("serving_p99_burn", severity="page",
                  histogram=telemetry.SERVING_REQUEST_LATENCY,
                  target_s=TARGET, objective=0.95, factor=2.0,
                  fast_window_s=2.0, slow_window_s=5.0,
                  for_s=1.0, group_by=()),
     slo.Threshold("serving_queue_pressure",
                   metric=telemetry.SERVING_FLEET_PRESSURE,
                   bound=1.0, op=">", for_s=0.5,
                   action="scale_serve")],
    interval_s=0.2, flight_dir=FLIGHT)
eng.start()
sched = control.JobScheduler(devices=devs,
                             workers={"w0": devs[:1], "w1": devs[1:]},
                             slo=eng, rebalance=False,
                             make_default=False).start()
job = sched.submit(control.ServeJob(
    lambda ctx: ServingFleet(m, params, devices=ctx.devices, slots=2,
                             page_size=8, prefill_buckets=[16],
                             max_chunk=4),
    chips=2, min_chips=1))
sched.wait(job.job_id, timeout=120, states=("running",))
deadline = time.monotonic() + 30
while job.fleet is None and time.monotonic() < deadline:
    time.sleep(0.02)
fl = job.fleet

def traffic(seconds, concurrency=2):
    """Steady short requests; returns [(prompt_idx, tokens)]."""
    out, stop = [], time.monotonic() + seconds
    with ThreadPoolExecutor(max_workers=concurrency) as ex:
        while time.monotonic() < stop:
            futs = [(i, ex.submit(fl.generate, prompts[i], 3))
                    for i in (0, 1, 2)]
            for i, f in futs:
                out.append((i, f.result(timeout=120)))
            time.sleep(0.05)
    return out

# ---- phase 1: warm history (ring must span the slow window), and
# with the SLO engine ON, greedy outputs stay token-identical --------
for i, got in traffic(6.0):
    if not np.array_equal(got, solo[i]):
        fail.append(f"SLO-on output differs from solo for prompt {i}")
        break
if eng.alert_state("serving_p99_burn") != "inactive":
    fail.append("burn alert not inactive under healthy traffic "
                f"({eng.alert_state('serving_p99_burn')})")

# ---- phase 2: chaos latency spike -> pending -> firing -------------
saw = set()
for r in fl._replicas:
    chaos.hang_replica(r.engine, 3.0)
with ThreadPoolExecutor(max_workers=8) as ex:
    futs = [ex.submit(fl.generate, prompts[i % 6], 3)
            for i in range(8)]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        saw.add(eng.alert_state("serving_p99_burn"))
        if "firing" in saw:
            break
        time.sleep(0.03)
    for f in futs:
        f.result(timeout=120)
if "pending" not in saw or "firing" not in saw:
    fail.append(f"burn alert lifecycle incomplete: saw {sorted(saw)} "
                "(wanted pending AND firing)")

# firing is visible on every surface
if reg.counter(telemetry.ALERTS_TOTAL).value(
        rule="serving_p99_burn", state="firing") < 1:
    fail.append("dl4j_tpu_alerts_total{state=firing} did not count")
ev = [e for e in flight_recorder.get_default().events()
      if e["kind"] == "alert" and e["rule"] == "serving_p99_burn"
      and e["state"] == "firing"]
if not ev:
    fail.append("no flight-recorder event for the firing transition")
# the dump is written in the tick's unlocked phase AFTER the state
# flips to firing — poll, never assert it exists the instant the
# alert is visible (same discipline as watchdog dumps)
dumps, deadline = [], time.monotonic() + 10
while not dumps and time.monotonic() < deadline:
    dumps = [d for d in flight_recorder.list_dumps(FLIGHT)
             if "slo_page" in d]
    time.sleep(0.05)
if not dumps:
    fail.append(f"page severity left no incident dump in {FLIGHT}")
else:
    loaded = flight_recorder.load_dump(dumps[-1])
    if not loaded["valid"]:
        fail.append("slo_page incident dump failed digest check")
ui = UIServer()
port = ui.start(port=0)
try:
    body = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/v1/alerts", timeout=10).read())
    rows = [a for a in body["alerts"]
            if a["rule"] == "serving_p99_burn"]
    if not rows or rows[0]["state"] not in ("firing", "resolved"):
        fail.append(f"/v1/alerts does not show the burn alert: "
                    f"{body['alerts']}")
finally:
    ui.stop()

# ---- phase 3: recovery traffic drains the fast window -> resolved --
deadline = time.monotonic() + 30
while eng.alert_state("serving_p99_burn") != "resolved" \
        and time.monotonic() < deadline:
    traffic(0.4)
if eng.alert_state("serving_p99_burn") != "resolved":
    fail.append("burn alert did not resolve after recovery "
                f"({eng.alert_state('serving_p99_burn')})")

# ---- phase 4: sustained queue pressure -> scheduler scale-up -------
fl.drain_replica(1)
deadline = time.monotonic() + 15
while sched.devices.free == 0 and time.monotonic() < deadline:
    time.sleep(0.02)
if sched.devices.free != 1:
    fail.append("drained replica's chip never returned to the pool")
chaos.hang_replica(fl._replicas[0].engine, 2.5)
with ThreadPoolExecutor(max_workers=12) as ex:
    futs = [ex.submit(fl.generate, prompts[i % 6], 2)
            for i in range(12)]
    deadline = time.monotonic() + 60
    while fl.alive_replicas() < 2 and time.monotonic() < deadline:
        time.sleep(0.05)
    for f in futs:
        f.result(timeout=120)
if fl.alive_replicas() != 2:
    fail.append("scheduler did not restart the drained replica on "
                "the queue-pressure alert")
elif reg.counter(telemetry.JOBS_RESTARTS).value(
        job=job.job_id, reason="queue_pressure_alert") < 1:
    fail.append("scale-up restart not counted under "
                "reason=queue_pressure_alert")

sched.shutdown()
eng.shutdown()

# ---- phase 5: SLO-off mode — token-identical, zero extra threads ---
with ServingFleet(m, params, replicas=1, slots=2, page_size=8,
                  prefill_buckets=[16], max_chunk=4) as off_fl:
    for i in (0, 3, 5):
        got = off_fl.generate(prompts[i], 3)
        if not np.array_equal(got, solo[i]):
            fail.append(f"SLO-off output differs from solo for "
                        f"prompt {i}")
            break
    if any(t.name == "SLOEvaluator" for t in threading.enumerate()
           if t.is_alive()):
        fail.append("SLOEvaluator thread alive in SLO-off mode")
leaked = [t.name for t in threading.enumerate()
          if t.is_alive() and t.name.startswith(
              ("SLOEvaluator", "JobScheduler", "JobRunner",
               "ServingEngine", "ServingFleetRouter"))]
if leaked:
    fail.append(f"threads survived shutdown: {leaked}")

if fail:
    sys.stderr.write("SLO gate FAILED:\n  " + "\n  ".join(fail) + "\n")
    sys.exit(1)
print("SLO gate OK: chaos latency spike drove serving_p99_burn "
      "pending -> firing -> resolved (flight event, alerts_total, "
      "/v1/alerts, digest-valid slo_page dump), queue-pressure alert "
      "restarted the drained replica, SLO-off serving token-identical "
      "with zero evaluator threads")
EOF
slogate=$?
rm -rf "$SLO_DIR"
if [ $slogate -ne 0 ]; then
    echo "FATAL: SLO smoke gate regressed" >&2
    exit 1
fi
# Profiler smoke gate (docs/OBSERVABILITY.md "Where the time goes"):
# the roofline program registry end-to-end. A registry-off tiny fit
# must stay bit-identical to a registry-on fit (off-mode hot paths
# unchanged); the registry-on fit + a few served requests must leave
# the train-step and serving sites with nonzero flops/bytes and a
# roofline verdict (and the tiny CPU LSTM step must NOT read
# compute_bound); GET /v1/programs serves the same view over HTTP; a
# forced POST /v1/profile capture round-trips digest-valid; and a
# chaos-driven (hang_replica) firing page alert produces exactly ONE
# rate-limited capture whose bundle path is stamped on the incident
# dump.
PROF_DIR=$(mktemp -d /tmp/dl4j_prof_gate.XXXXXX)
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    DL4J_PROF_GATE_DIR="$PROF_DIR" \
    python - <<'EOF'
import json
import os
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np

GATE = os.environ["DL4J_PROF_GATE_DIR"]
fail = []

from deeplearning4j_tpu.profiler import (
    chaos, flight_recorder, programs, slo, telemetry,
)
from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.serving import DecodeEngine
from deeplearning4j_tpu.ui.server import UIServer
from deeplearning4j_tpu.zoo import TextGenerationLSTM


def tiny_fit():
    """Identically-seeded tiny LSTM fit; returns raw param bytes."""
    np.random.seed(0)
    net = TextGenerationLSTM(vocab_size=8, hidden=16,
                             tbptt_length=0).init()
    rs = np.random.RandomState(0)
    ids = rs.randint(0, 8, (4, 12))
    x = np.eye(8, dtype=np.float32)[ids]
    y = np.eye(8, dtype=np.float32)[np.roll(ids, -1, axis=1)]
    for _ in range(2):
        net.fit(x, y)
    return b"".join(np.asarray(jax.device_get(leaf)).tobytes()
                    for leaf in jax.tree_util.tree_leaves(
                        net.params_list))


# --- A: registry-off fit is bit-identical to registry-on --------------
programs.set_enabled(False)
programs.reset()
off_bytes = tiny_fit()
if programs.snapshot() != {}:
    fail.append("off-mode registry snapshot not empty")
programs.set_enabled(True)
programs.reset()
on_bytes = tiny_fit()
if off_bytes != on_bytes:
    fail.append("registry-on fit params differ from registry-off "
                "(hot path not bit-identical)")

# --- B: train-step site has flops/bytes and a sane verdict ------------
snap = programs.get_default().snapshot()
mln = snap.get("sites", {}).get("mln_step")
if not mln:
    fail.append(f"mln_step missing from registry sites: "
                f"{sorted(snap.get('sites', {}))}")
else:
    if not (mln["flops"] > 0 and mln["bytes_accessed"] > 0):
        fail.append(f"mln_step flops/bytes not populated: {mln}")
    if mln["verdict"] == "compute_bound":
        fail.append("tiny CPU LSTM step classified compute_bound "
                    "(roofline verdict nonsense)")
    if mln["verdict"] not in ("dispatch_bound", "memory_bound"):
        fail.append(f"mln_step verdict unexpected: {mln['verdict']}")

# --- C: serving sites register through the AOT warm pool --------------
cfg = tiny_config(vocab=13, max_len=48, d_model=32, n_layers=2,
                  n_heads=4, d_ff=64)
cfg.dropout = 0.0
model = CausalLM(cfg, compute_dtype=jnp.float32)
params = model.init_params(jax.random.key(1))
rng = np.random.default_rng(0)
prompts = [rng.integers(0, 13, (n,)).astype(np.int32)
           for n in (5, 9, 3)]
with DecodeEngine(model, params, slots=2, page_size=8) as eng:
    for p in prompts:
        eng.submit(p, 4).result(timeout=120)
    snap = programs.get_default().snapshot()
    serving = {s: d for s, d in snap.get("sites", {}).items()
               if s.startswith("serving_")}
    decode = [s for s in serving if "decode" in s]
    prefill = [s for s in serving if "prefill" in s]
    if not decode or not prefill:
        fail.append(f"serving decode/prefill sites missing: "
                    f"{sorted(serving)}")
    for s, d in serving.items():
        if d["dispatches"] and not (d["flops"] > 0
                                    and d["bytes_accessed"] > 0):
            fail.append(f"serving site {s} dispatched without "
                        f"flops/bytes: {d}")
        if d["dispatches"] and d["verdict"] == "unknown":
            fail.append(f"serving site {s} has no verdict: {d}")

    # --- D: HTTP plane — GET /v1/programs + forced POST /v1/profile --
    ui = UIServer()
    port = ui.start(port=0)
    try:
        got = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/v1/programs?n=50",
            timeout=10).read())
        if "mln_step" not in got.get("sites", {}):
            fail.append("GET /v1/programs missing mln_step site")
        if not any(s.startswith("serving_") for s in got.get("sites", {})):
            fail.append("GET /v1/programs missing serving sites")
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/profile",
            data=json.dumps({"duration_s": 0.05,
                             "directory": GATE + "/manual"}).encode(),
            headers={"Content-Type": "application/json"})
        resp = json.loads(urllib.request.urlopen(req, timeout=30).read())
        bundle = resp.get("bundle")
        if not bundle:
            fail.append(f"POST /v1/profile returned no bundle: {resp}")
        else:
            cap = programs.load_capture(bundle)
            if not cap["valid"]:
                fail.append(f"manual capture bundle not digest-valid: "
                            f"{bundle}")
            if not cap["programs"]:
                fail.append("manual capture bundle has no programs.json "
                            "payload")
    finally:
        ui.stop()

    # --- E: chaos-driven page alert -> exactly one rate-limited
    # capture, stamped on the incident dump -------------------------
    rule = slo.Threshold(
        "prof_gate_p99", severity="page",
        metric=telemetry.SERVING_REQUEST_LATENCY, quantile=0.99,
        window_s=10.0, bound=0.25, op=">", group_by=())
    eng_slo = slo.SLOEngine(
        [rule], interval_s=999.0, make_default=False,
        flight_dir=GATE + "/flight", profile_dir=GATE + "/prof",
        profile_duration_s=0.05, profile_min_interval_s=3600.0)
    eng_slo.tick(now=0.0)
    chaos.hang_replica(eng, seconds=0.6)
    eng.submit(prompts[0], 3).result(timeout=120)
    eng_slo.tick(now=10.0)
    if eng_slo.alert_state("prof_gate_p99") != "firing":
        fail.append(f"chaos latency spike did not fire page alert: "
                    f"{eng_slo.alert_state('prof_gate_p99')}")
    firing = [a for a in eng_slo.alerts()
              if a.rule == "prof_gate_p99" and a.state == "firing"]
    if not firing:
        fail.append("no firing alert object for prof_gate_p99")
    else:
        a = firing[0]
        if not a.profile_bundle:
            fail.append("firing page alert has no profile_bundle")
        else:
            cap = programs.load_capture(a.profile_bundle)
            if not cap["valid"]:
                fail.append("alert-triggered capture not digest-valid")
        if not a.incident_dump:
            fail.append("firing page alert has no incident dump")
        else:
            dump = flight_recorder.load_dump(a.incident_dump)
            ctx = (dump.get("manifest") or {}).get("context", {})
            if not dump["valid"]:
                fail.append("incident dump not digest-valid")
            if ctx.get("profile_bundle") != a.profile_bundle:
                fail.append(f"incident dump context missing "
                            f"profile_bundle: {ctx}")
    # recover (fast requests only), then re-fire inside the rate
    # limit: the second firing must NOT capture again
    for p in prompts:
        eng.submit(p, 2).result(timeout=120)
    eng_slo.tick(now=20.0)
    if eng_slo.alert_state("prof_gate_p99") != "resolved":
        fail.append(f"alert did not resolve after recovery: "
                    f"{eng_slo.alert_state('prof_gate_p99')}")
    chaos.hang_replica(eng, seconds=0.6)
    eng.submit(prompts[1], 3).result(timeout=120)
    eng_slo.tick(now=30.0)
    if eng_slo.alert_state("prof_gate_p99") != "firing":
        fail.append("alert did not re-fire after second chaos spike")
    refired = [a for a in eng_slo.alerts()
               if a.rule == "prof_gate_p99" and a.state == "firing"]
    if refired and refired[0].profile_bundle:
        fail.append("re-fired alert captured again inside the rate "
                    "limit")
    reg = telemetry.MetricsRegistry.get_default()
    m = reg.peek(telemetry.PROFILE_CAPTURES)
    n_slo = 0.0
    if m is not None:
        n_slo = m._json().get('{trigger="slo:prof_gate_p99"}', 0.0)
    if n_slo != 1.0:
        fail.append(f"expected exactly one slo-triggered capture, "
                    f"counter says {n_slo}")
    eng_slo.shutdown()

if fail:
    sys.stderr.write("profiler gate FAILED:\n  "
                     + "\n  ".join(fail) + "\n")
    sys.exit(1)
print("profiler gate OK: registry-off fit bit-identical; mln_step + "
      "serving decode/prefill sites carry flops/bytes and roofline "
      "verdicts (LSTM step not compute_bound); /v1/programs serves "
      "the view; forced /v1/profile and the chaos-driven page alert "
      "each round-trip digest-valid bundles, with exactly one "
      "rate-limited slo capture stamped on the incident dump")
EOF
profgate=$?
rm -rf "$PROF_DIR"
if [ $profgate -ne 0 ]; then
    echo "FATAL: profiler smoke gate regressed" >&2
    exit 1
fi

# Autoscale chaos drill (docs/CONTROL_PLANE.md "Phase 3"): the closed
# loop, end to end, twice. A 2-replica fleet and a lower-priority
# train job (checkpointing through ObjectStoreBundleStore over a
# local "bucket") exhaust a 3-chip pool; hung replicas + a burst make
# serving_queue_pressure FIRE -> the scheduler checkpoint-preempts
# and PARKS the train job, takes its chip, and fleet.add_replica
# grows the fleet to 3 — every burst request completes with greedy
# outputs token-identical to solo generate() and ZERO warm-pool
# misses on the grown replica. The alert then resolves; after
# scale_down_hold_s the elastic replica is removed, the chip returns,
# and the parked job resumes at its exact step, finishing with params
# AND Adam moments bit-equal to an uninterrupted run. Pass 2 repeats
# the whole drill with DL4J_TPU_CHAOS_STORE_ERROR_RATE=1: the first
# attempt of every object-store op fails, so each park/resume bundle
# op must retry (ft_bundle_io_retries_total > 0) and still converge.
AS_DIR=$(mktemp -d /tmp/dl4j_autoscale_gate.XXXXXX)
cat > "$AS_DIR/autoscale_drill.py" <<'EOF'
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import control
from deeplearning4j_tpu.datasets import ArrayDataSetIterator
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.nn.conf import (
    DenseLayer, InputType, NeuralNetConfiguration, OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.profiler import chaos, flight_recorder, slo, telemetry
from deeplearning4j_tpu.serving import ServingFleet
from deeplearning4j_tpu.util.resilience import (
    FaultTolerance, LocalObjectStore, ObjectStoreBundleStore,
)

GATE = os.environ["DL4J_TPU_AUTOSCALE_GATE_DIR"]
CHAOS_STORE = os.environ.get("DL4J_TPU_CHAOS_STORE_ERROR_RATE") == "1"
TAG = "chaos-store" if CHAOS_STORE else "clean"
fail = []
reg = telemetry.MetricsRegistry.get_default()

rng = np.random.default_rng(0)
x = rng.normal(size=(48, 4)).astype(np.float32)
y = np.eye(2, dtype=np.float32)[(x.sum(1) > 0).astype(int)]


def make_net():
    return MultiLayerNetwork(
        (NeuralNetConfiguration.builder().seed(3)
         .updater(Adam(learning_rate=0.01)).list()
         .layer(DenseLayer(n_out=8, activation="tanh"))
         .layer(OutputLayer(n_out=2, activation="softmax",
                            loss="mcxent"))
         .setInputType(InputType.feedForward(4)).build()))


class SlowIter(ArrayDataSetIterator):
    def next(self):
        time.sleep(0.35)
        return super().next()


VOCAB = 17
cfg = tiny_config(vocab=VOCAB, max_len=48, d_model=32, n_layers=2,
                  n_heads=4, d_ff=64)
cfg.dropout = 0.0
gpt = CausalLM(cfg, compute_dtype=jnp.float32)
gparams = gpt.init_params(jax.random.key(1))
prompts = [rng.integers(0, VOCAB, (int(rng.integers(3, 12)),))
           .astype(np.int32) for _ in range(6)]
solo = {i: np.asarray(gpt.generate(
    gparams, jnp.asarray(p[None, :], jnp.int32), 3))[0]
    for i, p in enumerate(prompts)}

devs = jax.devices()[:3]
eng = slo.SLOEngine(
    [slo.Threshold("serving_queue_pressure",
                   metric=telemetry.SERVING_FLEET_PRESSURE,
                   bound=1.0, op=">", for_s=0.5,
                   action="scale_serve")],
    interval_s=0.2)
eng.start()
sched = control.JobScheduler(
    devices=devs, workers={"w0": devs[:2], "w1": [devs[2]]},
    slo=eng, rebalance=False, scale_down_hold_s=2.0,
    make_default=False).start()

# train-job checkpoints live in an object-store "bucket" — the
# bundle substrate the parked job's exact-resume rides on
store = ObjectStoreBundleStore(
    LocalObjectStore(os.path.join(GATE, f"bucket-{TAG}")),
    "train-1", cache_dir=os.path.join(GATE, f"cache-{TAG}"),
    io_backoff=0.01)
if CHAOS_STORE and not isinstance(store.client,
                                  chaos.FaultyObjectStore):
    fail.append("chaos env set but the store client is unwrapped")
retries_before = reg.counter(telemetry.FT_BUNDLE_IO_RETRIES).total()

serve = sched.submit(control.ServeJob(
    lambda ctx: ServingFleet(gpt, gparams, devices=ctx.devices,
                             slots=2, page_size=8,
                             prefill_buckets=[16], max_chunk=4),
    replicas=2, priority=5))
sched.wait(serve.job_id, timeout=300, states=("running",))
deadline = time.monotonic() + 120
while serve.fleet is None and time.monotonic() < deadline:
    time.sleep(0.02)
if serve.fleet is None:
    sys.stderr.write("autoscale drill: fleet never came up\n")
    sys.exit(1)
fl = serve.fleet

nets = []


def run_train(ctx):
    net = make_net()
    nets.append(net)
    net.init()
    net.fit(SlowIter(x, y, 8, shuffle=True, seed=5), epochs=3,
            fault_tolerance=ctx.fault_tolerance)
    return float(net._score)


# baseline: the 2-replica fleet is token-identical to solo (this
# also pays the decode-compile cost BEFORE the train job starts, so
# the slow iterator is still mid-fit when the burst needs its chip)
for i in (0, 1):
    if not np.array_equal(fl.generate(prompts[i], 3), solo[i]):
        fail.append(f"baseline output differs from solo ({i})")

train = sched.submit(control.TrainJob(
    run_train, chips=1,
    fault_tolerance=FaultTolerance(bundle_store=store,
                                   checkpoint_every=None,
                                   divergence_window=0)))
sched.wait(train.job_id, timeout=120, states=("running",))
deadline = time.monotonic() + 60
while (not nets or nets[0].getIterationCount() < 3) \
        and time.monotonic() < deadline:
    time.sleep(0.02)
if sched.devices.free != 0:
    fail.append(f"pool not exhausted before the burst "
                f"({sched.devices.free} free)")

# ---- burst: pressure fires -> park the train job -> grow to 3 ------
for r in list(fl._replicas):
    chaos.hang_replica(r.engine, 3.0)
with ThreadPoolExecutor(max_workers=12) as ex:
    futs = [ex.submit(fl.generate, prompts[i % 6], 3)
            for i in range(12)]
    deadline = time.monotonic() + 120
    while fl.alive_replicas() < 3 and time.monotonic() < deadline:
        time.sleep(0.05)
    outs = [f.result(timeout=300) for f in futs]
if fl.alive_replicas() != 3:
    fail.append("fleet never grew to 3 replicas "
                f"(alert={eng.alert_state('serving_queue_pressure', fleet=fl.fleet_id)})")
for i, got in enumerate(outs):
    if not np.array_equal(got, solo[i % 6]):
        fail.append(f"burst output {i} differs from solo")
        break
# the park is transient (the quiet-alert shrink can refund the chip
# and resume the job before this line runs) — assert the TRANSITION,
# not the state
deadline = time.monotonic() + 30
parked = []
while not parked and time.monotonic() < deadline:
    parked = [e for e in flight_recorder.get_default().events()
              if e["kind"] == "job_parked"
              and e.get("job") == train.job_id]
    time.sleep(0.05)
if not parked:
    fail.append(f"train job never parked for the grow "
                f"({train.state})")
if serve._elastic:
    grown = fl._by_rid.get(serve._elastic[-1][0])
    if grown is None:
        fail.append("elastic rid not registered in the fleet")
    elif grown.engine.stats()["warm_pool"]["misses"] != 0:
        fail.append("grown replica had warm-pool misses: "
                    f"{grown.engine.stats()['warm_pool']}")
else:
    fail.append("no elastic replica recorded on the serve job")
if reg.counter(telemetry.FLEET_SCALE_UP).value(
        fleet=fl.fleet_id) < 1:
    fail.append("fleet_scale_up_total did not count")

# ---- quiet: alert resolves -> shrink -> parked job resumes exactly -
deadline = time.monotonic() + 90
while fl.alive_replicas() > 2 and time.monotonic() < deadline:
    time.sleep(0.05)
if fl.alive_replicas() != 2:
    fail.append("fleet never shrank after the alert went quiet "
                f"(alert={eng.alert_state('serving_queue_pressure', fleet=fl.fleet_id)})")
if reg.counter(telemetry.FLEET_SCALE_DOWN).value(
        fleet=fl.fleet_id) < 1:
    fail.append("fleet_scale_down_total did not count")
sched.wait(train.job_id, timeout=180)
if train.state != "completed":
    fail.append(f"parked train job did not finish ({train.state}: "
                f"{train.error})")
if len(nets) != 2 or nets[-1].getIterationCount() != 18:
    fail.append(f"resume step count wrong: attempts={len(nets)}, "
                f"iter={nets[-1].getIterationCount() if nets else 0}")
# bit-identical to an uninterrupted run: params AND Adam moments
ref = make_net().init()
ref.fit(ArrayDataSetIterator(x, y, 8, shuffle=True, seed=5), epochs=3)
for a, b in zip(jax.tree_util.tree_leaves(
        (ref.params_list, ref.opt_states)),
        jax.tree_util.tree_leaves(
        (nets[-1].params_list, nets[-1].opt_states))):
    if not np.array_equal(np.asarray(a), np.asarray(b)):
        fail.append("resumed run not bit-identical to uninterrupted")
        break

kinds = [e["kind"] for e in flight_recorder.get_default().events()]
for want in ("job_preempt", "job_parked", "job_scale_up",
             "fleet_replica_added", "job_scale_down",
             "fleet_replica_removed", "job_resumed"):
    if want not in kinds:
        fail.append(f"missing flight event {want}")

if CHAOS_STORE:
    retried = reg.counter(
        telemetry.FT_BUNDLE_IO_RETRIES).total() - retries_before
    if retried <= 0:
        fail.append("chaos store pass: no bundle op retried")
    if store.client.injected <= 0:
        fail.append("chaos store pass: nothing injected")

sched.shutdown()
eng.shutdown()
time.sleep(0.2)
leaked = [t.name for t in threading.enumerate()
          if t.is_alive() and t.name.startswith(
              ("SLOEvaluator", "JobScheduler", "JobRunner",
               "ServingEngine", "ServingFleetRouter"))]
if leaked:
    fail.append(f"threads survived shutdown: {leaked}")

if fail:
    sys.stderr.write(f"autoscale drill ({TAG}) FAILED:\n  "
                     + "\n  ".join(fail) + "\n")
    sys.exit(1)
print(f"autoscale drill ({TAG}) OK: pressure alert parked the train "
      "job and grew the fleet 2->3 (token-identical burst, zero "
      "warm-pool misses), quiet alert shrank it back and the parked "
      "job resumed bit-identically at step 18"
      + (", every bundle op retried under store chaos"
         if CHAOS_STORE else ""))
EOF
export DL4J_TPU_AUTOSCALE_GATE_DIR="$AS_DIR"
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    PYTHONPATH=. python "$AS_DIR/autoscale_drill.py"
asgate1=$?
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    DL4J_TPU_CHAOS_STORE_ERROR_RATE=1 \
    PYTHONPATH=. python "$AS_DIR/autoscale_drill.py"
asgate2=$?
unset DL4J_TPU_AUTOSCALE_GATE_DIR
rm -rf "$AS_DIR"
if [ $asgate1 -ne 0 ] || [ $asgate2 -ne 0 ]; then
    echo "FATAL: autoscale chaos drill regressed (clean=$asgate1 chaos=$asgate2)" >&2
    exit 1
fi

# Timeseries smoke gate (docs/OBSERVABILITY.md "Querying metrics
# history"): the embedded TSDB end-to-end. With DL4J_TPU_TSDB=1 a
# served fleet's history must answer /v1/query PromQL-lite goldens
# exactly (increase == requests served, rate == a hand replay of the
# raw samples, p99 == histogram_quantile over the registry's own
# bucket deltas); a 2-worker federation drill (spin_task's
# dl4j_tpu_worker_drill_steps_total) must surface coordinator-side
# worker= series with positive increase over /v1/query; a forced
# incident dump must embed a digest-valid metrics.json carrying both
# local and federated series; and TSDB-off serving must stay
# token-identical with zero sampler threads.
TS_DIR=$(mktemp -d /tmp/dl4j_tsdb_gate.XXXXXX)
env JAX_PLATFORMS=cpu DL4J_TPU_TELEMETRY=1 \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    DL4J_TPU_TSDB=1 DL4J_TSDB_GATE_DIR="$TS_DIR" \
    python - <<'EOF'
import json
import os
import sys
import threading
import time
import urllib.parse
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import control
from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.profiler import flight_recorder, telemetry
from deeplearning4j_tpu.profiler import timeseries as ts
from deeplearning4j_tpu.serving import ServingFleet
from deeplearning4j_tpu.ui.server import UIServer

GATE = os.environ["DL4J_TSDB_GATE_DIR"]
fail = []

cfg = tiny_config(vocab=17, max_len=48, d_model=32, n_layers=2,
                  n_heads=4, d_ff=64)
cfg.dropout = 0.0
m = CausalLM(cfg, compute_dtype=jnp.float32)
params = m.init_params(jax.random.key(1))
rng = np.random.default_rng(0)
prompts = [rng.integers(0, 17, (int(rng.integers(3, 12)),)).astype(
    np.int32) for _ in range(6)]
solo = {i: np.asarray(m.generate(
    params, jnp.asarray(p[None, :], jnp.int32), 3))[0]
    for i, p in enumerate(prompts)}
reg = telemetry.MetricsRegistry.get_default()

# a near-inert thread interval makes the manual ticks the ONLY samples
# the goldens see; the servers' ensure_default() reuses this sampler
sampler = ts.ensure_default(interval_s=3600.0)
if sampler is None:
    sys.stderr.write("TSDB gate: ensure_default returned None with "
                     "DL4J_TPU_TSDB=1\n")
    sys.exit(1)
ui = UIServer()
port = ui.start(port=0)


def q(expr):
    body = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/v1/query?query="
        + urllib.parse.quote(expr), timeout=10).read())
    if body.get("status") != "success":
        raise RuntimeError(f"query failed: {body}")
    return {tuple(sorted(r["metric"].items())): float(r["value"][1])
            for r in body["data"]["result"]}


# ---- phase 1: serve -> manual ticks bracket a known traffic slice,
# /v1/query answers match hand-computed goldens exactly --------------
with ServingFleet(m, params, replicas=1, slots=2, page_size=8,
                  prefill_buckets=[16], max_chunk=4) as fl:
    for i in range(6):
        if not np.array_equal(fl.generate(prompts[i], 3), solo[i]):
            fail.append(f"TSDB-on output differs from solo ({i})")
            break
    sampler.tick_once()           # sample A: 6 requests on the books
    cap_a = reg.capture()
    time.sleep(0.3)
    for i in range(6):
        fl.generate(prompts[i], 3)
    sampler.tick_once()           # sample B: 12 requests
    cap_b = reg.capture()

    # golden 1: increase between the two samples == requests served
    got = q(f"sum (increase({telemetry.SERVING_REQUESTS}[600s]))")
    if list(got.values()) != [6.0]:
        fail.append(f"increase golden: wanted [6.0], got {got}")

    # golden 2: rate == hand replay (last-first)/(t_last-t_first)
    # over the raw samples the store actually holds
    want = 0.0
    db = ts.default_db()
    for _labels, _kind, _b, pts in db.select(
            telemetry.SERVING_REQUESTS, [], 0.0, time.time() + 1):
        if len(pts) >= 2 and pts[-1][0] > pts[0][0]:
            want += (pts[-1][1] - pts[0][1]) / (pts[-1][0] - pts[0][0])
    got = q(f"sum (rate({telemetry.SERVING_REQUESTS}[600s]))")
    if len(got) != 1 or abs(list(got.values())[0] - want) > 1e-9:
        fail.append(f"rate golden: wanted {want}, got {got}")

    # golden 3: p99 == histogram_quantile over the registry's own
    # bucket deltas between the two captures
    ha = cap_a.get(telemetry.SERVING_REQUEST_LATENCY,
                   {"series": {}})
    hb = cap_b[telemetry.SERVING_REQUEST_LATENCY]
    want_q = None
    for key, (_c, _s, buckets) in hb["series"].items():
        prev = ha["series"].get(key)
        delta = [b - (prev[2][i] if prev else 0)
                 for i, b in enumerate(buckets)]
        v = ts.histogram_quantile(hb["bounds"], delta, 0.99)
        if v is not None:
            want_q = v if want_q is None else max(want_q, v)
    got = q("max (histogram_quantile(0.99, "
            f"{telemetry.SERVING_REQUEST_LATENCY}[600s]))")
    if want_q is None or len(got) != 1 \
            or abs(list(got.values())[0] - want_q) > 1e-9:
        fail.append(f"p99 golden: wanted {want_q}, got {got}")

# ---- phase 2: 2-worker federation drill ----------------------------
with control.WorkerSupervisor(["w0", "w1"], heartbeat_s=0.1,
                              lease_s=10.0,
                              restart_delay_s=0.1) as sup:
    for w in ("w0", "w1"):
        sup.submit_task("deeplearning4j_tpu.control.worker:spin_task",
                        {"seconds": 60}, worker=w)
    drill = "dl4j_tpu_worker_drill_steps_total"
    fed = {}
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        sampler.tick_once()       # merge freshly pushed captures
        fed = {r["metric"].get("worker"): float(r["value"][1])
               for r in json.loads(urllib.request.urlopen(
                   f"http://127.0.0.1:{port}/v1/query?query="
                   + urllib.parse.quote(
                       f"sum by (worker) (increase({drill}[120s]))"),
                   timeout=10).read())["data"]["result"]}
        if fed.get("w0", 0.0) > 0 and fed.get("w1", 0.0) > 0:
            break
        time.sleep(0.2)
    if not (fed.get("w0", 0.0) > 0 and fed.get("w1", 0.0) > 0):
        fail.append("federated worker= series never showed positive "
                    f"increase coordinator-side: {fed}")
    for w in ("w0", "w1"):
        sup.preempt(w, deadline_s=30)

# ---- phase 3: the black box carries the metrics history ------------
sampler.tick_once()
path = flight_recorder.get_default().incident(
    "tsdb_gate_drill", directory=GATE)
if path is None:
    fail.append("forced incident dump was not written")
else:
    loaded = flight_recorder.load_dump(path)
    if not loaded["valid"]:
        fail.append("incident dump failed digest check")
    blob = json.dumps(loaded["metrics"] or {})
    if telemetry.SERVING_REQUESTS not in blob:
        fail.append("metrics.json missing local serving series")
    if "dl4j_tpu_worker_drill_steps_total" not in blob \
            or '"worker"' not in blob:
        fail.append("metrics.json missing federated worker series")

# ---- phase 4: TSDB-off — token-identical, zero sampler threads -----
ui.stop()
ts.shutdown_default()
ts.set_enabled(False)
if ts.ensure_default() is not None:
    fail.append("ensure_default started a sampler with the TSDB off")
deadline = time.monotonic() + 5
while any(t.name == ts.Sampler.THREAD_NAME
          for t in threading.enumerate() if t.is_alive()) \
        and time.monotonic() < deadline:
    time.sleep(0.05)
if any(t.name == ts.Sampler.THREAD_NAME
       for t in threading.enumerate() if t.is_alive()):
    fail.append("TSDBSampler thread alive after shutdown/off")
with ServingFleet(m, params, replicas=1, slots=2, page_size=8,
                  prefill_buckets=[16], max_chunk=4) as off_fl:
    for i in (0, 3, 5):
        if not np.array_equal(off_fl.generate(prompts[i], 3),
                              solo[i]):
            fail.append(f"TSDB-off output differs from solo ({i})")
            break

leaked = [t.name for t in threading.enumerate()
          if t.is_alive() and t.name.startswith(
              ("TSDBSampler", "WorkerSupervisor", "WorkerHeartbeat",
               "ServingEngine", "ServingFleetRouter"))]
if leaked:
    fail.append(f"threads survived shutdown: {leaked}")

if fail:
    sys.stderr.write("timeseries gate FAILED:\n  "
                     + "\n  ".join(fail) + "\n")
    sys.exit(1)
print("timeseries gate OK: /v1/query matched hand-computed "
      "increase/rate/p99 goldens, 2-worker federation drill surfaced "
      "worker= series coordinator-side, incident dump embedded "
      "digest-valid metrics.json with local + federated history, "
      "TSDB-off serving token-identical with zero sampler threads")
EOF
tsgate=$?
rm -rf "$TS_DIR"
if [ $tsgate -ne 0 ]; then
    echo "FATAL: timeseries smoke gate regressed" >&2
    exit 1
fi

exit $rc
