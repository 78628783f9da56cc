"""Shared benchmark plumbing for bench.py / bench_resnet.py /
bench_lstm.py: one peak-FLOPs table, one cost-analysis helper, one
char-LSTM workload (so the driver metric in bench.py and the CLI
sweep in bench_lstm.py can never diverge).

Methodology invariants (bench.py v3): device-resident inputs,
best-of-3 timing windows, every window ends with a device->host loss
read.
"""

from __future__ import annotations

import logging
import time

import jax
import jax.numpy as jnp

log = logging.getLogger("deeplearning4j_tpu")

# the peak-FLOPs table now lives with the profiler so the LIVE fit
# loops (profiler/model_health.py MFU gauge) and the bench scripts
# divide by the same denominator; re-exported here so existing
# `from bench_common import peak_flops, PEAK_FLOPS` keeps working
from deeplearning4j_tpu.profiler.flops import (  # noqa: E402,F401
    PEAK_FLOPS, PEAK_HBM_GBPS, peak_flops, peak_hbm_gbps,
)


def telemetry_snapshot():
    """Compile counts/times + device-memory watermarks + model-health
    series (per-layer grad norms / update ratios / MFU, when a
    HealthMonitor ran) from the process-wide telemetry registry
    (profiler/telemetry.py), for embedding in BENCH_*.json rounds
    alongside wall-clock: a result is only comparable if it compiled
    the same number of times, and this makes that visible. Call AFTER
    the timed windows."""
    from deeplearning4j_tpu.profiler import telemetry

    return telemetry.snapshot()


def aot_cost_flops(step, *args, site=None, **kwargs):
    """Per-step FLOPs from XLA's cost analysis of the compiled step.

    Note on double work: the later jitted `step(...)` call re-traces,
    but its XLA compilation hits the compile cache this AOT compile
    populated (measured ~1ms vs ~620ms on this stack), so the extra
    cost is one trace, not a second compile.

    ``site`` additionally registers the executable in the roofline
    program registry (profiler/programs.py) when that is enabled —
    ``bench.py --profile`` uses this so the attribution table covers
    the bench step even though it bypasses instrument_jit."""
    compiled = step.lower(*args, **kwargs).compile()
    if site is not None:
        from deeplearning4j_tpu.profiler import programs
        from deeplearning4j_tpu.profiler.telemetry import (
            _arg_signature,
        )

        if programs.enabled():
            programs.get_default().register(
                site, _arg_signature(args, kwargs), compiled,
                source="bench")
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    return float(ca.get("flops", 0.0)) or None


def roofline_row(site, *, seconds_per_step=None, steps=0):
    """Roofline-verdict row for ``site`` from the program registry
    (profiler/programs.py): verdict + achieved FLOP/s and GB/s — the
    per-bench "is this step compute- or memory-bound, and how close
    to the roof" line in the aggregate output.

    The bench timing loops bypass instrument_jit, so the registry has
    the program's static analysis but no dispatch wall time; feeding
    the measured window back in via ``seconds_per_step``/``steps``
    turns the static row into achieved throughput. None when the
    registry is off or the site never registered (cost_analysis
    unavailable)."""
    from deeplearning4j_tpu.profiler import programs

    reg = programs.get_default()
    rows = [r for r in reg.snapshot().get("programs", [])
            if r.get("site") == site]
    if not rows:
        return None
    if steps and seconds_per_step:
        for _ in range(int(steps)):
            reg.record_dispatch(site, rows[0]["signature"],
                                seconds_per_step)
        rows = [r for r in reg.snapshot().get("programs", [])
                if r.get("site") == site]
    r = rows[0]
    out = {"site": site, "verdict": r.get("verdict")}
    for k in ("arithmetic_intensity", "achieved_flops_per_s",
              "achieved_gbps", "mfu", "hbm_utilization"):
        if r.get(k) is not None:
            v = r[k]
            out[k] = round(v, 4) if isinstance(v, float) else v
    return out


def time_best_of(run, state, steps, trials=3):
    """Best-of-N windows of `steps` calls; `run(state, i) -> (state,
    loss)`; each window ends in a device->host loss read."""
    state, loss = run(state, 0)
    float(jnp.mean(loss))  # sync (compile + first step)
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        for i in range(steps):
            state, loss = run(state, i + 1)
        float(jnp.mean(loss))
        best = min(best, time.perf_counter() - t0)
    return best


def build_char_lstm(batch=256, seq=200, hidden=256, vocab=77,
                    dtype="bf16", precision=None, site=None):
    """Build (run, state0, flops_per_step, tokens_per_step) for the
    char-LSTM workload so callers can either time it standalone
    (run_char_lstm) or interleave it with the frozen yardstick in
    shared windows (bench.py _lstm_metrics). ``precision`` sets a
    mixed-precision policy (nn/precision.py) — with one, ``dtype`` is
    ignored and params stay fp32 masters. ``site`` registers the
    compiled step in the roofline program registry (see
    aot_cost_flops) so callers can emit a roofline_row."""
    import numpy as np

    from deeplearning4j_tpu.ndarray.dtypes import DataType
    from deeplearning4j_tpu.nn.multilayer.network import (
        MultiLayerNetwork,
    )
    from deeplearning4j_tpu.zoo.textgen_lstm import TextGenerationLSTM

    model = TextGenerationLSTM(vocab_size=vocab, hidden=hidden,
                               tbptt_length=0)
    conf = model.conf()
    if precision is not None:
        conf.precision = precision
    else:
        conf.dtype = DataType.from_any(dtype).value
    net = MultiLayerNetwork(conf).init()

    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (batch, seq))
    x = jax.device_put(jnp.asarray(
        np.eye(vocab, dtype=np.float32)[ids], net._input_dtype))
    y = jax.device_put(jnp.asarray(
        np.eye(vocab, dtype=np.float32)[np.roll(ids, -1, 1)],
        net._input_dtype))
    step = net._get_train_step(has_mask=False)
    scaling = net._loss_scale_state is not None

    def step_args(state, i):
        base = (state[0], state[1], state[2])
        ls = (state[3],) if scaling else ()
        return base + ls + (jnp.asarray(i), jnp.asarray(0), x, y, None,
                            None, jax.random.key(i))

    flops_per_step = aot_cost_flops(step, *step_args(
        (net.params_list, net.states_list, net.opt_states,
         net._loss_scale_state), 0), site=site)

    def run(state, i):
        out = step(*step_args(state, i))
        # (p, s, o[, ls], loss) -> state tuple + loss
        return out[:-1], out[-1]

    state0 = (net.params_list, net.states_list, net.opt_states) \
        + ((net._loss_scale_state,) if scaling else ())
    return run, state0, flops_per_step, batch * seq


def pipeline_ab_lstm(batch=64, hidden=128, vocab=50, n_batches=12,
                     t_lo=48, t_hi=200, epochs=2, depth=2, seed=0):
    """Device-pipeline A/B on the WORST recompile case: a ragged
    char-LSTM stream (varying sequence length + partial final batch).

    Side 'off' fits the raw stream (one XLA compile per distinct
    shape); side 'on' fits through DevicePrefetchIterator with the
    'bucket' policy (one compile per power-of-two bucket + async
    double-buffered transfers). Fresh identically-seeded nets per side
    and wall-clock INCLUDES compiles — the recompile storm is the cost
    being removed, so hiding it would be benching the wrong thing.

    Returns pipeline_off_s/on_s, per-side jit-compile counts, and
    pipeline_speedup = off/on.
    """
    import numpy as np

    from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator
    from deeplearning4j_tpu.datasets.device_prefetch import (
        BatchShapePolicy, DevicePrefetchIterator,
    )
    from deeplearning4j_tpu.nn.multilayer.network import (
        MultiLayerNetwork,
    )
    from deeplearning4j_tpu.profiler import telemetry
    from deeplearning4j_tpu.zoo.textgen_lstm import TextGenerationLSTM

    rng = np.random.default_rng(seed)
    eye = np.eye(vocab, dtype=np.float32)
    sets = []
    for i in range(n_batches):
        t = int(rng.integers(t_lo, t_hi))
        n = batch if i < n_batches - 1 else max(batch // 3, 1)
        ids = rng.integers(0, vocab, (n, t))
        sets.append(DataSet(eye[ids], eye[np.roll(ids, -1, 1)]))

    def make_net():
        conf = TextGenerationLSTM(vocab_size=vocab, hidden=hidden,
                                  tbptt_length=0).conf()
        return MultiLayerNetwork(conf).init()

    reg = telemetry.MetricsRegistry.get_default()
    compiles = lambda: reg.counter(telemetry.JIT_COMPILES).total()
    out = {}
    for name in ("off", "on"):
        net = make_net()
        it = ListDataSetIterator(sets, batch_size=batch)
        pf = None
        if name == "on":
            it = pf = DevicePrefetchIterator(
                it, depth=depth,
                policy=BatchShapePolicy("bucket", batch_size=batch),
                dtype=net._input_dtype)
        try:
            c0 = compiles()
            t0 = time.perf_counter()
            net.fit(it, epochs=epochs)
            float(net.score())  # device->host sync closes the window
            out[f"pipeline_{name}_s"] = round(
                time.perf_counter() - t0, 4)
            out[f"pipeline_{name}_compiles"] = int(compiles() - c0)
        finally:
            if pf is not None:
                pf.shutdown()
    out["pipeline_speedup"] = round(
        out["pipeline_off_s"] / out["pipeline_on_s"], 4)
    return out


def pipeline_ab_fixed(net, make_iter, depth=2, epochs=1):
    """Device-pipeline A/B on a FIXED-shape stream (e.g. ResNet
    images): same net, warmed first so both sides reuse one compiled
    executable — the delta is purely host->device transfer overlap.
    ``make_iter()`` must return a fresh DataSetIterator each call.
    Returns pipeline_off_s/on_s and pipeline_speedup = off/on.
    """
    from deeplearning4j_tpu.datasets.device_prefetch import (
        DevicePrefetchIterator,
    )

    net.fit(make_iter(), epochs=1)   # warm: compile + page in
    float(net.score())
    out = {}
    t0 = time.perf_counter()
    net.fit(make_iter(), epochs=epochs)
    float(net.score())
    out["pipeline_off_s"] = round(time.perf_counter() - t0, 4)
    with DevicePrefetchIterator(make_iter(), depth=depth,
                                dtype=net._input_dtype) as pf:
        t0 = time.perf_counter()
        net.fit(pf, epochs=epochs)
        float(net.score())
        out["pipeline_on_s"] = round(time.perf_counter() - t0, 4)
    out["pipeline_speedup"] = round(
        out["pipeline_off_s"] / out["pipeline_on_s"], 4)
    return out


def run_char_lstm(batch=256, seq=200, hidden=256, vocab=77, steps=10,
                  dtype="bf16", precision=None, site=None):
    """Char-LSTM train-step benchmark (BASELINE.md "Char-RNN LSTM"
    row, the CudnnLSTMHelper role — SURVEY.md §2.9). Returns
    tokens/sec, measured per-step FLOPs (or None), and first loss."""
    run, state0, flops_per_step, tokens_per_step = build_char_lstm(
        batch=batch, seq=seq, hidden=hidden, vocab=vocab, dtype=dtype,
        precision=precision, site=site)
    best = time_best_of(run, state0, steps)
    return {"tokens_per_sec": tokens_per_step * steps / best,
            "flops_per_step": flops_per_step,
            "tokens_per_step": tokens_per_step,
            "telemetry": telemetry_snapshot()}


def zero_ab(workload="dense", steps=8, trials=3, batch=None, hidden=None,
            classes=10, seq=32, precision=None):
    """Interleaved A/B of the ShardedTrainer sharing step: replicated
    weight update vs ZeRO-style update sharding (update_sharding=
    'zero', arXiv:2004.13336) on the full device mesh.

    Sides are fresh identically-seeded models on ONE shared mesh;
    windows interleave (A chunk, B chunk per trial) so tenant drift
    cancels, and each window drives all ``steps`` batches through ONE
    fit() call so the zero side's fit-exit master gather (`_finish`)
    amortizes exactly as it does in a real epoch. Reported per side:
    best-of-N window seconds, final loss, and the per-device
    master/opt byte gauges (dl4j_tpu_master_param_bytes /
    dl4j_tpu_opt_state_bytes) — the 1/N memory claim as a measured
    ratio. The device-memory watermark is reported ONCE, globally:
    both sides live in one process, so a per-side peak would be
    fiction — the gauges are the per-side number. Workloads: 'dense'
    (deep MLP), 'lstm' (char-LSTM MLN), 'resnet' (zoo ResNet-50
    ComputationGraph, CPU-shrunk off-accel).
    """
    import numpy as np

    from deeplearning4j_tpu.datasets import DataSet, ListDataSetIterator
    from deeplearning4j_tpu.parallel.mesh import build_mesh
    from deeplearning4j_tpu.parallel.sharded import ShardedTrainer
    from deeplearning4j_tpu.profiler import telemetry

    on_accel = jax.devices()[0].platform in ("tpu", "gpu")

    def make_model_and_batch():
        # fresh RandomState per call: both sides must see the SAME
        # batch (and identically-seeded params) or the loss comparison
        # measures data, not the update path
        rs = np.random.RandomState(0)
        from deeplearning4j_tpu.learning.updaters import Adam

        if workload == "dense":
            from deeplearning4j_tpu.nn.conf import (
                DenseLayer, InputType, NeuralNetConfiguration,
                OutputLayer,
            )
            from deeplearning4j_tpu.nn.multilayer.network import (
                MultiLayerNetwork,
            )

            h = hidden or (2048 if on_accel else 128)
            b = batch or (512 if on_accel else 32)
            bld = (NeuralNetConfiguration.builder().seed(7)
                   .updater(Adam(1e-3)))
            if precision:
                bld = bld.precision(precision)
            bld = bld.list()
            for _ in range(4):
                bld = bld.layer(DenseLayer(n_out=h, activation="relu"))
            conf = (bld.layer(OutputLayer(n_out=classes,
                                          activation="softmax",
                                          loss="mcxent"))
                    .setInputType(InputType.feedForward(h)).build())
            net = MultiLayerNetwork(conf).init()
            x = rs.randn(b, h).astype(np.float32)
            y = np.eye(classes, dtype=np.float32)[
                rs.randint(0, classes, b)]
            return net, DataSet(x, y)
        if workload == "lstm":
            from deeplearning4j_tpu.nn.multilayer.network import (
                MultiLayerNetwork,
            )
            from deeplearning4j_tpu.zoo.textgen_lstm import (
                TextGenerationLSTM,
            )

            h = hidden or (256 if on_accel else 64)
            b = batch or (256 if on_accel else 16)
            vocab = 64
            conf = TextGenerationLSTM(vocab_size=vocab, hidden=h,
                                      tbptt_length=0).conf()
            if precision:
                conf.precision = precision
            net = MultiLayerNetwork(conf).init()
            eye = np.eye(vocab, dtype=np.float32)
            ids = rs.integers(0, vocab, (b, seq)) \
                if hasattr(rs, "integers") else rs.randint(0, vocab,
                                                           (b, seq))
            return net, DataSet(eye[ids], eye[np.roll(ids, -1, 1)])
        if workload == "resnet":
            from deeplearning4j_tpu.nn.graph.graph import (
                ComputationGraph,
            )
            from deeplearning4j_tpu.zoo.resnet50 import ResNet50

            shape = (224, 224, 3) if on_accel else (32, 32, 3)
            ncls = 1000 if on_accel else classes
            b = batch or (64 if on_accel else 8)
            conf = ResNet50(num_classes=ncls, in_shape=shape).conf()
            if precision:
                conf.precision = precision
            net = ComputationGraph(conf).init()
            h, w, c = shape
            x = rs.rand(b, h, w, c).astype(np.float32)
            y = np.eye(ncls, dtype=np.float32)[rs.randint(0, ncls, b)]
            return net, DataSet(x, y)
        raise ValueError(f"unknown zero_ab workload {workload!r}")

    mesh = build_mesh()
    sides = {}
    trainers = {}
    for name, us in (("replicated", None), ("update_sharded", "zero")):
        net, ds = make_model_and_batch()
        trainers[name] = (ShardedTrainer(net, mesh=mesh, mode="sharing",
                                         update_sharding=us), net, ds)
    # warm both sides (compile + placement) before any timed window
    for tr, net, ds in trainers.values():
        tr.fit(ds)
        float(net.score())

    best = {name: float("inf") for name in trainers}
    for _ in range(trials):
        for name, (tr, net, ds) in trainers.items():
            t0 = time.perf_counter()
            tr.fit(ListDataSetIterator([ds] * steps))
            float(net.score())   # device->host sync closes the window
            best[name] = min(best[name], time.perf_counter() - t0)

    reg = telemetry.MetricsRegistry.get_default()
    mg = reg.gauge(telemetry.MASTER_PARAM_BYTES)
    og = reg.gauge(telemetry.OPT_STATE_BYTES)
    for name, (tr, net, ds) in trainers.items():
        sides[name] = {
            "step_s": round(best[name] / steps, 6),
            "final_loss": float(net.score()),
            "master_param_bytes": mg.value(mode=name, site="sharded"),
            "opt_state_bytes": og.value(mode=name, site="sharded"),
        }
    out = {"workload": workload, "mesh_data": mesh.shape["data"],
           "steps": steps, "sides": sides,
           "peak_bytes_in_use":
               telemetry.sample_device_memory().get("peak_bytes_in_use")}
    rep, zer = sides["replicated"], sides["update_sharded"]
    out["zero_step_speedup"] = round(rep["step_s"] / zer["step_s"], 4)
    if rep["master_param_bytes"]:
        out["master_bytes_ratio"] = round(
            zer["master_param_bytes"] / rep["master_param_bytes"], 4)
    if rep["opt_state_bytes"]:
        out["opt_bytes_ratio"] = round(
            zer["opt_state_bytes"] / rep["opt_state_bytes"], 4)
    if rep["final_loss"]:
        out["loss_delta_rel"] = round(
            abs(zer["final_loss"] - rep["final_loss"])
            / abs(rep["final_loss"]), 6)
    out["telemetry"] = telemetry_snapshot()
    return out


def _verify_master_dtypes(params_tree, opt_tree, expect="float32"):
    """Every floating param leaf must be the master dtype — the A/B
    below refuses to report a 'mixed' speedup whose params silently
    leaked to bf16 (that would be the naive mode). Opt-state is pinned
    only for fp32 masters: naive low-precision configs deliberately
    keep f32 accumulators (updaters._zeros_f32)."""
    bad = []
    trees = [("param", params_tree)]
    if expect == "float32":
        trees.append(("opt", opt_tree))
    for tag, tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            dt = getattr(leaf, "dtype", None)
            if dt is not None and jnp.issubdtype(dt, jnp.floating) \
                    and str(dt) != expect:
                bad.append(f"{tag}:{dt}")
    return sorted(set(bad))


def precision_ab(workload="lstm", steps=10, batch=None, seq=128,
                 policies=("float32", "mixed_bfloat16", "bfloat16"),
                 **kw):
    """Precision A/B/C on one workload: full-f32 vs the mixed_bfloat16
    POLICY (fp32 masters, bf16 compute) vs naive full-bf16 (params and
    updates downcast — fast but unprotected; the pre-policy benches'
    mode). Workloads: "lstm" (char-LSTM MultiLayerNetwork), "resnet"
    (zoo ResNet-50 ComputationGraph), "bert" (models TransformerEncoder
    MLM step).

    Fresh identically-seeded model per side; device-resident inputs;
    best-of-3 windows via time_best_of. Per side reports steps/sec and
    the verified master param/opt dtypes; top-level ratios
    ``mixed_speedup_vs_f32`` (the acceptance number — the policy's win
    with fp32 protection intact) and ``naive_speedup_vs_f32`` (the
    unprotected ceiling it should approach)."""
    import numpy as np

    sides = {}
    for pol in policies:
        mixed = str(pol).startswith("mixed")
        expect_master = "float32" if (mixed or pol == "float32") \
            else str(jnp.dtype(pol))

        if workload == "lstm":
            b = batch or 256
            run, state0, flops, _tok = build_char_lstm(
                batch=b, seq=seq, precision=pol if mixed else None,
                dtype="f32" if pol == "float32" else pol, **kw)
            params_opt = (state0[0], state0[2])
        elif workload == "resnet":
            from deeplearning4j_tpu.nn.graph.graph import ComputationGraph
            from deeplearning4j_tpu.zoo.resnet50 import ResNet50

            b = batch or 64
            classes = kw.get("classes", 1000)
            conf = ResNet50(num_classes=classes,
                            in_shape=kw.get("in_shape", (224, 224, 3))
                            ).conf()
            if mixed:
                conf.precision = pol
            else:
                conf.dtype = str(jnp.dtype(pol)) if pol != "float32" \
                    else "float32"
            net = ComputationGraph(conf).init()
            rng = np.random.default_rng(0)
            h, w, c = kw.get("in_shape", (224, 224, 3))
            x = jax.device_put(jnp.asarray(
                rng.normal(0, 1, (b, h, w, c)), net._input_dtype))
            y = jax.device_put(jnp.asarray(
                np.eye(classes, dtype=np.float32)[
                    rng.integers(0, classes, b)]))
            inputs = {conf.network_inputs[0]: x}
            labels = {conf.network_outputs[0]: y}
            step = net._get_train_step()
            scaling = net._loss_scale_state is not None

            def step_args(state, i, _in=inputs, _lb=labels,
                          _scaling=scaling):
                base = (state[0], state[1], state[2])
                ls = (state[3],) if _scaling else ()
                return base + ls + (jnp.asarray(i), jnp.asarray(0),
                                    _in, _lb, {}, {}, jax.random.key(i))

            flops = aot_cost_flops(step, *step_args(
                (net.params_map, net.states_map, net.opt_states,
                 net._loss_scale_state), 0))

            def run(state, i, _step=step, _args=step_args):
                out = _step(*_args(state, i))
                return out[:-1], out[-1]

            state0 = (net.params_map, net.states_map, net.opt_states) \
                + ((net._loss_scale_state,) if scaling else ())
            params_opt = (state0[0], state0[2])
        elif workload == "bert":
            from deeplearning4j_tpu.learning.updaters import Adam
            from deeplearning4j_tpu.models.transformer import (
                TransformerEncoder, bert_base, tiny_config,
            )

            on_accel = jax.devices()[0].platform in ("tpu", "gpu")
            cfg = bert_base() if on_accel else tiny_config(
                vocab=1024, max_len=seq, d_model=128, n_layers=2,
                n_heads=4, d_ff=512)
            # policy mapping onto the encoder's param/compute split:
            # f32 = (f32, f32); mixed_bf16 = (f32, bf16);
            # naive = (dt, dt). The encoder has no loss-scaling path,
            # so a mixed_float16 side would really be a bf16 run
            # reported under the f16 label — refuse instead
            if pol == "float32":
                cfg.dtype, cfg.compute_dtype = "float32", "float32"
            elif pol == "mixed_bfloat16":
                cfg.dtype, cfg.compute_dtype = "float32", "bfloat16"
            elif mixed:
                raise ValueError(
                    f"precision_ab('bert') does not support {pol!r}: "
                    "TransformerEncoder has no dynamic-loss-scaling "
                    "path (use the lstm/resnet workloads for "
                    "mixed_float16)")
            else:
                cfg.dtype = cfg.compute_dtype = str(jnp.dtype(pol))
            expect_master = cfg.dtype
            b = batch or (96 if on_accel else 8)
            model = TransformerEncoder(cfg)
            updater = Adam(1e-4)
            step = model.make_train_step(updater)
            rng = jax.random.key(0)
            params = model.init_params(rng)
            opt = updater.init_state(params)
            ids = jax.random.randint(rng, (b, seq), 0, cfg.vocab_size)
            lbl = jax.random.randint(rng, (b, seq), 0, cfg.vocab_size)
            rs = np.random.RandomState(0)
            m = np.zeros((b, seq), np.float32)
            for r in range(b):
                m[r, rs.choice(seq, min(19, seq - 1),
                               replace=False)] = 1.0
            mask_pos = jnp.asarray(m)
            flops = aot_cost_flops(step, params, opt, jnp.asarray(0),
                                   ids, lbl, mask_pos, rng)

            def run(state, i, _step=step, _ids=ids, _lbl=lbl,
                    _m=mask_pos, _rng=rng):
                p, o, loss = _step(state[0], state[1], jnp.asarray(i),
                                   _ids, _lbl, _m, _rng)
                return (p, o), loss

            state0 = (params, opt)
            params_opt = (params, opt)
        else:
            raise ValueError(f"unknown precision_ab workload {workload!r}")

        best = time_best_of(run, state0, steps)
        bad = _verify_master_dtypes(*params_opt, expect=expect_master)
        sides[str(pol)] = {
            "steps_per_sec": round(steps / best, 4),
            "flops_per_step": flops,
            "master_dtype": expect_master,
            "dtype_leaks": bad,   # must be [] — see _verify_master_dtypes
        }

    out = {"workload": workload, "sides": sides}
    f32 = sides.get("float32", {}).get("steps_per_sec")
    for name, key in (("mixed_speedup_vs_f32", "mixed_bfloat16"),
                      ("naive_speedup_vs_f32", "bfloat16")):
        if f32 and key in sides:
            out[name] = round(sides[key]["steps_per_sec"] / f32, 4)
    out["telemetry"] = telemetry_snapshot()
    return out
