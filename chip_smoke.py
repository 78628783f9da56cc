#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system starts on the chip.

Drives the program's main path once, through the entry points a user
calls, at GPT-2-small's published widths (vocab 50257, context 1024,
d_model 768, 12 layers, 12 heads, d_ff 3072; random weights from a
seed): **train a few steps, then serve a few requests over HTTP with
the same weights**, then compiles every Pallas kernel a default TPU path
reaches and compares it with its XLA reference, and — on a host with
four chips — runs the sharded training paths and a four-replica fleet.

    python chip_smoke.py        # needs a TPU; one process; ~10 min cold

It fails (exit code != 0, no result line) when JAX finds no TPU, when
the device kind has no entry in ``profiler/flops.py:PEAK_FLOPS``, and
when any phase raises — no phase is wrapped in a handler. The last line
of stdout is one JSON object with exactly these keys, the device as JAX
reports it::

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}

The line before it (``report: {...}``, also written to
``chiprun_out/chip_smoke.json``) is the full report: the same two keys
plus versions, the compile cache, per-phase ``ok`` and facts, and
``"claim": null`` — every number in it is a bring-up observation (did
it run, was it right, how long did set-up take), not a benchmark.

The phase functions take the model and the sizes as arguments, so
``tests/test_chip_smoke.py`` runs the same control flow at a toy size
on the CPU; the script itself has no CPU mode.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

#: the cyclic token language of the train and serve phases: a row is
#: ``(start + t) % PERIOD``, so the next token is a function of the
#: current one and a trained model's greedy output has an exact oracle
PERIOD = 64

#: serve-phase requests as (prompt tokens, new tokens): different
#: lengths, one prompt above 256 tokens, all in flight together
REQUESTS = ((300, 24), (17, 40), (64, 16), (129, 33))

#: stated floors and tolerances (printed with the results)
MIN_LOSS_DROP = 4.0        # last train loss below the first by this much
MIN_AGREEMENT = 0.9        # engine vs generate(), and vs the cycle
BF16_TOL = 2e-2            # kernel vs XLA reference, bf16 operands
FP8_TOL = 0.125            # fp8 pages: the reference dequantizes to bf16,
#                            the kernel to f32; outputs reach |4|, where
#                            one bf16 step is 0.03
F32_TOL = 1e-5             # fused update vs formula, f32
MESH_LOSS_RTOL = 2e-2      # (2,2)-mesh first-step loss vs 1x1 mesh, bf16
SPREAD_MIN = 0.05          # min/max of per-device bytes a phase added


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.perf_counter() - _T0:7.1f}s] {msg}",
          flush=True)


# ------------------------------------------------------------ the gate
def device_gate() -> dict:
    """Fail unless JAX runs on a TPU whose kind the peaks table knows.
    Runs before any model code; returns the device facts."""
    import jax
    import jaxlib

    from deeplearning4j_tpu.profiler.flops import PEAK_FLOPS

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    if jax.default_backend() != "tpu" or kind not in PEAK_FLOPS:
        raise SystemExit(
            f"chip_smoke: needs a TPU with an entry in profiler/flops.py "
            f"PEAK_FLOPS; JAX found platform={platform!r} "
            f"device_kind={kind!r} ({len(devs)} device(s)). This script "
            "has no CPU mode — tests/test_chip_smoke.py is the dry run.")
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not importable"
    return {"platform": platform, "kind": kind, "count": len(devs),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu_version}


# --------------------------------------------------------------- data
def cyclic_rows(rng, n: int, length: int, period: int = PERIOD):
    """``n`` rows of the cyclic language, random phase per row."""
    import numpy as np

    start = rng.integers(0, period, (n, 1))
    return ((start + np.arange(length)[None, :]) % period) \
        .astype(np.int32)


# -------------------------------------------------------------- train
def phase_train(model, *, batch: int, seq_len: int, steps: int,
                lr: float, min_drop: float, period: int = PERIOD,
                seed: int = 0):
    """``steps`` Adam steps of ``CausalLM.make_train_step`` on cyclic
    rows made from ``seed``. Pass = every loss finite and the last
    below the first by ``min_drop``. Returns (params, report)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.learning.updaters import Adam

    params = model.init_params(jax.random.key(seed))
    updater = Adam(learning_rate=lr)
    opt_state = updater.init_state(params)
    step = model.make_train_step(updater)
    rng = np.random.default_rng(seed)
    losses, step_s = [], []
    for i in range(steps):
        ids = jnp.asarray(cyclic_rows(rng, batch, seq_len + 1, period))
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, jnp.asarray(i),
                                       ids, jax.random.key(seed + i))
        losses.append(float(loss))       # device->host: the step is done
        step_s.append(time.perf_counter() - t0)
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite train loss: {losses}")
    if not losses[-1] < losses[0] - min_drop:
        raise AssertionError(
            f"train loss fell {losses[0]:.3f} -> {losses[-1]:.3f}, less "
            f"than the stated margin {min_drop}")
    report = {
        "ok": True, "steps": steps, "tokens_per_step": batch * seq_len,
        "params": model.num_params(params),
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4), "min_drop": min_drop,
        "first_step_s_incl_compile": round(step_s[0], 2),
        "later_step_s_median": round(float(np.median(step_s[1:])), 4),
    }
    return params, report


# -------------------------------------------------------------- serve
def phase_serve(model, params, requests, *, min_agreement: float,
                period: int = PERIOD, seed: int = 1):
    """The trained ``params`` behind ``DecodeEngine`` at its DEFAULTS
    (attention path, buckets, chunks, warm start) and
    ``JsonModelServer``; ``requests`` sent together over HTTP.

    Pass = every response is HTTP 200 (the client raises on anything
    else) with the requested number of in-vocabulary tokens, no
    warm-pool miss, no compile at the serving sites after ``start()``,
    and greedy output agreeing with ``CausalLM.generate()`` on the same
    params, and with the cycle, to ``min_agreement``."""
    import numpy as np

    from deeplearning4j_tpu.profiler import telemetry
    from deeplearning4j_tpu.remote.server import (
        JsonModelServer, JsonRemoteInference,
    )
    from deeplearning4j_tpu.serving import DecodeEngine
    from deeplearning4j_tpu.serving.kv_pages import pages_needed

    if not telemetry.enabled():
        raise AssertionError("telemetry is off (DL4J_TPU_TELEMETRY=0): "
                             "the recompile counters cannot be read")
    compiles = telemetry.MetricsRegistry.get_default().counter(
        telemetry.JIT_COMPILES)
    sites = ("serving_decode", "serving_prefill")
    rng = np.random.default_rng(seed)
    prompts = [cyclic_rows(rng, 1, t0, period)[0] for t0, _ in requests]

    eng = DecodeEngine(model, params)
    server = JsonModelServer(engine=eng)
    try:
        t0 = time.perf_counter()
        eng.start()
        warmup_s = time.perf_counter() - t0
        log(f"serve: engine up, attn_mode={eng._attn_mode}, "
            f"{len(eng._warm._exec)} warm programs in {warmup_s:.1f}s")
        at_start = {s: compiles.value(site=s) for s in sites}
        client = JsonRemoteInference(
            f"http://127.0.0.1:{server.start()}", timeout=900, retries=0)
        results: list = [None] * len(requests)

        def post(i, prompt, n):
            results[i] = client.generate_full(prompt, n)

        threads = [threading.Thread(target=post, args=(i, p, n))
                   for i, (p, (_, n)) in enumerate(zip(prompts, requests))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(900)
        stats = eng.stats()
        after = {s: compiles.value(site=s) for s in sites}
        misses = eng._warm.misses
    finally:
        server.stop()
        eng.shutdown()

    per_request, agree, cyclic, total = [], 0, 0, 0
    for (t0, n), prompt, body in zip(requests, prompts, results):
        if body is None:
            raise AssertionError(f"request ({t0},{n}) got no response")
        toks = np.asarray(body["tokens"], np.int64)
        if toks.shape != (n,) or toks.min() < 0 \
                or toks.max() >= model.cfg.vocab_size:
            raise AssertionError(
                f"request ({t0},{n}): bad tokens {toks.tolist()}")
        ref = np.asarray(model.generate(params, prompt[None], n))[0]
        want = (int(prompt[-1]) + 1 + np.arange(n)) % period
        agree += int((toks == ref).sum())
        cyclic += int((toks == want).sum())
        total += n
        per_request.append({"prompt_tokens": t0, "tokens": n,
                            "ttft_ms": body["ttft_ms"],
                            "latency_ms": body["latency_ms"],
                            "agree_generate": float((toks == ref).mean()),
                            "agree_cycle": float((toks == want).mean())})
    # two requests held KV pages at once iff the pool's high-water mark
    # exceeds what the largest single request needs
    largest = max(pages_needed(t0 + n, stats["page_size"])
                  for t0, n in requests)
    high_water = stats["kv_pages"]["high_water"]
    if high_water <= largest:
        raise AssertionError(
            f"no two requests were in flight together: KV high-water "
            f"{high_water} pages <= largest single request {largest}")
    if misses:
        raise AssertionError(f"{misses} warm-pool miss(es)")
    if after != at_start:
        raise AssertionError(f"serving sites compiled after start(): "
                             f"{at_start} -> {after}")
    report = {
        "ok": True, "attn_mode": eng._attn_mode,
        "kv_dtype": stats["kv_dtype"], "slots": stats["slots"],
        "prefill_buckets": stats["prefill_buckets"],
        "max_chunk": stats["max_chunk"],
        "warm_programs": len(eng._warm._exec),
        "warmup_s": round(warmup_s, 2),
        "warm_hits": stats["warm_pool"]["hits"], "warm_misses": misses,
        "compiles_after_start": int(sum(after.values())
                                    - sum(at_start.values())),
        "kv_pages_high_water": high_water,
        "kv_pages_largest_request": largest,
        "agreement_generate": round(agree / total, 4),
        "agreement_cycle": round(cyclic / total, 4),
        "min_agreement": min_agreement,
        "requests": per_request,
    }
    if agree / total < min_agreement or cyclic / total < min_agreement:
        raise AssertionError(
            f"greedy agreement below the stated floor {min_agreement}: "
            f"{report}")
    return report


# ------------------------------------------------------------ kernels
def phase_kernels(cfg, *, slots: int = 8, page_size: int = 16,
                  bucket: int = 256):
    """Every Pallas kernel a default TPU path reaches, compiled by
    Mosaic (never interpreted) at the smoke model's shapes and compared
    with its XLA reference in this process."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.ops import flash_attention as fa
    from deeplearning4j_tpu.ops.fused_update_pallas import (
        adam_segment_update,
    )
    from deeplearning4j_tpu.ops.paged_attention_pallas import \
        paged_attention

    H, hd = cfg.n_heads, cfg.head_dim
    P = cfg.max_len // page_size
    n_pages = 1 + slots * P
    layers, layer = 2, 1          # two layers of pool are enough here
    report = {"ok": True}

    def max_err(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    def check(name, got, ref, tol):
        err = max_err(got, ref)
        finite = bool(jnp.all(jnp.isfinite(got.astype(jnp.float32))))
        report[name] = {"max_abs_err": err, "tol": tol}
        log(f"kernels: {name} max|err|={err:.3g} (tol {tol})")
        if not finite or not err <= tol:
            raise AssertionError(f"{name}: err {err} > {tol} "
                                 f"(finite={finite})")

    # -- paged attention: decode (N=slots, Q=1) + prefix-prefill (N=1)
    def pool(store_dtype, with_scales):
        ks = jax.random.split(jax.random.key(0), 4)
        shape = (layers, n_pages, page_size, H * hd)
        kv = {"k": jax.random.normal(ks[0], shape).astype(store_dtype),
              "v": jax.random.normal(ks[1], shape).astype(store_dtype)}
        if with_scales:
            for name, k in (("k_scale", ks[2]), ("v_scale", ks[3])):
                kv[name] = 0.5 + jnp.abs(jax.random.normal(
                    k, (layers, n_pages, H), jnp.float32))
        return kv

    def paged_case(name, kv, n, q_len, qbase, tol):
        q = jax.random.normal(jax.random.key(7), (n, q_len, H, hd)) \
            .astype(jnp.bfloat16)
        tables = jnp.asarray(
            1 + (np.arange(n * P).reshape(n, P) * 7) % (n_pages - 1),
            jnp.int32)
        qbase = jnp.asarray(qbase, jnp.int32)
        got = jax.jit(lambda q, kv, t, b: paged_attention(
            q, kv, layer, t, b, mode="pallas"))(q, kv, tables, qbase)
        ref = jax.jit(lambda q, kv, t, b: paged_attention(
            q, kv, layer, t, b, mode="xla"))(q, kv, tables, qbase)
        check(name, got, ref, tol)

    kv = pool(jnp.bfloat16, False)
    decode_pos = [(37 * (i + 1)) % (cfg.max_len - 1) for i in range(slots)]
    paged_case("paged_attention_decode", kv, slots, 1, decode_pos,
               BF16_TOL)
    paged_case("paged_attention_prefix_prefill", kv, 1, bucket, [100],
               BF16_TOL)
    # the opt-in fp8 KV variant of the same kernel (kv_dtype="fp8_e4m3")
    kv8 = pool(jnp.float8_e4m3fn, True)
    paged_case("paged_attention_decode_fp8", kv8, slots, 1, decode_pos,
               FP8_TOL)

    # -- fused Adam update on a shard whose length is not a multiple of
    #    1024 (nor of 128: the ragged tail and the partial last block)
    n = 1_000_003
    ks = jax.random.split(jax.random.key(1), 4)
    master, grad = (jax.random.normal(k, (n,), jnp.float32)
                    for k in ks[:2])
    m = 0.1 * jax.random.normal(ks[2], (n,), jnp.float32)
    v = 0.01 * jnp.abs(jax.random.normal(ks[3], (n,), jnp.float32))
    scalars = jnp.asarray([0.5, 1e-3], jnp.float32)

    def update(mode):
        return jax.jit(lambda *a: adam_segment_update(
            *a, beta1=0.9, beta2=0.999, eps=1e-8, mode=mode))(
                master, m, v, grad, scalars)

    for name, got, ref in zip(("master", "m", "v"), update("pallas"),
                              update("xla")):
        check(f"fused_adam_update_{name}", got, ref, F32_TOL)

    # -- flash attention at T=2048, dh=64: what impl="auto" picks on TPU
    q, k, vv = (jax.random.normal(kk, (2, H, 2048, hd))
                .astype(jnp.bfloat16)
                for kk in jax.random.split(jax.random.key(2), 3))
    got = jax.jit(lambda q, k, v: fa.attention(q, k, v, impl="auto"))(
        q, k, vv)
    ref = jax.jit(lambda q, k, v: fa._xla_attention(q, k, v, None,
                                                    False))(q, k, vv)
    check("flash_attention_auto_T2048", got, ref, BF16_TOL)
    return report


# ---------------------------------------------------------- multichip
def _bytes_in_use(devices):
    return [int(d.memory_stats()["bytes_in_use"]) for d in devices]


def _spread(name: str, devices, before) -> list:
    """Bytes each device gained since ``before`` (call while the
    sub-phase's arrays are alive). No device may have been left out:
    each must hold a fair part of what the sub-phase added."""
    added = [a - b for a, b in zip(_bytes_in_use(devices), before)]
    log(f"multichip: {name} bytes added per device {added}")
    if min(added) <= 0 or min(added) < SPREAD_MIN * max(added):
        raise AssertionError(
            f"{name}: a device holds ~nothing while another holds "
            f"everything: {added}")
    return added


def multichip_sharded_paths(devices) -> dict:
    """(c) every sharded training path of
    ``__graft_entry__.multichip_body`` — TP, ring-SP, GPipe, MoE-EP,
    PPxEP, compressed-DP, averaging-DP — at its tiny shapes."""
    import __graft_entry__ as graft

    before = _bytes_in_use(devices)
    body = graft.multichip_body(devices)
    return {"losses": body["losses"],
            "parity_deltas": {k: float(v) for k, v
                              in body["parity_deltas"].items()},
            "bytes_added_per_device": _spread("sharded_paths", devices,
                                              before)}


def multichip_zero(devices) -> dict:
    """(d) ``ShardedTrainer`` with ZeRO update sharding and Adam: the
    fused update kernel under ``shard_map`` on real shards (layer
    widths chosen so that no shard length is a multiple of 1024)."""
    import numpy as np

    from deeplearning4j_tpu.datasets import DataSet
    from deeplearning4j_tpu.learning.updaters import Adam
    from deeplearning4j_tpu.nn.conf import (
        DenseLayer, InputType, NeuralNetConfiguration, OutputLayer,
    )
    from deeplearning4j_tpu.nn.multilayer.network import (
        MultiLayerNetwork,
    )
    from deeplearning4j_tpu.ops.fused_update_pallas import (
        fused_update_mode,
    )
    from deeplearning4j_tpu.parallel.mesh import build_mesh
    from deeplearning4j_tpu.parallel.sharded import ShardedTrainer

    before = _bytes_in_use(devices)
    conf = (NeuralNetConfiguration.builder().seed(5)
            .updater(Adam(1e-3)).list()
            .layer(DenseLayer(n_out=1031, activation="tanh"))
            .layer(DenseLayer(n_out=1031, activation="tanh"))
            .layer(OutputLayer(n_out=10, activation="softmax",
                               loss="mcxent"))
            .setInputType(InputType.feedForward(777)).build())
    net = MultiLayerNetwork(conf).init()
    trainer = ShardedTrainer(
        net, mesh=build_mesh(num_data=len(devices), devices=devices),
        mode="sharing", update_sharding="zero")
    rs = np.random.RandomState(0)
    x = rs.randn(64, 777).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rs.randint(0, 10, 64)]
    losses = []
    for _ in range(4):
        trainer.fit(DataSet(x, y))
        losses.append(float(net.score()))
    if not (all(np.isfinite(losses)) and losses[-1] < losses[0]):
        raise AssertionError(f"ZeRO losses {losses}")
    return {"fused_update_mode": fused_update_mode(),
            "losses": [round(v, 4) for v in losses],
            "bytes_added_per_device": _spread("zero", devices, before)}


def multichip_bert(devices) -> dict:
    """(a) the README quickstart at its real width: BERT-base,
    ``make_train_step(Adam, mesh)`` + ``shard_params`` on a
    (data=2, model=2) mesh, 3 steps; the first loss against the same
    step on a 1x1 mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.learning.updaters import Adam
    from deeplearning4j_tpu.models.transformer import (
        TransformerEncoder, bert_base,
    )
    from deeplearning4j_tpu.parallel.mesh import build_mesh

    cfg = bert_base()
    cfg.dropout = 0.0            # parity needs one dropout stream
    bert = TransformerEncoder(cfg)
    updater = Adam(learning_rate=1e-4)
    params0 = bert.init_params()
    rng = jax.random.key(0)
    batch, seqlen = 8, 128
    ids = jax.random.randint(rng, (batch, seqlen), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.key(1), (batch, seqlen), 0,
                                cfg.vocab_size)
    mask_pos = jnp.ones((batch, seqlen), jnp.float32)

    def run_mesh(mesh, steps):
        step = bert.make_train_step(updater, mesh)
        p = bert.shard_params(
            jax.tree_util.tree_map(jnp.copy, params0), mesh)
        o = updater.init_state(p)
        losses = []
        with mesh:
            for i in range(steps):
                p, o, loss = step(p, o, jnp.asarray(i), ids, labels,
                                  mask_pos, rng)
                losses.append(float(loss))
        return p, o, losses

    ref = run_mesh(build_mesh(num_data=1, num_model=1,
                              devices=devices[:1]), 1)[2]
    before = _bytes_in_use(devices)
    p, o, losses = run_mesh(
        build_mesh(num_data=2, num_model=2, devices=devices), 3)
    rel = abs(losses[0] - ref[0]) / abs(ref[0])
    report = {"losses": [round(v, 4) for v in losses],
              "loss_1x1": round(ref[0], 4),
              "first_step_rel_delta": rel, "rtol": MESH_LOSS_RTOL}
    if not (all(np.isfinite(losses)) and rel <= MESH_LOSS_RTOL):
        raise AssertionError(f"BERT 2x2 vs 1x1: {report}")
    report["bytes_added_per_device"] = _spread("bert_2x2", devices,
                                               before)
    return report


def multichip_fleet(devices, lm, lm_params) -> dict:
    """(b) a fleet of four engines, one device each, answering 8
    requests: two layers of the smoke model at full width."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.gpt import CausalLM
    from deeplearning4j_tpu.models.transformer import TransformerConfig
    from deeplearning4j_tpu.serving import ServingFleet

    cut = TransformerConfig(**{**lm.cfg.__dict__, "n_layers": 2})
    small = CausalLM(cut, compute_dtype=lm._cdtype)
    # host copies, so that every replica (device 0's too) allocates
    # its own parameters and the per-device byte check is uniform
    small_params = jax.tree_util.tree_map(
        np.asarray, dict(lm_params, layers=lm_params["layers"][:2]))
    before = _bytes_in_use(devices)
    fleet = ServingFleet(small, small_params, devices=devices,
                         slots=2, max_chunk=4,
                         prefill_buckets=[64, 256])
    try:
        fleet.start()
        rng = np.random.default_rng(3)
        reqs = [fleet.submit(cyclic_rows(rng, 1, 20 + 9 * i)[0], 32)
                for i in range(8)]
        outs = [r.result(timeout=600) for r in reqs]
        replicas = []
        for dev, rep in zip(devices, fleet._replicas):
            leaves = jax.tree_util.tree_leaves(
                (rep.engine.params, rep.engine.pool.tree()))
            where = {next(iter(leaf.devices())) for leaf in leaves}
            replicas.append({"device": str(dev),
                             "on_own_device": where == {dev},
                             "tokens": rep.engine.n_tokens})
        added = _spread("fleet", devices, before)
    finally:
        fleet.shutdown()
    if not (all(len(o) == 32 for o in outs)
            and all(r["on_own_device"] and r["tokens"] > 0
                    for r in replicas)):
        raise AssertionError(f"fleet placement: {replicas}")
    return {"replicas": replicas, "bytes_added_per_device": added}


def phase_multichip(devices, lm, lm_params) -> dict:
    """Four real devices. (c) runs first, while devices 1..3 are still
    empty and its tiny arrays show in the byte counts."""
    devices = list(devices)[:4]
    return {
        "ok": True, "devices": len(devices), "spread_min": SPREAD_MIN,
        "c_sharded_paths": multichip_sharded_paths(devices),
        "d_zero_update_sharding": multichip_zero(devices),
        "a_bert_base_2x2": multichip_bert(devices),
        "b_fleet_4_replicas": multichip_fleet(devices, lm, lm_params),
    }


# ---------------------------------------------------------------- main
def result_line(summary: dict) -> str:
    """The last stdout line: exactly ``ok`` and ``device`` (platform,
    kind, count). Everything else is in the report line before it."""
    dev = summary["device"]
    return json.dumps({
        "ok": bool(summary["ok"]),
        "device": {"platform": str(dev["platform"]),
                   "kind": str(dev["kind"]), "count": int(dev["count"])}})


def main() -> int:
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()

    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) \
            else 0

    entries_at_start = cache_entries()
    device = device_gate()
    log(f"device: {device}; compile cache: {cache_dir} "
        f"({entries_at_start} entries at start)")

    import jax

    from deeplearning4j_tpu.models.gpt import CausalLM
    from deeplearning4j_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(vocab_size=50257, max_len=1024, d_model=768,
                            n_layers=12, n_heads=12, d_ff=3072)
    model = CausalLM(cfg)                       # bf16 compute
    summary = {"ok": False, "device": {k: device[k] for k in
                                       ("platform", "kind", "count")},
               "versions": {k: device[k] for k in
                            ("jax", "jaxlib", "libtpu")},
               "compile_cache": {"dir": cache_dir,
                                 "entries_at_start": entries_at_start},
               "phases": {}}
    phases = summary["phases"]

    log("train: GPT-2-small widths, 8 x 1024 tokens per step")
    params, phases["train"] = phase_train(
        model, batch=8, seq_len=1024, steps=60, lr=3e-4,
        min_drop=MIN_LOSS_DROP)
    log(f"train: {phases['train']}")

    log("serve: DecodeEngine defaults behind JsonModelServer")
    phases["serve"] = phase_serve(model, params, REQUESTS,
                                  min_agreement=MIN_AGREEMENT)
    log(f"serve: {phases['serve']}")

    phases["kernels"] = phase_kernels(cfg)

    if jax.device_count() >= 4:
        phases["multichip"] = phase_multichip(jax.devices(), model,
                                              params)
    else:
        phases["multichip"] = {
            "ok": True,
            "skipped": f"multichip: skipped ({jax.device_count()} "
                       "device)"}
        log(phases["multichip"]["skipped"])

    summary["compile_cache"]["entries_at_end"] = cache_entries()
    summary["ok"] = all(p["ok"] for p in phases.values())
    summary["wall_s"] = round(time.perf_counter() - _T0, 1)
    summary["claim"] = None
    report = json.dumps(summary)
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        f.write(report + "\n")
    print("report: " + report, flush=True)
    print(result_line(summary), flush=True)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
