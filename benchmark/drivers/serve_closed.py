"""Closed-loop serving: ``clients`` callers, each submitting its next
request to ``DecodeEngine.submit`` when the last one has streamed to its
end. Every token is stamped on the client's side of ``stream()``.

The program is used only through ``CausalLM``, ``TransformerConfig``,
``DecodeEngine`` (constructor settings from the configuration file's
``deployment.engine`` and nothing else: the platform chooses the
attention path), ``submit`` / ``ServingRequest.stream`` / ``cancel``
and ``engine.stats()``.

The window is cut to whole deliveries: it opens when the clients are
let go and closes at the last token delivered inside ``--seconds``.
The engine hands tokens over a burst at a time (up to 4 chunks of 8
steps for every slot, 8 s of work at today's speed), so a window closed
by the clock would count a whole burst or none of it, a fifth of a
run's tokens. Rates are all the tokens of the cut window over its
length, as a training window is cut to whole steps.
"""

import gc
import itertools
import threading
import time

import numpy as np


def _percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def _warm_pool(engine, plan, vocab, seed):
    """Run once every prefill bucket the mix's prompts can land in and,
    where the mix decodes at all, one request long enough to pass
    through every decode chunk size. Nothing the traffic does not use."""
    rng = np.random.default_rng(seed)
    lengths = [len(r["prompt"]) for reqs in plan for r in reqs]
    longest_out = max(r["max_new_tokens"] for reqs in plan for r in reqs)
    buckets = sorted({min(b for b in engine.prefill_buckets if b >= n)
                      for n in lengths})
    reqs = []
    for i, b in enumerate(buckets):
        n = min(b, max(lengths))
        new = 2 * engine.max_chunk if i == 0 else 2
        new = min(new, longest_out, engine.max_context - n)
        reqs.append(engine.submit(rng.integers(0, vocab, n, dtype=np.int32), new))
    for r in reqs:
        r.result(timeout=600)
    return buckets


def run(run):
    import jax
    from deeplearning4j_tpu.serving.engine import DecodeEngine

    from_file = run.cell
    cfg, mix, ref = from_file.config, from_file.traffic, from_file.reference
    vocab = int(cfg["vocab_size"])
    model = from_file.program.causal_lm(cfg)
    params = ref.make_params(cfg, run.seed, layout="program")
    jax.block_until_ready(params)
    run.phase("weights")
    engine = DecodeEngine(model, params, **cfg["deployment"]["engine"])
    del params
    engine.start()
    run.phase("compile_or_cache")
    plan = from_file.generator.serving_requests(mix, vocab, run.seed)
    buckets = _warm_pool(engine, plan, vocab, run.seed)
    run.say(f"engine attn_mode={engine.stats()['attn_mode']} "
            f"buckets warmed {buckets} chunks up to {engine.max_chunk}")
    run.phase("warm_pool")

    temperature = float(mix.get("temperature", 0.0))
    clients = int(mix["clients"])
    records, lock = [], threading.Lock()
    stop, go = threading.Event(), threading.Event()

    def submit(c, r):
        rec = {"client": c, "prompt": r["prompt"], "max_new": r["max_new_tokens"],
               "stamps": [], "tokens": [], "status": None}
        with lock:
            records.append(rec)
        try:
            with run.annotate("submit"):
                rec["t_submit"] = time.perf_counter()
                rec["req"] = engine.submit(r["prompt"], r["max_new_tokens"],
                                           temperature)
        except Exception as e:          # refused: a miss
            rec["status"] = f"error: {type(e).__name__}: {e}"[:200]
        return rec

    def read(rec):
        if rec["status"] is not None:
            return
        try:
            it = rec["req"].stream()
            while True:
                with run.annotate("stream_read"):
                    tok = next(it, None)
                if tok is None:
                    break
                rec["stamps"].append(time.perf_counter())
                rec["tokens"].append(int(tok))
            rec["status"] = rec["req"].finish_reason
        except Exception as e:          # failed: a miss
            rec["status"] = f"error: {type(e).__name__}: {e}"[:200]

    streams = [itertools.cycle(plan[c]) for c in range(clients)]
    firsts = []

    def client(c):
        go.wait()
        read(firsts[c])
        while not stop.is_set():
            read(submit(c, next(streams[c])))

    threads = [threading.Thread(target=client, args=(c,), daemon=True,
                                name=f"bench-client-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    run.phase("traffic_warmup")

    s0 = engine.stats()
    t0 = run.setup_done()
    # every client's first request goes in from this one thread, back to
    # back: the engine then admits them together. Submitted from the
    # clients' threads, three of them or all four made the first burst,
    # by the threads' luck, and the window's whole schedule followed.
    firsts.extend(submit(c, next(streams[c])) for c in range(clients))
    go.set()
    t_end = t0 + run.seconds
    # the traced sub-window is the window's last seconds, and the trace
    # is stopped (which takes seconds) only once the window has closed
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if run.trace_if_due(now, t_end):
            continue
        time.sleep(max(min(t_end, run.trace_next_due(t_end)) - now, 0.0005))
    stop.set()
    s1 = engine.stats()
    run.trace_stop()
    # requests cut off by the window's end are aborted now; they did not
    # fail, and they are no correctness sample
    with lock:
        recs = list(records)
    for rec in recs:
        if rec["status"] is None and rec.get("req") is not None:
            rec["req"].cancel()
    for t in threads:
        t.join(timeout=300)
    alive = [t.name for t in threads if t.is_alive()]
    run.memory_peak()
    engine.shutdown()
    if alive:
        raise RuntimeError(f"client threads did not end: {alive}")
    for rec in recs:
        rec.pop("req", None)
    del engine, model
    gc.collect()

    # ------------------------------------------------ end-to-end numbers
    t1 = max((ts for r in recs for ts in r["stamps"] if t0 <= ts <= t_end),
             default=t_end)                # the last delivery in --seconds
    run.window_s = t1 - t0
    inwin = lambda t: t0 <= t <= t1
    bad = [r for r in recs if str(r["status"]).startswith("error")]
    run.attempted, run.failed = len(recs), len(bad)
    for r in bad[:3]:
        run.say(f"failed request: {r['status']}")
    n_tok, gaps, flops, dec_tok = 0, [], 0, 0
    for r in recs:
        plen = len(r["prompt"])
        for i, ts in enumerate(r["stamps"]):
            if not inwin(ts):
                continue
            n_tok += 1
            flops += from_file.flops.served_token_flops(cfg, plen, i)
            if i:
                dec_tok += 1
                if inwin(r["stamps"][i - 1]):
                    gaps.append((ts - r["stamps"][i - 1]) * 1e3)
    ttft = [(r["stamps"][0] - r["t_submit"]) * 1e3 for r in recs
            if r["stamps"] and inwin(r["stamps"][0])]
    run.e2e["serve_tok_s"] = n_tok / run.window_s
    run.samples["token_gaps_ms"] = gaps
    steps = s1["decode_steps"] - s0["decode_steps"]
    occ = (s1["avg_occupancy"] * s1["decode_steps"]
           - s0["avg_occupancy"] * s0["decode_steps"])
    run.counters.update({
        "slots": s1["slots"], "decode_steps": steps,
        "dispatches": s1["dispatches"] - s0["dispatches"],
        "occupancy_sum": occ, "kv_pages": s1["kv_pages"],
        "attn_mode": s1["attn_mode"], "model_flops": flops,
        "tokens": n_tok, "decode_tokens": dec_tok})
    done = [r for r in recs if r["status"] == "length" and r["stamps"]
            and inwin(r["stamps"][-1])]
    run.say(f"window {run.window_s:.3f}s of {run.seconds:g}s tokens {n_tok} "
            f"gaps {len(gaps)} ttft samples {len(ttft)} attempted "
            f"{run.attempted} failed {run.failed} finished in window "
            f"{len(done)} decode steps {steps}")
    stamps = sorted(ts for r in recs for ts in r["stamps"] if inwin(ts))
    if stamps:                  # the deliveries: tokens that came together
        cuts = [i for i in range(1, len(stamps))
                if stamps[i] - stamps[i - 1] > 0.05] + [len(stamps)]
        run.say("deliveries (s:tokens so far): " + " ".join(
            f"{stamps[i - 1] - t0:.2f}:{i}" for i in cuts))
    if ttft:
        run.say("ttft (ms): " + " ".join(f"{x:.0f}" for x in ttft))
    if gaps:
        run.say("token gaps (ms): " + ", ".join(
            f"p{q}={_percentile(gaps, q):.1f}" for q in (50, 90, 95, 99, 100))
            + f"; over 1 s: {sum(g > 1e3 for g in gaps)}; longest: "
            + " ".join(f"{g:.0f}" for g in sorted(gaps)[-12:]))
    run.reduce_trace()

    # ------------------------------------------------------- correctness
    # the reference's numbers by the names the mix's limits use; one
    # with no limit there is printed and not compared
    numbers = {"length_mismatch": None, "served_gap": None,
               "served_gap_mean": None, "served_mismatch_share": None}
    if done:
        numbers["length_mismatch"] = sum(
            len(r["tokens"]) != r["max_new"] for r in done)
        rng = np.random.default_rng(run.seed)
        k = int(mix["check"]["sample_requests"])
        longest = max(range(len(done)), key=lambda i: len(done[i]["tokens"]))
        rest = [i for i in rng.permutation(len(done)) if i != longest]
        pick = [longest] + [int(i) for i in rest[:k - 1]]
        run.samples["checked"] = [(done[i]["prompt"], done[i]["tokens"])
                                  for i in pick]
        t_ref = time.perf_counter()
        got = ref.check_served(
            cfg, run.seed, run.samples["checked"],
            max_tokens=int(mix["output_len"].get("max", 0)) or None)
        numbers.update(served_gap=got["widest_gap"],
                       served_gap_mean=got["mean_gap"],
                       served_mismatch_share=got["mismatch_share"])
        run.say(f"reference: {len(pick)} requests, {got['compared']} served "
                f"tokens compared in {time.perf_counter() - t_ref:.1f}s, "
                f"widest gap at {got['at']}")
    run.samples["numbers"] = numbers
    run.say("compared and not: " + ", ".join(
        f"{k}={v:.6g}" if v is not None else f"{k}=None"
        for k, v in numbers.items()))
    for name, limit in mix["check"]["limits"].items():
        run.check(name, numbers[name], limit)
