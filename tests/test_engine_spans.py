"""The one span primitive (``telemetry.span``) and the serving engine's
own account on it: ids and causes, late attributes, the profiler's
clock, compilations as events, and each engine boundary recorded once
with its counts."""

import glob
import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.profiler import telemetry, tracing


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    tracing.reset()
    yield
    telemetry.set_enabled(True)
    tracing.set_enabled(False)
    telemetry.reset()
    tracing.reset()


def _ring(name=None):
    return [e for e in telemetry.recent_trace_events(50_000)
            if name is None or e["name"] == name]


# ---------------------------------------------------------- the primitive
def test_every_record_has_an_id_and_nesting_names_the_parent_by_id():
    with telemetry.span("outer", engine="e0") as outer:
        with telemetry.span("inner", k=4) as inner:
            pass
    solo = telemetry.record_span("over", time.perf_counter() - 0.01,
                                 request=9, parent=outer.id)
    recs = {e["name"]: e["args"] for e in _ring()}
    assert recs["outer"]["id"] == outer.id and "parent" not in recs["outer"]
    assert recs["inner"]["id"] == inner.id != outer.id
    assert recs["inner"]["parent"] == outer.id
    assert (recs["outer"]["depth"], recs["inner"]["depth"]) == (0, 1)
    assert recs["over"] == {"request": 9, "parent": outer.id, "id": solo}
    assert len({outer.id, inner.id, solo}) == 3


def test_an_explicit_parent_crosses_threads_and_beats_the_stack():
    def worker(cause):
        with telemetry.span("enclosing"):
            with telemetry.span("caused", parent=cause, request=3):
                pass

    with telemetry.span("cause") as cause:
        t = threading.Thread(target=worker, args=(cause.id,))
        t.start()
        t.join(timeout=30)
    assert not t.is_alive()
    caused = _ring("caused")[0]
    assert caused["args"]["parent"] == cause.id
    assert caused["args"]["request"] == 3 and caused["args"]["depth"] == 1
    # another thread's stack is its own: the cause is no stack parent
    assert "parent" not in _ring("enclosing")[0]["args"]


def test_ids_stay_unique_and_parents_right_under_many_threads():
    import sys

    n_threads, n_each = 32, 200

    def worker(i):
        for j in range(n_each):
            with telemetry.span("outer", w=i) as o:
                with telemetry.span("inner", w=i, j=j) as c:
                    c.set(seen_parent=o.id)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    recs = _ring()
    assert len(recs) == 2 * n_threads * n_each
    assert len({e["args"]["id"] for e in recs}) == len(recs)
    outer = {e["args"]["id"]: e for e in recs if e["name"] == "outer"}
    for e in recs:
        if e["name"] == "inner":
            assert e["args"]["parent"] == e["args"]["seen_parent"]
            assert outer[e["args"]["parent"]]["args"]["w"] == e["args"]["w"]
            assert outer[e["args"]["parent"]]["tid"] == e["tid"]


def test_set_lands_in_the_record_and_never_in_the_labels():
    with telemetry.span("burst", metric="burst_seconds", engine="e1",
                        request=5) as sp:
        sp.set(steps=8)
        sp.set(dispatches=2, steps=12)
    args = _ring("burst")[0]["args"]
    assert args["steps"] == 12 and args["dispatches"] == 2
    h = telemetry.MetricsRegistry.get_default().histogram("burst_seconds")
    assert h.count(engine="e1") == 1
    text = telemetry.MetricsRegistry.get_default().to_prometheus()
    for structural in ("steps", "request", "depth", "parent", "id="):
        assert structural not in text


def test_spans_between_reads_the_ring_by_the_callers_own_clock():
    t_a = time.perf_counter()
    with telemetry.span("early"):
        pass
    t_b = time.perf_counter()
    with telemetry.span("late"):
        pass
    t_c = time.perf_counter()
    assert [e["name"] for e in telemetry.spans_between(t_a, t_b)] == ["early"]
    assert [e["name"] for e in telemetry.spans_between(t_b, t_c)] == ["late"]
    assert [e["name"] for e in telemetry.spans_between(t_a, t_c, "late")] \
        == ["late"]
    # a record belongs to the interval it ENDED in
    telemetry.record_span("straddles", t_a, t_c)
    assert "straddles" not in [
        e["name"] for e in telemetry.spans_between(t_a, t_b)]


def test_the_handle_is_inert_when_telemetry_is_off():
    telemetry.set_enabled(False)
    with telemetry.span("ghost", k=1) as sp:
        sp.set(tokens=3)
        ran = True
    # nothing kept, but the clock is read: the timeline is another switch
    assert ran and sp.id is None and sp.t0 <= sp.t1
    assert telemetry.record_span("ghost2", time.perf_counter()) is None
    assert _ring() == []


def test_a_span_is_a_dl4j_event_on_the_profilers_clock(tmp_path):
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with telemetry.span("engine.dispatch", k=8, live=3,
                            ctx_tokens=1234) as a:
            time.sleep(0.002)
        time.sleep(0.005)
        with telemetry.span("engine.sync", steps=8) as b:
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("dl4j:"):
                    found[ev.name] = (ev.start_ns, ev.duration_ns,
                                      dict(ev.stats))
    assert set(found) == {"dl4j:engine.dispatch", "dl4j:engine.sync"}
    start, dur, stats = found["dl4j:engine.dispatch"]
    assert (stats["k"], stats["live"], stats["ctx_tokens"]) == (8, 3, 1234)
    assert stats["id"] == a.id
    ring = {e["args"]["id"]: e for e in _ring()}
    # pc_us is the ring's own reading of the span's start
    assert stats["pc_us"] == pytest.approx(ring[a.id]["ts"], abs=1.0)
    assert dur / 1e3 == pytest.approx(ring[a.id]["dur"], abs=1000.0)
    # so the two clocks differ by one offset: within 1 ms for both spans
    start_b, _, stats_b = found["dl4j:engine.sync"]
    offset_a = start / 1e3 - stats["pc_us"]
    offset_b = start_b / 1e3 - stats_b["pc_us"]
    assert abs(offset_a - offset_b) < 1000.0
    assert stats_b["pc_us"] == pytest.approx(ring[b.id]["ts"], abs=1.0)


def test_a_fresh_jit_counts_once_and_a_cached_call_not_at_all():
    telemetry.watch_compilations()
    x = jnp.arange(7.0)                     # its own programs, before t0
    f = jax.jit(lambda v: jnp.tanh(v) * 3.0 + 1.0)
    t0 = time.perf_counter()
    f(x).block_until_ready()
    t1 = time.perf_counter()
    f(x).block_until_ready()
    f(x + 1.0).block_until_ready()          # same shape: the cached program
    t2 = time.perf_counter()
    mine = [e for e in telemetry.spans_between(t0, t1)
            if e["name"].startswith("jit.") and "lambda" in e["args"]["fun"]]
    assert [e["name"] for e in mine] in (["jit.compile"], ["jit.cache_load"])
    assert mine[0]["dur"] > 0 and mine[0]["args"]["id"]
    assert [e for e in telemetry.spans_between(t1, t2)
            if e["name"].startswith("jit.")
            and "lambda" in e["args"]["fun"]] == []


# ---------------------------------------------------------------- engine
@pytest.fixture(scope="module")
def gpt():
    from deeplearning4j_tpu.models.gpt import CausalLM
    from deeplearning4j_tpu.models.transformer import tiny_config

    cfg = tiny_config(vocab=17, max_len=64, d_model=32, n_layers=2,
                      n_heads=4, d_ff=64)
    cfg.dropout = 0.0
    m = CausalLM(cfg, compute_dtype=jnp.float32)
    return m, m.init_params(jax.random.key(1))


#: (prompt length, new tokens) of the three requests on two slots
WORK = [(5, 9), (11, 4), (3, 13)]


def _serve(gpt, **kw):
    from deeplearning4j_tpu.serving import DecodeEngine

    m, params = gpt
    with DecodeEngine(m, params, slots=2, page_size=8, **kw) as eng:
        reqs = [eng.submit(np.arange(1, 1 + p, dtype=np.int32) % 17, n)
                for p, n in WORK]
        out = [r.result(timeout=120).tolist() for r in reqs]
        stats = eng.stats()
    return reqs, out, stats


def _children(parent_id, name):
    return [e for e in _ring(name) if e["args"].get("parent") == parent_id]


@pytest.mark.parametrize("traced", [False, True])
def test_each_engine_boundary_is_in_the_ring_once_with_its_counts(gpt, traced):
    tracing.set_enabled(traced)
    reqs, out, stats = _serve(gpt)
    assert [len(o) for o in out] == [n for _, n in WORK]
    bursts = _ring("engine.burst")
    assert bursts and all("parent" not in b["args"] for b in bursts)
    # one sync and one emit per burst, under it; the dispatches' steps
    # and attended positions add up to the counters
    k_sum = ctx_sum = 0
    for b in bursts:
        bid = b["args"]["id"]
        (sync,), (emit,) = _children(bid, "engine.sync"), \
            _children(bid, "engine.emit")
        disp = _children(bid, "engine.dispatch")
        assert len(disp) == sync["args"]["dispatches"]
        assert sum(d["args"]["k"] for d in disp) == sync["args"]["steps"]
        assert emit["args"]["tokens"] > 0
        k_sum += sync["args"]["steps"]
        ctx_sum += sum(d["args"]["ctx_tokens"] for d in disp)
        # the children lie inside the burst, in order; its first
        # dispatch alone may lie before it (sent ahead, while the burst
        # before it still had its tokens to hand out)
        t = [disp[-1]["ts"], sync["ts"], emit["ts"],
             emit["ts"] + emit["dur"]]
        assert t == sorted(t) and b["ts"] <= t[1]
        assert all(b["ts"] <= d["ts"] for d in disp[1:])
        assert t[-1] <= b["ts"] + b["dur"] + 1.0
    assert len(_ring("engine.sync")) == len(bursts) == len(_ring("engine.emit"))
    assert k_sum == stats["decode_steps"]
    assert len(_ring("engine.dispatch")) == stats["dispatches"]
    # by hand: a request of P prompt tokens and N new ones decodes N - 1
    # steps (the first token is the prefill's), step i over P + i positions
    by_hand = sum((n - 1) * p + n * (n - 1) // 2 for p, n in WORK)
    assert ctx_sum == stats["attended_tokens"] == by_hand
    # and the pages those positions occupy, step by step (pages of 8)
    pages_by_hand = sum(-(-(p + i) // 8) for p, n in WORK
                        for i in range(1, n))
    assert sum(d["args"]["ctx_pages"] for d in _ring("engine.dispatch")) \
        == stats["attended_pages"] == pages_by_hand
    assert stats["prefill_tokens"] == sum(p for p, _ in WORK)
    assert stats["prefill_bucket_tokens"] == sum(
        e["args"]["bucket"] for e in _ring("engine.prefill")) \
        >= stats["prefill_tokens"]
    # one queue wait, one admit and one prefill per request, under its id
    for r in reqs:
        for name in ("request.queue_wait", "engine.admit", "engine.prefill"):
            mine = [e for e in _ring(name)
                    if e["args"].get("request") == r.request_id]
            assert len(mine) == 1, (name, r.request_id)
        admit = [e for e in _ring("engine.admit")
                 if e["args"]["request"] == r.request_id][0]
        (prefill,) = _children(admit["args"]["id"], "engine.prefill")
        assert prefill["args"]["request"] == r.request_id
        assert prefill["args"]["prompt_tokens"] == r.prompt.size
        assert admit["args"]["reuse"] == "cold" and admit["args"]["pages"] > 0
    # no boundary twice, under either switch: the old names are gone
    # and the timeline's own names are not in the ring
    names = {e["name"] for e in _ring()}
    assert not names & {"serving_decode_step", "serving_prefill",
                        "queue_wait", "prefill", "decode_burst"}
    for r in reqs:
        tl = tracing.timeline(r.request_id)
        if not traced:
            assert tl is None
            continue
        got = [e["name"] for e in tl["events"]]
        assert got[:2] == ["queue_wait", "prefill"] and got[-1] == "finish"
        assert "decode_burst" in got
        # the timeline points at the ring's records instead of copying
        ids = {e["args"]["id"] for e in _ring()}
        assert all(e["span"] in ids for e in tl["events"]
                   if e["name"] != "finish")


def test_output_is_the_same_token_for_token_with_telemetry_off(gpt):
    _, on, stats_on = _serve(gpt)
    telemetry.reset()
    telemetry.set_enabled(False)
    _, off, stats_off = _serve(gpt)
    assert on == off
    assert _ring() == []
    # the counts are the engine's own and do not depend on the switch
    # (steps and dispatches depend on when the third request joins)
    for key in ("attended_tokens", "prefill_tokens",
                "prefill_bucket_tokens"):
        assert stats_on[key] == stats_off[key] > 0


@pytest.mark.parametrize("spec", [None, {"k": 3}])
def test_the_timeline_survives_telemetry_off(gpt, spec):
    """The two switches are independent: ``DL4J_TPU_TELEMETRY=0`` with
    ``DL4J_TPU_TRACING=1`` serves the same tokens and keeps the
    timeline, whose times come from the span handles."""
    kw = {"spec_decode": spec} if spec else {}
    _, on, _ = _serve(gpt, **kw)
    telemetry.reset()
    telemetry.set_enabled(False)
    tracing.set_enabled(True)
    reqs, off, _ = _serve(gpt, **kw)
    assert on == off and [len(o) for o in off] == [n for _, n in WORK]
    assert _ring() == []
    burst = "verify" if spec else "decode_burst"
    for r in reqs:
        tl = tracing.timeline(r.request_id)
        assert tl["finish_reason"] == "length"
        ev = tl["events"]
        names = [e["name"] for e in ev]
        assert names[:2] == ["queue_wait", "prefill"] and names[-1] == "finish"
        assert burst in names
        assert set(names) <= {"queue_wait", "prefill", "decode_burst",
                              "verify", "finish"}
        # real intervals, in order, and no id of a record nobody kept
        assert all(e["dur_ms"] >= 0 and "span" not in e for e in ev)
        assert [e["ts_ms"] for e in ev] == sorted(e["ts_ms"] for e in ev)
        assert sum(e["dur_ms"] for e in ev if e["name"] == burst) > 0


def test_the_fleets_route_survives_telemetry_off(gpt):
    from deeplearning4j_tpu.serving.fleet import ServingFleet

    telemetry.set_enabled(False)
    tracing.set_enabled(True)
    m, params = gpt
    with ServingFleet(m, params, replicas=2, slots=2, page_size=8) as fleet:
        reqs = [fleet.submit(np.arange(1, 6, dtype=np.int32), 4)
                for _ in range(3)]
        assert all(len(r.result(timeout=120)) == 4 for r in reqs)
    assert _ring() == []
    for r in reqs:
        names = [e["name"] for e in tracing.timeline(r.request_id)["events"]]
        assert {"route", "queue_wait", "prefill", "finish"} <= set(names)


def test_a_suffix_prefill_counts_the_context_its_kernel_reads(gpt):
    """Behind a cached prefix the prefill runs the paged kernel (more
    than one query, one read of the context): its positions are on
    ``engine.prefill`` and in ``attended_tokens``; a cold prefill, which
    does not run the kernel, has none."""
    from deeplearning4j_tpu.serving import DecodeEngine

    m, params = gpt
    prompt = (np.arange(1, 22, dtype=np.int32) % 17)      # 21 tokens
    with DecodeEngine(m, params, slots=2, page_size=8,
                      prefix_cache=True) as eng:
        eng.submit(prompt, 3).result(timeout=120)
        eng.submit(prompt, 3).result(timeout=120)
        stats = eng.stats()
    cold, warm = _ring("engine.prefill")
    assert "ctx_tokens" not in cold["args"] and cold["args"]["hit_tokens"] == 0
    assert warm["args"]["hit_tokens"] == 16         # two full pages
    assert warm["args"]["ctx_tokens"] == 21
    assert "ctx_pages" not in cold["args"] and warm["args"]["ctx_pages"] == 3
    reuse = [a["args"]["reuse"] for a in _ring("engine.admit")]
    assert reuse[0] == "cold" and reuse[1] != "cold"
    decode = sum(d["args"]["ctx_tokens"] for d in _ring("engine.dispatch"))
    assert decode == 2 * (2 * 21 + 3)        # two steps each: 22 + 23
    assert stats["attended_tokens"] == decode + 21
    # 22 and 23 positions lie on 3 pages of 8, for both requests
    assert sum(d["args"]["ctx_pages"] for d in _ring("engine.dispatch")) \
        == 2 * 2 * 3 == stats["attended_pages"] - 3
    assert stats["prefill_tokens"] == 21 + 5


def test_the_timeline_over_http_keeps_its_four_event_names(gpt):
    from deeplearning4j_tpu.remote.server import JsonModelServer
    from deeplearning4j_tpu.serving import DecodeEngine

    tracing.set_enabled(True)
    m, params = gpt
    with DecodeEngine(m, params, slots=2, page_size=8) as eng:
        srv = JsonModelServer(engine=eng)
        port = srv.start()
        try:
            body = json.dumps({"prompt_ids": [1, 2, 3, 4],
                               "max_new_tokens": 6}).encode()
            rq = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/serving/generate", data=body,
                headers={"Content-Type": "application/json"})
            rid = json.loads(urllib.request.urlopen(
                rq, timeout=120).read())["request_id"]
            tl = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/serving/requests/{rid}",
                timeout=30).read())
        finally:
            srv.stop()
    assert {e["name"] for e in tl["events"]} == \
        {"queue_wait", "prefill", "decode_burst", "finish"}
    assert len(_ring("request.queue_wait")) == 1
    assert len(_ring("engine.prefill")) == 1


def test_a_verify_burst_is_one_step_and_counts_what_its_lanes_attend(gpt):
    reqs, out, stats = _serve(gpt, spec_decode={"k": 3})
    assert [len(o) for o in out] == [n for _, n in WORK]
    assert stats["spec"]["verify_dispatches"] > 0
    disp = _ring("engine.dispatch")
    assert sum(d["args"]["k"] for d in disp) == stats["decode_steps"]
    assert sum(d["args"]["ctx_tokens"] for d in disp) == \
        stats["attended_tokens"]
    verify = [d for d in disp if "verify" in d["args"]]
    # a lane's queries share one read of what it holds: at most its
    # whole sequence, however many drafts it scores
    whole = sum(p + n for p, n in WORK)
    assert all(0 < d["args"]["ctx_tokens"] <= whole for d in verify)
    # pages: what the positions occupy, lane by lane, so between the
    # positions over a page's size and that plus a page a live lane
    assert sum(d["args"]["ctx_pages"] for d in disp) == \
        stats["attended_pages"]
    assert all(d["args"]["ctx_tokens"] / 8 <= d["args"]["ctx_pages"]
               < d["args"]["ctx_tokens"] / 8 + d["args"]["live"] * d["args"]["k"]
               for d in disp)
    assert len(verify) == stats["spec"]["verify_dispatches"]
    assert all(d["args"]["k"] == 1 and d["args"]["verify"] == 3
               for d in verify)
    assert len(_ring("engine.sync")) == len(_ring("engine.burst"))


def test_the_fleets_route_is_the_parent_of_the_replicas_admit(gpt):
    from deeplearning4j_tpu.serving.fleet import ServingFleet

    m, params = gpt
    with ServingFleet(m, params, replicas=2, slots=2, page_size=8) as fleet:
        reqs = [fleet.submit(np.arange(1, 6, dtype=np.int32), 4)
                for _ in range(3)]
        for r in reqs:
            r.result(timeout=120)
    routes = {e["args"]["id"]: e for e in _ring("fleet.route")}
    admits = _ring("engine.admit")
    assert len(routes) == len(admits) == 3
    for a in admits:
        route = routes[a["args"]["parent"]]
        assert route["args"]["request"] == a["args"]["request"]
        assert route["args"]["replica"] == a["args"]["engine"]
        assert route["tid"] != a["tid"]


def test_a_model_that_selects_counts_what_it_reads_and_what_it_scores():
    """A model whose ``cache_spec()`` names ``selected`` (learned sparse
    attention): each dispatch carries the contexts cut to the selection
    and the positions the indexer scored beside ``ctx_tokens``, and
    ``stats()`` the pool's stores by name; a model without the key
    carries neither attribute."""
    from deeplearning4j_tpu.models.glm_moe_dsa import (GlmMoeDsaConfig,
                                                       GlmMoeDsaLM)
    from deeplearning4j_tpu.serving import DecodeEngine

    m = GlmMoeDsaLM(GlmMoeDsaConfig(
        vocab_size=17, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=2,
        indexer_types=("full", "shared"),
        mlp_layer_types=("dense", "sparse"), num_attention_heads=2,
        q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, index_n_heads=2,
        index_head_dim=16, index_topk=8, n_routed_experts=4,
        num_experts_per_tok=2, max_position_embeddings=64), jnp.float32)
    with DecodeEngine(m, m.init_params(jax.random.key(0)), slots=2,
                      page_size=8, max_context=64) as eng:
        reqs = [eng.submit(np.arange(1, 1 + p, dtype=np.int32) % 17, n)
                for p, n in WORK]
        for r in reqs:
            r.result(timeout=120)
        stats = eng.stats()
    calls = [e["args"] for e in _ring("engine.dispatch")]
    assert calls and sum(a["ctx_tokens"] for a in calls) \
        == stats["attended_tokens"]
    for a in calls:
        assert a["ctx_index_tokens"] == a["ctx_tokens"]
        assert 0 < a["ctx_selected_tokens"] <= min(
            a["ctx_tokens"], 8 * a["live"] * a["k"])
    # contexts run to 16, twice the selection
    assert any(a["ctx_selected_tokens"] < a["ctx_tokens"] for a in calls)
    assert set(stats["kv_pages"]["store_bytes"]) == {"latent", "index_k"}
