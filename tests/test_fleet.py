"""Serving fleet (serving/fleet.py): replicated decode engines behind
one KV-aware router with disaggregated prefill — token-identity vs a
solo engine (N=1 and N=2, lane on and off), session affinity,
kill-a-replica failover with exact replay, drain/restart elastic
resize, shared AOT warm pools, capacity 429s, and the HTTP front-end's
routing fields."""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.profiler import (
    chaos, flight_recorder, telemetry, tracing,
)
from deeplearning4j_tpu.serving import (
    CapacityRejected, DecodeEngine, ServingFleet,
)

VOCAB = 17


def _model():
    cfg = tiny_config(vocab=VOCAB, max_len=64, d_model=32, n_layers=2,
                      n_heads=4, d_ff=64)
    cfg.dropout = 0.0
    return CausalLM(cfg, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def params(model):
    return model.init_params(jax.random.key(1))


def _solo(model, params, prompt, new):
    return np.asarray(model.generate(
        params, jnp.asarray(np.asarray(prompt)[None, :], jnp.int32),
        new))[0]


def _fleet(model, params, **kw):
    """Fleet with a slimmed AOT surface (3 prefill buckets, short
    chunk ladder) so each test's startup stays ~1s — the full bucket
    ladder is the CI fleet smoke gate's job."""
    kw.setdefault("slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_buckets", [8, 16, 40])
    kw.setdefault("max_chunk", 4)
    return ServingFleet(model, params, **kw)


def _mixed_specs(n, rng, long_every=3):
    specs = []
    for i in range(n):
        t0 = (int(rng.integers(20, 40)) if long_every and
              i % long_every == 0 else int(rng.integers(3, 12)))
        specs.append((rng.integers(0, VOCAB, (t0,)).astype(np.int32),
                      int(rng.integers(2, 10))))
    return specs


# ----------------------------------------------------- token identity
class TestFleetParity:
    @pytest.mark.slow
    def test_single_replica_no_disagg_identical_to_solo(self, model,
                                                        params):
        """Acceptance: a fleet of N=1 with disaggregation off is
        greedy token-identical to a solo engine (and to generate())."""
        rng = np.random.default_rng(0)
        specs = _mixed_specs(4, rng, long_every=0)
        with _fleet(model, params, replicas=1) as fl:
            outs = [fl.submit(p, n).result(120) for p, n in specs]
        for (p, n), got in zip(specs, outs):
            np.testing.assert_array_equal(got,
                                          _solo(model, params, p, n))

    @pytest.mark.slow
    def test_two_replicas_with_lane_identical_to_solo(self, model,
                                                      params):
        """Concurrent mixed-length traffic over 2 replicas + the
        disaggregated prefill lane stays token-identical: the lane's
        prefill is the same forward at the same bucket padding, and
        the adopt scatter commits the same bytes."""
        rng = np.random.default_rng(1)
        specs = _mixed_specs(12, rng)
        with _fleet(model, params, replicas=2, prefill_threshold=16,
                    prefix_cache=True) as fl:
            with ThreadPoolExecutor(max_workers=8) as ex:
                hs = list(ex.map(lambda pn: fl.submit(pn[0], pn[1]),
                                 specs))
            outs = [h.result(timeout=300) for h in hs]
            lane = fl._lane.stats()
        assert lane["prefills"] >= 1, "no prompt took the lane"
        for (p, n), got in zip(specs, outs):
            np.testing.assert_array_equal(got,
                                          _solo(model, params, p, n))

    def test_shared_aot_zero_compiles_for_second_replica(self, model,
                                                         params):
        reg = telemetry.MetricsRegistry.get_default()
        compiles = reg.counter(telemetry.JIT_COMPILES)

        def site_total():
            return sum(compiles.value(site=s) for s in
                       ("serving_decode", "serving_prefill",
                        "serving_adopt", "serving_lane_prefill",
                        "serving_prefix_prefill", "serving_cow_copy"))

        fl = _fleet(model, params, replicas=2, prefill_threshold=16)
        fl.start()
        try:
            before = site_total()
            st = fl.stats()
            # replica 1 adopted replica 0's executables wholesale
            assert st["replicas"][1]["warm_pool"]["adopted"] > 0
            assert st["replicas"][0]["warm_pool"]["adopted"] == 0
            rng = np.random.default_rng(2)
            hs = [fl.submit(rng.integers(0, VOCAB, (t0,)).astype(
                np.int32), 3) for t0 in (5, 25, 9, 30)]
            for h in hs:
                h.result(120)
            assert site_total() == before, \
                "post-startup request paid a serving-site compile"
        finally:
            fl.shutdown()


# -------------------------------------------------- routing + affinity
class TestPlacement:
    def test_replicas_without_devices_raise_on_a_multichip_tpu(
            self, model, params, monkeypatch):
        """The CPU mesh shares the default device across replicas on
        purpose; on a TPU host that is a pile-up on chip 0."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(ValueError, match="needs devices="):
            ServingFleet(model, params, replicas=2)


class TestRouting:
    def test_session_affinity_routes_back_warm(self, model, params):
        rng = np.random.default_rng(3)
        with _fleet(model, params, replicas=2, prefix_cache=True,
                    session_capacity=4) as fl:
            t1 = rng.integers(0, VOCAB, (9,)).astype(np.int32)
            r1 = fl.submit(t1, 5, session_id="conv")
            o1 = r1.result(60)
            t2 = np.concatenate(
                [t1, o1, rng.integers(0, VOCAB, (3,)).astype(np.int32)])
            r2 = fl.submit(t2, 5, session_id="conv")
            o2 = r2.result(60)
            assert r2.routing["reason"] == "affinity"
            assert r2.routing["replica"] == r1.routing["replica"]
            assert r2.cache_hit_tokens == t1.size + o1.size - 1
            np.testing.assert_array_equal(
                o2, _solo(model, params, t2, 5))

    def test_prefix_locality_prefers_warm_replica(self, model, params):
        """The KV-aware score: a prompt whose prefix pages live on
        replica k routes to k (hit hint beats raw free capacity)."""
        rng = np.random.default_rng(4)
        sys_p = rng.integers(0, VOCAB, (24,)).astype(np.int32)
        with _fleet(model, params, replicas=2,
                    prefix_cache=True) as fl:
            first = fl.submit(np.concatenate(
                [sys_p, rng.integers(0, VOCAB, (4,)).astype(np.int32)]),
                4)
            first.result(60)
            warm_rep = first.routing["replica"]
            hits = 0
            for _ in range(4):
                r = fl.submit(np.concatenate(
                    [sys_p,
                     rng.integers(0, VOCAB, (4,)).astype(np.int32)]), 4)
                r.result(60)
                hits += (r.routing["replica"] == warm_rep
                         and r.cache_hit_tokens >= 16)
            assert hits == 4, f"only {hits}/4 warm-routed"

# --------------------------------------------------- failure + resize
class TestFailover:
    def test_kill_replica_replays_exactly_and_sessions_readmit_cold(
            self, model, params):
        rng = np.random.default_rng(5)
        fl = _fleet(model, params, replicas=2, prefix_cache=True,
                    session_capacity=4)
        fl.start()
        try:
            t1 = rng.integers(0, VOCAB, (8,)).astype(np.int32)
            s1 = fl.submit(t1, 4, session_id="conv")
            s1.result(60)
            doomed = s1.routing["replica"]
            idx = next(i for i, r in enumerate(fl._replicas)
                       if r.engine.engine_id == doomed)
            # a long request drawn to the doomed replica by the page of
            # its prompt that s1 left in that replica's prefix cache
            # (a new session has no affinity yet, and the pages s1's
            # session pins there would send it elsewhere), plus
            # bystanders spread across the fleet
            long_p = np.concatenate(
                [t1, rng.integers(0, VOCAB, (4,)).astype(np.int32)])
            victim = fl.submit(long_p, 52, session_id="conv2")
            others = [fl.submit(
                rng.integers(0, VOCAB, (6,)).astype(np.int32), 8)
                for _ in range(4)]
            deadline = time.time() + 30
            while not victim.tokens and time.time() < deadline:
                time.sleep(0.0002)
            assert victim.tokens, "victim never started"
            assert victim.routing["replica"] == doomed
            # stall the doomed scheduler before the kill: a fully warm
            # compile cache can otherwise finish the victim between
            # the progress poll and the kill, leaving nothing in
            # flight to re-route (at most the pass already executing
            # slips through the stall)
            chaos.hang_replica(fl._replicas[idx].engine, 2.0)
            fl.kill_replica(idx)
            got = victim.result(timeout=120)
            np.testing.assert_array_equal(
                got, _solo(model, params, long_p, 52))
            for o in others:
                o.result(timeout=120)
            assert fl.alive_replicas() == 1
            # flight recorder saw the death and the re-route
            kinds = [e["kind"]
                     for e in flight_recorder.get_default().events()]
            assert "fleet_replica_dead" in kinds
            assert "fleet_reroute" in kinds
            # the session pinned on the dead replica re-admits cold
            o1 = np.asarray(s1.tokens, np.int32)
            t2 = np.concatenate(
                [t1, o1, rng.integers(0, VOCAB, (2,)).astype(np.int32)])
            r2 = fl.submit(t2, 4, session_id="conv")
            o2 = r2.result(60)
            assert r2.routing["replica"] != doomed
            np.testing.assert_array_equal(
                o2, _solo(model, params, t2, 4))
        finally:
            fl.shutdown()
        survivors = [r for r in fl._replicas if r.engine.pool]
        for r in survivors:
            assert r.engine.pool.allocated == 0

    @pytest.mark.slow
    def test_drain_then_restart_replica(self, model, params):
        rng = np.random.default_rng(6)
        with _fleet(model, params, replicas=2) as fl:
            fl.generate(rng.integers(0, VOCAB, (5,)).astype(np.int32),
                        3)
            assert fl.drain_replica(1)
            assert fl.alive_replicas() == 1
            p = rng.integers(0, VOCAB, (6,)).astype(np.int32)
            np.testing.assert_array_equal(
                fl.generate(p, 4), _solo(model, params, p, 4))
            fl.restart_replica(1)
            assert fl.alive_replicas() == 2
            # restarted replica adopts a live donor's warm pool
            assert fl._replicas[1].engine._warm.adopted > 0
            p2 = rng.integers(0, VOCAB, (7,)).astype(np.int32)
            np.testing.assert_array_equal(
                fl.generate(p2, 4), _solo(model, params, p2, 4))

    @pytest.mark.slow
    def test_drain_with_inflight_session_pinned_requests(
            self, model, params):
        """Elastic-resize coverage the kill path doesn't give:
        draining a replica that holds session-PINNED pages while a
        session request is still decoding there. The in-flight request
        must finish (drain waits), the drained pool must reach 0
        allocated pages (pins released with the shutdown), and the
        session's next turn must cold-restart cleanly on the survivor
        with token-identical output."""
        rng = np.random.default_rng(11)
        with _fleet(model, params, replicas=2, prefix_cache=True,
                    session_capacity=4) as fl:
            t1 = rng.integers(0, VOCAB, (9,)).astype(np.int32)
            r1 = fl.submit(t1, 4, session_id="pin")
            o1 = r1.result(60)
            target = r1.routing["replica"]
            idx = next(i for i, r in enumerate(fl._replicas)
                       if r.engine.engine_id == target)
            eng = fl._replicas[idx].engine
            assert eng._sessions.stats()["sessions"] == 1
            # turn 2 of the same session decodes ON the pinned replica
            # (affinity) while the drain starts — it re-pins mid-drain
            t2 = np.concatenate(
                [t1, o1, rng.integers(0, VOCAB, (2,)).astype(np.int32)])
            r2 = fl.submit(t2, 24, session_id="pin")
            deadline = time.time() + 30
            while len(r2.tokens) < 2 and time.time() < deadline:
                time.sleep(0.005)
            assert r2.routing["replica"] == target
            assert fl.drain_replica(idx, timeout=120)
            # the in-flight session request FINISHED during the drain
            o2 = r2.result(10)
            np.testing.assert_array_equal(
                o2, _solo(model, params, t2, 24))
            # pins released, pool fully drained on the dead replica
            assert eng.pool.allocated == 0
            assert eng.pool.shared_pages() == 0
            # next turn cold-restarts on the survivor, token-identical
            t3 = np.concatenate(
                [t2, o2, rng.integers(0, VOCAB, (2,)).astype(np.int32)])
            r3 = fl.submit(t3, 4, session_id="pin")
            o3 = r3.result(60)
            assert r3.routing["replica"] != target
            assert r3.cache_hit_tokens == 0        # cold re-admit
            np.testing.assert_array_equal(
                o3, _solo(model, params, t3, 4))
            # ...and RE-pins on the survivor: turn 4 is warm again
            t4 = np.concatenate(
                [t3, o3, rng.integers(0, VOCAB, (2,)).astype(np.int32)])
            r4 = fl.submit(t4, 4, session_id="pin")
            o4 = r4.result(60)
            assert r4.routing["replica"] == r3.routing["replica"]
            assert r4.cache_hit_tokens > 0
            np.testing.assert_array_equal(
                o4, _solo(model, params, t4, 4))


# ------------------------------------------------------ capacity 429s
class TestCapacity:
    def test_engine_full_queue_raises_structured_reject(self, model,
                                                        params):
        eng = DecodeEngine(model, params, slots=1, page_size=8,
                           max_queue=1, warm_start=False)
        eng.start()
        try:
            held = [eng.submit(np.asarray([1, 2], np.int32), 30,
                               eos_id=VOCAB)]
            deadline = time.time() + 30
            while not eng._active.any() and time.time() < deadline:
                time.sleep(0.002)
            held.append(eng.submit(np.asarray([1, 2], np.int32), 4))
            with pytest.raises(CapacityRejected) as ei:
                for _ in range(4):   # queue depth 1: must trip now
                    held.append(
                        eng.submit(np.asarray([1, 2], np.int32), 4))
            assert ei.value.retry_after_s > 0
            reg = telemetry.MetricsRegistry.get_default()
            assert reg.counter(telemetry.SERVING_REJECTS).value(
                engine=eng.engine_id) >= 1
        finally:
            eng.shutdown()

    def test_fleet_full_queue_raises_structured_reject(self, model,
                                                       params):
        fl = ServingFleet(model, params, replicas=1, slots=1,
                          page_size=8, max_queue=1, warm_start=False)
        # never started: the router drains nothing, so the 2nd+3rd
        # submissions must overflow the fleet queue deterministically
        fl._router = threading.Thread(target=lambda: None)  # inert
        try:
            fl.submit(np.asarray([1, 2], np.int32), 4)
            with pytest.raises(CapacityRejected) as ei:
                fl.submit(np.asarray([1, 2], np.int32), 4)
                fl.submit(np.asarray([1, 2], np.int32), 4)
            assert ei.value.retry_after_s > 0
        finally:
            fl._stop.set()
            for r in fl._replicas:
                r.engine.shutdown()

    @pytest.mark.slow
    def test_http_429_and_client_backoff_retry(self, model, params):
        """HTTP front-end answers the reject with a structured 429 +
        Retry-After; JsonRemoteInference retries with backoff and
        succeeds once capacity frees."""
        import json
        import urllib.error
        import urllib.request

        from deeplearning4j_tpu.remote.server import (
            JsonModelServer, JsonRemoteInference,
        )

        eng = DecodeEngine(model, params, slots=1, page_size=8,
                           max_queue=1, prefill_buckets=[8],
                           max_chunk=2)
        srv = JsonModelServer(engine=eng)
        port = srv.start()
        eng.start()
        try:
            blocker = eng.submit(np.asarray([1, 2], np.int32), 40,
                                 eos_id=VOCAB)
            deadline = time.time() + 30
            while not eng._active.any() and time.time() < deadline:
                time.sleep(0.002)
            filler = eng.submit(np.asarray([3, 4], np.int32), 2)
            # raw request: structured 429 with Retry-After header
            body = json.dumps({"prompt_ids": [1, 2],
                               "max_new_tokens": 2}).encode()
            got429 = None
            for _ in range(6):
                try:
                    urllib.request.urlopen(urllib.request.Request(
                        f"http://127.0.0.1:{port}/v1/serving/generate",
                        data=body,
                        headers={"Content-Type": "application/json"}),
                        timeout=30).read()
                except urllib.error.HTTPError as e:
                    if e.code == 429:
                        got429 = e
                        break
                    raise
            assert got429 is not None, "queue never filled to a 429"
            assert float(got429.headers["Retry-After"]) > 0
            payload = json.loads(got429.read())
            assert payload["retry_after_s"] > 0
            # retrying client: blocker/filler drain within its backoff
            # budget, so generate() succeeds instead of raising
            cli = JsonRemoteInference(f"http://127.0.0.1:{port}",
                                      retries=8, max_backoff_s=0.5)
            out = cli.generate(np.asarray([5, 6], np.int32), 3)
            np.testing.assert_array_equal(
                out, _solo(model, params,
                           np.asarray([5, 6], np.int32), 3))
            blocker.result(120)
            filler.result(120)
        finally:
            srv.stop()
            eng.shutdown()


# ------------------------------------------------------ HTTP fleet
class TestHttpFleet:
    @pytest.mark.slow
    def test_server_over_fleet_routing_fields_and_stats(self, model,
                                                        params):
        import json
        import urllib.request

        from deeplearning4j_tpu.remote.server import (
            JsonModelServer, JsonRemoteInference,
        )

        was = tracing.enabled()
        tracing.set_enabled(True)
        fl = _fleet(model, params, replicas=2, prefill_threshold=16)
        srv = JsonModelServer(engine=fl)
        port = srv.start()
        try:
            cli = JsonRemoteInference(f"http://127.0.0.1:{port}")
            p = np.arange(24, dtype=np.int32) % VOCAB   # lane-long
            out = cli.generate_full(p, 4)
            np.testing.assert_array_equal(
                np.asarray(out["tokens"], np.int32),
                _solo(model, params, p, 4))
            assert out["engine"] is not None
            assert out["routing"]["replica"] == out["engine"]
            assert out["routing"]["lane"] is True
            assert out["routing"]["attempts"] == 1
            # per-replica tags visible in the request traces
            tl = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/serving/requests/"
                f"{out['request_id']}", timeout=10).read())
            assert tl["attrs"]["engine"] == out["engine"]
            summaries = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/serving/requests",
                timeout=10).read())
            mine = next(s for s in summaries["recent"]
                        if s["request_id"] == out["request_id"])
            assert mine["engine"] == out["engine"]
            assert mine["lane_prefill_ms"] >= 0
            st = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/serving/stats",
                timeout=10).read())
            assert st["fleet"] and st["alive_replicas"] == 2
            info = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/serving/info",
                timeout=10).read())
            assert info["engine"]["slots"] == 4
        finally:
            srv.stop()
            fl.shutdown()
            tracing.set_enabled(was)
            tracing.reset()


# --------------------------------------------- GenerativeInference
class TestGenerativeInferenceFleet:
    @pytest.mark.slow
    def test_wrapper_builds_fleet_and_serves(self, model, params):
        from deeplearning4j_tpu.parallel.wrapper import (
            GenerativeInference,
        )

        p = np.asarray([2, 4, 6], np.int32)
        with GenerativeInference(model, params, replicas=2, slots=2,
                                 page_size=8) as gi:
            from deeplearning4j_tpu.serving.fleet import ServingFleet
            assert isinstance(gi.engine, ServingFleet)
            out = gi.output(p, 5)
            assert gi.n_requests == 1
            assert gi.n_dispatches >= 1
            assert gi.stats()["alive_replicas"] == 2
        np.testing.assert_array_equal(out, _solo(model, params, p, 5))


# -------------------------------------------------- fleet telemetry
class TestFleetTelemetry:
    @pytest.mark.slow
    def test_fleet_counters_and_snapshot(self, model, params):
        reg = telemetry.MetricsRegistry.get_default()
        with _fleet(model, params, replicas=2,
                    prefill_threshold=16) as fl:
            eids = [r.engine.engine_id for r in fl._replicas]
            rng = np.random.default_rng(7)
            for t0 in (5, 25, 7, 30):
                fl.generate(
                    rng.integers(0, VOCAB, (t0,)).astype(np.int32), 3)
            assert reg.gauge(
                telemetry.SERVING_FLEET_REPLICAS).value() == 2
            routed = reg.counter(telemetry.SERVING_FLEET_ROUTED)
            assert sum(routed.value(reason="score", engine=e)
                       for e in eids) >= 4
            assert reg.counter(
                telemetry.SERVING_LANE_PREFILLS).total() >= 2
            st = fl.stats()
            assert st["fleet"] and len(st["replicas"]) == 2
            assert st["alive_replicas"] == 2
            assert st["slots"] == 4
            for k in ("page_size", "max_context", "quantization",
                      "prefill_buckets"):
                assert k in st, k
            assert st["router"]["routed"].get("score", 0) >= 4
            assert st["prefill_lane"]["threshold"] == 16
            ps = fl.prefix_stats()
            assert ps["fleet"] and len(ps["replicas"]) == 2
        snap = telemetry.serving_snapshot()
        for key in ("fleet_routed", "fleet_live_replicas",
                    "lane_prefills", "handoff_seconds"):
            assert key in snap, key

    @pytest.mark.slow
    def test_killed_replica_series_retired(self, model, params):
        """Stale-series regression (PR 14): a killed replica's
        engine-labelled GAUGE series must disappear (no ghost engine
        frozen at its last reading for serving_snapshot(), /metrics,
        or SLO rules to evaluate) while the fleet's cumulative
        aggregates keep its history."""
        reg = telemetry.MetricsRegistry.get_default()
        rng = np.random.default_rng(3)
        with _fleet(model, params, replicas=2) as fl:
            for _ in range(4):
                fl.generate(rng.integers(0, VOCAB, (6,)).astype(
                    np.int32), 3)
            agg_before = telemetry.serving_snapshot()[
                "aggregate"]["requests_total"]
            victim = fl._replicas[0]
            vid = victim.engine.engine_id
            # the victim served traffic: its gauges exist pre-kill
            assert any(dict(k).get("engine") == vid for k in reg.gauge(
                telemetry.SERVING_KV_PAGE_UTILIZATION).values())
            fl.kill_replica(0)
            deadline = time.monotonic() + 10
            while (victim.alive or victim.needs_cleanup) \
                    and time.monotonic() < deadline:
                time.sleep(0.02)     # router health pass cleans up
            assert not victim.alive and not victim.needs_cleanup
            # every gauge series of the dead engine is gone...
            for name in (telemetry.SERVING_KV_PAGE_UTILIZATION,
                         telemetry.SERVING_QUEUE_DEPTH,
                         telemetry.SERVING_SLOT_OCCUPANCY):
                m = reg.peek(name)
                if m is not None:
                    assert not any(
                        dict(k).get("engine") == vid
                        for k in m.values()), name
            # ...and /metrics stops exposing it
            assert f'engine="{vid}"' not in "\n".join(
                line for line in reg.to_prometheus().splitlines()
                if line.startswith(("dl4j_tpu_serving_kv",
                                    "dl4j_tpu_serving_queue_depth",
                                    "dl4j_tpu_serving_slot")))
            snap = telemetry.serving_snapshot()
            assert vid not in snap["engines"]
            # fleet aggregates stay correct: the dead engine's served
            # requests still count
            assert snap["aggregate"]["requests_total"] == agg_before
            # the survivor still serves and its series stay live
            sid = fl._replicas[1].engine.engine_id
            fl.generate(rng.integers(0, VOCAB, (6,)).astype(
                np.int32), 3)
            assert sid in telemetry.serving_snapshot()["engines"]

    @pytest.mark.slow
    def test_fleet_pressure_gauge_published_and_retired(self, model,
                                                        params):
        reg = telemetry.MetricsRegistry.get_default()
        with _fleet(model, params, replicas=1) as fl:
            fl.generate(np.asarray([1, 2, 3], np.int32), 3)
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                series = reg.gauge(
                    telemetry.SERVING_FLEET_PRESSURE).values()
                if (("fleet", fl.fleet_id),) in series:
                    break
                time.sleep(0.05)
            assert (("fleet", fl.fleet_id),) in reg.gauge(
                telemetry.SERVING_FLEET_PRESSURE).values()
            fid = fl.fleet_id
        # shutdown retires the fleet's pressure series
        assert (("fleet", fid),) not in reg.gauge(
            telemetry.SERVING_FLEET_PRESSURE).values()


# -------------------------------------------------- runtime elasticity
class TestElasticScale:
    """Phase-3 elasticity: replicas added/removed at RUNTIME on stable
    ids, with warm-pool adoption and token identity preserved."""

    @pytest.mark.slow
    def test_add_replica_adopts_warm_and_stays_token_identical(
            self, model, params):
        """Growing a live 1-replica fleet: the new replica adopts the
        donor's AOT warm pool (same device), registers atomically, and
        traffic across the grown fleet stays token-identical to solo —
        with ZERO post-adopt warm-pool misses."""
        rng = np.random.default_rng(21)
        reg = telemetry.MetricsRegistry.get_default()
        with _fleet(model, params, replicas=1) as fl:
            fl.generate(rng.integers(0, VOCAB, (5,)).astype(np.int32),
                        3)
            rid = fl.add_replica()
            assert rid == 1 and fl.alive_replicas() == 2
            st = fl.stats()
            assert st["pending_scale"] == 0
            assert [r["id"] for r in st["replicas"]] == [0, 1]
            new_eng = fl._by_rid[rid].engine
            assert new_eng._warm.adopted > 0     # same-device adopt
            specs = _mixed_specs(8, rng)
            with ThreadPoolExecutor(max_workers=6) as ex:
                hs = list(ex.map(lambda pn: fl.submit(pn[0], pn[1]),
                                 specs))
            outs = [h.result(timeout=300) for h in hs]
            for (p, n), got in zip(specs, outs):
                np.testing.assert_array_equal(
                    got, _solo(model, params, p, n))
            # the acceptance bar: nothing compiled on the new
            # replica's hot path after adoption
            assert new_eng.stats()["warm_pool"]["misses"] == 0
            assert new_eng.n_dispatches > 0      # it actually served
            # size gauge reflects the grown fleet
            assert reg.gauge(telemetry.SERVING_FLEET_SIZE).values()[
                (("fleet", fl.fleet_id),)] == 2

    @pytest.mark.slow
    def test_remove_replica_with_pinned_sessions(self, model, params):
        """Satellite: scale-down while sessions are PINNED to the
        doomed replica. remove_replica drains it (in-flight requests
        finish), its pool empties, the session's next turn
        cold-restarts on a survivor and RE-pins warm — token output
        never diverges from solo."""
        rng = np.random.default_rng(22)
        with _fleet(model, params, replicas=2, prefix_cache=True,
                    session_capacity=4) as fl:
            t1 = rng.integers(0, VOCAB, (9,)).astype(np.int32)
            r1 = fl.submit(t1, 4, session_id="pin")
            o1 = r1.result(60)
            target = r1.routing["replica"]
            doomed = next(r for r in fl._replicas
                          if r.engine.engine_id == target)
            eng = doomed.engine
            assert eng._sessions.stats()["sessions"] == 1
            assert fl.remove_replica(doomed.rid, timeout=120)
            # identity retired: the old id is gone, not renumbered
            with pytest.raises(IndexError):
                fl.drain_replica(doomed.rid)
            st = fl.stats()
            assert doomed.rid not in [r["id"] for r in st["replicas"]]
            assert eng.pool.allocated == 0       # pins released
            # next session turn cold-restarts on the survivor...
            t2 = np.concatenate(
                [t1, o1, rng.integers(0, VOCAB, (2,)).astype(np.int32)])
            r2 = fl.submit(t2, 4, session_id="pin")
            o2 = r2.result(60)
            assert r2.routing["replica"] != target
            assert r2.cache_hit_tokens == 0
            np.testing.assert_array_equal(
                o2, _solo(model, params, t2, 4))
            # ...and re-pins warm for the turn after
            t3 = np.concatenate(
                [t2, o2, rng.integers(0, VOCAB, (2,)).astype(np.int32)])
            r3 = fl.submit(t3, 4, session_id="pin")
            o3 = r3.result(60)
            assert r3.routing["replica"] == r2.routing["replica"]
            assert r3.cache_hit_tokens > 0
            np.testing.assert_array_equal(
                o3, _solo(model, params, t3, 4))

    def test_remove_replica_tombstones_tsdb_series(self, model,
                                                   params):
        """Satellite: scale-down retires the dead engine's gauge
        series in the time-series store too (telemetry.
        retire_engine_series -> timeseries.tombstone_series) —
        instant queries stop answering for the removed replica while
        its pre-death history stays readable, and the survivor's
        series is untouched."""
        from deeplearning4j_tpu.profiler import timeseries as ts

        was = telemetry.enabled()
        telemetry.set_enabled(True)
        db = ts.TimeSeriesDB()
        ts.install(db)
        reg = telemetry.MetricsRegistry.get_default()
        try:
            with _fleet(model, params, replicas=2) as fl:
                eids = [r.engine.engine_id for r in fl._replicas]
                g = reg.gauge(telemetry.SERVING_SLOT_OCCUPANCY)
                for e in eids:
                    g.set(0.5, engine=e)
                t0 = time.time()
                db.ingest(t0, reg.capture())
                dead, alive = eids[0], eids[1]
                assert fl.remove_replica(fl._replicas[0].rid)
                now = time.time()
                occ = "dl4j_tpu_serving_slot_occupancy"
                assert ts.query(f'{occ}{{engine="{dead}"}}',
                                t=now, db=db) == []
                assert ts.query(f'{occ}{{engine="{alive}"}}',
                                t=now, db=db) == \
                    [({"engine": alive}, 0.5)]
                # pre-death history is still there (range reads with
                # no instant don't drop tombstoned series)
                hist = db.select(occ, [], t0 - 1, now + 1)
                assert {r[0]["engine"] for r in hist} == set(eids)
        finally:
            ts.install(None, None)
            telemetry.set_enabled(was)

    def test_rid_stability_and_last_replica_guard(self, model,
                                                  params):
        """Replica ids are STABLE handles, not list positions: after
        removing id 0, id 1 still addresses the same engine; the next
        add mints id 2; and the last live replica refuses removal."""
        rng = np.random.default_rng(23)
        with _fleet(model, params, replicas=2) as fl:
            keep_eng = fl._by_rid[1].engine
            assert fl.remove_replica(0)
            assert fl.alive_replicas() == 1
            assert fl._by_rid[1].engine is keep_eng
            with pytest.raises(ValueError):
                fl.remove_replica(1)             # last live replica
            rid = fl.add_replica()
            assert rid == 2
            assert [r["id"] for r in fl.stats()["replicas"]] == [1, 2]
            # stable-id drain/restart still address the right engine
            assert fl.drain_replica(2)
            fl.restart_replica(2)
            assert fl.alive_replicas() == 2
            p = rng.integers(0, VOCAB, (6,)).astype(np.int32)
            np.testing.assert_array_equal(
                fl.generate(p, 4), _solo(model, params, p, 4))
