"""Elastic control plane (control/scheduler.py): gang scheduling over
one device fleet, health verdicts (worker death / stall / divergence),
checkpoint-and-migrate onto a reduced topology, retry budgets with
backoff, serving jobs with replica restart and capacity hand-back —
plus the satellites: engine/fleet request cancel, the chaos hang
injector, and the idempotent HTTP generate."""

import json
import tempfile
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import control
from deeplearning4j_tpu.datasets import ArrayDataSetIterator
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.nn.conf import (
    DenseLayer, InputType, NeuralNetConfiguration, OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.profiler import chaos, flight_recorder, telemetry
from deeplearning4j_tpu.remote.server import (
    JsonModelServer, JsonRemoteInference,
)
from deeplearning4j_tpu.serving import DecodeEngine, ServingFleet
from deeplearning4j_tpu.util.resilience import FaultTolerance

DEVS = jax.devices()
VOCAB = 17


def small_net(seed=9):
    return MultiLayerNetwork(
        (NeuralNetConfiguration.builder().seed(seed)
         .updater(Adam(learning_rate=0.01)).list()
         .layer(DenseLayer(n_out=8, activation="tanh"))
         .layer(OutputLayer(n_out=2, activation="softmax",
                            loss="mcxent"))
         .setInputType(InputType.feedForward(4)).build())).init()


def toy_data(n=48, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 4)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[(x.sum(1) > 0).astype(int)]
    return x, y


X, Y = toy_data()


def data_iter():
    return ArrayDataSetIterator(X, Y, 8, shuffle=True, seed=5)


class SlowIter(ArrayDataSetIterator):
    """Stateful iterator with a per-batch delay, so a drill can land
    mid-fit deterministically."""

    def __init__(self, *a, delay=0.03, **kw):
        super().__init__(*a, **kw)
        self._delay = delay

    def next(self):
        time.sleep(self._delay)
        return super().next()


def make_sched(**kw):
    kw.setdefault("devices", DEVS[:4])
    kw.setdefault("workers", {"w0": DEVS[:2], "w1": DEVS[2:4]})
    kw.setdefault("rebalance", False)
    return control.JobScheduler(**kw)


def _gpt_model():
    cfg = tiny_config(vocab=VOCAB, max_len=64, d_model=32, n_layers=2,
                      n_heads=4, d_ff=64)
    cfg.dropout = 0.0
    return CausalLM(cfg, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def gpt():
    m = _gpt_model()
    return m, m.init_params(jax.random.key(1))


def _engine(model, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_buckets", [8, 16, 40])
    kw.setdefault("max_chunk", 4)
    return DecodeEngine(model, params, **kw)


# ======================================================================
# device fleet
# ======================================================================
class TestDeviceFleet:
    def test_gang_all_or_nothing(self):
        fl = control.DeviceFleet(devices=DEVS[:3],
                                 workers={"w": DEVS[:3]})
        assert fl.acquire(4, "j") is None
        got = fl.acquire(3, "j")
        assert len(got) == 3 and fl.free == 0
        fl.release(got)
        assert fl.free == 3

    def test_release_is_idempotent_per_device(self):
        fl = control.DeviceFleet(devices=DEVS[:2],
                                 workers={"w": DEVS[:2]})
        got = fl.acquire(2, "j")
        fl.release(got)
        fl.release(got)      # double hand-back must not inflate
        assert fl.free == 2 and fl.total == 2

    def test_lose_and_restore_worker(self):
        fl = control.DeviceFleet(
            devices=DEVS[:4],
            workers={"a": DEVS[:2], "b": DEVS[2:4]})
        lost = fl.lose_worker("b")
        assert len(lost) == 2 and fl.free == 2 and fl.lost == 2
        assert fl.acquire(3, "j") is None     # gang can't span the dead
        assert fl.is_lost(DEVS[2])
        fl.restore_worker("b")
        assert fl.free == 4 and fl.lost == 0


# ======================================================================
# scheduler core
# ======================================================================
class TestScheduler:
    def test_train_job_completes_and_releases_devices(self):
        holder = {}

        def run(ctx):
            net = small_net()
            holder["net"] = net
            net.fit(data_iter(), epochs=2,
                    fault_tolerance=ctx.fault_tolerance)
            return float(net._score)

        with make_sched() as s:
            job = s.submit(control.TrainJob(run, name="ok", chips=1))
            s.wait(job.job_id, timeout=120)
            assert job.state == "completed", job.status()
            assert holder["net"].getIterationCount() == 12
            assert job.devices == [] and s.devices.free == 4
            assert job.result == pytest.approx(
                float(holder["net"]._score))
        kinds = [e["kind"] for e in flight_recorder.get_default().events()]
        assert "job_submit" in kinds and "job_finished" in kinds

    def test_retry_budget_with_backoff_then_success(self):
        attempts = []

        def run(ctx):
            attempts.append(ctx.attempt)
            if ctx.attempt == 1:
                raise RuntimeError("flaky infra")
            net = small_net()
            net.fit(data_iter(), epochs=1,
                    fault_tolerance=ctx.fault_tolerance)

        with make_sched() as s:
            job = s.submit(control.TrainJob(
                run, chips=1, max_retries=2, backoff_s=0.05))
            s.wait(job.job_id, timeout=120)
            assert job.state == "completed"
            assert attempts == [1, 2]
            assert job.retries_used == 1

    def test_retry_budget_exhausted_fails(self):
        def run(ctx):
            raise RuntimeError("always broken")

        with make_sched() as s:
            job = s.submit(control.TrainJob(
                run, chips=1, max_retries=1, backoff_s=0.01))
            s.wait(job.job_id, timeout=60)
            assert job.state == "failed"
            assert "retry budget exhausted" in job.error
            assert s.devices.free == 4

    def test_cancel_pending_job(self):
        ev = threading.Event()

        def hog(ctx):
            ev.wait(20)

        def never(ctx):            # pragma: no cover - must not run
            raise AssertionError("cancelled job ran")

        with make_sched() as s:
            h = s.submit(control.TrainJob(hog, chips=4))
            s.wait(h.job_id, timeout=30, states=("running",))
            j = s.submit(control.TrainJob(never, chips=4))
            time.sleep(0.1)
            assert j.state == "pending"
            s.cancel(j.job_id)
            assert j.state == "cancelled"
            ev.set()
            s.wait(h.job_id, timeout=30)

    def test_gang_scheduling_two_jobs_share_fleet(self):
        """A 2-chip job and a 1-chip job run concurrently on disjoint
        device grants."""
        grants = {}
        ev = threading.Event()

        def run(name):
            def _r(ctx):
                grants[name] = list(ctx.devices)
                ev.wait(30)
            return _r

        with make_sched() as s:
            a = s.submit(control.TrainJob(run("a"), chips=2))
            b = s.submit(control.TrainJob(run("b"), chips=1))
            s.wait(a.job_id, timeout=30, states=("running",))
            s.wait(b.job_id, timeout=30, states=("running",))
            # "running" is set before run_fn executes: wait, bounded,
            # for both runner threads to record their grants
            deadline = time.time() + 30
            while len(grants) < 2 and time.time() < deadline:
                time.sleep(0.01)
            assert len(grants["a"]) == 2 and len(grants["b"]) == 1
            assert not set(grants["a"]) & set(grants["b"])
            ev.set()
            s.wait(a.job_id, timeout=30)
            s.wait(b.job_id, timeout=30)


# ======================================================================
# the migration drill (kill a worker mid-fit)
# ======================================================================
class TestMigration:
    def test_worker_kill_migrates_and_finishes_bit_identical(
            self, tmp_path):
        """SIGKILL-equivalent worker death mid-fit: the job recovers
        its newest periodic bundle, reschedules onto the surviving
        worker, finishes at the exact total step count, and the final
        loss is BIT-identical to an uninterrupted run (PR 4's resume
        guarantee, now driven by the scheduler)."""
        nets = []

        def run(ctx):
            net = small_net(seed=3)
            nets.append(net)
            it = (SlowIter(X, Y, 8, shuffle=True, seed=5)
                  if ctx.attempt == 1 else data_iter())
            net.fit(it, epochs=3,
                    fault_tolerance=ctx.fault_tolerance)
            return float(net._score)

        with make_sched() as s:
            job = s.submit(control.TrainJob(
                run, chips=1, checkpoint_dir=str(tmp_path / "ck"),
                checkpoint_every=3, backoff_s=0.05))
            s.wait(job.job_id, timeout=60, states=("running",))
            deadline = time.time() + 60
            while (not nets or nets[0].getIterationCount() < 5) \
                    and time.time() < deadline:
                assert job.state not in control.TERMINAL, job.status()
                time.sleep(0.01)
            worker = ("w0" if job.devices[0] in DEVS[:2] else "w1")
            s.kill_worker(worker)
            s.wait(job.job_id, timeout=120)
            assert job.state == "completed", job.status()
            assert job.retries_used == 1 and job.attempts == 2
            # rescheduled on the SURVIVING worker's devices
            survivors = DEVS[2:4] if worker == "w0" else DEVS[:2]
            # exact total step count
            assert nets[-1].getIterationCount() == 18
        # bit-identical to an uninterrupted run (same seed/data)
        ref = small_net(seed=3)
        ref.fit(data_iter(), epochs=3)
        assert float(ref._score) == job.result
        # the death is an incident dump; resume + migration visible
        kinds = [e["kind"]
                 for e in flight_recorder.get_default().events()]
        assert "job_worker_lost" in kinds or any(
            i["reason"] == "job_worker_lost"
            for i in flight_recorder.get_default().incidents)
        assert "auto_resume" in kinds

    def test_stall_verdict_preempts_and_migrates(self, tmp_path):
        """Chaos hang injector: a step stalls past the watchdog
        deadline; the scheduler's stall verdict preempts (checkpoint at
        the next boundary — the post-hang step) and reschedules; the
        job still finishes at the exact step count."""
        nets = []

        def run(ctx):
            net = small_net(seed=4)
            nets.append(net)
            net.fit(data_iter(), epochs=2,
                    fault_tolerance=ctx.fault_tolerance)
            return float(net._score)

        cfg = chaos.ChaosConfig(hang_step=3, hang_seconds=1.0)
        with chaos.installed(cfg):
            with make_sched() as s:
                job = s.submit(control.TrainJob(
                    run, chips=1,
                    checkpoint_dir=str(tmp_path / "ck"),
                    checkpoint_every=100,   # preemption bundle only
                    step_deadline=0.25, stall_grace_s=30.0,
                    backoff_s=0.05))
                s.wait(job.job_id, timeout=120)
                assert job.state == "completed", job.status()
                assert job.attempts == 2
                assert job.migrations >= 1
                assert job.retries_used == 0   # scheduler's fault, free
                assert nets[-1].getIterationCount() == 12
        kinds = [e["kind"]
                 for e in flight_recorder.get_default().events()]
        assert "job_stalled" in kinds
        ref = small_net(seed=4)
        ref.fit(data_iter(), epochs=2)
        assert float(ref._score) == job.result

    def test_divergence_abort_is_terminal(self, tmp_path):
        """DivergenceError = the guard already spent its budget: the
        scheduler fails the job instead of retry-looping a run that
        will re-diverge."""
        def run(ctx):
            net = small_net()
            bad = np.full_like(X, np.nan)
            ft = ctx.fault_tolerance
            ft.divergence_window = 4
            ft.max_rollbacks = 0
            net.fit(ArrayDataSetIterator(bad, Y, 8), epochs=1,
                    fault_tolerance=ft)

        with make_sched() as s:
            job = s.submit(control.TrainJob(
                run, chips=1, checkpoint_dir=str(tmp_path / "ck"),
                max_retries=3, backoff_s=0.01))
            s.wait(job.job_id, timeout=120)
            assert job.state == "failed"
            assert "divergence" in job.error
            assert job.retries_used == 0


# ======================================================================
# serving jobs
# ======================================================================
class TestServeJob:
    @pytest.mark.slow
    def test_serve_job_serves_drains_and_hands_back_capacity(
            self, gpt):
        model, params = gpt

        def build(ctx):
            return ServingFleet(model, params, devices=ctx.devices,
                                slots=2, page_size=8,
                                prefill_buckets=[8, 16, 40],
                                max_chunk=4)

        rng = np.random.default_rng(7)
        with make_sched(devices=DEVS[:2],
                        workers={"w0": DEVS[:2]}) as s:
            job = s.submit(control.ServeJob(build, replicas=1))
            s.wait(job.job_id, timeout=120, states=("running",))
            deadline = time.time() + 60
            while job.fleet is None and time.time() < deadline:
                time.sleep(0.02)
            assert job.fleet is not None
            prompt = rng.integers(0, VOCAB, (6,)).astype(np.int32)
            out = job.generate(prompt, 5, timeout=60)
            assert out.shape == (5,)
            assert s.devices.free == 1     # 1 of 2 chips in use
            s.drain(job.job_id)
            s.wait(job.job_id, timeout=60)
            assert job.state == "drained"
            assert s.devices.free == 2     # capacity handed back

    @pytest.mark.slow
    def test_rebalance_drains_idle_replica_for_starved_train(
            self, gpt):
        """Train-vs-serve rebalancing: a train job starving for a chip
        claims a replica from an idle serving fleet — the drain hands
        the chip back through the capacity listener and the train job
        runs."""
        model, params = gpt

        def build(ctx):
            return ServingFleet(model, params, devices=ctx.devices,
                                slots=2, page_size=8,
                                prefill_buckets=[8, 16, 40],
                                max_chunk=4)

        ran = threading.Event()

        def run(ctx):
            ran.set()

        with make_sched(devices=DEVS[:2], workers={"w0": DEVS[:2]},
                        rebalance=True,
                        rebalance_after_s=0.3) as s:
            serve = s.submit(control.ServeJob(build, replicas=2))
            s.wait(serve.job_id, timeout=120, states=("running",))
            deadline = time.time() + 60
            while serve.fleet is None and time.time() < deadline:
                time.sleep(0.02)
            assert s.devices.free == 0
            train = s.submit(control.TrainJob(run, chips=1))
            s.wait(train.job_id, timeout=120)
            assert train.state == "completed"
            assert ran.is_set()
            assert serve.fleet.alive_replicas() == 1
            kinds = [e["kind"] for e in
                     flight_recorder.get_default().events()]
            assert "job_rebalance" in kinds
            s.cancel(serve.job_id)
            s.wait(serve.job_id, timeout=60)

    @pytest.mark.slow
    def test_replica_death_on_healthy_chip_restarts(self, gpt):
        model, params = gpt

        def build(ctx):
            return ServingFleet(model, params, replicas=2, slots=2,
                                page_size=8,
                                prefill_buckets=[8, 16, 40],
                                max_chunk=4)

        rng = np.random.default_rng(8)
        with make_sched(devices=DEVS[:2],
                        workers={"w0": DEVS[:2]}) as s:
            job = s.submit(control.ServeJob(build, replicas=2))
            s.wait(job.job_id, timeout=120, states=("running",))
            deadline = time.time() + 60
            while job.fleet is None and time.time() < deadline:
                time.sleep(0.02)
            fleet = job.fleet
            fleet.kill_replica(1)
            deadline = time.time() + 60
            while fleet.alive_replicas() < 2 \
                    and time.time() < deadline:
                time.sleep(0.05)
            assert fleet.alive_replicas() == 2   # scheduler restarted
            prompt = rng.integers(0, VOCAB, (6,)).astype(np.int32)
            assert job.generate(prompt, 4, timeout=60).shape == (4,)
            s.cancel(job.job_id)
            s.wait(job.job_id, timeout=60)


# ======================================================================
# satellites: cancel / abort
# ======================================================================
class TestCancel:
    def test_engine_cancel_mid_decode_frees_slot_and_pages(self, gpt):
        model, params = gpt
        rng = np.random.default_rng(9)
        with _engine(model, params) as eng:
            prompt = rng.integers(0, VOCAB, (6,)).astype(np.int32)
            req = eng.submit(prompt, 48)
            it = req.stream()
            got = [next(it), next(it)]       # decoding is live
            assert req.cancel()
            rest = list(it)                  # stream ends cleanly
            assert req.done
            assert req.finish_reason == "cancelled"
            assert req._error is None
            toks = req.result(10)            # partial tokens, no raise
            assert 2 <= len(toks) < 48
            assert list(toks[:2]) == got
            deadline = time.time() + 10
            while eng.pool.allocated and time.time() < deadline:
                time.sleep(0.01)
            assert eng.pool.allocated == 0   # pages drained to rc0
            assert not req.cancel()          # already done

    def test_engine_cancel_queued_request_never_runs(self, gpt):
        model, params = gpt
        rng = np.random.default_rng(10)
        with _engine(model, params, slots=1, max_queue=8) as eng:
            blocker = eng.submit(
                rng.integers(0, VOCAB, (6,)).astype(np.int32), 40)
            queued = eng.submit(
                rng.integers(0, VOCAB, (6,)).astype(np.int32), 8)
            assert queued.cancel()
            queued._done.wait(10)
            assert queued.finish_reason == "cancelled"
            assert queued.tokens == []
            blocker.result(60)               # unaffected neighbor
            assert len(blocker.tokens) == 40

    def test_cancel_closes_trace_with_reason(self, gpt):
        from deeplearning4j_tpu.profiler import tracing

        model, params = gpt
        rng = np.random.default_rng(11)
        prev = tracing.enabled()
        tracing.set_enabled(True)
        try:
            with _engine(model, params) as eng:
                req = eng.submit(
                    rng.integers(0, VOCAB, (6,)).astype(np.int32), 48)
                next(req.stream())
                req.cancel()
                req._done.wait(10)
                tl = tracing.timeline(str(req.request_id))
                assert tl is not None
                assert tl["finish_reason"] == "cancelled"
                fin = [e for e in tl["events"]
                       if e["name"] == "finish"]
                assert fin and fin[0]["reason"] == "cancelled"
        finally:
            tracing.set_enabled(prev)

    @pytest.mark.slow
    def test_fleet_request_cancel_and_cancel_pending(self, gpt):
        model, params = gpt
        rng = np.random.default_rng(12)
        fl = ServingFleet(model, params, replicas=1, slots=2,
                          page_size=8, prefill_buckets=[8, 16, 40],
                          max_chunk=4)
        fl.start()
        try:
            prompt = rng.integers(0, VOCAB, (6,)).astype(np.int32)
            freq = fl.submit(prompt, 48)
            it = freq.stream()
            next(it)
            assert freq.cancel()
            list(it)
            assert freq.finish_reason == "cancelled"
            assert freq._error is None
            # cancel_pending sweeps whatever is live
            more = [fl.submit(
                rng.integers(0, VOCAB, (5,)).astype(np.int32), 30)
                for _ in range(3)]
            n = fl.cancel_pending()
            assert n >= 1
            for m in more:
                m._done.wait(30)
                assert m.done
            eng = fl._replicas[0].engine
            deadline = time.time() + 10
            while eng.pool.allocated and time.time() < deadline:
                time.sleep(0.01)
            assert eng.pool.allocated == 0
        finally:
            fl.shutdown()


# ======================================================================
# satellites: chaos hang injector + idempotent HTTP generate
# ======================================================================
class TestChaosHang:
    def test_hang_replica_stalls_then_recovers(self, gpt):
        model, params = gpt
        rng = np.random.default_rng(13)
        with _engine(model, params) as eng:
            eng.generate(
                rng.integers(0, VOCAB, (6,)).astype(np.int32), 2,
                timeout=60)
            chaos.hang_replica(eng, seconds=0.4)
            t0 = time.perf_counter()
            out = eng.generate(
                rng.integers(0, VOCAB, (6,)).astype(np.int32), 3,
                timeout=60)
            assert out.shape == (3,)
            assert time.perf_counter() - t0 >= 0.35
        kinds = [e["kind"]
                 for e in flight_recorder.get_default().events()]
        assert "chaos_hang" in kinds

    def test_compile_grace_extends_first_step_only(self):
        """The first step of every attempt pays the jit compile; the
        scheduler's stall verdict must not read it as a stall. The
        grace applies to step 0 of a run and nothing else — warm steps
        keep the tight deadline."""
        from deeplearning4j_tpu.util.resilience import FaultTolerance

        ft = FaultTolerance(step_deadline=0.25, compile_grace_s=120.0)
        assert ft._watchdog(step=0).deadline == 120.25
        assert ft._watchdog(step=1).deadline == 0.25
        assert ft._watchdog(step=7).deadline == 0.25
        # default stays 0: standalone fits keep the historical
        # fire-on-compile behavior the tracing drills depend on
        bare = FaultTolerance(step_deadline=0.02)
        assert bare._watchdog(step=0).deadline == 0.02
        # TrainJob's auto-built policy arms the grace
        job = control.TrainJob(lambda ctx: None, step_deadline=0.25)
        assert job.fault_tolerance.compile_grace_s == 120.0

    def test_train_hang_env_knobs_parse(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_CHAOS", "1")
        monkeypatch.setenv("DL4J_TPU_CHAOS_HANG_STEP", "5")
        monkeypatch.setenv("DL4J_TPU_CHAOS_HANG_SECONDS", "0.5")
        monkeypatch.setenv("DL4J_TPU_CHAOS_KILL_AT", "9")
        cfg = chaos.ChaosConfig.from_env()
        assert cfg.hang_step == 5
        assert cfg.hang_seconds == 0.5
        assert cfg.kill_at_step == 9


class TestIdempotency:
    @pytest.mark.slow
    def test_replayed_post_returns_original_request(self, gpt):
        model, params = gpt
        rng = np.random.default_rng(14)
        with _engine(model, params) as eng:
            srv = JsonModelServer(engine=eng)
            payload = {
                "prompt_ids": rng.integers(
                    0, VOCAB, (6,)).astype(np.int32).tolist(),
                "max_new_tokens": 5,
                "idempotency_key": "k-123",
            }
            a = srv.generate(dict(payload))
            b = srv.generate(dict(payload))   # the replayed POST
            assert b["request_id"] == a["request_id"]
            assert b["tokens"] == a["tokens"]
            assert b.get("replayed") is True
            assert "replayed" not in a
            # a DIFFERENT key is a fresh request
            c = srv.generate(dict(payload, idempotency_key="k-456"))
            assert c["request_id"] != a["request_id"]

    @pytest.mark.slow
    def test_client_threads_key_through_http_retries(self, gpt):
        model, params = gpt
        rng = np.random.default_rng(15)
        with _engine(model, params) as eng:
            srv = JsonModelServer(engine=eng)
            port = srv.start()
            try:
                cli = JsonRemoteInference(
                    f"http://127.0.0.1:{port}", timeout=60)
                prompt = rng.integers(0, VOCAB, (6,)).astype(np.int32)
                out = cli.generate_full(prompt, 4)
                assert len(out["tokens"]) == 4
                # the client minted a key; a manual replay of the same
                # key joins the original request
                with srv._idem_lock:
                    key = next(reversed(srv._idem))
                body = json.dumps({
                    "prompt_ids": prompt.tolist(),
                    "max_new_tokens": 4,
                    "idempotency_key": key}).encode()
                r = urllib.request.urlopen(urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/serving/generate",
                    data=body,
                    headers={"Content-Type": "application/json"}),
                    timeout=60)
                replay = json.loads(r.read())
                assert replay["replayed"] is True
                assert replay["request_id"] == out["request_id"]
                assert replay["tokens"] == out["tokens"]
            finally:
                srv.stop()


# ======================================================================
# /v1/jobs HTTP surface + telemetry embedding
# ======================================================================
class TestJobsHTTP:
    def test_jobs_endpoints_on_ui_server(self):
        from deeplearning4j_tpu.ui.server import UIServer

        ev = threading.Event()

        def hold(ctx):
            ev.wait(30)

        with make_sched() as s:
            s.register_factory(
                "hold", lambda **kw: control.TrainJob(hold, **kw))
            ui = UIServer()
            port = ui.start(port=0)
            try:
                base = f"http://127.0.0.1:{port}"
                # submit through HTTP via the registered factory
                body = json.dumps({"factory": "hold",
                                   "params": {"chips": 1,
                                              "tenant": "t9"}}).encode()
                r = urllib.request.urlopen(urllib.request.Request(
                    base + "/v1/jobs", data=body,
                    headers={"Content-Type": "application/json"}),
                    timeout=10)
                sub = json.loads(r.read())
                jid = sub["job_id"]
                assert sub["tenant"] == "t9"
                listing = json.loads(urllib.request.urlopen(
                    base + "/v1/jobs", timeout=10).read())
                assert any(j["job_id"] == jid
                           for j in listing["jobs"])
                assert listing["devices"]["total"] == 4
                one = json.loads(urllib.request.urlopen(
                    base + f"/v1/jobs/{jid}", timeout=10).read())
                assert one["kind"] == "train"
                # cancel over HTTP
                r = urllib.request.urlopen(urllib.request.Request(
                    base + f"/v1/jobs/{jid}/cancel", data=b"{}",
                    headers={"Content-Type": "application/json"}),
                    timeout=10)
                ev.set()
                s.wait(jid, timeout=30)
                assert s.job(jid).state in ("cancelled", "completed")
            finally:
                ev.set()
                ui.stop()

    def test_jobs_http_404_without_scheduler(self):
        from deeplearning4j_tpu.ui.server import UIServer

        assert control.default_scheduler() is None
        ui = UIServer()
        port = ui.start(port=0)
        try:
            try:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/v1/jobs", timeout=10)
                assert False, "expected 404"
            except urllib.error.HTTPError as e:
                assert e.code == 404
        finally:
            ui.stop()

    def test_snapshot_embeds_jobs(self):
        def run(ctx):
            pass

        with make_sched() as s:
            job = s.submit(control.TrainJob(run, chips=1,
                                            tenant="acme"))
            s.wait(job.job_id, timeout=30)
            snap = telemetry.snapshot()
            assert "jobs" in snap
            rows = snap["jobs"]["jobs"]
            assert any(r["job_id"] == job.job_id
                       and r["tenant"] == "acme" for r in rows)
        assert control.default_scheduler() is None


# ======================================================================
# periodic checkpoints (resilience satellite the scheduler rides on)
# ======================================================================
class TestPeriodicCheckpoints:
    def test_periodic_bundles_written_and_pruned(self, tmp_path):
        from deeplearning4j_tpu.util import resilience

        net = small_net()
        ck = str(tmp_path / "ck")
        ft = FaultTolerance(checkpoint_dir=ck, auto_resume=False,
                            checkpoint_every=4, keep_last=2)
        before = telemetry.MetricsRegistry.get_default().counter(
            telemetry.FT_PERIODIC_CHECKPOINTS).total()
        net.fit(data_iter(), epochs=2, fault_tolerance=ft)
        after = telemetry.MetricsRegistry.get_default().counter(
            telemetry.FT_PERIODIC_CHECKPOINTS).total()
        assert after - before == 3      # 12 steps / every 4
        bundles = resilience._list_bundles(ck)
        assert len(bundles) == 2        # keep_last pruning
        path = resilience.latest_valid_bundle(ck)
        assert path is not None
        with open(f"{path}/resume.json") as f:
            meta = json.load(f)
        assert meta["periodic"] is True
        assert meta["iterator_state"] is not None

    def test_inject_fault_dies_without_checkpoint_then_resumes(
            self, tmp_path):
        from deeplearning4j_tpu.util import resilience

        ck = str(tmp_path / "ck")
        net = small_net(seed=6)
        ft = FaultTolerance(checkpoint_dir=ck, checkpoint_every=3)
        it = SlowIter(X, Y, 8, shuffle=True, seed=5, delay=0.02)

        def late_kill():
            while net.getIterationCount() < 5:
                time.sleep(0.005)
            ft.inject_fault(control.DeviceLostError("host gone"))

        killer = threading.Thread(target=late_kill, daemon=True)
        killer.start()
        with pytest.raises(control.DeviceLostError):
            net.fit(it, epochs=2, fault_tolerance=ft)
        killer.join(10)
        # no checkpoint at death: newest bundle is a periodic one at a
        # multiple of 3, strictly before the death step
        path = resilience.latest_valid_bundle(ck)
        assert path is not None
        with open(f"{path}/resume.json") as f:
            assert json.load(f)["periodic"] is True
        # resume on a FRESH model finishes bit-identical
        net2 = small_net(seed=6)
        net2.fit(data_iter(), epochs=2, auto_resume=ck,
                 fault_tolerance=FaultTolerance(checkpoint_dir=ck))
        ref = small_net(seed=6)
        ref.fit(data_iter(), epochs=2)
        assert net2.getIterationCount() == ref.getIterationCount()
        for a, b in zip(jax.tree_util.tree_leaves(net2.params_list),
                        jax.tree_util.tree_leaves(ref.params_list)):
            np.testing.assert_array_equal(np.asarray(a),
                                          np.asarray(b))


# ======================================================================
# phase 3: elasticity (manual scale through the fleet endpoint)
# ======================================================================
class TestElasticity:
    def test_fleet_endpoints_error_conventions(self):
        with make_sched() as s:
            obj, code = control.http_fleet_get("/v1/fleet")
            assert code == 200 and obj == {"fleets": []}
            obj, code = control.http_fleet_get("/v1/fleet/nope")
            assert code == 404
            obj, code = control.http_fleet_post("/v1/fleet/scale", {})
            assert code == 400 and "target" in obj["error"]
            obj, code = control.http_fleet_post("/v1/fleet/scale",
                                                {"target": 0})
            assert code == 400
            obj, code = control.http_fleet_post("/v1/fleet/scale",
                                                {"target": 2})
            assert code == 404          # no running serve job
            obj, code = control.http_fleet_post("/v1/fleet/other", {})
            assert code == 404

    @pytest.mark.slow
    def test_http_scale_grows_and_shrinks_fleet(self, gpt):
        """Operator scaling end to end: POST /v1/fleet/scale grows a
        live fleet onto a freshly acquired chip (replica registered,
        chip accounted), serves token-identically, then shrinks back
        — replica drained, chip returned to the pool. Manual scale is
        PINNED: the auto scale-down pass must not undo it."""
        model, params = gpt

        def build(ctx):
            return ServingFleet(model, params, devices=ctx.devices,
                                slots=2, page_size=8,
                                prefill_buckets=[8, 16, 40],
                                max_chunk=4)

        rng = np.random.default_rng(31)
        with make_sched(devices=DEVS[:2], workers={"w0": DEVS[:2]},
                        scale_down_hold_s=0.01) as s:
            job = s.submit(control.ServeJob(build, replicas=1))
            s.wait(job.job_id, timeout=120, states=("running",))
            deadline = time.time() + 60
            while job.fleet is None and time.time() < deadline:
                time.sleep(0.02)
            assert s.devices.free == 1
            obj, code = control.http_fleet_post(
                "/v1/fleet/scale", {"target": 2})
            assert code == 200, obj
            assert obj["replicas"] == 2 and obj["manual"] == 1
            assert s.devices.free == 0       # second chip in use
            assert job.fleet.alive_replicas() == 2
            prompt = rng.integers(0, VOCAB, (6,)).astype(np.int32)
            out = job.generate(prompt, 5, timeout=60)
            assert out.shape == (5,)
            # manual replicas survive the auto scale-down pass
            time.sleep(0.2)
            s._maybe_scale_down()
            assert job.fleet.alive_replicas() == 2
            # a third replica has no chip to land on: clean 400, no
            # half-built replica, no leaked pending_scale
            obj, code = control.http_fleet_post(
                "/v1/fleet/scale", {"target": 3})
            assert code == 400
            assert job.fleet.alive_replicas() == 2
            assert job.fleet.stats()["pending_scale"] == 0
            # shrink back: drain hands the chip to the pool
            obj, code = control.http_fleet_post(
                "/v1/fleet/scale", {"target": 1})
            assert code == 200, obj
            assert job.fleet.alive_replicas() == 1
            deadline = time.time() + 30
            while s.devices.free < 1 and time.time() < deadline:
                time.sleep(0.02)
            assert s.devices.free == 1
            kinds = [e["kind"] for e in
                     flight_recorder.get_default().events()]
            assert "job_scale_up" in kinds
            assert "job_scale_down" in kinds
            assert "fleet_replica_added" in kinds
            assert "fleet_replica_removed" in kinds
            s.cancel(job.job_id)
            s.wait(job.job_id, timeout=60)
