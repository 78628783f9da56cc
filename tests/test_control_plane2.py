"""Control plane phase 2 (control/worker.py + scheduler/resilience
additions): workers as supervised OS processes with heartbeat leases,
cluster preemption notices (deadline-aware checkpoint-and-drain,
degrade-to-periodic-bundle when the window is shorter than a step),
job priorities (checkpoint-preempt + park + bit-identical resume),
and the BundleStore abstraction (shared-filesystem cross-host
discovery, transient-I/O retry, the cross-host keep_last pruning
fix)."""

import hashlib
import json
import os
import tempfile
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import control
from deeplearning4j_tpu.datasets import ArrayDataSetIterator
from deeplearning4j_tpu.learning import Adam
from deeplearning4j_tpu.nn.conf import (
    DenseLayer, InputType, NeuralNetConfiguration, OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.profiler import chaos, flight_recorder, telemetry
from deeplearning4j_tpu.util import resilience
from deeplearning4j_tpu.util.resilience import (
    FaultTolerance, LocalBundleStore, NoticePoller, SharedFSBundleStore,
)

DEVS = jax.devices()


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
    def __init__(self, *a, delay=0.05, **kw):
        super().__init__(*a, **kw)
        self._delay = delay

    def next(self):
        time.sleep(self._delay)
        return super().next()


def slow_iter(delay=0.05):
    return SlowIter(X, Y, 8, shuffle=True, seed=5, delay=delay)


def make_sched(**kw):
    kw.setdefault("devices", DEVS[:4])
    kw.setdefault("workers", {"w0": DEVS[:2], "w1": DEVS[2:4]})
    kw.setdefault("rebalance", False)
    return control.JobScheduler(**kw)


def tree_leaves(net):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(
        (net.params_list, net.opt_states))]


@pytest.fixture
def metrics_on():
    prev = telemetry.enabled()
    telemetry.set_enabled(True)
    yield telemetry.MetricsRegistry.get_default()
    telemetry.set_enabled(prev)


def counter_total(name):
    return telemetry.MetricsRegistry.get_default().counter(name).total()


# ======================================================================
# bundle stores
# ======================================================================
class TestBundleStore:
    def test_local_store_roundtrip_and_retire(self, tmp_path):
        net = small_net()
        store = LocalBundleStore(tmp_path)
        path = store.write(net, {"rng": [0, 1], "epochs_remaining": 1})
        assert store.latest_valid() == path
        assert resilience.validate_bundle(path)
        disc = store.discover()
        assert len(disc) == 1 and disc[0]["valid"] \
            and disc[0]["complete"]
        store.retire()
        assert store.latest_valid() is None

    def test_shared_store_cross_host_discovery(self, tmp_path):
        """A bundle written by one host is discovered, digest-valid,
        by a DIFFERENT store instance over the same root — the
        survivor's view after the writer died with its local disk."""
        net = small_net()
        writer = SharedFSBundleStore(tmp_path, "job-7")
        path = writer.write(net, {"rng": [0], "epochs_remaining": 0})
        survivor = SharedFSBundleStore(tmp_path, "job-7")
        assert survivor.latest_valid() == path
        disc = survivor.discover()
        assert disc[0]["host"] == "p0"
        # a different namespace is a different job: no cross-talk
        other = SharedFSBundleStore(tmp_path, "job-8")
        assert other.latest_valid() is None

    def test_ft_bundle_store_knob(self, tmp_path):
        store = SharedFSBundleStore(tmp_path, "jobX")
        ft = FaultTolerance(bundle_store=store, divergence_window=0)
        assert ft.checkpoint_dir == store.directory
        assert ft.store() is store
        # checkpoint_dir alone keeps resolving to a local store
        ft2 = FaultTolerance(checkpoint_dir=str(tmp_path),
                             divergence_window=0)
        assert isinstance(ft2.store(), LocalBundleStore)
        assert FaultTolerance(divergence_window=0).store() is None

    def test_write_retries_transient_oserror(self, tmp_path,
                                             monkeypatch, metrics_on):
        """Transient OSError during write_bundle retries with backoff
        before surfacing — the shared-filesystem hiccup posture."""
        net = small_net()
        store = SharedFSBundleStore(tmp_path, "flaky", io_backoff=0.01)
        real = resilience.write_bundle
        fails = {"n": 0}

        def flaky(*a, **kw):
            if fails["n"] < 2:
                fails["n"] += 1
                raise OSError("NFS hiccup")
            return real(*a, **kw)

        monkeypatch.setattr(resilience, "write_bundle", flaky)
        before = counter_total(telemetry.FT_BUNDLE_IO_RETRIES)
        path = store.write(net, {"rng": [0], "epochs_remaining": 0})
        assert os.path.isdir(path) and fails["n"] == 2
        assert counter_total(telemetry.FT_BUNDLE_IO_RETRIES) \
            - before == 2

    def test_write_retry_budget_exhausts(self, tmp_path, monkeypatch):
        net = small_net()
        store = SharedFSBundleStore(tmp_path, "dead", io_retries=1,
                                    io_backoff=0.01)
        monkeypatch.setattr(
            resilience, "write_bundle",
            lambda *a, **kw: (_ for _ in ()).throw(OSError("gone")))
        with pytest.raises(OSError):
            store.write(net, {"rng": [0], "epochs_remaining": 0})

    def test_validate_retries_io_before_falling_back(
            self, tmp_path, monkeypatch):
        """A transient read error must not condemn a good bundle."""
        net = small_net()
        store = SharedFSBundleStore(tmp_path, "j", io_backoff=0.01)
        path = store.write(net, {"rng": [0], "epochs_remaining": 0})
        real = resilience._sha256
        fails = {"n": 0}

        def flaky(p):
            if fails["n"] < 1:
                fails["n"] += 1
                raise OSError("stale NFS handle")
            return real(p)

        monkeypatch.setattr(resilience, "_sha256", flaky)
        assert store.latest_valid() == path
        assert fails["n"] == 1


class TestObjectStoreBundle:
    """ObjectStoreBundleStore: rename-less commit protocol over
    S3/GCS-style put/get/list/delete — uncommitted prefixes invisible,
    torn uploads caught by digest, retries counted, remote retire
    authoritative."""

    def _store(self, client, ns="job-1", **kw):
        kw.setdefault("cache_dir", tempfile.mkdtemp(
            prefix="dl4j_ostore_test."))
        kw.setdefault("io_backoff", 0.005)
        return resilience.ObjectStoreBundleStore(client, ns, **kw)

    def test_roundtrip_cross_host_and_uncommitted_invisible(self):
        net = small_net()
        client = resilience.InMemoryObjectStore()
        writer = self._store(client)
        path = writer.write(net, {"rng": [0, 1],
                                  "epochs_remaining": 1})
        assert writer.latest_valid() == path
        assert resilience.validate_bundle(path)
        # a SECOND store with a FRESH cache over the same client is
        # the survivor after the writer host died: it materializes
        # the committed bundle locally, digest-valid
        survivor = self._store(client)
        p2 = survivor.latest_valid()
        assert p2 is not None and p2 != path
        assert p2.startswith(survivor.directory)
        assert resilience.validate_bundle(p2)
        disc = survivor.discover()
        assert disc[0]["valid"] and disc[0]["complete"]
        assert disc[0]["host"] == "p0"
        # an UNCOMMITTED member prefix (crashed mid-upload) is
        # invisible: readers only enumerate the commit namespace
        client.put("job-1/bundles/bundle-0000000099/tok/model.zip",
                   b"half a bl")
        assert [it for it, _, _ in survivor._commits()] == [0]
        # namespace isolation
        assert self._store(client, ns="job-2").latest_valid() is None

    def test_torn_upload_never_visible(self):
        """A blob torn AFTER commit (the bytes under the key are
        truncated — the store_torn chaos shape) fails digest
        verification at read; discovery falls back to the previous
        committed bundle instead of restoring garbage."""
        net = small_net()
        client = resilience.InMemoryObjectStore()
        writer = self._store(client)
        good = writer.write(net, {"rng": [0], "epochs_remaining": 0})
        net._iteration = 1
        writer.write(net, {"rng": [1], "epochs_remaining": 0})
        it, name, mf = writer._commits()[0]
        assert it == 1
        key = writer._key("bundles", name, mf["prefix"], "model.zip")
        client.put(key, client.get(key)[: 100])    # tear it
        reader = self._store(client)
        got = reader.latest_valid()
        assert got is not None
        assert os.path.basename(got) == os.path.basename(good)

    def test_chaos_store_every_op_retries(self, monkeypatch,
                                          metrics_on):
        """DL4J_TPU_CHAOS_STORE_ERROR_RATE=1: the first attempt of
        every (op, key) fails, the retry succeeds — a full write +
        restore round-trip completes with every bundle op retried at
        least once, all counted in ft_bundle_io_retries_total."""
        monkeypatch.setenv("DL4J_TPU_CHAOS_STORE_ERROR_RATE", "1")
        net = small_net()
        store = self._store(resilience.InMemoryObjectStore(),
                            ns="chaotic")
        assert isinstance(store.client, chaos.FaultyObjectStore)
        before = counter_total(telemetry.FT_BUNDLE_IO_RETRIES)
        path = store.write(net, {"rng": [0], "epochs_remaining": 0})
        assert store.latest_valid() == path
        assert store.client.injected >= 3   # puts + commit + reads
        assert counter_total(telemetry.FT_BUNDLE_IO_RETRIES) \
            - before >= 3
        inj = counter_total(telemetry.CHAOS_INJECTED)
        assert inj >= 3

    def test_chaos_torn_puts_retry_to_whole_blobs(self, monkeypatch):
        """DL4J_TPU_CHAOS_STORE_TORN_RATE=1: every first put uploads
        half the payload and errors; the retried put overwrites whole
        (last-write-wins) — a fresh reader restores digest-valid."""
        monkeypatch.setenv("DL4J_TPU_CHAOS_STORE_TORN_RATE", "1")
        net = small_net()
        client = resilience.InMemoryObjectStore()
        store = self._store(client, ns="torn")
        store.write(net, {"rng": [0], "epochs_remaining": 0})
        assert store.client.injected >= 3
        monkeypatch.delenv("DL4J_TPU_CHAOS_STORE_TORN_RATE")
        reader = self._store(client, ns="torn")
        assert reader.latest_valid() is not None

    def test_retire_is_cluster_authoritative(self):
        """After retire(), NO reader may resume — not even one whose
        local cache still holds a stale materialized copy: a
        reachable store with zero commits is authoritative."""
        net = small_net()
        client = resilience.InMemoryObjectStore()
        writer = self._store(client)
        writer.write(net, {"rng": [0], "epochs_remaining": 0})
        reader = self._store(client)
        assert reader.latest_valid() is not None   # cache warmed
        writer.retire()
        assert writer._commits() == []
        assert reader.latest_valid() is None       # stale cache loses

    def test_ft_accepts_object_store_and_prunes_remote(self,
                                                       tmp_path):
        """The FaultTolerance bundle_store= knob takes the object
        store (cache dir anchors checkpoint_dir), and LocalObjectStore
        gives two 'hosts' a shared bucket with keep_last enforced
        remotely — commit first to delete, blobs swept after."""
        store = self._store(
            resilience.LocalObjectStore(tmp_path / "bucket"))
        ft = FaultTolerance(bundle_store=store, divergence_window=0)
        assert ft.checkpoint_dir == store.directory
        assert ft.store() is store
        net = small_net()
        for i in range(3):
            net._iteration = i
            store.write(net, {"rng": [0], "epochs_remaining": 0},
                        keep_last=2)
        assert [it for it, _, _ in store._commits()] == [2, 1]
        # pruned bundles' blobs are gone from the bucket too
        stale = [k for k in store.client.list("job-1/bundles/")
                 if "/bundle-0000000000/" in k]
        assert stale == []


def _fake_bundle(directory, iteration, expected_shards=None,
                 missing_shard=None):
    """Craft a minimal digest-valid bundle dir for pruning tests."""
    path = os.path.join(directory, f"bundle-{iteration:010d}")
    os.makedirs(path)
    with open(os.path.join(path, "resume.json"), "w") as f:
        f.write("{}")
    digest = hashlib.sha256(b"{}").hexdigest()
    manifest = {"format": resilience._RESUME_FORMAT,
                "iteration": iteration, "host": "p0",
                "digests": {"resume.json": digest}}
    if expected_shards:
        manifest["expected_shards"] = list(expected_shards)
        for m in expected_shards:
            if m == missing_shard:
                continue
            with open(os.path.join(path, m), "wb") as f:
                f.write(b"x")
            with open(os.path.join(path, m + ".sha256"), "w") as f:
                f.write(hashlib.sha256(b"x").hexdigest())
    with open(os.path.join(path, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    return path


class TestPruningRace:
    def test_only_process_zero_prunes(self, tmp_path):
        for i in range(4):
            _fake_bundle(tmp_path, i)
        resilience._prune_bundles(str(tmp_path), 1, process_index=1)
        assert len(resilience._list_bundles(str(tmp_path))) == 4
        resilience._prune_bundles(str(tmp_path), 1, process_index=0)
        left = resilience._list_bundles(str(tmp_path))
        assert [it for it, _ in left] == [3]

    def test_incomplete_newer_bundle_survives_prune(self, tmp_path):
        """The race fix: a slower host's still-being-published bundle
        (expected shard missing) is NEVER pruned out from under it,
        while torn bundles older than the cutoff do go."""
        shards = ["zero_shards_p0.npz", "zero_shards_p1.npz"]
        _fake_bundle(tmp_path, 1, shards,
                     missing_shard="zero_shards_p1.npz")  # old torn
        _fake_bundle(tmp_path, 2, shards)                 # complete
        _fake_bundle(tmp_path, 3, shards)                 # complete
        slow = _fake_bundle(tmp_path, 4, shards,
                            missing_shard="zero_shards_p1.npz")
        resilience._prune_bundles(str(tmp_path), 1, process_index=0)
        left = {it for it, _ in
                resilience._list_bundles(str(tmp_path))}
        # keep_last=1 complete -> bundle 3; the newer incomplete 4
        # survives (slow host still writing); 1 and 2 go
        assert left == {3, 4}
        assert os.path.isdir(slow)
        # the slow host finishes publishing: bundle 4 becomes complete
        # and the next prune retires 3
        with open(os.path.join(slow, "zero_shards_p1.npz"),
                  "wb") as f:
            f.write(b"x")
        with open(os.path.join(slow, "zero_shards_p1.npz.sha256"),
                  "w") as f:
            f.write(hashlib.sha256(b"x").hexdigest())
        resilience._prune_bundles(str(tmp_path), 1, process_index=0)
        assert {it for it, _ in
                resilience._list_bundles(str(tmp_path))} == {4}

    def test_validate_checks_foreign_shard_sidecars(self, tmp_path):
        shards = ["zero_shards_p0.npz", "zero_shards_p1.npz"]
        path = _fake_bundle(tmp_path, 5, shards)
        # p0's shard digest rides the manifest in real bundles; here
        # both ride sidecars — tamper with p1's payload
        assert resilience.validate_bundle(path)
        with open(os.path.join(path, "zero_shards_p1.npz"),
                  "wb") as f:
            f.write(b"CORRUPT")
        assert not resilience.validate_bundle(path)


# ======================================================================
# preemption notices (FaultTolerance level)
# ======================================================================
class TestPreemptionNotice:
    def test_earliest_deadline_wins(self):
        ft = FaultTolerance(divergence_window=0)
        ft.request_preemption(deadline_s=60, kind="http")
        ft.request_preemption(deadline_s=5, kind="metadata")
        ft.request_preemption(deadline_s=300, kind="api")
        assert ft.notice.kind == "metadata"
        assert ft.notice.remaining() <= 5

    def test_notice_checkpoint_clears_and_counts(self, tmp_path):
        net = small_net()
        ft = FaultTolerance(checkpoint_dir=str(tmp_path),
                            divergence_window=0)
        ft.request_preemption(deadline_s=30, kind="notice")
        net.fit(data_iter(), epochs=2, fault_tolerance=ft)
        # checkpointed at the FIRST boundary and exited
        assert net.getIterationCount() == 1
        assert ft.preemptions_checkpointed == 1
        assert ft.notice is None and not ft.preemption_requested
        assert ft.store().latest_valid() is not None
        events = [e for e in flight_recorder.get_default().events()
                  if e["kind"] == "preemption_notice"]
        assert events and events[-1]["notice_kind"] == "notice"

    def test_notice_poller_file_stub(self, tmp_path):
        ft = FaultTolerance(divergence_window=0)
        notice = tmp_path / "maintenance.json"
        poller = NoticePoller(ft, file=str(notice), poll_s=0.02)
        poller.start()
        try:
            time.sleep(0.1)
            assert not ft.preemption_requested
            notice.write_text(json.dumps({"deadline_s": 7}))
            deadline = time.time() + 5
            while not ft.preemption_requested \
                    and time.time() < deadline:
                time.sleep(0.02)
            assert ft.preemption_requested
            assert ft.notice.kind == "metadata"
            assert 6 < ft.notice.remaining() <= 7
            assert poller.delivered
        finally:
            poller.stop()

    def test_notice_poller_from_env(self, tmp_path):
        ft = FaultTolerance(divergence_window=0)
        assert NoticePoller.from_env(ft, env={}) is None
        p = NoticePoller.from_env(ft, env={
            "DL4J_TPU_PREEMPT_NOTICE_FILE": str(tmp_path / "n"),
            "DL4J_TPU_PREEMPT_DEADLINE_S": "12"})
        assert p is not None and p.default_deadline_s == 12
        # empty-body file: default deadline applies
        (tmp_path / "n").write_text("")
        assert p.check_once() and ft.notice.deadline_s == 12

    def test_chaos_notice_injector(self, tmp_path, metrics_on):
        """DL4J_TPU_CHAOS_PREEMPT_AT=<step>,<deadline> delivers a fake
        maintenance event (no SIGTERM): the fit checkpoints at the
        next boundary and drains."""
        net = small_net()
        ft = FaultTolerance(checkpoint_dir=str(tmp_path),
                            divergence_window=0)
        before = counter_total(telemetry.CHAOS_INJECTED)
        with chaos.installed(chaos.ChaosConfig(preempt_at_step=3,
                                               preempt_deadline_s=30)):
            net.fit(data_iter(), epochs=2, fault_tolerance=ft)
        assert net.getIterationCount() == 3
        assert ft.preemptions_checkpointed == 1
        assert counter_total(telemetry.CHAOS_INJECTED) - before == 1
        events = [e for e in flight_recorder.get_default().events()
                  if e["kind"] == "chaos_injected"
                  and e.get("fault") == "preempt_notice"]
        assert events and events[-1]["deadline_s"] == 30
        # resume finishes the run exactly
        net2 = small_net()
        net2.fit(data_iter(), epochs=2, fault_tolerance=FaultTolerance(
            checkpoint_dir=str(tmp_path), divergence_window=0))
        assert net2.getIterationCount() == 12

    def test_chaos_preempt_worker_on_ft(self):
        ft = FaultTolerance(divergence_window=0)
        chaos.preempt_worker(ft, deadline_s=9)
        assert ft.preemption_requested \
            and ft.notice.kind == "chaos_notice"


# ======================================================================
# job priorities: preempt, park, resume
# ======================================================================
class TestPriority:
    def test_priority_preempts_parks_and_resumes_bit_identical(
            self, tmp_path, metrics_on):
        """The satellite lifecycle: a low-priority job is checkpoint-
        preempted when a high-priority gang can't fit, parks in
        ``preempted``, and resumes BIT-IDENTICALLY (Adam moments
        included) when capacity frees."""
        nets = []
        high_done = threading.Event()

        def run_low(ctx):
            net = small_net(seed=3)
            nets.append(net)
            net.fit(slow_iter(0.05), epochs=3,
                    fault_tolerance=ctx.fault_tolerance)
            return float(net._score)

        def run_high(ctx):
            high_done.wait(30)

        before = counter_total(telemetry.JOBS_PREEMPTIONS)
        with make_sched() as s:
            low = s.submit(control.TrainJob(
                run_low, chips=4, checkpoint_dir=str(tmp_path),
                checkpoint_every=None))
            s.wait(low.job_id, timeout=120, states=("running",))
            while not nets or nets[0].getIterationCount() < 3:
                time.sleep(0.02)
            high = s.submit(control.TrainJob(run_high, chips=4,
                                             priority=5))
            # low parks; high takes the full gang
            s.wait(low.job_id, timeout=60, states=("preempted",))
            s.wait(high.job_id, timeout=60, states=("running",))
            assert low.devices == [] and s.devices.free == 0
            assert counter_total(telemetry.JOBS_PREEMPTIONS) \
                - before >= 1
            high_done.set()
            s.wait(high.job_id, timeout=60)
            # capacity freed: low resumes and finishes exactly
            s.wait(low.job_id, timeout=120)
            assert low.state == "completed", low.status()
            assert low.migrations == 0 and low.retries_used == 0
        assert len(nets) == 2
        assert nets[-1].getIterationCount() == 18   # 3 epochs x 6
        kinds = [e["kind"] for e in
                 flight_recorder.get_default().events()]
        assert "job_preempt" in kinds and "job_parked" in kinds \
            and "job_resumed" in kinds
        # bit-identical to an uninterrupted run: params AND moments
        ref = small_net(seed=3)
        ref.fit(data_iter(), epochs=3)
        for a, b in zip(tree_leaves(ref), tree_leaves(nets[-1])):
            assert np.array_equal(a, b)

    def test_default_priorities_keep_fifo_no_preemption(
            self, metrics_on):
        ev = threading.Event()

        def hold(ctx):
            ev.wait(30)

        def quick(ctx):
            pass

        before = counter_total(telemetry.JOBS_PREEMPTIONS)
        with make_sched() as s:
            a = s.submit(control.TrainJob(hold, chips=4))
            s.wait(a.job_id, timeout=30, states=("running",))
            b = s.submit(control.TrainJob(quick, chips=4))
            time.sleep(0.4)
            # same priority: b waits, a is NOT preempted
            assert a.state == "running" and b.state == "pending"
            ev.set()
            s.wait(a.job_id, timeout=30)
            s.wait(b.job_id, timeout=30)
        assert counter_total(telemetry.JOBS_PREEMPTIONS) == before

    def test_cancel_parked_job(self, tmp_path):
        def run_low(ctx):
            net = small_net()
            net.fit(slow_iter(0.05), epochs=5,
                    fault_tolerance=ctx.fault_tolerance)

        ev = threading.Event()

        def hold(ctx):
            ev.wait(30)

        with make_sched() as s:
            low = s.submit(control.TrainJob(
                run_low, chips=4, checkpoint_dir=str(tmp_path)))
            s.wait(low.job_id, timeout=60, states=("running",))
            time.sleep(0.3)
            s.submit(control.TrainJob(hold, chips=4, priority=2))
            s.wait(low.job_id, timeout=60, states=("preempted",))
            s.cancel(low.job_id)
            assert low.state == "cancelled"
            ev.set()


# ======================================================================
# worker preemption notices (scheduler level)
# ======================================================================
class TestWorkerPreempt:
    def test_notice_drains_migrates_and_counts(self, tmp_path,
                                               metrics_on):
        attempt_devices = []
        nets = []

        def run(ctx):
            attempt_devices.append(list(ctx.devices))
            net = small_net(seed=4)
            nets.append(net)
            net.fit(slow_iter(0.05), epochs=2,
                    fault_tolerance=ctx.fault_tolerance)

        before = counter_total(telemetry.JOBS_PREEMPTIONS)
        with make_sched() as s:
            job = s.submit(control.TrainJob(
                run, chips=2, checkpoint_dir=str(tmp_path),
                checkpoint_every=None))
            s.wait(job.job_id, timeout=120, states=("running",))
            while not nets or nets[0].getIterationCount() < 2:
                time.sleep(0.02)
            doomed = s.devices.worker_of(job.devices[0])
            s.preempt_worker(doomed, deadline_s=30.0)
            s.wait(job.job_id, timeout=120)
            assert job.state == "completed", job.status()
            # drained BEFORE the kill: one logical migration, no retry
            assert job.migrations == 1 and job.retries_used == 0
            assert counter_total(telemetry.JOBS_PREEMPTIONS) \
                - before == 1
            # relaunched OFF the condemned worker
            survivors = {d for d in DEVS[:4]
                         if s.devices.worker_of(d) != doomed}
            assert set(attempt_devices[1]) <= survivors
            assert nets[-1].getIterationCount() == 12
            # the maintenance window passes: capacity comes back
            assert s.devices.free == 2
            s.restore_worker(doomed)
            assert s.devices.free == 4
        kinds = [e["kind"] for e in
                 flight_recorder.get_default().events()]
        assert "worker_preempt_notice" in kinds
        assert "job_worker_restored" in kinds

    def test_deadline_expires_mid_step_degrades_to_periodic(
            self, tmp_path, metrics_on):
        """The notice window is shorter than a step: the kill lands
        first, recovery is the newest PERIODIC bundle on the
        survivors, and it still counts ONE logical migration (the
        platform's fault, not the job's retry budget)."""
        nets = []

        def run(ctx):
            net = small_net(seed=6)
            nets.append(net)
            net.fit(slow_iter(0.4), epochs=2,
                    fault_tolerance=ctx.fault_tolerance)

        with make_sched() as s:
            job = s.submit(control.TrainJob(
                run, chips=2, checkpoint_dir=str(tmp_path),
                checkpoint_every=2, backoff_s=0.05))
            s.wait(job.job_id, timeout=120, states=("running",))
            while not nets or nets[0].getIterationCount() < 3:
                time.sleep(0.02)
            doomed = s.devices.worker_of(job.devices[0])
            # 1ms window vs a 400ms step: no boundary inside it
            s.preempt_worker(doomed, deadline_s=0.001)
            s.wait(job.job_id, timeout=180)
            assert job.state == "completed", job.status()
            assert job.retries_used == 0, job.status()
            assert job.migrations == 1
            assert nets[-1].getIterationCount() == 12
            assert s.devices.lost == 2

    def test_preempt_worker_unknown_raises(self):
        with make_sched() as s:
            with pytest.raises(KeyError):
                s.preempt_worker("nope")


# ======================================================================
# worker processes under the supervisor
# ======================================================================
class TestWorkerSupervisor:
    def test_task_roundtrip_heartbeats_and_gauges(self, metrics_on):
        with control.WorkerSupervisor(
                ["w0", "w1"], heartbeat_s=0.1, lease_s=10.0) as sup:
            task = sup.submit_task(
                "deeplearning4j_tpu.control.worker:echo_task",
                {"value": 42})
            task.wait(120)
            assert task.state == "completed"
            assert task.result["echo"] == {"value": 42}
            # nothing orders the other worker's first heartbeat before
            # this worker's echo: wait for it, bounded
            deadline = time.time() + 60
            while {v["state"] for v in sup.workers_status().values()} \
                    != {"alive"} and time.time() < deadline:
                time.sleep(0.05)
            st = sup.workers_status()
            assert {v["state"] for v in st.values()} == {"alive"}
            sup._publish_gauges(force=True)
            g = telemetry.MetricsRegistry.get_default().gauge(
                telemetry.WORKER_PROCESSES)
            vals = {dict(k).get("state"): v
                    for k, v in g.values().items()}
            assert vals.get("alive") == 2
            assert telemetry.MetricsRegistry.get_default().gauge(
                telemetry.WORKER_HEARTBEAT_AGE).values()
        assert control.default_supervisor() is None

    def test_worker_env_assigns_one_chip_per_process_on_a_tpu_host(
            self, tmp_path):
        """The supervisor stays off JAX; whether the host has chips is
        what its environment says (JAX_PLATFORMS). Worker i gets chip i
        and a TPU-only platform list, so it cannot fall back to CPU."""
        tpu = control.WorkerSupervisor(
            ["w0", "w1"], control_dir=str(tmp_path / "a"),
            env={"JAX_PLATFORMS": "tpu,cpu"}, make_default=False)
        e0, e1 = tpu._worker_env("w0"), tpu._worker_env("w1")
        assert (e0["TPU_VISIBLE_CHIPS"], e1["TPU_VISIBLE_CHIPS"]) \
            == ("0", "1")
        assert e0["JAX_PLATFORMS"] == e1["JAX_PLATFORMS"] == "tpu"
        assert e0["TPU_MESH_CONTROLLER_PORT"] \
            != e1["TPU_MESH_CONTROLLER_PORT"]
        cpu = control.WorkerSupervisor(
            ["w0"], control_dir=str(tmp_path / "b"),
            env={"JAX_PLATFORMS": "cpu"}, make_default=False)
        assert "TPU_VISIBLE_CHIPS" not in cpu._worker_env("w0")

    def test_sigkill_migrates_task_and_respawns_worker(self):
        """A SIGKILLed worker PROCESS: its task migrates onto the
        survivor; the supervisor respawns the worker, whose heartbeat
        brings it back alive."""
        with control.WorkerSupervisor(
                ["w0", "w1"], heartbeat_s=0.1, lease_s=10.0,
                restart_delay_s=0.1) as sup:
            task = sup.submit_task(
                "deeplearning4j_tpu.control.worker:spin_task", {})
            deadline = time.time() + 120
            while task.state != "running" and time.time() < deadline:
                time.sleep(0.05)
            first = task.worker
            while (sup.workers_status()[first]["step"] or 0) < 3 \
                    and time.time() < deadline:
                time.sleep(0.05)
            sup.kill(first)
            while (task.worker == first or task.state != "running") \
                    and time.time() < deadline:
                time.sleep(0.05)
            assert task.worker != first and task.migrations == 1
            # the killed worker respawns and heartbeats back to life
            while sup.workers_status()[first]["state"] != "alive" \
                    and time.time() < deadline:
                time.sleep(0.1)
            st = sup.workers_status()[first]
            assert st["state"] == "alive" and st["restarts"] == 1
            kinds = [e["kind"] for e in
                     flight_recorder.get_default().events()]
            assert "worker_process_dead" in kinds
            assert "worker_task_migrated" in kinds
            sup.preempt(task.worker, deadline_s=30)   # clean drain
            deadline = time.time() + 60
            while task.state == "running" \
                    and time.time() < deadline:
                time.sleep(0.05)

    def test_maintenance_cycle_restores_capacity_budget_free(self):
        """A noticed worker drains, dies at the deadline, respawns
        after the maintenance window, and its first heartbeat restores
        fleet capacity — WITHOUT consuming the crash-restart budget
        (a planned return is not a crash recovery)."""
        with make_sched() as s:
            with control.WorkerSupervisor(
                    ["w0", "w1"], heartbeat_s=0.1, lease_s=10.0,
                    restart_delay_s=0.1, scheduler=s) as sup:
                deadline = time.time() + 120
                while set(sup.alive()) != {"w0", "w1"} \
                        and time.time() < deadline:
                    time.sleep(0.05)
                s.preempt_worker("w0", deadline_s=1.5)
                while s.devices.lost == 0 \
                        and time.time() < deadline:
                    time.sleep(0.05)
                assert s.devices.lost == 2
                # the window passes: respawn + restore, budget intact
                while s.devices.lost != 0 \
                        and time.time() < deadline:
                    time.sleep(0.1)
                assert s.devices.free == 4
                assert sup.workers_status()["w0"]["restarts"] == 0

    def test_scheduler_supervisor_wiring(self):
        """Process death maps onto lose_worker; the respawned
        worker's heartbeat maps onto restore_worker capacity."""
        with make_sched() as s:
            with control.WorkerSupervisor(
                    ["w0", "w1"], heartbeat_s=0.1, lease_s=10.0,
                    restart_delay_s=0.1, scheduler=s) as sup:
                deadline = time.time() + 120
                while set(sup.alive()) != {"w0", "w1"} \
                        and time.time() < deadline:
                    time.sleep(0.05)
                assert s.devices.free == 4
                sup.kill("w0")
                while s.devices.lost == 0 \
                        and time.time() < deadline:
                    time.sleep(0.05)
                assert s.devices.lost == 2 and s.devices.free == 2
                # the respawn restores the fleet capacity
                while s.devices.lost != 0 \
                        and time.time() < deadline:
                    time.sleep(0.1)
                assert s.devices.free == 4
                kinds = [e["kind"] for e in
                         flight_recorder.get_default().events()]
                assert "job_worker_restored" in kinds


# ======================================================================
# /v1/workers HTTP surface
# ======================================================================
class TestWorkersHTTP:
    def test_workers_endpoints(self):
        from deeplearning4j_tpu.ui.server import UIServer

        with make_sched() as s:
            ui = UIServer()
            port = ui.start(port=0)
            base = f"http://127.0.0.1:{port}"
            try:
                listing = json.loads(urllib.request.urlopen(
                    base + "/v1/workers", timeout=10).read())
                assert set(listing["workers"]) == {"w0", "w1"}
                one = json.loads(urllib.request.urlopen(
                    base + "/v1/workers/w1", timeout=10).read())
                assert one["devices"] == 2
                # maintenance notice over HTTP condemns the worker
                r = urllib.request.urlopen(urllib.request.Request(
                    base + "/v1/workers/w1/preempt",
                    data=json.dumps({"deadline_s": 30}).encode(),
                    headers={"Content-Type": "application/json"}),
                    timeout=10)
                assert json.loads(r.read())["notice"] == "delivered"
                assert s.devices.free == 2
                assert s.devices.workers()["w1"]["condemned"]
                # restore lifts the notice
                r = urllib.request.urlopen(urllib.request.Request(
                    base + "/v1/workers/w1/restore", data=b"{}",
                    headers={"Content-Type": "application/json"}),
                    timeout=10)
                assert len(json.loads(
                    r.read())["devices_restored"]) == 2
                assert s.devices.free == 4
                try:
                    urllib.request.urlopen(urllib.request.Request(
                        base + "/v1/workers/nope/preempt", data=b"{}"),
                        timeout=10)
                    assert False, "expected 404"
                except urllib.error.HTTPError as e:
                    assert e.code == 404
            finally:
                ui.stop()

    def test_workers_http_404_without_control_plane(self):
        from deeplearning4j_tpu.ui.server import UIServer

        assert control.default_scheduler() is None
        assert control.default_supervisor() is None
        ui = UIServer()
        port = ui.start(port=0)
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/workers", timeout=10)
            assert False, "expected 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
        finally:
            ui.stop()
