"""Unified telemetry layer (profiler/telemetry.py): metrics registry,
host spans + Chrome-trace export, recompilation detector, device-memory
watermarks, /metrics + /telemetry endpoints — plus regression tests for
the listener fixes that ride with it (PerformanceListener samples/sec,
TimeIterationListener frequency/rate, CheckpointListener atomicity,
single-transfer check_numerics).
"""

import json
import logging
import os
import time
import urllib.request
import zipfile

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.profiler import telemetry


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    telemetry.set_enabled(True)
    yield
    telemetry.reset()


def _tiny_net(n_in=3, seed_updater=None):
    from deeplearning4j_tpu.learning.updaters import Sgd
    from deeplearning4j_tpu.nn.conf import (
        DenseLayer, InputType, NeuralNetConfiguration, OutputLayer,
    )
    from deeplearning4j_tpu.nn.multilayer.network import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder()
            .updater(seed_updater or Sgd(1e-2)).list()
            .layer(DenseLayer(n_out=4, activation="relu"))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))
            .setInputType(InputType.feedForward(n_in)).build())
    return MultiLayerNetwork(conf).init()


def _batch(n, n_in=3, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, n_in).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, n)]
    return x, y


# ---------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------
class TestMetricsRegistry:
    def test_counter_inc_and_labels(self):
        reg = telemetry.MetricsRegistry()
        c = reg.counter("requests_total", "help text")
        c.inc()
        c.inc(2, route="/a")
        c.inc(3, route="/a")
        assert c.value() == 1
        assert c.value(route="/a") == 5
        assert c.total() == 6
        # idempotent get-or-create returns the same object
        assert reg.counter("requests_total") is c

    def test_gauge_last_write_wins(self):
        reg = telemetry.MetricsRegistry()
        g = reg.gauge("bytes_in_use")
        g.set(10)
        g.set(7, device="0")
        g.set(3)
        assert g.value() == 3
        assert g.value(device="0") == 7

    def test_histogram_bucket_counts_and_capture(self):
        reg = telemetry.MetricsRegistry()
        h = reg.histogram("lat", buckets=(0.1, 0.5, 1.0))
        for v in (0.05, 0.3, 0.3, 0.7, 5.0):
            h.observe(v, engine="e0")
        cap = reg.capture()
        cnt, tot, buckets = cap["lat"]["series"][(("engine", "e0"),)]
        assert cnt == 5 and tot == pytest.approx(6.35)
        # non-cumulative per-bucket counts; last slot is +Inf overflow
        assert buckets == (1, 2, 1, 1)
        assert cap["lat"]["bounds"] == (0.1, 0.5, 1.0)
        text = reg.to_prometheus()
        assert 'lat_bucket{engine="e0",le="0.5"} 3' in text
        assert 'lat_bucket{engine="e0",le="+Inf"} 5' in text
        # counters/gauges capture raw values
        reg.counter("c").inc(3, k="a")
        assert reg.capture()["c"]["values"][(("k", "a"),)] == 3

    def test_remove_matching_and_engine_retire(self):
        reg = telemetry.MetricsRegistry()
        g = reg.gauge("g")
        g.set(1.0, engine="dead")
        g.set(2.0, engine="live")
        c = reg.counter("c")
        c.inc(5, engine="dead")
        h = reg.histogram("h")
        h.observe(0.1, engine="dead")
        # gauges-only removal drops the series, not the metric
        assert reg.remove_matching("engine", "dead",
                                   kinds=("gauge",)) == 1
        assert g.values() == {(("engine", "live"),): 2.0}
        assert c.value(engine="dead") == 5       # counters retained
        assert h.count(engine="dead") == 1
        # unrestricted removal sweeps every kind
        assert reg.remove_matching("engine", "dead") == 2
        assert c.value(engine="dead") == 0
        assert h.count(engine="dead") == 0

    def test_histogram_percentiles_and_bounds(self):
        reg = telemetry.MetricsRegistry()
        h = reg.histogram("lat", max_samples=64)
        for v in range(1, 101):
            h.observe(float(v))
        assert h.count() == 100
        assert h.sum() == pytest.approx(5050.0)
        p = h.percentiles()
        # reservoir is bounded: keeps the LAST 64 samples (37..100)
        assert 60 <= p["p50"] <= 75
        assert p["p99"] >= 95
        assert len(h._buf[()]) == 64

    def test_kind_conflict_raises(self):
        reg = telemetry.MetricsRegistry()
        reg.counter("x_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x_total")

    def test_prometheus_exposition_format(self):
        reg = telemetry.MetricsRegistry()
        reg.counter("c_total", "a counter").inc(2, site="s")
        reg.gauge("g").set(1.5)
        reg.histogram("h").observe(0.25, phase="etl")
        text = reg.to_prometheus()
        assert "# TYPE c_total counter" in text
        assert 'c_total{site="s"} 2' in text
        assert "# TYPE g gauge" in text
        assert "g 1.5" in text
        # histograms export proper cumulative _bucket{le=...} series
        # (scrapers run histogram_quantile over the same buckets the
        # in-process SLO engine windows)
        assert "# TYPE h histogram" in text
        assert 'h_bucket{phase="etl",le="0.25"} 1' in text
        assert 'h_bucket{phase="etl",le="0.1"} 0' in text
        assert 'h_bucket{phase="etl",le="+Inf"} 1' in text
        assert 'h_count{phase="etl"} 1' in text
        assert 'h_sum{phase="etl"} 0.25' in text
        # every non-comment line is "name{labels} value"
        for line in text.strip().splitlines():
            if line.startswith("#"):
                continue
            name, _, value = line.rpartition(" ")
            assert name and float(value) is not None

    def test_label_escaping(self):
        reg = telemetry.MetricsRegistry()
        reg.counter("c").inc(1, k='va"l\\ue')
        text = reg.to_prometheus()
        assert 'k="va\\"l\\\\ue"' in text

    def test_label_newline_escaping(self):
        reg = telemetry.MetricsRegistry()
        reg.counter("c").inc(1, k="a\nb")
        assert 'k="a\\nb"' in reg.to_prometheus()

    def test_help_and_type_for_every_metric(self):
        # scraper conformance: every metric family gets a # HELP and a
        # # TYPE line, even help-less ones, with HELP text escaped
        reg = telemetry.MetricsRegistry()
        reg.counter("c_total", "counts\nthings with \\slashes").inc()
        reg.gauge("g")          # no help
        reg.histogram("h", "a histogram").observe(1.0)
        text = reg.to_prometheus()
        assert "# HELP c_total counts\\nthings with \\\\slashes" in text
        assert "# HELP g" in text
        assert "# HELP h a histogram" in text
        for name, kind in (("c_total", "counter"), ("g", "gauge"),
                           ("h", "histogram")):
            assert f"# TYPE {name} {kind}" in text

    def test_nonfinite_values_render_prometheus_style(self):
        # the exposition format spells NaN / +Inf / -Inf; python's %g
        # ("nan"/"inf") is rejected by real scrapers
        reg = telemetry.MetricsRegistry()
        reg.gauge("g").set(float("nan"), k="a")
        reg.gauge("g").set(float("inf"), k="b")
        reg.gauge("g").set(float("-inf"), k="c")
        text = reg.to_prometheus()
        assert 'g{k="a"} NaN' in text
        assert 'g{k="b"} +Inf' in text
        assert 'g{k="c"} -Inf' in text
        assert "nan" not in text and "inf" not in text

    def test_spans_dropped_counter_on_wrap(self, monkeypatch):
        import collections

        monkeypatch.setattr(telemetry, "_trace_events",
                            collections.deque(maxlen=3))
        t0 = time.perf_counter()
        for i in range(5):
            telemetry.record_span(f"s{i}", t0)
        # the truncation is attributable on the export itself (the
        # export paths flush the pending count into the counter — the
        # record hot path only bumps an int under the trace lock)
        assert telemetry.chrome_trace()["otherData"][
            "spans_dropped"] == 2
        c = telemetry.MetricsRegistry.get_default().counter(
            telemetry.SPANS_DROPPED)
        assert c.total() == 2
        # flushing is not double-counting
        telemetry.flush_dropped_spans()
        assert c.total() == 2

    def test_json_dump(self):
        reg = telemetry.MetricsRegistry()
        reg.counter("c_total").inc(3)
        reg.histogram("h").observe(1.0)
        d = reg.to_json()
        assert d["c_total"]["kind"] == "counter"
        assert d["c_total"]["values"]["total"] == 3
        assert d["h"]["values"]["total"]["count"] == 1
        json.dumps(d)  # serializable

    def test_thread_safety(self):
        import threading

        reg = telemetry.MetricsRegistry()
        c = reg.counter("n_total")

        def worker():
            for _ in range(1000):
                c.inc()

        ts = [threading.Thread(target=worker) for _ in range(8)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert c.value() == 8000


# ---------------------------------------------------------------------
# spans + Chrome trace export
# ---------------------------------------------------------------------
class TestSpans:
    def test_nesting_recorded(self):
        with telemetry.span("outer"):
            with telemetry.span("inner"):
                time.sleep(0.001)
        evs = telemetry.chrome_trace()["traceEvents"]
        names = {e["name"]: e for e in evs}
        # the parent is named by its id, which every record carries
        assert names["inner"]["args"]["parent"] == \
            names["outer"]["args"]["id"]
        assert "parent" not in names["outer"]["args"]
        assert names["inner"]["args"]["depth"] == 1
        assert names["outer"]["args"]["depth"] == 0
        # inner completes first, nests inside outer's interval
        assert names["inner"]["ts"] >= names["outer"]["ts"]
        assert names["inner"]["dur"] <= names["outer"]["dur"]

    def test_chrome_trace_event_fields(self):
        with telemetry.span("s", foo="bar"):
            pass
        tr = telemetry.chrome_trace()
        assert "traceEvents" in tr
        for e in tr["traceEvents"]:
            assert e["ph"] == "X"
            assert isinstance(e["ts"], float)
            assert isinstance(e["dur"], float) and e["dur"] >= 0
            assert "pid" in e and "tid" in e and "name" in e

    def test_export_parses_as_json(self, tmp_path):
        with telemetry.span("a"):
            pass
        path = telemetry.export_chrome_trace(str(tmp_path / "trace.json"))
        with open(path) as f:
            loaded = json.load(f)
        assert loaded["traceEvents"][0]["name"] == "a"
        assert loaded["displayTimeUnit"] == "ms"

    def test_span_metric_observation(self):
        with telemetry.span("timed", metric="my_seconds", phase="x"):
            pass
        h = telemetry.MetricsRegistry.get_default().histogram("my_seconds")
        assert h.count(phase="x") == 1
        # depth/parent must NOT leak into metric labels
        assert 'depth' not in telemetry.MetricsRegistry.get_default() \
            .to_prometheus()

    def test_disabled_records_nothing(self):
        telemetry.set_enabled(False)
        with telemetry.span("ghost"):
            pass
        telemetry.record_phase("etl_wait", time.perf_counter())
        assert telemetry.chrome_trace()["traceEvents"] == []


# ---------------------------------------------------------------------
# recompilation detector
# ---------------------------------------------------------------------
class TestRecompileDetector:
    def test_stable_shapes_compile_once(self):
        net = _tiny_net()
        x, y = _batch(8)
        for _ in range(3):
            net.fit(x, y)
        c = telemetry.MetricsRegistry.get_default().counter(
            telemetry.JIT_COMPILES)
        assert c.value(site="mln_step") == 1

    def test_induced_retrace_counts_and_times(self):
        """Acceptance: fitting the same network on two distinct batch
        shapes reports >= 2 compiles with nonzero compile time."""
        net = _tiny_net()
        net.fit(*_batch(8))
        net.fit(*_batch(16))
        reg = telemetry.MetricsRegistry.get_default()
        c = reg.counter(telemetry.JIT_COMPILES)
        assert c.value(site="mln_step") >= 2
        assert reg.histogram(telemetry.JIT_COMPILE_SECONDS) \
            .sum(site="mln_step") > 0
        # compile events land in the host trace, with signatures
        evs = [e for e in telemetry.chrome_trace()["traceEvents"]
               if e["name"] == "jit_compile:mln_step"]
        assert len(evs) >= 2
        assert "signature" in evs[0]["args"]

    def test_graph_site_counted(self):
        from deeplearning4j_tpu.nn.conf import (
            DenseLayer, InputType, OutputLayer,
        )
        from deeplearning4j_tpu.nn.graph.config import (
            ComputationGraphConfiguration,
        )
        from deeplearning4j_tpu.nn.graph.graph import ComputationGraph

        conf = (ComputationGraphConfiguration.graphBuilder()
                .addInputs("in")
                .setInputTypes(InputType.feedForward(3))
                .addLayer("d", DenseLayer(n_out=4, activation="relu"),
                          "in")
                .addLayer("out", OutputLayer(n_out=2,
                                             activation="softmax",
                                             loss="mcxent"), "d")
                .setOutputs("out").build())
        g = ComputationGraph(conf).init()
        g.fit(*_batch(8))
        g.fit(*_batch(12))
        c = telemetry.MetricsRegistry.get_default().counter(
            telemetry.JIT_COMPILES)
        assert c.value(site="cg_step") == 2

    def test_vjp_only_site_uses_signature_probe(self):
        """cg_ext_forward is only ever called under jax.vjp, where the
        executable cache never grows — the signature probe must count
        its compiles anyway."""
        from deeplearning4j_tpu.nn.conf import (
            DenseLayer, InputType, OutputLayer,
        )
        from deeplearning4j_tpu.nn.graph.config import (
            ComputationGraphConfiguration,
        )
        from deeplearning4j_tpu.nn.graph.graph import ComputationGraph

        conf = (ComputationGraphConfiguration.graphBuilder()
                .addInputs("in")
                .setInputTypes(InputType.feedForward(3))
                .addLayer("out", OutputLayer(n_out=2,
                                             activation="softmax",
                                             loss="mcxent"), "in")
                .setOutputs("out").build())
        g = ComputationGraph(conf).init()
        for n in (4, 4, 8):
            x = np.ones((n, 3), np.float32)
            err = np.ones((n, 2), np.float32)
            g.backpropGradient([x], [err], train=False)
        c = telemetry.MetricsRegistry.get_default().counter(
            telemetry.JIT_COMPILES)
        assert c.value(site="cg_ext_forward") == 2

    def test_storm_warning(self, monkeypatch, caplog):
        monkeypatch.setenv("DL4J_TPU_RECOMPILE_STORM_THRESHOLD", "3")
        fn = telemetry.instrument_jit("storm_site",
                                      jax.jit(lambda x: x + 1))
        with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
            for n in range(1, 5):
                fn(jnp.ones(n))
        msgs = [r.message for r in caplog.records
                if "RECOMPILE STORM" in r.message]
        assert msgs and "storm_site" in msgs[0]

    def test_wrapper_passes_through_lower(self):
        """AOT cost analysis (bench_common.aot_cost_flops) must still
        reach .lower() through the instrumented wrapper."""
        fn = telemetry.instrument_jit("aot", jax.jit(lambda x: x * 2))
        compiled = fn.lower(jnp.ones(4)).compile()
        assert compiled.cost_analysis() is not None

    def test_bench_snapshot_carries_compiles(self):
        net = _tiny_net()
        net.fit(*_batch(4))
        import bench_common

        snap = bench_common.telemetry_snapshot()
        assert snap["jit_compiles_total"] >= 1
        assert snap["per_site"]["mln_step"]["compiles"] >= 1
        assert snap["per_site"]["mln_step"]["compile_seconds"] > 0


# ---------------------------------------------------------------------
# step phases + device memory
# ---------------------------------------------------------------------
class TestStepPhases:
    def test_phase_histogram_from_iterator_fit(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.datasets.iterator import (
            ListDataSetIterator,
        )

        net = _tiny_net()
        x, y = _batch(8)
        it = ListDataSetIterator([DataSet(x, y)], batch_size=8)
        net.setListeners(_NullListener())
        net.fit(it, epochs=2)
        h = telemetry.MetricsRegistry.get_default().histogram(
            telemetry.STEP_PHASE_SECONDS)
        assert h.count(phase="etl_wait") >= 2
        assert h.count(phase="device_step") >= 2
        assert h.count(phase="listener_host") >= 2
        assert h.sum(phase="device_step") > 0

    def test_device_memory_graceful_on_cpu(self):
        # CPU backend reports no memory_stats -> {} and no crash; the
        # probe result is cached so repeated calls stay cheap
        out = telemetry.sample_device_memory()
        assert out == {} or "bytes_in_use" in out

    def test_explicit_device_bypasses_cached_verdict(self):
        telemetry.sample_device_memory()   # latches False on CPU

        class FakeDevice:
            id = 3

            def memory_stats(self):
                return {"bytes_in_use": 10, "peak_bytes_in_use": 20}

        out = telemetry.sample_device_memory(FakeDevice())
        assert out["bytes_in_use"] == 10
        g = telemetry.MetricsRegistry.get_default().gauge(
            telemetry.DEVICE_PEAK_BYTES)
        assert g.value(device="3") == 20

    def test_force_samples_with_telemetry_disabled(self):
        # StatsListener's memory report must survive
        # DL4J_TPU_TELEMETRY=0: force=True still probes (gauges are
        # left untouched — telemetry is off), plain calls stay no-ops
        class FakeDevice:
            id = 7

            def memory_stats(self):
                return {"bytes_in_use": 5, "peak_bytes_in_use": 9}

        telemetry.set_enabled(False)
        try:
            assert telemetry.sample_device_memory(FakeDevice()) == {}
            out = telemetry.sample_device_memory(FakeDevice(),
                                                 force=True)
            assert out["bytes_in_use"] == 5
            g = telemetry.MetricsRegistry.get_default().gauge(
                telemetry.DEVICE_PEAK_BYTES)
            assert g.value(device="7") == 0.0   # not published
        finally:
            telemetry.set_enabled(True)

    def test_probe_exception_does_not_latch(self):
        class Flaky:
            id = 0
            calls = 0

            def memory_stats(self):
                Flaky.calls += 1
                if Flaky.calls == 1:
                    raise RuntimeError("transient init race")
                return {"bytes_in_use": 1, "peak_bytes_in_use": 2}

        d = Flaky()
        assert telemetry.sample_device_memory(d) == {}
        assert telemetry.sample_device_memory(d)["bytes_in_use"] == 1


class _NullListener:
    def iterationDone(self, model, iteration, epoch):
        pass

    def onEpochEnd(self, model):
        pass


# ---------------------------------------------------------------------
# /metrics + /telemetry endpoints
# ---------------------------------------------------------------------
class TestEndpoints:
    def test_metrics_and_telemetry(self):
        from deeplearning4j_tpu.ui.server import UIServer

        net = _tiny_net()
        net.fit(*_batch(8))
        net.fit(*_batch(16))
        ui = UIServer()   # fresh instance; do not pollute the singleton
        port = ui.start(port=0)
        try:
            base = f"http://127.0.0.1:{port}"
            resp = urllib.request.urlopen(base + "/metrics")
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
            # acceptance: valid Prometheus text with the compile counter
            # and a step-phase histogram
            assert "# TYPE dl4j_tpu_jit_compiles_total counter" in text
            assert 'dl4j_tpu_jit_compiles_total{site="mln_step"} 2' in text
            assert "dl4j_tpu_step_phase_seconds" in text
            for line in text.strip().splitlines():
                if not line.startswith("#"):
                    float(line.rpartition(" ")[2])

            tel = json.loads(urllib.request.urlopen(
                base + "/telemetry").read())
            assert tel["snapshot"]["jit_compiles_total"] >= 2
            assert tel["metrics"]["dl4j_tpu_jit_compiles_total"][
                "kind"] == "counter"
            assert tel["trace_event_count"] >= 1
            assert all("ph" in e for e in tel["trace_events"])
        finally:
            ui.stop()


# ---------------------------------------------------------------------
# listener fixes (satellites)
# ---------------------------------------------------------------------
class _FakeModel:
    def __init__(self, batch=32):
        self._last_batch_size = batch

    def score(self):
        return 0.5


class TestPerformanceListenerFix:
    def test_samples_per_sec_computed(self):
        from deeplearning4j_tpu.optimize.listeners import (
            PerformanceListener,
        )

        lines = []
        l = PerformanceListener(frequency=5, report_batch=True,
                                printer=lines.append)
        m = _FakeModel(batch=32)
        l.iterationDone(m, 1, 0)
        l.iterationDone(m, 6, 0)
        assert not np.isnan(l.samples_per_sec)
        assert l.samples_per_sec == pytest.approx(
            l.batches_per_sec * 32)
        assert "samples/sec" in lines[0]

    def test_report_batch_false_skips(self):
        from deeplearning4j_tpu.optimize.listeners import (
            PerformanceListener,
        )

        lines = []
        l = PerformanceListener(frequency=5, report_batch=False,
                                printer=lines.append)
        m = _FakeModel()
        l.iterationDone(m, 1, 0)
        l.iterationDone(m, 6, 0)
        assert np.isnan(l.samples_per_sec)
        assert "samples/sec" not in lines[0]

    def test_real_fit_populates_batch_size(self):
        from deeplearning4j_tpu.optimize.listeners import (
            PerformanceListener,
        )

        lines = []
        net = _tiny_net()
        net.setListeners(PerformanceListener(frequency=1,
                                             printer=lines.append))
        x, y = _batch(16)
        net.fit(x, y, epochs=3)
        assert net._last_batch_size == 16
        assert any("samples/sec" in s for s in lines)


class TestTimeIterationListenerFix:
    def test_frequency_honored(self):
        from deeplearning4j_tpu.optimize.listeners import (
            TimeIterationListener,
        )

        lines = []
        l = TimeIterationListener(100, printer=lines.append, frequency=2)
        m = _FakeModel()
        for i in range(1, 7):
            l.iterationDone(m, i, 0)
        # first call arms the clock; reports at iterations 2, 4, 6
        assert len(lines) == 3

    def test_rate_uses_elapsed_iterations(self):
        from deeplearning4j_tpu.optimize.listeners import (
            TimeIterationListener,
        )

        lines = []
        l = TimeIterationListener(10_000, printer=lines.append,
                                  frequency=1)
        m = _FakeModel()
        # resumed training: iteration counter starts at 5000 — ETA must
        # come from the 2 iterations we actually saw, not 5002
        l.iterationDone(m, 5000, 0)
        time.sleep(0.02)
        l.iterationDone(m, 5001, 0)
        l.iterationDone(m, 5002, 0)
        assert l._start_iter == 5000
        eta = float(lines[-1].split("ETA ")[1].rstrip("s"))
        # ~0.01s/iter * 5000 remaining ≈ 50s; the old absolute-iteration
        # rate would have claimed under a second
        assert eta > 5


class TestCheckpointListenerFix:
    def test_skips_iteration_zero_and_writes_atomically(self, tmp_path):
        from deeplearning4j_tpu.optimize.listeners import (
            CheckpointListener,
        )

        net = _tiny_net()
        l = CheckpointListener(str(tmp_path), save_every_n_iterations=5,
                               keep_last=2)
        l.iterationDone(net, 0, 0)
        assert l.lastCheckpoint() is None
        assert not list(tmp_path.iterdir())
        l.iterationDone(net, 5, 0)
        path = tmp_path / "checkpoint_iter_5.zip"
        assert path.exists()
        assert not (tmp_path / "checkpoint_iter_5.zip.tmp").exists()
        with zipfile.ZipFile(path) as zf:   # complete, readable archive
            assert "configuration.json" in zf.namelist()

    def test_failed_save_leaves_no_partial(self, tmp_path, monkeypatch):
        from deeplearning4j_tpu.optimize.listeners import (
            CheckpointListener,
        )
        from deeplearning4j_tpu.util import model_serializer

        def boom(*a, **k):
            raise RuntimeError("disk full")

        monkeypatch.setattr(model_serializer.ModelSerializer,
                            "writeModel", boom)
        l = CheckpointListener(str(tmp_path), save_every_n_iterations=1)
        with pytest.raises(RuntimeError, match="disk full"):
            l.iterationDone(_FakeModel(), 1, 0)
        assert not list(tmp_path.iterdir())   # no truncated zip, no tmp


class TestCheckNumericsFix:
    def test_single_device_get(self, monkeypatch):
        from deeplearning4j_tpu import profiler as prof

        calls = []
        orig = jax.device_get

        def counting(x):
            calls.append(x)
            return orig(x)

        monkeypatch.setattr(jax, "device_get", counting)
        tree = {"a": jnp.ones(4), "b": jnp.zeros((2, 3)),
                "c": jnp.arange(3),            # int: not fetched
                "d": [jnp.full(2, 1.5)]}
        prof.check_numerics(tree, prof.ProfilerMode.ANY_PANIC)
        assert len(calls) == 1                 # ONE transfer, all leaves
        assert len(calls[0]) == 3              # the floating leaves only

    def test_still_raises_and_reduces(self):
        from deeplearning4j_tpu import profiler as prof

        with pytest.raises(prof.NumericsException, match="NaN"):
            prof.check_numerics(
                [np.ones(3), np.asarray([np.nan])],
                prof.ProfilerMode.NAN_PANIC, "ctx")
        with pytest.raises(prof.NumericsException, match="Inf"):
            prof.check_numerics(np.asarray([np.inf]),
                                prof.ProfilerMode.INF_PANIC)
        # NAN_PANIC ignores Inf; ints ignored entirely
        prof.check_numerics(np.asarray([np.inf]),
                            prof.ProfilerMode.NAN_PANIC)
        prof.check_numerics(np.arange(5), prof.ProfilerMode.ANY_PANIC)

    def test_bfloat16_swept(self):
        from deeplearning4j_tpu import profiler as prof

        bad = jnp.asarray([np.nan], jnp.bfloat16)
        with pytest.raises(prof.NumericsException):
            prof.check_numerics(bad, prof.ProfilerMode.NAN_PANIC)


class TestTelemetryListener:
    def test_bridges_metrics(self):
        from deeplearning4j_tpu.optimize.listeners import (
            TelemetryListener,
        )

        net = _tiny_net()
        net.setListeners(TelemetryListener(frequency=1))
        x, y = _batch(8)
        net.fit(x, y, epochs=3)
        reg = telemetry.MetricsRegistry.get_default()
        assert reg.counter("dl4j_tpu_iterations_total").total() == 3
        assert reg.gauge("dl4j_tpu_score").value() == pytest.approx(
            float(net.score()))

    def test_kill_switch_skips_score_sync(self):
        from deeplearning4j_tpu.optimize.listeners import (
            TelemetryListener,
        )

        class SyncTrap:
            def score(self):
                raise AssertionError(
                    "score() must not sync when telemetry is off")

        telemetry.set_enabled(False)
        l = TelemetryListener(frequency=1)
        l.iterationDone(SyncTrap(), 1, 0)
        l.onEpochEnd(SyncTrap())
        telemetry.set_enabled(True)
        assert telemetry.MetricsRegistry.get_default().counter(
            "dl4j_tpu_iterations_total").total() == 0
