"""Roofline program registry + managed device profiling
(profiler/programs.py, the peak tables in profiler/flops.py, the
trace shims in profiler/__init__, the SLO engine's page-capture hook,
and the flight recorder's programs.json dump member)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deeplearning4j_tpu.profiler as profiler
from deeplearning4j_tpu.profiler import flops as flops_mod
from deeplearning4j_tpu.profiler import (
    flight_recorder, programs, slo, telemetry,
)


@pytest.fixture(autouse=True)
def _clean_programs():
    programs.set_enabled(False)
    programs.reset()
    yield
    programs.set_enabled(False)
    programs.reset()


class _FakeProfiler:
    """Stand-in for jax.profiler: records start/stop calls and drops a
    file into the trace dir so capture bundles have content. Mirrors
    the real contract (second start raises RuntimeError)."""

    def __init__(self):
        self.starts = 0
        self.stops = 0
        self._active = False

    def install(self, monkeypatch):
        monkeypatch.setattr(jax.profiler, "start_trace", self.start)
        monkeypatch.setattr(jax.profiler, "stop_trace", self.stop)
        return self

    def start(self, log_dir):
        if self._active:
            raise RuntimeError("profiler already started")
        self._active = True
        self.starts += 1
        os.makedirs(log_dir, exist_ok=True)
        with open(os.path.join(log_dir, "trace.bin"), "wb") as f:
            f.write(b"\x00fake-xplane")

    def stop(self):
        self._active = False
        self.stops += 1


def _register_square(reg, site="t_site", n=64, seconds=(0.01,)):
    """Register one real compiled executable + dispatches."""
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((n, n), jnp.float32)
    sig = f"float32[{n}, {n}]"
    reg.register(site, sig, f.lower(x).compile(),
                 source="jit", compile_seconds=0.5)
    for s in seconds:
        reg.record_dispatch(site, sig, s)
    return sig


# ------------------------------------------------------------ peaks
class TestPeakTables:
    def test_known_device_reads_table(self, monkeypatch):
        kind = jax.devices()[0].device_kind
        monkeypatch.setitem(flops_mod.PEAK_FLOPS, kind,
                            {"bf16": 2e12, "f32": 1e12})
        monkeypatch.setitem(flops_mod.PEAK_HBM_GBPS, kind, 3.0)
        assert flops_mod.peak_flops("bf16") == 2e12
        assert flops_mod.peak_flops("float32") == 1e12
        assert flops_mod.peak_hbm_gbps() == 3.0

    def test_unknown_device_none_with_one_warning(self, monkeypatch,
                                                  caplog):
        kind = jax.devices()[0].device_kind
        assert kind not in flops_mod.PEAK_FLOPS     # CPU smoke premise
        assert kind not in flops_mod.PEAK_HBM_GBPS
        monkeypatch.setattr(flops_mod, "_warned_unknown_peak", set())
        monkeypatch.setattr(flops_mod, "_warned_unknown_hbm", set())
        with caplog.at_level("WARNING", logger="deeplearning4j_tpu"):
            assert flops_mod.peak_flops("bf16") is None
            assert flops_mod.peak_hbm_gbps() is None
            first = [r for r in caplog.records
                     if "peak" in r.getMessage().lower()]
            assert flops_mod.peak_flops("bf16") is None   # warn-once
            assert flops_mod.peak_hbm_gbps() is None
        again = [r for r in caplog.records
                 if "peak" in r.getMessage().lower()]
        assert len(first) == 2 and len(again) == 2


    def test_snapshot_raises_on_an_accelerator_without_peaks(
            self, monkeypatch):
        """The CPU is classified against the labelled nominal ratios;
        an accelerator missing from the peaks table is an error."""
        reg = programs.ProgramRegistry()
        assert reg.snapshot()["peak_source"] == "nominal"     # CPU

        class _Chip:
            platform, device_kind = "tpu", "TPU v0 imaginary"

        monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()])
        with pytest.raises(RuntimeError, match="PEAK_FLOPS"):
            reg.snapshot()


# --------------------------------------------------------- verdicts
class TestRooflineVerdict:
    def test_no_cost_numbers_is_unknown(self):
        assert programs.roofline_verdict(None, None) == "unknown"
        assert programs.roofline_verdict(0, 1e6) == "unknown"
        assert programs.roofline_verdict(1e6, 0) == "unknown"

    def test_tiny_program_is_dispatch_bound(self):
        # roofline time ~5ns on nominal v5e peaks: launch overhead wins
        assert programs.roofline_verdict(1e6, 1e4) == "dispatch_bound"

    def test_low_ai_is_memory_bound(self):
        # AI=10 against a ~240 flops/byte nominal ridge
        assert programs.roofline_verdict(1e13, 1e12) == "memory_bound"

    def test_high_ai_is_compute_bound(self):
        assert programs.roofline_verdict(1e14, 1e11) == "compute_bound"

    def test_measured_dispatch_needs_real_peaks(self):
        # nominal mode must IGNORE measured wall time: CPU dispatch
        # seconds against a TPU roofline would mislabel everything
        assert programs.roofline_verdict(
            1e13, 1e12, avg_dispatch_s=999.0) == "memory_bound"
        # with real peaks, 60s measured vs a 1s roofline model is
        # launch/host overhead
        assert programs.roofline_verdict(
            1e13, 1e12, avg_dispatch_s=60.0,
            peak_fl=1e13, peak_bw_gbps=1000.0) == "dispatch_bound"
        assert programs.roofline_verdict(
            1e13, 1e12, avg_dispatch_s=5.0,
            peak_fl=1e13, peak_bw_gbps=1000.0) == "compute_bound"


# --------------------------------------------------------- registry
class TestProgramRegistry:
    def test_register_extracts_cost_and_memory(self):
        reg = programs.ProgramRegistry()
        sig = _register_square(reg)
        reg.record_dispatch("t_site", sig, None)     # untimed (compile)
        reg.record_dispatch("t_site", "nope", 9.9)   # unknown: dropped
        reg.record_dispatch("t_site", None, 9.9)
        snap = reg.snapshot()
        (row,) = snap["programs"]
        assert row["site"] == "t_site" and row["signature"] == sig
        assert row["flops"] > 0 and row["bytes_accessed"] > 0
        assert row["arithmetic_intensity"] == pytest.approx(
            row["flops"] / row["bytes_accessed"])
        assert row["dispatches"] == 2            # timed + untimed
        assert row["dispatch_seconds"] == pytest.approx(0.01)
        assert row["compile_seconds"] == 0.5
        assert len(row["hlo_digest"]) == 16
        assert set(row["memory"]) == {
            "temp_bytes", "argument_bytes", "output_bytes",
            "generated_code_bytes"}
        assert row["verdict"] in programs.VERDICTS
        # untimed dispatches must not fabricate achieved rates from
        # a partial denominator
        assert row["achieved_flops_per_s"] == pytest.approx(
            row["flops"] / 0.01)
        site = snap["sites"]["t_site"]
        assert site["dispatches"] == 2
        assert site["flops"] == pytest.approx(row["flops"] * 2)
        assert site["verdict"] == row["verdict"]

    def test_recompile_keeps_dispatch_history(self):
        reg = programs.ProgramRegistry()
        sig = _register_square(reg, seconds=(0.01, 0.02))
        _register_square(reg, seconds=())            # refresh, same key
        (row,) = reg.snapshot()["programs"]
        assert row["dispatches"] == 2
        assert row["dispatch_seconds"] == pytest.approx(0.03)

    def test_top_n_truncates_programs_not_sites(self):
        reg = programs.ProgramRegistry()
        for i, s in enumerate(("a", "b", "c")):
            _register_square(reg, site=s, n=8, seconds=(0.01 * (i + 1),))
        snap = reg.snapshot(top_n=1)
        assert len(snap["programs"]) == 1
        assert snap["programs"][0]["site"] == "c"    # most device time
        assert set(snap["sites"]) == {"a", "b", "c"}

    def test_module_snapshot_empty_until_registered(self):
        assert programs.snapshot() == {}
        programs.set_enabled(True)
        _register_square(programs.get_default(), n=8)
        assert programs.snapshot()["sites"].keys() == {"t_site"}

    def test_off_mode_record_dispatch_is_noop(self):
        assert not programs.enabled()
        programs.record_dispatch("t_site", "sig", 1.0)  # must not raise
        assert programs.snapshot() == {}

    def test_instrument_jit_populates_registry(self):
        programs.set_enabled(True)
        telemetry.set_enabled(True)
        wrapped = telemetry.instrument_jit(
            "prog_test_site", jax.jit(lambda x: x * 2 + 1))
        x = jnp.ones((16,), jnp.float32)
        for _ in range(3):
            wrapped(x)
        snap = programs.get_default().snapshot()
        site = snap["sites"].get("prog_test_site")
        assert site is not None
        # compile-call wall time is compile, not execution: only the
        # post-compile dispatches are counted
        assert site["dispatches"] == 3
        (row,) = [r for r in snap["programs"]
                  if r["site"] == "prog_test_site"]
        assert row["signature"] == "float32[16]"
        assert row["compile_seconds"] > 0


# ------------------------------------------------------- trace shims
class TestTraceShims:
    def test_double_start_is_idempotent_with_warning(self, monkeypatch,
                                                     tmp_path):
        fake = _FakeProfiler().install(monkeypatch)
        assert profiler.start_trace(str(tmp_path)) is True
        # the old code called jax.profiler.start_trace again here and
        # got RuntimeError from inside XLA
        assert profiler.start_trace(str(tmp_path)) is False
        assert fake.starts == 1
        assert profiler.stop_trace() is True
        assert profiler.stop_trace() is False
        assert fake.stops == 1

    def test_trace_ctx_does_not_stop_an_outer_trace(self, monkeypatch,
                                                    tmp_path):
        fake = _FakeProfiler().install(monkeypatch)
        assert profiler.start_trace(str(tmp_path / "outer")) is True
        with profiler.trace(str(tmp_path / "inner")):   # start refused
            pass
        assert fake.stops == 0                # inner exit: no stop
        assert profiler.stop_trace() is True  # outer still active
        assert fake.stops == 1

    def test_trace_ctx_stops_on_body_exception(self, monkeypatch,
                                               tmp_path):
        fake = _FakeProfiler().install(monkeypatch)
        with pytest.raises(ValueError):
            with profiler.trace(str(tmp_path)):
                raise ValueError("boom")
        assert (fake.starts, fake.stops) == (1, 1)
        assert programs.profile_session().active() is None

    def test_failed_start_leaves_slot_free(self, monkeypatch, tmp_path):
        def refuse(log_dir):
            raise RuntimeError("backend refused")

        monkeypatch.setattr(jax.profiler, "start_trace", refuse)
        with pytest.raises(RuntimeError):
            profiler.start_trace(str(tmp_path))
        assert programs.profile_session().active() is None


# ---------------------------------------------------------- captures
class TestProfileSession:
    def test_capture_roundtrips_digest_valid(self, monkeypatch,
                                             tmp_path):
        _FakeProfiler().install(monkeypatch)
        programs.set_enabled(True)
        _register_square(programs.get_default(), n=8)
        sess = programs.ProfileSession(directory=str(tmp_path))
        path = sess.capture(0.0, trigger="unit")
        assert path and os.path.basename(path).startswith("profile-")
        cap = programs.load_capture(path)
        assert cap["valid"] is True
        assert cap["manifest"]["trigger"] == "unit"
        assert "trace/trace.bin" in cap["manifest"]["digests"]
        assert cap["programs"]["sites"].keys() == {"t_site"}
        assert sess.last_bundle == path

    def test_tampered_bundle_is_invalid(self, monkeypatch, tmp_path):
        _FakeProfiler().install(monkeypatch)
        sess = programs.ProfileSession(directory=str(tmp_path))
        path = sess.capture(0.0, trigger="unit")
        with open(os.path.join(path, "programs.json"), "a") as f:
            f.write(" ")
        assert programs.load_capture(path)["valid"] is False

    def test_capture_refused_while_manual_trace_active(
            self, monkeypatch, tmp_path):
        fake = _FakeProfiler().install(monkeypatch)
        sess = programs.ProfileSession(directory=str(tmp_path))
        assert sess.start_manual(str(tmp_path / "t"))
        assert sess.capture(0.0, trigger="unit") is None
        assert fake.starts == 1               # no second start attempt
        assert sess.stop_manual()

    def test_capture_failure_never_raises_and_frees_slot(
            self, monkeypatch, tmp_path):
        def boom(log_dir):
            raise RuntimeError("no backend")

        monkeypatch.setattr(jax.profiler, "start_trace", boom)
        sess = programs.ProfileSession(directory=str(tmp_path))
        assert sess.capture(0.0, trigger="unit") is None
        assert sess.active() is None
        assert sess.capture("bogus", trigger="unit") is None

    def test_pruning_keeps_newest(self, monkeypatch, tmp_path):
        _FakeProfiler().install(monkeypatch)
        monkeypatch.setattr(programs.ProfileSession, "KEEP_CAPTURES", 2)
        sess = programs.ProfileSession(directory=str(tmp_path))
        paths = [sess.capture(0.0, trigger=f"t{i}") for i in range(3)]
        assert all(paths)
        left = programs.list_captures(str(tmp_path))
        assert len(left) == 2
        assert paths[-1] in left

    def test_rate_limit_spans_automated_but_not_manual(
            self, monkeypatch, tmp_path):
        _FakeProfiler().install(monkeypatch)
        sess = programs.ProfileSession(directory=str(tmp_path))
        # a forced manual capture must NOT start the automated window
        assert sess.capture(0.0, trigger="manual")
        first = sess.maybe_capture(trigger="slo:a", duration_s=0.0,
                                   min_interval_s=3600.0)
        assert first is not None
        # ...but an automated capture does rate-limit the next one
        assert sess.maybe_capture(trigger="slo:b", duration_s=0.0,
                                  min_interval_s=3600.0) is None

    def test_capture_emits_flight_event_and_counter(self, monkeypatch,
                                                    tmp_path):
        _FakeProfiler().install(monkeypatch)
        reg = telemetry.MetricsRegistry.get_default()
        m = reg.peek(telemetry.PROFILE_CAPTURES)
        key = '{trigger="prog-unit-ev"}'
        before = (m._json().get(key, 0.0) if m is not None else 0.0)
        sess = programs.ProfileSession(directory=str(tmp_path))
        path = sess.capture(0.0, trigger="prog-unit-ev")
        evs = [e for e in flight_recorder.get_default().events()
               if e["kind"] == "profile_capture"
               and e.get("trigger") == "prog-unit-ev"]
        assert evs and evs[-1]["bundle"] == path
        after = reg.peek(telemetry.PROFILE_CAPTURES)._json()[key]
        assert after == before + 1.0


# -------------------------------------------------------------- http
class TestHttpHandlers:
    def test_programs_endpoint_shape_and_validation(self):
        programs.set_enabled(True)
        _register_square(programs.get_default(), n=8)
        out, status = programs.http_programs("")
        assert status == 200 and len(out["programs"]) == 1
        out, status = programs.http_programs("n=abc")
        assert status == 400
        for s in ("a", "b"):
            _register_square(programs.get_default(), site=s, n=8)
        out, status = programs.http_programs("n=1")
        assert status == 200 and len(out["programs"]) == 1

    def test_profile_endpoint_validation(self, monkeypatch, tmp_path):
        assert programs.http_profile("nope")[1] == 400
        assert programs.http_profile({"duration_s": "x"})[1] == 400
        assert programs.http_profile({"duration_s": 1e9})[1] == 400
        _FakeProfiler().install(monkeypatch)
        sess = programs.profile_session()
        assert sess.start_manual(str(tmp_path / "t"))
        assert programs.http_profile({})[1] == 409
        assert sess.stop_manual()
        out, status = programs.http_profile(
            {"duration_s": 0.0, "directory": str(tmp_path)})
        assert status == 200
        assert programs.load_capture(out["bundle"])["valid"]


# ----------------------------------------------------- slo page hook
class TestSLOProfileHook:
    def _fire(self, tmp_path, **engkw):
        reg = telemetry.MetricsRegistry()
        eng = slo.SLOEngine(
            [slo.Threshold("hot", metric="g", bound=1.0, op=">",
                           severity="page", group_by=())],
            registry=reg, make_default=False,
            flight_dir=str(tmp_path / "fl"),
            profile_dir=str(tmp_path / "pr"),
            profile_duration_s=0.0, **engkw)
        reg.gauge("g").set(5.0)
        eng.tick(now=0.0)
        (a,) = [a for a in eng.alerts() if a.state == "firing"]
        return eng, a

    def test_auto_mode_rides_the_registry_opt_in(self, monkeypatch,
                                                 tmp_path):
        _FakeProfiler().install(monkeypatch)
        _eng, a = self._fire(tmp_path)        # programs disabled: no
        assert a.profile_bundle is None       # capture, incident still
        assert a.incident_dump is not None    # written
        assert "profile_bundle" in a.to_dict()

    def test_page_alert_captures_and_stamps_incident(self, monkeypatch,
                                                     tmp_path):
        _FakeProfiler().install(monkeypatch)
        programs.set_enabled(True)
        _eng, a = self._fire(tmp_path)
        assert a.profile_bundle is not None
        assert programs.load_capture(a.profile_bundle)["valid"]
        assert a.to_dict()["profile_bundle"] == a.profile_bundle
        dump = flight_recorder.load_dump(a.incident_dump)
        assert dump["valid"]
        assert dump["manifest"]["context"]["profile_bundle"] \
            == a.profile_bundle

    def test_profile_on_page_false_disables(self, monkeypatch,
                                            tmp_path):
        _FakeProfiler().install(monkeypatch)
        programs.set_enabled(True)
        _eng, a = self._fire(tmp_path, profile_on_page=False)
        assert a.profile_bundle is None

    def test_refire_inside_min_interval_is_rate_limited(
            self, monkeypatch, tmp_path):
        _FakeProfiler().install(monkeypatch)
        programs.set_enabled(True)
        eng, a = self._fire(tmp_path,
                            profile_min_interval_s=3600.0)
        assert a.profile_bundle is not None
        eng.registry.peek("g").set(0.0)
        eng.tick(now=1.0)
        assert eng.alert_state("hot") == "resolved"
        eng.registry.peek("g").set(5.0)
        eng.tick(now=2.0)
        (b,) = [x for x in eng.alerts() if x.state == "firing"]
        assert b.profile_bundle is None       # inside the window


# -------------------------------------------- flight dump + snapshot
class TestObservabilityEmbeds:
    def test_incident_dump_carries_programs_member(self, tmp_path):
        programs.set_enabled(True)
        _register_square(programs.get_default(), n=8)
        fr = flight_recorder.FlightRecorder(directory=str(tmp_path),
                                            enabled=True)
        fr.record("unit", note="x")
        path = fr.incident("prog_unit")
        dump = flight_recorder.load_dump(path)
        assert dump["valid"]
        assert "programs.json" in dump["manifest"]["digests"]
        assert dump["programs"]["sites"].keys() == {"t_site"}

    def test_telemetry_snapshot_embeds_registry(self):
        telemetry.set_enabled(True)
        programs.set_enabled(True)
        _register_square(programs.get_default(), n=8)
        snap = telemetry.snapshot()
        assert snap["programs"]["sites"].keys() == {"t_site"}

    def test_off_mode_snapshot_has_no_programs_key(self):
        telemetry.set_enabled(True)
        assert "programs" not in telemetry.snapshot()
