"""The per-layer metrics that read the program's own spans: each reader
on a hand-made ring (and, for the kernel's roofline share, the recorded
trace) against a hand count, nothing to read giving None, and the
rehearsal of each cell listing the new names."""

import json
import os
import shutil
import time
import types

import pytest

from bench_helpers import BENCH, ROOT, TINY, bench_run, load, tiny_cfg

from deeplearning4j_tpu.profiler import telemetry

tr = load("trace_reduce.py")
spans = load("program_spans.py")

SERVE_READERS = ["queue_wait_ms_p50", "prefill_span_ms_p50",
                 "engine_host_ms_per_step", "compiles_in_window.serve"]
READERS = SERVE_READERS + ["paged_attn_roofline_pct",
                           "compiles_in_window.train"]


def read(name, run):
    return load(f"readers/{name}.py").read(run)


@pytest.fixture
def ring():
    """A hand-made ring far from this process's own clock readings:
    process start T, set-up 10 s, a window of 20 s -> [T+10, T+30]."""
    telemetry.reset()
    telemetry.set_enabled(True)
    T = time.perf_counter() + 1000.0
    rec = lambda name, a, b, **kw: telemetry.record_span(
        name, T + a, T + b, **kw)

    def burst(a, b, sync, emit, dispatches):
        bid = rec("engine.burst", a, b, engine="e0")
        t = a
        for k, ctx, dur in dispatches:
            rec("engine.dispatch", t, t + dur, parent=bid, k=k, live=2,
                ctx_tokens=ctx)
            t += dur
        rec("engine.sync", *sync, parent=bid,
            steps=sum(k for k, _, _ in dispatches),
            dispatches=len(dispatches))
        rec("engine.emit", *emit, parent=bid, tokens=3)

    def admission(rid, admit, prefill, wait, hit=0):
        aid = rec("engine.admit", *admit, request=rid, engine="e0", slot=0,
                  reuse="prefix" if hit else "cold", pages=2)
        rec("request.queue_wait", *wait, request=rid, engine="e0",
            prompt_tokens=9)
        # a suffix prefill runs the paged kernel over the 9 positions
        late = {"ctx_tokens": 9} if hit else {}
        rec("engine.prefill", *prefill, parent=aid, request=rid, bucket=16,
            engine="e0", prompt_tokens=9, hit_tokens=hit, **late)

    rec("jit.compile", 2.0, 3.0, fun="jit(step)")            # set-up
    rec("jit.cache_load", 12.0, 12.5, fun="jit(decode)")     # in the window
    rec("jit.compile", 29.9, 30.5, fun="jit(late)")          # ends after it
    burst(8.0, 9.6, (8.1, 9.5), (9.5, 9.6), [(8, 500, 0.01)])   # before
    # in: host 4.1 - 3.98 = 0.12 s, 12 steps
    burst(10.0, 14.1, (10.02, 14.0), (14.0, 14.05),
          [(8, 100, 0.010), (4, 60, 0.006)])
    # in by its sync (29.9), though the burst closes after the window:
    # host 2.2 - 1.88 = 0.32 s, 2 steps
    burst(28.0, 30.2, (28.02, 29.9), (29.9, 30.2), [(2, 30, 0.004)])
    burst(30.3, 31.2, (30.4, 31.0), (31.0, 31.2), [(4, 700, 0.01)])  # after
    admission(0, (4.9, 5.3), (5.0, 5.2), (4.0, 5.0), hit=4)         # before
    admission(1, (15.0, 15.5), (15.1, 15.4), (13.0, 15.1))   # self 0.2
    admission(2, (19.95, 20.15), (20.0, 20.1), (19.9, 20.0), hit=4)  # 0.1
    admission(3, (25.0, 25.6), (25.0, 25.5), (25.0, 25.0))    # self 0.1
    yield T
    telemetry.reset()


def fake_run(T, trace=None, peaks=None, cfg=None):
    said = []
    return types.SimpleNamespace(
        t_start=T, e2e={"setup_s": 10.0}, window_s=20.0, seconds=21.0,
        trace=trace, devices=[0], said=said, say=said.append,
        cell=types.SimpleNamespace(peaks=peaks, config=cfg))


def test_the_span_readers_give_the_hand_count(ring):
    run = fake_run(ring)
    assert read("queue_wait_ms_p50", run) == pytest.approx(100.0)  # 2100 100 0
    assert "3 samples" in run.said[-1]
    assert read("prefill_span_ms_p50", run) == pytest.approx(300.0)  # 300 100 500
    # (0.12 + 0.32) of the bursts + (0.2 + 0.1 + 0.1) of the admissions
    # over 12 + 2 steps
    assert read("engine_host_ms_per_step", run) == pytest.approx(840.0 / 14)
    assert read("compiles_in_window.serve", run) == 1
    assert read("compiles_in_window.train", run) == 1
    got = spans.bursts(run)
    assert [s["args"]["steps"] for _, s, _ in got] == [12, 2]
    assert [[d["args"]["k"] for d in ds] for _, _, ds in got] == [[8, 4], [2]]


def test_an_even_count_takes_the_middle_of_the_two(ring):
    run = fake_run(ring)
    run.window_s = 12.0              # [T+10, T+22]: requests 1 and 2
    assert read("queue_wait_ms_p50", run) == pytest.approx(1100.0)
    assert read("prefill_span_ms_p50", run) == pytest.approx(200.0)
    # the burst whose sync ends at T+29.9 is out now
    assert read("engine_host_ms_per_step", run) == pytest.approx(
        (120.0 + 200.0 + 100.0) / 12)
    assert read("compiles_in_window.serve", run) == 1


def _recorded(rename):
    """The recorded trace with some operations renamed to the kernel ->
    (reduced trace, the renamed events' plain share of the window)."""
    trace = json.load(open(os.path.join(BENCH, "data", "recorded_trace.json")))
    lo, hi = trace.pop("known")["window_ns"]
    total = 0
    for plane, evs in trace["device"].items():
        for i, (name, s, d) in enumerate(evs):
            if name.startswith("neg.") or name.startswith("copy."):
                assert lo <= s and s + d <= hi
                evs[i] = [rename(name), s, d]
                total += d
    assert total > 0
    return tr.reduce(trace), total / (hi - lo)


@pytest.mark.parametrize("spelling", [
    lambda n: "paged_attention." + n.split(".")[1] + "[tpu_custom_call]",
    lambda n: "closed_call." + n.split(".")[1] + "[tpu_custom_call]"])
def test_the_roofline_share_is_needed_bytes_over_the_kernels_time(ring, spelling):
    trace, share = _recorded(spelling)
    cfg = tiny_cfg()                          # 2 layers of width 64
    cfg["dtypes"] = {"kv_pool": "bfloat16"}
    run = fake_run(ring, trace, {"hbm_bytes_per_s": 819e9}, cfg)
    # 190 positions the window's decode steps attended and 9 its one
    # suffix prefill did (the kernel, with more than one query), x 2
    # layers x K and V x 64 wide x 2 bytes
    least_s = (100 + 60 + 30 + 9) * 2 * 2 * 64 * 2 / 819e9
    want = 100.0 * least_s / (share * 20.0)
    assert read("paged_attn_roofline_pct", run) == pytest.approx(want, rel=1e-6)
    assert "199 attended positions (9 of them by suffix prefills)" \
        in run.said[-1]
    # fp8 pages halve the bytes the same contexts need
    cfg["dtypes"]["kv_pool"] = "float8_e4m3fn"
    assert read("paged_attn_roofline_pct", run) == pytest.approx(want / 2, rel=1e-6)


def test_the_kernels_own_name_wins_over_the_custom_calls():
    rd = load("readers/paged_attn_roofline_pct.py")
    per = {"paged_attention.3[tpu_custom_call]": 1.0, "fusion.2": 2.0,
           "flash.1[tpu_custom_call]": 3.0}
    assert rd.kernel_names(per) == ["paged_attention.3[tpu_custom_call]"]
    del per["paged_attention.3[tpu_custom_call]"]
    assert rd.kernel_names(per) == ["flash.1[tpu_custom_call]"]
    assert rd.kernel_names({"fusion.2": 2.0}) == []


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_gives_none_and_never_raises(ring, name, monkeypatch):
    trace, _ = _recorded(lambda n: "paged_attention." + n.split(".")[1])
    cfg = dict(tiny_cfg(), dtypes={"kv_pool": "bfloat16"})
    peaks = {"hbm_bytes_per_s": 819e9}
    assert read(name, fake_run(ring, trace, peaks, cfg)) is not None
    # the window before anything ran: no record of its own
    early = fake_run(ring - 500.0, trace, peaks, cfg)
    assert read(name, early) is None
    if name == "paged_attn_roofline_pct":
        # no trace, no peak for this device, no kernel in the trace
        assert read(name, fake_run(ring, None, peaks, cfg)) is None
        assert read(name, fake_run(ring, trace, None, cfg)) is None
        plain = tr.reduce(json.load(open(os.path.join(
            BENCH, "data", "recorded_trace.json"))))
        assert read(name, fake_run(ring, plain, peaks, cfg)) is None
    # telemetry switched off: the ring is not the run's account
    telemetry.set_enabled(False)
    assert read(name, fake_run(ring, trace, peaks, cfg)) is None
    telemetry.set_enabled(True)
    # a program from before the spans (the parent commit) has no accessor
    monkeypatch.delattr(telemetry, "spans_between")
    assert read(name, fake_run(ring, trace, peaks, cfg)) is None


def test_a_ring_that_was_not_listening_counts_no_compiles(ring):
    run = fake_run(ring)
    run.window_s = 1.0
    assert read("compiles_in_window.serve", run) == 0   # [T+10, T+11]: none
    telemetry.clear_trace()
    assert read("compiles_in_window.serve", run) is None


def rehearse_apart(tmp_path, workload, seconds):
    """One traced rehearsal from a copy of the benchmark. The harness
    keeps its trace under ``<root>/benchmark_out/trace``, and the
    rehearsals of the other test files, in other workers, use the
    checkout's own."""
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    return bench_run.measure(
        ["--workload", workload, "--seed", str(2**31 + 3), "--seconds",
         str(seconds), "--trace", "1", "--rehearse", TINY,
         "--root", str(tmp_path)])


def test_the_decode_rehearsal_lists_the_new_names_and_its_spans_add_up(tmp_path):
    run, line = rehearse_apart(tmp_path, "gpt2-large.batch-decode", 2)
    assert line["correct"] is True
    assert set(SERVE_READERS) <= set(line["metrics"])
    # a CPU trace has no device plane: the kernel's share has no time
    assert "paged_attn_roofline_pct" not in line["metrics"]
    got = spans.bursts(run)
    k_sum = sum(d["args"]["k"] for _, _, ds in got for d in ds)
    assert k_sum == sum(s["args"]["steps"] for _, s, _ in got) > 0
    # the driver reads stats() a moment after the window's end: a burst
    # that ended in that moment is in its delta and not in the window
    extra = run.counters["decode_steps"] - k_sum
    assert 0 <= extra <= 4 * 8
    assert read("compiles_in_window.serve", run) is not None
    waits = spans.ended_in_window(run, "request.queue_wait")
    assert len(waits) == len({w["args"]["request"] for w in waits}) >= 4
    assert read("engine_host_ms_per_step", run) > 0


def test_the_training_rehearsal_lists_its_new_name(tmp_path):
    run, line = rehearse_apart(tmp_path, "gpt2-medium.pretrain-1k", 1.5)
    assert line["correct"] is True
    assert "compiles_in_window.train" in line["metrics"]
    assert read("compiles_in_window.train", run) == 0
    # the set-up's compilations are in the ring, before the window
    before = [e for e in telemetry.spans_between(
        run.t_start, run.t_start + run.e2e["setup_s"])
        if e["name"] in spans.JIT]
    assert before and all("fun" in e["args"] for e in before)
