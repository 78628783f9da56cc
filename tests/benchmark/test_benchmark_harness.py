"""The harness end to end on the CPU: it refuses to measure off a TPU,
its rehearsal prints the contract's last line, new cells come in as new
files, and a timed path broken underneath comes out as not correct."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench_helpers import BENCH, ROOT, TINY, bench_run, harness, rehearse

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
CELLS = ["gpt2-large.batch-decode", "gpt2-medium.pretrain-1k"]


def _cli(*args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def test_refuses_to_measure_off_tpu():
    p = _cli("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr
    assert not p.stdout.strip()           # no result line


def test_refuses_where_only_the_benchmark_is_there(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=600)
    assert p.returncode != 0 and not p.stdout.strip()
    assert "not in this checkout" in p.stderr


def test_unknown_workload_is_refused():
    with pytest.raises(harness.Refused, match="no workload"):
        harness.Cell(ROOT, "no-such.cell")


def test_device_kind_missing_from_peaks_fails_the_run(monkeypatch):
    import jax

    cell = harness.Cell(ROOT, CELLS[0])

    class Dev:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(harness.Refused, match="no row in"):
        harness.look_for_chip(cell, None)
    Dev.device_kind = "TPU v5 lite"
    cell.chips = 4
    with pytest.raises(harness.Refused, match="needs 4 chips"):
        harness.look_for_chip(cell, None)
    cell.chips = 1
    info, devs = harness.look_for_chip(cell, None)
    assert cell.peaks["bf16_flops_per_s"] == 197e12
    assert cell.peaks["hbm_bytes_per_s"] == 819e9 and cell.peaks["source"]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_contracts_last_line(cell, trace, capsys):
    rc = bench_run.main(["--workload", cell, "--seed", str(2**31 + 17),
                         "--seconds", "1.5", "--trace", str(trace),
                         "--rehearse", TINY])
    out, err = capsys.readouterr()
    assert rc == 0
    lines = out.strip().splitlines()
    line = json.loads(lines[-1])
    assert list(line)[:5] == CONTRACT_KEYS and list(line)[-1] == "checks"
    assert set(line) <= set(CONTRACT_KEYS) | {"breakdown", "checks"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    kind = "per_layer" if trace else "end_to_end"
    mine = {m["name"] for m in bench[kind]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) <= mine
    if not trace:                        # a CPU trace has no device plane
        assert set(line["metrics"]) == mine
    for m in line["metrics"].values():
        assert m["value"] is None        # a CPU run gives no device number
    for prior in lines[:-1]:             # every line names the device
        assert prior.startswith("[cpu cpu x")
    assert "setup phases (s): import=" in out
    for name, c in line["checks"].items():
        assert f"check {name}: value" in err
    assert err.strip().splitlines()[-1] == "correct: True"


def test_every_metric_has_a_reader_and_a_cell_that_reports_what_it_moves():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "readers", m["name"] + ".py"))
        assert set(m["workloads"]) <= cells
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))


# ------------------------------------------------- new cells are new files
DRIVER_STUB = '''
def run(run):
    run.phase("nothing")
    run.setup_done()
    run.window_s = 1.0
    run.e2e["widgets_s"] = float(run.cell.traffic["widgets"])
    run.counters["stub"] = run.cell.config["n_embd"]
    run.attempted = 3
    run.check("stub_check", 0, 0)
'''
READER_STUB = '''
def read(run):
    return 2.0 * run.counters["stub"]
'''


def test_a_new_cell_comes_in_as_new_files_and_entries_only(tmp_path, capsys):
    before = {}
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for d, _, files in os.walk(tmp_path / "benchmark"):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()
    b = tmp_path / "benchmark"
    cfg = json.load(open(b / "configs" / "gpt2-medium.json"))
    cfg["n_embd"] = 21
    (b / "configs" / "newmodel.json").write_text(json.dumps(cfg))
    (b / "traffic" / "newmix.json").write_text(json.dumps(
        {"driver": "newdriver", "widgets": 5, "who": "a test"}))
    (b / "drivers" / "newdriver.py").write_text(DRIVER_STUB)
    (b / "readers" / "new_metric.py").write_text(READER_STUB)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["configs"].append({"name": "newmodel", "source": "a test",
                             "file": "benchmark/configs/newmodel.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "newmodel.newmix", "config": "newmodel",
                               "traffic": "newmix", "chips": 1, "why": "a test"})
    bench["end_to_end"].append({"name": "widgets_s", "unit": "widgets/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["newmodel.newmix"]})
    bench["per_layer"].append({"name": "new_metric", "unit": "things",
                               "better": "higher", "source": "program_counter",
                               "layer": "a test", "moves": "widgets_s",
                               "workloads": ["newmodel.newmix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for trace, want in ((0, "widgets_s"), (1, "new_metric")):
        rc = bench_run.main(["--workload", "newmodel.newmix", "--seed", "1",
                             "--seconds", "1", "--trace", str(trace),
                             "--rehearse", TINY, "--root", str(tmp_path)])
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert rc == 0 and line["correct"] and want in line["metrics"]
        assert "setup_s" in line["metrics"] or trace
    for p, content in before.items():     # no file that was there was edited
        assert open(p, "rb").read() == content


@pytest.mark.parametrize("trace", [0, 1])
def test_the_decode_cell_counts_what_its_metrics_need(trace):
    run, line = rehearse(CELLS[0], seed=41, seconds=2, trace=trace)
    assert line["correct"] is True and run.failed == 0
    c = run.counters
    if trace:
        assert {"engine_occupancy_pct", "engine_steps_per_dispatch",
                "kv_high_water_pct", "itl_ms_p99"} <= set(line["metrics"])
        assert 0 < c["occupancy_sum"] <= c["decode_steps"]
        assert run.cell.reader("itl_ms_p99").read(run) >= 0
    else:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
        assert run.e2e["serve_tok_s"] > 0
        assert c["tokens"] >= c["decode_tokens"] > 0
    # the window is cut to the last delivery inside --seconds
    assert 0 < run.window_s <= run.seconds
    assert run.e2e["serve_tok_s"] == pytest.approx(c["tokens"] / run.window_s)


# ------------------------------------- a broken timed path is not correct
def test_an_altered_token_is_not_correct(monkeypatch):
    from deeplearning4j_tpu.serving.engine import ServingRequest

    push = ServingRequest._push
    seen = [0]

    def altered(self, token):
        seen[0] += 1
        # one token in 3, altered where it is produced
        push(self, (token + 1) % 503 if seen[0] % 3 == 0 else token)

    monkeypatch.setattr(ServingRequest, "_push", altered)
    run, line = rehearse(CELLS[0], seed=21)
    got = run.checks["served_gap_mean"]
    assert got["value"] > got["limit"]
    assert line["correct"] is False


def _break_step(monkeypatch, wrap):
    from deeplearning4j_tpu.models.gpt import CausalLM

    make = CausalLM.make_train_step
    monkeypatch.setattr(CausalLM, "make_train_step",
                        lambda self, updater: wrap(make(self, updater)))


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    def wrap(step):
        def broken(params, opt, it, ids, rng):
            return params, opt, step(params, opt, it, ids, rng)[2]
        return broken

    _break_step(monkeypatch, wrap)
    run, line = rehearse(CELLS[1], seed=22)
    assert line["correct"] is False
    assert run.checks["change_gap"]["value"] == pytest.approx(1.0)
    assert run.checks["change_gap"]["value"] > run.checks["change_gap"]["limit"]


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    def wrap(step):
        def broken(params, opt, it, ids, rng):
            return step(params, opt, it, ids[: ids.shape[0] // 2], rng)
        return broken

    _break_step(monkeypatch, wrap)
    run, line = rehearse(CELLS[1], seed=23)
    assert line["correct"] is False
    assert run.checks["grad_gap"]["value"] > run.checks["grad_gap"]["limit"]


# ------------------------------- the control (float8) is not correct
def test_the_float8_control_is_not_correct_when_serving():
    """The reference in float8, put in the program's place on the same
    prompts: the tokens it puts first lie further below the float32
    reference's best, on average, than the limit allows."""
    run, line = rehearse(CELLS[0], seed=24)
    assert line["correct"] is True
    cell = run.cell
    got = cell.reference.check_served(
        cell.config, run.seed, run.samples["checked"], precision="fp8")
    assert got["compared"] == sum(
        len(tokens) for _, tokens in run.samples["checked"]) > 0
    assert got["mean_gap"] > run.checks["served_gap_mean"]["limit"]
    assert set(run.samples["numbers"]) > set(run.checks)   # some only printed


def test_the_float8_control_is_not_correct_when_training():
    """The reference in float8 on the same batches fails at least one
    of the numbers the cell compares, and no sound number fails."""
    run, line = rehearse(CELLS[1], seed=25)
    assert line["correct"] is True
    cell, s = run.cell, run.samples
    ctl = cell.reference.train_reference(
        cell.config, run.seed, s["batches"],
        cell.config["deployment"]["trainer"], "fp8",
        int(cell.traffic["reference_rows_per_block"]))
    got = cell.reference.compare_training(ctl, s["reference"])
    limits = cell.traffic["check"]["limits"]
    assert any(got[k] > lim for k, lim in limits.items()), got


def test_sound_runs_are_correct_on_other_seeds():
    for cell, seed in ((CELLS[0], 31), (CELLS[1], 32), (CELLS[0], 2**31 + 33)):
        run, line = rehearse(cell, seed=seed)
        assert line["correct"] is True, (cell, run.checks)
