"""The LFM2-MoE family in the benchmark, on the CPU: its cell through
``run.py --rehearse`` (the contract's line, sound seeds correct, the
float8 control and a planted fault not correct), its configuration file
against the published numbers, its arithmetic and its four readers."""

import json
import os
import shutil
import types

import pytest

from bench_helpers import BENCH, ROOT, bench_run, harness, load

CELL = "lfm2-24b-a2b.long-decode"
TINY = os.path.join(BENCH, "rehearse_tiny_lfm2.json")
#: the catalog row's ``config`` (model-configs guide, LFM2-24B-A2B)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


def rehearse(seed, seconds=1.5, trace=0, root=ROOT):
    return bench_run.measure(
        ["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), "--rehearse", TINY, "--root", str(root)])


@pytest.fixture(scope="module")
def own_root(tmp_path_factory):
    """A copy of the benchmark to run TRACED rehearsals from: a traced
    run keeps its trace under ``<root>/benchmark_out/trace`` and clears
    that directory first, and another test file's traced run (another
    worker) may be using the checkout's own."""
    root = tmp_path_factory.mktemp("lfm2_cell")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def config():
    return harness.load_json(os.path.join(BENCH, "configs",
                                          "lfm2-24b-a2b.json"))


# ------------------------------------------------------- the cell's runs
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(trace, capsys, own_root):
    rc = bench_run.main(["--workload", CELL, "--seed", str(2**31 + 19),
                         "--seconds", "1.5", "--trace", str(trace),
                         "--rehearse", TINY, "--root", str(own_root)])
    out, err = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    if trace:
        # the program's counts reach the readers (no device plane here,
        # so the two shares of a roofline find nothing and are left out)
        assert {"moe_experts_touched_pct", "moe_expert_load_max_over_mean",
                "engine_occupancy_pct", "kv_high_water_pct"} \
            <= set(line["metrics"])
        assert "moe_experts_roofline_pct" not in line["metrics"]
    else:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert err.strip().splitlines()[-1] == "correct: True"


def test_the_counts_are_of_live_lanes_and_add_up(own_root):
    run, line = rehearse(41, seconds=2, trace=1, root=own_root)
    assert line["correct"] is True
    touched = run.cell.reader("moe_experts_touched_pct").read(run)
    uneven = run.cell.reader("moe_expert_load_max_over_mean").read(run)
    # 8 experts, 2 a token, at most 4 live lanes: between 2 and 8 of 8
    assert 25.0 <= touched <= 100.0
    # the hottest expert has at least the mean, at most every lane
    assert 1.0 <= uneven <= 8 / 2
    both = run.cell.reader("moe_experts_roofline_pct").touched(run)
    assert both[0] > 0 and both[1] > 0


@pytest.mark.parametrize("seed", [31, 2**31 + 33])
def test_sound_runs_are_correct_and_the_float8_control_is_not(seed):
    run, line = rehearse(seed)
    assert line["correct"] is True, run.checks
    limit = run.checks["served_gap_mean"]["limit"]
    got = run.cell.reference.check_served(
        run.cell.config, run.seed, run.samples["checked"], precision="fp8")
    assert got["compared"] == sum(len(t) for _, t in run.samples["checked"])
    assert got["mean_gap"] > limit


def test_conv_state_taken_at_the_buckets_end_is_not_correct(monkeypatch):
    """The planted fault: prefill hands over the conv window of the
    padded bucket's last positions instead of the prompt's."""
    import deeplearning4j_tpu.models.lfm2_moe as m

    def at_the_end(self, ci, window):
        self.states.append(window[0][-self.k:])

    monkeypatch.setattr(m._Prefill, "conv_put", at_the_end)
    run, line = rehearse(23)
    got = run.checks["served_gap_mean"]
    assert got["value"] > got["limit"] and line["correct"] is False


# -------------------------------------------------------- the files
def test_configuration_holds_every_published_number():
    cfg = config()
    reduced = set(cfg["reduced"])
    assert reduced == {"num_hidden_layers", "layer_types", "num_dense_layers"}
    for key, value in PUBLISHED.items():
        if key not in reduced:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 9
    # one leading dense conv layer, then two whole published periods
    assert cfg["layer_types"] == ["conv"] + ["full_attention", "conv",
                                             "conv", "conv"] * 2
    assert cfg["num_dense_layers"] == 1
    assert cfg["published"]["num_hidden_layers"] == 40
    assert {"tie_word_embeddings", "head_dim", "weights", "expert_bias"} \
        <= set(cfg["assumed"])
    assert cfg["dtypes"] == {"params": "bfloat16", "compute": "bfloat16",
                             "kv_pool": "bfloat16"}
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in bench["configs"]}["lfm2-24b-a2b"]
    assert entry["source"] == cfg["source"] and \
        set(entry["reduced"]) == reduced


def test_the_mix_fits_the_engine():
    cfg, mix = config(), harness.load_json(
        os.path.join(BENCH, "traffic", "long-decode.json"))
    eng = cfg["deployment"]["engine"]
    assert mix["clients"] == eng["slots"] == 16
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= eng["max_context"]
    assert mix["prompt_len"]["max"] <= max(eng["prefill_buckets"])
    assert set(mix["check"]["limits"]) == {"served_gap_mean",
                                           "length_mismatch"}


def test_arithmetic_of_the_family():
    f, cfg = load("flops/lfm2_moe.py"), config()
    assert f.n_params(cfg) == 5_177_950_976
    assert f.expert_bytes(cfg) == 3 * 2048 * 1536 * 2
    assert f.kv_bytes_per_position(cfg) == 2 * 8 * 64 * 2 * 2
    # 4 experts a token, not 64
    all_64 = dict(cfg, num_experts_per_tok=64)
    per_expert = 6 * 2048 * 1536
    assert f.block_flops_per_token(all_64) - f.block_flops_per_token(cfg) \
        == 8 * 60 * per_expert
    # the causal half of attention, in the 2 attention layers
    assert f.attn_flops(cfg, 10) == 4 * 32 * 64 * 2 * 10
    assert f.served_token_flops(cfg, 100, 0) == f.prefill_flops(cfg, 100) \
        == 100 * f.block_flops_per_token(cfg) + f.attn_flops(cfg, 5050) \
        + f.head_flops(cfg)
    assert f.served_token_flops(cfg, 100, 7) == f.decode_flops(cfg, 107)


def test_program_wants_parameters_in_the_compute_dtype():
    prog, cfg = load("program/lfm2_moe.py"), config()
    model = prog.causal_lm(cfg)
    assert model.cfg.num_experts == 64 and model.cfg.head_dim == 64
    assert model.cache_spec()["kv_layers"] == 2
    mixed = dict(cfg, dtypes=dict(cfg["dtypes"], params="float32"))
    with pytest.raises(ValueError, match="compute dtype"):
        prog.causal_lm(mixed)


# ------------------------------------------------------- the readers
def _fake_run(monkeypatch, syncs, prefills, per_name):
    import program_spans

    bursts = [({"args": {"id": i}}, {"args": a}, [
        {"args": {"ctx_tokens": 1000}}]) for i, a in enumerate(syncs)]
    monkeypatch.setattr(program_spans, "bursts", lambda run: bursts or None)
    monkeypatch.setattr(program_spans, "admissions",
                        lambda run: [({}, {"args": a}) for a in prefills])
    cell = harness.Cell(ROOT, CELL)
    cell.peaks = cell.peaks_table["TPU v5 lite"]
    run = types.SimpleNamespace(
        cell=cell, window_s=50.0, say=lambda text: None,
        trace={"window_s": 2.0, "per_name": per_name})
    return run


def test_roofline_readers_arithmetic(monkeypatch):
    counts = {"experts_touched": 400, "expert_layer_steps": 10,
              "expert_assignments": 640, "expert_load_max": 45}
    run = _fake_run(monkeypatch, [counts, counts],
                    [{"experts_touched": 100}],
                    {"moe_experts.3[tpu_custom_call]": 0.5,
                     "moe_experts.7[tpu_custom_call]": 0.3,
                     "paged_attention.1[tpu_custom_call]": 0.4,
                     "fusion.9": 0.2})
    got = run.cell.reader("moe_experts_roofline_pct").read(run)
    least = 900 * 18_874_368 / 819e9
    assert got == pytest.approx(100 * least / (0.8 / 2.0 * 50.0))
    gqa = run.cell.reader("paged_attn_roofline_pct.gqa").read(run)
    assert gqa == pytest.approx(
        100 * (2000 * 4096 / 819e9) / (0.4 / 2.0 * 50.0))
    assert run.cell.reader("moe_experts_touched_pct").read(run) \
        == pytest.approx(100 * 800 / 20 / 64)
    assert run.cell.reader("moe_expert_load_max_over_mean").read(run) \
        == pytest.approx(90 * 64 / 1280)


@pytest.mark.parametrize("reader", [
    "moe_experts_roofline_pct", "paged_attn_roofline_pct.gqa",
    "moe_experts_touched_pct", "moe_expert_load_max_over_mean"])
def test_readers_find_nothing_in_a_program_without_the_counts(
        monkeypatch, reader):
    """The parent commit's spans carry no expert counts and its trace
    no such kernel: every new reader returns None and does not raise."""
    run = _fake_run(monkeypatch, [{"steps": 8}], [{"prompt_tokens": 5}],
                    {"fusion.9": 0.2})
    assert run.cell.reader(reader).read(run) is None
    empty = _fake_run(monkeypatch, [], [], {})
    assert empty.cell.reader(reader).read(empty) is None
