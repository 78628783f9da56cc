"""The EXAONE-MoE family in the benchmark, on the CPU: its cell through
``run.py --rehearse`` (the contract's line, sound seeds correct, the
float8 control and two planted faults not correct), its configuration
file against the published numbers, its arithmetic and its three new
readers on recorded spans."""

import json
import os
import shutil
import types

import pytest

from bench_helpers import BENCH, ROOT, bench_run, harness, load

CELL = "k-exaone-236b-a23b.reason-decode"
TINY = os.path.join(BENCH, "rehearse_tiny_exaone.json")
L, G = "sliding_attention", "full_attention"
#: the catalog row's ``config`` (model-configs guide, K-EXAONE-236B-A23B),
#: its three per-layer lists by their pattern
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 6144, "intermediate_size": 18432,
    "layer_types": [L, L, L, G] * 12, "max_position_embeddings": 262144,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "model_type": "exaone_moe", "moe_intermediate_size": 2048,
    "mtp_layer_types": [G], "mtp_sliding_windows": [0], "n_group": 1,
    "norm_topk_prob": True, "num_attention_heads": 64, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 8, "num_nextn_predict_layers": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "sliding_window": 128, "sliding_window_pattern": "LLLG",
    "sliding_windows": [128, 128, 128, 0] * 12,
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 153600}
REDUCED = {"num_hidden_layers", "layer_types", "sliding_windows",
           "mlp_layer_types", "num_experts", "vocab_size",
           "num_nextn_predict_layers", "mtp_layer_types",
           "mtp_sliding_windows"}


def rehearse(seed, seconds=1.5, trace=0, root=ROOT):
    return bench_run.measure(
        ["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), "--rehearse", TINY, "--root", str(root)])


@pytest.fixture(scope="module")
def own_root(tmp_path_factory):
    """A copy of the benchmark to run TRACED rehearsals from (a traced
    run clears ``<root>/benchmark_out/trace``, which another worker's
    traced run may be using in the checkout)."""
    root = tmp_path_factory.mktemp("exaone_cell")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def config():
    return harness.load_json(os.path.join(BENCH, "configs",
                                          "k-exaone-236b-a23b.json"))


def mix():
    return harness.load_json(os.path.join(BENCH, "traffic",
                                          "reason-decode.json"))


# ------------------------------------------------------- the cell's runs
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(trace, capsys, own_root):
    rc = bench_run.main(["--workload", CELL, "--seed", str(2**31 + 29),
                         "--seconds", "1.5", "--trace", str(trace),
                         "--rehearse", TINY, "--root", str(own_root)])
    out, err = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    if trace:
        # the program's counts reach the readers (no device plane here,
        # so the shares of a roofline find nothing and are left out)
        assert {"moe_local_assignment_pct", "kv_window_share_pct",
                "moe_experts_touched_pct", "moe_expert_load_max_over_mean",
                "engine_occupancy_pct", "kv_high_water_pct",
                "compiles_in_window.serve"} <= set(line["metrics"])
        assert not {"attn_cache_roofline_pct", "moe_experts_roofline_pct"} \
            & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert err.strip().splitlines()[-1] == "correct: True"


def test_the_new_counts_add_up(own_root):
    run, line = rehearse(43, seconds=2, trace=1, root=own_root)
    assert line["correct"] is True
    local = run.cell.reader("moe_local_assignment_pct").read(run)
    # 2 of 8 experts held, top-2: 25 when even; random weights, few rows
    assert 5.0 < local < 60.0
    share = run.cell.reader("kv_window_share_pct").read(run)
    kv = run.counters["kv_pages"]
    # 4 slots x 4 window layers x 5 pages of 4 x 32 lanes x K, V x 2 B
    assert kv["window_bytes"] == 4 * 4 * 5 * 4 * 32 * 2 * 2
    assert share == pytest.approx(
        100 * kv["window_high_water_bytes"]
        / (kv["window_high_water_bytes"]
           + kv["high_water"] * kv["page_bytes"]))
    touched = run.cell.reader("moe_experts_touched_pct").read(run)
    assert 0.0 < touched <= 100.0        # of the 2 held
    # the dispatch spans carry what the window layers had to read
    import program_spans
    calls = [d["args"] for _, _, ds in program_spans.bursts(run) for d in ds]
    assert calls and all(
        0 < a["ctx_window_tokens"] <= a["ctx_tokens"] for a in calls)
    assert any(a["ctx_window_tokens"] < a["ctx_tokens"] for a in calls)


@pytest.mark.parametrize("seed", [37, 2**31 + 41])
def test_sound_runs_are_correct_and_the_float8_control_is_not(seed):
    run, line = rehearse(seed)
    assert line["correct"] is True, run.checks
    limit = run.checks["served_gap_mean"]["limit"]
    got = run.cell.reference.check_served(
        run.cell.config, run.seed, run.samples["checked"], precision="fp8")
    assert got["compared"] == sum(len(t) for _, t in run.samples["checked"])
    assert got["mean_gap"] > limit


def test_rings_taken_at_the_buckets_end_are_not_correct(monkeypatch):
    """The planted fault: prefill hands over the window layers' rings
    as they stand after the padded bucket's last position instead of
    the prompt's."""
    import deeplearning4j_tpu.models.exaone_moe as m

    real = m._Prefill._ring

    def at_the_end(self, c):
        self.t0 = c.shape[1]
        return real(self, c)

    monkeypatch.setattr(m._Prefill, "_ring", at_the_end)
    run, line = rehearse(23)
    got = run.checks["served_gap_mean"]
    assert got["value"] > got["limit"] and line["correct"] is False


def test_a_window_one_position_short_is_not_correct(monkeypatch):
    """The planted fault of a window of 15 for 16 (127 for 128), in
    the program alone."""
    prog = load("program/exaone_moe.py")
    real = prog.causal_lm
    monkeypatch.setattr(prog, "causal_lm", lambda cfg: real(
        dict(cfg, sliding_window=cfg["sliding_window"] - 1)))
    run, line = rehearse(29)
    got = run.checks["served_gap_mean"]
    assert got["value"] > got["limit"] and line["correct"] is False


# -------------------------------------------------------- the files
def test_configuration_holds_every_published_number():
    cfg = config()
    assert set(cfg["reduced"]) == REDUCED
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    # the cut: one leading dense layer and the four that follow it, a
    # whole period of the published pattern
    assert cfg["num_hidden_layers"] == 5
    assert cfg["layer_types"] == PUBLISHED["layer_types"][:5] \
        == [L, L, L, G, L]
    assert cfg["sliding_windows"] == PUBLISHED["sliding_windows"][:5]
    assert cfg["mlp_layer_types"] == PUBLISHED["mlp_layer_types"][:5]
    # the share: an eighth of the experts and of the vocabulary, the
    # router at its published width
    assert cfg["num_experts"] * 8 == cfg["n_routed_experts"] \
        == PUBLISHED["num_experts"] == cfg["published"]["num_experts"]
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"] \
        == cfg["published"]["vocab_size"]
    assert cfg["num_nextn_predict_layers"] == 0 \
        and cfg["published"]["num_nextn_predict_layers"] == 1
    assert cfg["published"]["num_hidden_layers"] == 48
    assert "8 chips share each layer" in cfg["deployment"]["stands_for"]
    assert {"norm_placement", "qk_norm_and_rope", "router", "weights",
            "expert_bias", "eos"} <= set(cfg["assumed"])
    assert cfg["dtypes"] == {"params": "bfloat16", "compute": "bfloat16",
                             "kv_pool": "bfloat16"}
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in bench["configs"]}["k-exaone-236b-a23b"]
    assert entry["source"] == cfg["source"] and \
        set(entry["reduced"]) == REDUCED
    assert not REDUCED & {"hidden_size", "intermediate_size", "head_dim",
                          "moe_intermediate_size", "num_experts_per_tok",
                          "sliding_window"}


def test_the_mix_fits_the_engine():
    cfg, m = config(), mix()
    eng = cfg["deployment"]["engine"]
    assert m["clients"] == eng["slots"]
    assert m["prompt_len"]["max"] + m["output_len"]["max"] \
        <= eng["max_context"]
    assert m["prompt_len"]["max"] <= max(eng["prefill_buckets"])
    assert set(m["check"]["limits"]) == {"served_gap_mean",
                                         "length_mismatch"}
    assert m["check"]["sample_requests"] == 8


def test_the_cell_is_in_the_lists_the_issue_names():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    mine = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert mine == {
        "serve_tok_s", "setup_s", "engine_occupancy_pct",
        "engine_steps_per_dispatch", "itl_ms_p99", "kv_high_water_pct",
        "mfu_pct.serve", "device_idle_pct.serve", "queue_wait_ms_p50",
        "prefill_span_ms_p50", "engine_host_ms_per_step",
        "compiles_in_window.serve", "moe_experts_roofline_pct",
        "moe_experts_touched_pct", "moe_expert_load_max_over_mean",
        "attn_cache_roofline_pct", "moe_local_assignment_pct",
        "kv_window_share_pct"}
    for name in mine - {"serve_tok_s", "setup_s"}:
        assert os.path.isfile(os.path.join(BENCH, "readers", name + ".py"))


def test_arithmetic_of_the_family():
    f, cfg = load("flops/exaone_moe.py"), config()
    attn = 6144 * 8192 + 2 * 6144 * 1024 + 8192 * 6144 + 2 * 128
    expert = 3 * 6144 * 2048
    sparse = attn + 17 * expert + 6144 * 128 + 128 + 2 * 6144
    dense = attn + 3 * 6144 * 18432 + 2 * 6144
    assert f.n_params(cfg) == dense + 4 * sparse + 2 * 19200 * 6144 + 6144 \
        == 3_712_028_416
    assert f.expert_bytes(cfg) == expert * 2 == 75_497_472
    assert f.sizes(cfg)["E"] == 16 and f.sizes(cfg)["Er"] == 128
    # a position's K and V: 8 KV heads x 128 x 2 x 2 B a layer; one
    # global layer over the context, four window layers over the window
    assert f.kv_bytes_needed(cfg, 1000, 128) == 4096 * (1000 + 4 * 128)
    # per token: the router's 128 outputs, the shared expert and ONE
    # routed expert in expectation (8 x 16 / 128), not 8 and not 128
    moe = 2 * 6144 * 128 + 2 * 6 * 6144 * 2048
    per_layer = 2 * 6144 * 8192 * 2 + 4 * 6144 * 1024
    assert f.block_flops_per_token(cfg) \
        == 5 * per_layer + 6 * 6144 * 18432 + 4 * moe
    # the causal half of attention: whole in the global layer, cut to
    # the window in the four window layers
    assert f.attn_flops(cfg, 10, 7) == 4 * 64 * 128 * (10 + 4 * 7)
    assert f.prefill_flops(cfg, 100) == 100 * f.block_flops_per_token(cfg) \
        + f.attn_flops(cfg, 5050, 5050) + f.head_flops(cfg)
    t = 1000
    window_pairs = 128 * 129 // 2 + (t - 128) * 128
    assert f.prefill_flops(cfg, t) == t * f.block_flops_per_token(cfg) \
        + f.attn_flops(cfg, t * (t + 1) // 2, window_pairs) \
        + f.head_flops(cfg)
    assert f.served_token_flops(cfg, 600, 7) == f.decode_flops(cfg, 607) \
        == f.block_flops_per_token(cfg) + f.attn_flops(cfg, 607, 128) \
        + 2 * 19200 * 6144


def test_program_takes_the_share_from_the_file():
    prog, cfg = load("program/exaone_moe.py"), config()
    model = prog.causal_lm(cfg)
    c = model.cfg
    assert (c.num_experts, c.n_routed_experts, c.expert_offset) \
        == (16, 128, 0)
    assert c.vocab_size == 19200 and c.ring_pages == 9
    spec = model.cache_spec()
    assert spec["kv_layers"] == 1 and spec["window"] == 128
    assert spec["state"]["k"] == (4, 9, 16, 1024)
    mixed = dict(cfg, dtypes=dict(cfg["dtypes"], params="float32"))
    with pytest.raises(ValueError, match="compute dtype"):
        prog.causal_lm(mixed)
    with pytest.raises(ValueError, match="not implemented"):
        prog.causal_lm(dict(cfg, num_nextn_predict_layers=1))


# ------------------------------------------------------- the readers
def _fake_run(monkeypatch, syncs, calls, per_name, kv_pages=None):
    import program_spans

    bursts = [({"args": {"id": i}}, {"args": a}, [{"args": c} for c in calls])
              for i, a in enumerate(syncs)]
    monkeypatch.setattr(program_spans, "bursts", lambda run: bursts or None)
    cell = harness.Cell(ROOT, CELL)
    cell.peaks = cell.peaks_table["TPU v5 lite"]
    return types.SimpleNamespace(
        cell=cell, window_s=50.0, say=lambda text: None,
        counters={"kv_pages": kv_pages} if kv_pages else {},
        trace={"window_s": 2.0, "per_name": per_name})


def test_new_readers_arithmetic(monkeypatch):
    counts = {"expert_assignments": 70, "expert_assignments_routed": 512,
              "experts_touched": 30, "expert_layer_steps": 2}
    call = {"ctx_tokens": 100_000, "ctx_window_tokens": 8_000, "k": 8}
    run = _fake_run(
        monkeypatch, [counts, counts], [call, call],
        {"paged_attention.1[tpu_custom_call]": 0.3,
         "window_attention.2[tpu_custom_call]": 0.1,
         "moe_experts.3[tpu_custom_call]": 0.9, "fusion.9": 0.2},
        {"capacity": 1000, "high_water": 500, "page_bytes": 65536,
         "window_bytes": 64_000_000, "window_high_water_bytes": 48_000_000})
    got = run.cell.reader("attn_cache_roofline_pct").read(run)
    # 2 bursts x 2 calls; one global and four window layers; both
    # kernels' names count
    need = 4096 * (400_000 + 4 * 32_000)
    assert got == pytest.approx(
        100 * (need / 819e9) / ((0.3 + 0.1) / 2.0 * 50.0))
    assert run.cell.reader("moe_local_assignment_pct").read(run) \
        == pytest.approx(100 * 140 / 1024)
    assert run.cell.reader("kv_window_share_pct").read(run) \
        == pytest.approx(100 * 48e6 / (48e6 + 500 * 65536))
    # the accepted readers take the experts HELD from the family's files
    assert run.cell.reader("moe_experts_touched_pct").read(run) \
        == pytest.approx(100 * 60 / 4 / 16)


@pytest.mark.parametrize("reader", [
    "attn_cache_roofline_pct", "moe_local_assignment_pct",
    "kv_window_share_pct"])
def test_readers_find_nothing_in_a_program_without_the_counts(
        monkeypatch, reader):
    """A program whose spans carry no window or routed counts and whose
    ``stats()`` knows no window store (the parent commit, or a model
    with one kind of cache): every new reader returns None and does not
    raise."""
    run = _fake_run(monkeypatch, [{"steps": 8, "expert_assignments": 5}],
                    [{"ctx_tokens": 1000}],
                    {"paged_attention.1[tpu_custom_call]": 0.2},
                    {"capacity": 10, "high_water": 5, "page_bytes": 64})
    assert run.cell.reader(reader).read(run) is None
    empty = _fake_run(monkeypatch, [], [], {})
    assert empty.cell.reader(reader).read(empty) is None
