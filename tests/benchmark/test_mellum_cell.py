"""The Mellum family in the benchmark, on the CPU: its cell through
``run.py --rehearse`` (the contract's line, sound seeds correct, the
float8 control and four planted faults not correct), its configuration
file against the published numbers, its mix against the engine, its
arithmetic, and its place in the lists of the metrics it reports."""

import json
import os
import shutil

import pytest

from bench_helpers import BENCH, ROOT, bench_run, harness, load

CELL = "mellum2-12b-a2.5b.repo-chat-decode"
TINY = os.path.join(BENCH, "rehearse_tiny_mellum.json")
S, F = "sliding_attention", "full_attention"
YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
#: the catalog row's ``config`` (model-configs guide,
#: Mellum2-12B-A2.5B-Instruct), its two per-layer lists by their pattern
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": [S, S, S, F] * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {F: YARN,
                        S: {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
REDUCED = {"num_hidden_layers", "layer_types", "mlp_layer_types"}
#: the metrics the cell reports besides serve_tok_s and setup_s
READ = {"engine_occupancy_pct", "engine_steps_per_dispatch", "itl_ms_p99",
        "kv_high_water_pct", "mfu_pct.serve", "device_idle_pct.serve",
        "queue_wait_ms_p50", "prefill_span_ms_p50",
        "engine_host_ms_per_step", "compiles_in_window.serve",
        "moe_experts_roofline_pct", "moe_experts_touched_pct",
        "moe_expert_load_max_over_mean", "attn_cache_roofline_pct",
        "kv_window_share_pct"}


def rehearse(seed, seconds=1.5, trace=0, root=ROOT):
    return bench_run.measure(
        ["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), "--rehearse", TINY, "--root", str(root)])


@pytest.fixture(scope="module")
def own_root(tmp_path_factory):
    """A copy of the benchmark to run TRACED rehearsals from (a traced
    run clears ``<root>/benchmark_out/trace``, which another worker's
    traced run may be using in the checkout)."""
    root = tmp_path_factory.mktemp("mellum_cell")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def config():
    return harness.load_json(os.path.join(BENCH, "configs",
                                          "mellum2-12b-a2.5b.json"))


def mix():
    return harness.load_json(os.path.join(BENCH, "traffic",
                                          "repo-chat-decode.json"))


# ------------------------------------------------------- the cell's runs
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(trace, capsys, own_root):
    rc = bench_run.main(["--workload", CELL, "--seed", str(2**31 + 31),
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
        assert {"kv_window_share_pct", "moe_experts_touched_pct",
                "moe_expert_load_max_over_mean", "engine_occupancy_pct",
                "kv_high_water_pct", "compiles_in_window.serve"} \
            <= set(line["metrics"]) <= READ
        assert not {"attn_cache_roofline_pct", "moe_experts_roofline_pct"} \
            & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert err.strip().splitlines()[-1] == "correct: True"


def test_the_counts_reach_the_readers(own_root):
    run, line = rehearse(43, seconds=2, trace=1, root=own_root)
    assert line["correct"] is True
    kv = run.counters["kv_pages"]
    # 4 slots x 6 window layers x 5 pages of 4 x 32 lanes x K, V x 4 B
    assert kv["window_bytes"] == 4 * 6 * 5 * 4 * 32 * 2 * 4
    share = run.cell.reader("kv_window_share_pct").read(run)
    assert share == pytest.approx(
        100 * kv["window_high_water_bytes"]
        / (kv["window_high_water_bytes"]
           + kv["high_water"] * kv["page_bytes"]))
    touched = run.cell.reader("moe_experts_touched_pct").read(run)
    assert 0.0 < touched <= 100.0
    assert run.cell.reader("moe_expert_load_max_over_mean").read(run) >= 1.0
    # the dispatch spans carry what the window layers had to read
    import program_spans
    calls = [d["args"] for _, _, ds in program_spans.bursts(run) for d in ds]
    assert calls and all(
        0 < a["ctx_window_tokens"] <= a["ctx_tokens"] for a in calls)
    assert any(a["ctx_window_tokens"] < a["ctx_tokens"] for a in calls)
    # every expert is held: all that is routed is assigned here
    syncs = [s["args"] for _, s, _ in program_spans.bursts(run)]
    assert all(a["expert_assignments"] == a["expert_assignments_routed"]
               for a in syncs)


@pytest.mark.parametrize("seed", [37, 2**31 + 41])
def test_sound_runs_are_correct_and_the_float8_control_is_not(seed):
    run, line = rehearse(seed)
    assert line["correct"] is True, run.checks
    limit = run.checks["served_gap_mean"]["limit"]
    got = run.cell.reference.check_served(
        run.cell.config, run.seed, run.samples["checked"], precision="fp8")
    assert got["compared"] == sum(len(t) for _, t in run.samples["checked"])
    assert got["mean_gap"] > limit


def _planted(fault, cfg):
    """The configuration the program is built from, with one fault."""
    rp = dict(cfg["rope_parameters"])
    if fault == "plain_rope_on_full":
        rp[F] = {"rope_type": "default", "rope_theta": rp[F]["rope_theta"]}
    elif fault == "no_attention_factor":
        rp[F] = dict(rp[F], attention_factor=1.0)
    elif fault == "window_one_short":
        return dict(cfg, sliding_window=cfg["sliding_window"] - 1)
    elif fault == "router_not_renormalised":
        return dict(cfg, norm_topk_prob=False)
    return dict(cfg, rope_parameters=rp)


@pytest.mark.parametrize("fault", [
    "plain_rope_on_full", "no_attention_factor", "window_one_short",
    "router_not_renormalised"])
def test_a_planted_fault_is_not_correct(monkeypatch, fault):
    """The program built with one fault (the full layers rotated by
    plain RoPE, the attention factor left out, a window of 15 for 16,
    the router's top-k weights not renormalised), the reference as
    published."""
    prog = load("program/mellum.py")
    real = prog.causal_lm
    monkeypatch.setattr(prog, "causal_lm",
                        lambda cfg: real(_planted(fault, cfg)))
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
    # the cut: two whole periods of the published pattern, every expert
    # and every vocabulary row
    assert cfg["num_hidden_layers"] == 8
    assert cfg["layer_types"] == PUBLISHED["layer_types"][:8] \
        == [S, S, S, F] * 2
    assert cfg["mlp_layer_types"] == ["sparse"] * 8
    assert cfg["published"]["num_hidden_layers"] == 28
    assert "pipeline of one-chip stages" in cfg["deployment"]["stands_for"]
    assert {"qk_norm", "window", "router", "intermediate_size", "mtp",
            "rotation", "weights", "eos"} <= set(cfg["assumed"])
    assert cfg["dtypes"] == {"params": "bfloat16", "compute": "bfloat16",
                             "kv_pool": "bfloat16"}
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in bench["configs"]}["mellum2-12b-a2.5b"]
    assert entry["source"] == cfg["source"] and \
        set(entry["reduced"]) == REDUCED
    assert not REDUCED & {"hidden_size", "head_dim", "moe_intermediate_size",
                          "num_experts_per_tok", "num_experts",
                          "sliding_window", "vocab_size", "rope_parameters"}


def test_the_mix_fits_the_engine():
    cfg, m = config(), mix()
    eng = cfg["deployment"]["engine"]
    assert m["clients"] == eng["slots"] == 64
    assert m["prompt_len"]["max"] + m["output_len"]["max"] \
        <= eng["max_context"] == 12288
    assert m["prompt_len"]["max"] <= max(eng["prefill_buckets"]) == 8192
    assert all(b % eng["page_size"] == 0 for b in eng["prefill_buckets"])
    assert set(m["check"]["limits"]) == {"served_gap_mean",
                                         "length_mismatch"}
    assert m["check"]["sample_requests"] == 4
    gen = load("traffic_gen.py")
    plan = gen.serving_requests(m, cfg["vocab_size"], 1)
    lens = [len(r["prompt"]) for reqs in plan for r in reqs]
    assert len(lens) == 256 and min(lens) >= 256
    # most prompts already pass the window: window and full layers
    # read different spans in one queue
    assert sum(n > cfg["sliding_window"] for n in lens) > 0.75 * len(lens)
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= eng["max_context"]
               for reqs in plan for r in reqs)


def test_the_cell_is_in_the_lists_it_joins():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    mine = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert {"serve_tok_s", "setup_s"} | READ <= mine
    assert "moe_local_assignment_pct" not in mine   # it would read 100
    for name in mine - {"serve_tok_s", "setup_s"}:
        assert os.path.isfile(os.path.join(BENCH, "readers", name + ".py"))


def test_arithmetic_of_the_family():
    f, cfg = load("flops/mellum.py"), config()
    attn = 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304
    expert = 3 * 2304 * 896
    layer = attn + 2 * 2304 + 2304 * 64 + 64 * expert
    assert attn == 21_233_664 and layer == 417_747_456
    assert f.n_params(cfg) == 8 * layer + 2 * 98304 * 2304 + 2304 \
        == 3_794_966_784
    assert f.expert_bytes(cfg) == expert * 2 == 12_386_304
    # a position's K and V: 4 KV heads x 128 x 2 x 2 B a layer; two full
    # layers over the context, six window layers over the window
    assert f.kv_bytes_needed(cfg, 5000, 1024) == 2048 * (2 * 5000 + 6 * 1024)
    # per token: the router's 64 outputs and the 8 experts routed to,
    # all held; intermediate_size counts nothing
    per_layer = 2 * 2304 * 4096 * 2 + 4 * 2304 * 512 \
        + 2 * 2304 * 64 + 8 * 6 * 2304 * 896
    assert f.block_flops_per_token(cfg) == 8 * per_layer
    assert f.attn_flops(cfg, 10, 7) == 4 * 32 * 128 * (2 * 10 + 6 * 7)
    t = 3000
    window_pairs = 1024 * 1025 // 2 + (t - 1024) * 1024
    assert f.prefill_flops(cfg, t) == t * f.block_flops_per_token(cfg) \
        + f.attn_flops(cfg, t * (t + 1) // 2, window_pairs) \
        + f.head_flops(cfg)
    assert f.served_token_flops(cfg, 2000, 7) == f.decode_flops(cfg, 2007) \
        == f.block_flops_per_token(cfg) + f.attn_flops(cfg, 2007, 1024) \
        + 2 * 98304 * 2304


def test_program_takes_the_model_from_the_file():
    prog, cfg = load("program/mellum.py"), config()
    model = prog.causal_lm(cfg)
    c = model.cfg
    assert (c.num_experts, c.n_routed_experts, c.expert_offset) == (64, 64, 0)
    assert c.vocab_size == 98304 and c.ring_pages == 65
    assert c.rope_entry(F) == YARN
    spec = model.cache_spec()
    assert spec["kv_layers"] == 2 and spec["window"] == 1024
    assert spec["state"]["k"] == (6, 65, 16, 512)
    mixed = dict(cfg, dtypes=dict(cfg["dtypes"], params="float32"))
    with pytest.raises(ValueError, match="compute dtype"):
        prog.causal_lm(mixed)
    with pytest.raises(ValueError, match="not implemented"):
        prog.causal_lm(dict(cfg, tie_word_embeddings=True))
