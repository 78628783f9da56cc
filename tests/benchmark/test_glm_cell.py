"""The GLM-MoE-DSA family in the benchmark, on the CPU: its cell through
``run.py --rehearse`` (the contract's line, sound seeds correct, the
float8 control and the planted faults of the selection and of the
latent row not correct), its configuration file against the published
numbers, its arithmetic and its three new readers on recorded spans."""

import json
import os
import shutil
import types

import pytest

from bench_helpers import BENCH, ROOT, bench_run, harness, load

CELL = "glm-5.2.long-reason-decode"
TINY = os.path.join(BENCH, "rehearse_tiny_glm.json")
F, S = "full", "shared"
#: the catalog row's ``config`` (model-configs guide, GLM-5.2), its two
#: per-layer lists by their pattern
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "head_dim": 192, "hidden_act": "silu", "hidden_size": 6144,
    "index_head_dim": 128, "index_n_heads": 32,
    "index_share_for_mtp_iteration": True, "index_skip_topk_offset": 3,
    "index_topk": 2048, "index_topk_freq": 4, "index_topk_pattern": None,
    "indexer_rope_interleave": True,
    "indexer_types": [F] * 3 + [S, S, S, F] * 18 + [S] * 3,
    "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 1048576,
    "mlp_layer_types": ["dense"] * 3 + ["sparse"] * 75,
    "model_type": "glm_moe_dsa", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 78, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 1, "q_lora_rank": 2048, "qk_head_dim": 256,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_interleave": True,
    "rope_parameters": {"rope_theta": 8000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 256, "vocab_size": 154880}
REDUCED = {"num_hidden_layers", "indexer_types", "mlp_layer_types",
           "first_k_dense_replace", "num_experts", "vocab_size",
           "num_nextn_predict_layers"}


def rehearse(seed, seconds=1.5, trace=0, root=ROOT):
    return bench_run.measure(
        ["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), "--rehearse", TINY, "--root", str(root)])


@pytest.fixture(scope="module")
def own_root(tmp_path_factory):
    """A copy of the benchmark to run TRACED rehearsals from (a traced
    run clears ``<root>/benchmark_out/trace``, which another worker's
    traced run may be using in the checkout)."""
    root = tmp_path_factory.mktemp("glm_cell")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def config():
    return harness.load_json(os.path.join(BENCH, "configs", "glm-5.2.json"))


def mix():
    return harness.load_json(os.path.join(BENCH, "traffic",
                                          "long-reason-decode.json"))


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
        assert {"attn_selected_share_pct", "moe_local_assignment_pct",
                "moe_experts_touched_pct", "moe_expert_load_max_over_mean",
                "engine_occupancy_pct", "kv_high_water_pct",
                "compiles_in_window.serve"} <= set(line["metrics"])
        assert not {"sparse_attn_roofline_pct", "index_select_roofline_pct",
                    "moe_experts_roofline_pct"} & set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"serve_tok_s", "setup_s"}
    assert err.strip().splitlines()[-1] == "correct: True"


def test_the_new_counts_add_up(own_root):
    run, line = rehearse(43, seconds=2, trace=1, root=own_root)
    assert line["correct"] is True
    share = run.cell.reader("attn_selected_share_pct").read(run)
    # contexts of 10-120 against a selection of 16: well under all of it
    assert 20.0 < share < 100.0
    import program_spans
    calls = [d["args"] for _, _, ds in program_spans.bursts(run) for d in ds]
    assert calls and all(
        0 < a["ctx_selected_tokens"] <= a["ctx_tokens"]
        == a["ctx_index_tokens"] for a in calls)
    assert share == pytest.approx(
        100 * sum(a["ctx_selected_tokens"] for a in calls)
        / sum(a["ctx_tokens"] for a in calls))
    kv = run.counters["kv_pages"]
    # (1 + 4 slots x 8 pages) x 16 positions x float32 (the rehearsal's
    # dtype): 5 layers of 128 lanes (a latent of 32 + 8), 2 full
    # layers of 16
    assert kv["store_bytes"] == {"latent": 5 * 33 * 16 * 128 * 4,
                                 "index_k": 2 * 33 * 16 * 16 * 4}
    assert kv["page_bytes"] * 33 == sum(kv["store_bytes"].values())
    local = run.cell.reader("moe_local_assignment_pct").read(run)
    assert 5.0 < local < 60.0          # 2 of 8 held, top-2: 25 when even


@pytest.mark.parametrize("seed", [37, 2**31 + 41])
def test_sound_runs_are_correct_and_the_float8_control_is_not(seed):
    run, line = rehearse(seed)
    assert line["correct"] is True, run.checks
    limit = run.checks["served_gap_mean"]["limit"]
    got = run.cell.reference.check_served(
        run.cell.config, run.seed, run.samples["checked"], precision="fp8")
    assert got["compared"] == sum(len(t) for _, t in run.samples["checked"])
    assert got["mean_gap"] > limit


def _unrotated(monkeypatch):
    import deeplearning4j_tpu.models.glm_moe_dsa as m

    real = m.GlmMoeDsaLM._attn

    def attn(self, li, lp, x, cache, pos):
        attend = cache.attend
        raw = (x @ lp["wkv_a"])[..., self.cfg.kv_lora_rank:]
        cache.attend = lambda li, lp, qn, qr, c_kv, k_r, index, pos: \
            attend(li, lp, qn, qr, c_kv, raw, index, pos)
        try:
            return real(self, li, lp, x, cache, pos)
        finally:
            del cache.attend

    monkeypatch.setattr(m.GlmMoeDsaLM, "_attn", attn)


#: fault -> what the program is given in place of the file's value
FAULTS = {
    "attends_every_position": {"index_topk": 10 ** 6},
    "stale_selection": {"indexer_types": [F, S, S, S, S]},
    "topk_one_short": None,        # index_topk - 1, whatever it is here
    "unrotated_k_r": _unrotated,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_not_correct(monkeypatch, fault):
    """The planted faults, in the program alone: attention over every
    position instead of the selected ones; the second full layer
    keeping the first one's (stale) selection; a top-k one short; a
    latent row whose ``k_r`` was never rotated."""
    plant = FAULTS[fault]
    if callable(plant):
        plant(monkeypatch)
    else:
        prog = load("program/glm_moe_dsa.py")
        real = prog.causal_lm
        over = plant or (lambda cfg: {"index_topk": cfg["index_topk"] - 1})
        monkeypatch.setattr(prog, "causal_lm", lambda cfg: real(
            dict(cfg, **(over(cfg) if callable(over) else over))))
    run, line = rehearse(23)
    got = run.checks["served_gap_mean"]
    assert got["value"] > got["limit"] and line["correct"] is False


# ------------------------------- the selection itself, not the tokens
#: fault -> (what the program is given, the layer it shows in, how)
SELECTION_FAULTS = {
    "sound": ({}, None, None),
    "attends_every_position": ({"index_topk": 10 ** 6}, 0, "count_off"),
    "stale_selection": ({"indexer_types": [F, S, S, S, S]}, 4,
                        "beyond_share"),
    "topk_one_short": ({"index_topk": 15}, 0, "count_off"),
}


@pytest.mark.parametrize("fault", sorted(SELECTION_FAULTS))
def test_selection_check_reads_the_positions_the_program_attended(
        monkeypatch, fault):
    """``tools/selection_check.py``: the positions each decode step of
    each attention layer was handed, against the reference's selection
    in force at that layer. A sound program agrees position for
    position (float32 here); each fault of the selection shows in the
    layer it was planted in, whatever the tokens did."""
    tool = load("tools/selection_check.py")
    over, layer, how = SELECTION_FAULTS[fault]
    prog = load("program/glm_moe_dsa.py")
    real = prog.causal_lm
    monkeypatch.setattr(prog, "causal_lm",
                        lambda cfg: real(dict(cfg, **over)))
    cell = harness.Cell(ROOT, CELL, TINY)
    got = tool.check(cell, 29, prompt_tokens=70, new_tokens=24,
                     say=lambda text: None)
    assert got["steps"] == 23 and sorted(got["layers"]) == [0, 1, 2, 3, 4]
    if layer is None:
        assert got["selection_ok"] is True
        assert all(row["agree_min"] == 1.0 and row["count_off"] == 0
                   and row["steps"] == 23 for row in got["layers"].values())
        return
    assert got["selection_ok"] is False
    assert got["layers"][layer][how] > (0.2 if how == "beyond_share" else 0)
    # and the layers before the fault are untouched by it
    assert all(got["layers"][li]["count_off"] == 0
               and got["layers"][li]["beyond_share"] == 0.0
               for li in range(layer))


# -------------------------------------------------------- the files
def test_configuration_holds_every_published_number(tmp_path):
    cfg = config()
    assert set(cfg["reduced"]) == REDUCED
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    # the cut: published layers 2-6, the last leading dense layer and
    # one whole period of the indexer's pattern after it
    assert cfg["num_hidden_layers"] == 5
    assert cfg["indexer_types"] == PUBLISHED["indexer_types"][2:7] \
        == [F, S, S, S, F]
    assert cfg["mlp_layer_types"] == PUBLISHED["mlp_layer_types"][2:7]
    assert cfg["first_k_dense_replace"] == 1
    # the share: a sixteenth of the experts, an eighth of the
    # vocabulary, the router at its published width
    assert cfg["num_experts"] * 16 == cfg["n_routed_experts"] \
        == PUBLISHED["n_routed_experts"] == 256
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"] \
        == cfg["published"]["vocab_size"]
    assert cfg["num_nextn_predict_layers"] == 0 \
        and cfg["published"]["num_nextn_predict_layers"] == 1
    assert cfg["published"]["num_hidden_layers"] == 78
    assert "16 chips share each layer" in cfg["deployment"]["stands_for"]
    assert "layers 2-6" in cfg["deployment"]["stands_for"]
    assert {"block", "rope", "indexer", "index_share", "router", "weights",
            "expert_bias", "eos"} <= set(cfg["assumed"])
    assert cfg["dtypes"] == {"params": "bfloat16", "compute": "bfloat16",
                             "kv_pool": "bfloat16"}
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in bench["configs"]}["glm-5.2"]
    assert entry["source"] == cfg["source"] and \
        set(entry["reduced"]) == REDUCED
    # no width is cut
    assert not any(k.endswith(("_dim", "_rank")) or k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_experts_per_tok", "index_topk") for k in REDUCED)
    # the catalog itself, where this machine has it
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        row = next(r for r in map(json.loads, open(catalog))
                   if r["name"] == "GLM-5.2")
        assert row["config"] == PUBLISHED
        assert row["source_url"] == cfg["source"]


@pytest.mark.parametrize("bias_range, steady", [(None, True), (0.02, False)])
def test_the_held_share_of_the_routing_does_not_follow_the_seed(
        bias_range, steady):
    """The routed product reads the experts a step touches, so a step's
    bytes follow the share of the routing that lands on the 16 experts
    held. With the selection bias as the file states it (at rest) that
    share is the same from seed to seed; drawn at 0.02 it is not: the
    top 8 of 256 sigmoid scores lie where the sigmoid is flat, and the
    bias outweighs the router's product (the cell was refused for the
    spread that gave, PR 34). The router at its published shape, a
    layer's other leaves left unmade."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = load("reference/glm_moe_dsa.py")
    cfg = config()
    if bias_range is None:
        assert cfg["expert_bias_range"] == 0.0
    else:
        cfg = dict(cfg, expert_bias_range=bias_range)
    z, leaves = ref.sizes(cfg), ref.layer_leaves(cfg, 1)
    held = []
    for seed in (11, 12, 13, 14, 15, 2**31 + 16):
        key = ref.seed_key(seed)
        lp = {name: ref._leaf(key, 2, ref._LEAVES.index(name),
                              *leaves[name]).astype(jnp.float32)
              for name in ("router", "router_bias")}
        x = jax.random.normal(jax.random.fold_in(key, 99),
                              (4096, z["d"]), jnp.float32)
        idx, _ = ref.route(lp, x, z)
        held.append(float(np.mean(np.asarray(idx) < z["E"])))
    spread = (max(held) - min(held)) / (z["E"] / z["Er"])
    assert (spread < 0.12) == steady, held
    if steady:
        assert not np.any(np.asarray(lp["router_bias"]))
    else:
        assert spread > 0.25, held


def test_the_mix_fits_the_engine():
    cfg, m = config(), mix()
    eng = cfg["deployment"]["engine"]
    assert eng == {"slots": 32, "page_size": 16, "max_context": 14336,
                   "prefill_buckets": [1024, 2048, 3072, 4096, 6144, 8192]}
    assert m["clients"] == eng["slots"] and m["requests_per_client"] == 4
    assert m["prompt_len"]["max"] + m["output_len"]["max"] \
        == eng["max_context"]
    assert m["prompt_len"]["max"] <= max(eng["prefill_buckets"])
    assert set(m["check"]["limits"]) == {"served_gap_mean",
                                         "length_mismatch"}
    assert m["check"]["sample_requests"] == 4
    assert m["driver"] == "serve_closed" and m["temperature"] == 0.0
    # nearly every decode step selects: prompts alone reach index_topk
    gen = load("traffic_gen.py")
    plan = gen.serving_requests(m, cfg["vocab_size"], 1)
    lens = [len(r["prompt"]) for reqs in plan for r in reqs]
    assert len(lens) == 128 and min(lens) >= 1024
    assert sum(n >= cfg["index_topk"] for n in lens) > 0.85 * len(lens)
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= eng["max_context"]
               for reqs in plan for r in reqs)


def test_the_cell_is_in_the_lists_the_issue_names():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    mine = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert mine == {
        "serve_tok_s", "setup_s", "engine_occupancy_pct",
        "engine_steps_per_dispatch", "itl_ms_p99", "kv_high_water_pct",
        "mfu_pct.serve", "device_idle_pct.serve", "queue_wait_ms_p50",
        "prefill_span_ms_p50", "engine_host_ms_per_step",
        "compiles_in_window.serve", "moe_experts_roofline_pct",
        "moe_experts_touched_pct", "moe_expert_load_max_over_mean",
        "moe_local_assignment_pct", "sparse_attn_roofline_pct",
        "index_select_roofline_pct", "attn_selected_share_pct"}
    for name in mine - {"serve_tok_s", "setup_s"}:
        assert os.path.isfile(os.path.join(BENCH, "readers", name + ".py"))
    new = [m for m in bench["per_layer"] if m["name"] in (
        "sparse_attn_roofline_pct", "index_select_roofline_pct",
        "attn_selected_share_pct")]
    assert bench["per_layer"][-3:] == new
    assert all(m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
               for m in new)


def test_arithmetic_of_the_family():
    f, cfg = load("flops/glm_moe_dsa.py"), config()
    attn = 6144 * 2048 + 2048 * (64 * 256) + 6144 * 576 \
        + 512 * (64 * 448) + (64 * 256) * 6144
    assert attn + 2048 + 512 == 165_022_208
    indexer = 2048 * 4096 + 6144 * 128 + 6144 * 32
    expert = 3 * 6144 * 2048
    norms = 2 * 6144 + 2048 + 512
    dense = attn + norms + indexer + 256 + 3 * 6144 * 12288
    sparse = attn + norms + 17 * expert + 6144 * 256 + 256
    assert dense == 400_898_816 and sparse == 808_336_128
    assert f.n_params(cfg) == dense + 3 * sparse + (sparse + indexer + 256) \
        + 2 * 19360 * 6144 + 6144 == 3_881_517_056
    assert f.expert_bytes(cfg) == expert * 2 == 75_497_472
    z = f.sizes(cfg)
    assert (z["E"], z["Er"], z["full"], z["shared"]) == (16, 256, 2, 3)
    # a position's latent row: 576 numbers x 2 B in each of 5 layers,
    # whatever it rests in; an indexer key: 128 x 2 B in 2 layers
    assert f.latent_bytes_needed(cfg, 2048) == 5 * 2048 * 1152
    assert f.index_bytes_needed(cfg, 6000) == 2 * 6000 * 256
    # per token: the router's 256 outputs, the shared expert and HALF a
    # routed expert in expectation (8 x 16 / 256), not 8 and not 256
    moe = 2 * 6144 * 256 + 1.5 * 6 * 6144 * 2048
    assert f.block_flops_per_token(cfg) == 5 * 2 * attn + 2 * 2 * indexer \
        + 6 * 6144 * 12288 + 4 * moe
    # a decode step: min(context, 2048) keys an attention layer at the
    # published per-head widths, the whole context an indexer layer
    ctx = lambda sel, scored: 5 * 2 * 64 * (256 + 256) * sel \
        + 2 * 2 * 32 * 128 * scored
    assert f.context_flops(cfg, 2048, 6000) == ctx(2048, 6000)
    assert f.served_token_flops(cfg, 5000, 7) == f.decode_flops(cfg, 5007) \
        == f.block_flops_per_token(cfg) + ctx(2048, 5007) + 2 * 19360 * 6144
    assert f.decode_flops(cfg, 100) == f.block_flops_per_token(cfg) \
        + ctx(100, 100) + 2 * 19360 * 6144
    t = 5000
    pairs = 2048 * 2049 // 2 + (t - 2048) * 2048
    assert f.prefill_flops(cfg, t) == t * f.block_flops_per_token(cfg) \
        + ctx(pairs, t * (t + 1) // 2) + f.head_flops(cfg)


def test_program_takes_the_share_from_the_file():
    prog, cfg = load("program/glm_moe_dsa.py"), config()
    model = prog.causal_lm(cfg)
    c = model.cfg
    assert (c.num_experts, c.n_routed_experts, c.expert_offset) \
        == (16, 256, 0)
    assert c.vocab_size == 19360 and c.index_topk == 2048
    assert model.cache_spec() == {
        "stores": {"latent": (5, 640), "index_k": (2, 128)},
        "state": None, "selected": 2048}
    mixed = dict(cfg, dtypes=dict(cfg["dtypes"], params="float32"))
    with pytest.raises(ValueError, match="compute dtype"):
        prog.causal_lm(mixed)
    with pytest.raises(ValueError, match="not implemented"):
        prog.causal_lm(dict(cfg, num_nextn_predict_layers=1))


# ------------------------------------------------------- the readers
def _fake_run(monkeypatch, calls, per_name, device_ops=()):
    import program_spans

    bursts = [({"args": {"id": 0}}, {"args": {"steps": 8}},
               [{"args": c} for c in calls])] if calls else []
    monkeypatch.setattr(program_spans, "bursts", lambda run: bursts or None)
    cell = harness.Cell(ROOT, CELL)
    cell.peaks = cell.peaks_table["TPU v5 lite"]
    return types.SimpleNamespace(
        cell=cell, window_s=50.0, say=lambda text: None,
        counters={"slots": 32},
        trace={"window_s": 2.0, "per_name": per_name,
               "device_ops": [list(d) for d in device_ops]})


def test_new_readers_arithmetic(monkeypatch):
    call = {"ctx_tokens": 1_500_000, "ctx_selected_tokens": 500_000,
            "ctx_index_tokens": 1_500_000, "k": 8}
    run = _fake_run(monkeypatch, [call, call], {
        "sparse_latent_attention.1[tpu_custom_call]": 0.12,
        "fusion.7": 0.08, "index_scores.3[tpu_custom_call]": 0.05,
        "sort.2": 0.07, "moe_experts.3[tpu_custom_call]": 0.9,
        "fusion.9": 0.2},
        # the breakdown by kind: the gather is the fusion whose result is
        # 32 slots x 2,048 rows of 640 lanes
        [("moe_experts[tpu_custom_call] bf16[256,2048]", 0.9),
         ("fusion f32[32]", 0.2), ("fusion bf16[65536,640]", 0.06),
         ("fusion s32[65536]", 0.02)])
    assert run.cell.reader("attn_selected_share_pct").read(run) \
        == pytest.approx(100 / 3)
    # 1,000,000 selected positions x 5 layers x 1,152 B over the kernel's
    # and the gather's time (the gather found by its kind and shape)
    assert run.cell.reader("sparse_attn_roofline_pct").read(run) \
        == pytest.approx(100 * (5 * 1e6 * 1152 / 819e9)
                         / ((0.12 + 0.08) / 2.0 * 50.0))
    # 3,000,000 scored positions x 2 full layers x 256 B over the
    # scoring's and the top-k's time
    assert run.cell.reader("index_select_roofline_pct").read(run) \
        == pytest.approx(100 * (2 * 3e6 * 256 / 819e9)
                         / ((0.05 + 0.07) / 2.0 * 50.0))


@pytest.mark.parametrize("reader", [
    "sparse_attn_roofline_pct", "index_select_roofline_pct",
    "attn_selected_share_pct"])
def test_readers_find_nothing_in_a_program_without_the_counts(
        monkeypatch, reader):
    """A program whose spans carry no selected or scored counts and
    whose trace holds neither kernel (the parent commit, or a model
    that attends every position): every new reader returns None and
    does not raise."""
    run = _fake_run(monkeypatch, [{"ctx_tokens": 1000}],
                    {"paged_attention.1[tpu_custom_call]": 0.2,
                     "sort.4": 0.1, "gather.2": 0.1})
    assert run.cell.reader(reader).read(run) is None
    empty = _fake_run(monkeypatch, [], {})
    assert empty.cell.reader(reader).read(empty) is None


def test_the_sparse_roofline_is_not_read_from_the_kernel_alone(monkeypatch):
    """The kernel is in the trace, positions were selected, and the
    gather of the rows is not among the breakdown's operations (another
    shape than the configuration's, or an addresses fusion alone): the
    stage's time is unknown, so there is nothing to read, and not a
    share ten times too high."""
    call = {"ctx_tokens": 1_500_000, "ctx_selected_tokens": 500_000, "k": 8}
    names = {"sparse_latent_attention.1[tpu_custom_call]": 0.12,
             "fusion.7": 0.08}
    for ops in ([("fusion bf16[65536,576]", 0.06)],
                [("fusion s32[65536]", 0.02)], []):
        run = _fake_run(monkeypatch, [call], names, ops)
        assert run.cell.reader("sparse_attn_roofline_pct").read(run) is None
