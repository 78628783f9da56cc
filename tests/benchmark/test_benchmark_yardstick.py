"""The yardstick's arithmetic, on the CPU at a tiny size: seeded
traffic, the model arithmetic against a hand count, the plain reference
against the program in float32, and the trace reduction on the recorded
trace."""

import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_helpers import BENCH, load, tiny_cfg

gen = load("traffic_gen.py")
flops = load("flops/gpt2.py")
ref = load("reference/gpt2.py")
tr = load("trace_reduce.py")

MIX = {"clients": 3, "requests_per_client": 4, "deal_seed": 5,
       "prompt_len": {"dist": "lognormal", "median": 40, "sigma": 0.6,
                      "min": 8, "max": 100},
       "output_len": {"dist": "lognormal", "median": 9, "sigma": 0.5,
                      "min": 4, "max": 16},
       "first_output_fraction": "uniform"}


def _flat(plan):
    return [(r["prompt"].tolist(), r["max_new_tokens"]) for c in plan for r in c]


def test_traffic_same_seed_same_requests():
    assert _flat(gen.serving_requests(MIX, 503, 2**31 + 5)) == \
        _flat(gen.serving_requests(MIX, 503, 2**31 + 5))


def test_traffic_other_seed_other_ids_same_requests_in_the_same_order():
    a = gen.serving_requests(MIX, 503, 1)
    b = gen.serving_requests(MIX, 503, 2)
    assert _flat(a) != _flat(b)
    sizes = lambda p: [(len(r["prompt"]), r["max_new_tokens"])
                       for c in p for r in c]
    assert sizes(a) == sizes(b)          # the work on offer is the mix's
    other = gen.serving_requests(dict(MIX, deal_seed=6), 503, 1)
    assert sizes(other) != sizes(a)
    assert sorted(n for n, _ in sizes(other)) == sorted(n for n, _ in sizes(a))


def test_first_requests_keep_evenly_spaced_shares_of_their_length():
    whole = gen.serving_requests(
        {k: v for k, v in MIX.items() if k != "first_output_fraction"}, 503, 1)
    cut = gen.serving_requests(MIX, 503, 1)
    shares = sorted(c[0]["max_new_tokens"] / w[0]["max_new_tokens"]
                    for c, w in zip(cut, whole))
    assert all(c[1:] and [r["max_new_tokens"] for r in c[1:]] ==
               [r["max_new_tokens"] for r in w[1:]] for c, w in zip(cut, whole))
    assert shares[0] < shares[-1] <= 1.0


def test_a_new_length_distribution_is_a_new_file(tmp_path):
    shutil.copy(os.path.join(BENCH, "traffic_gen.py"), tmp_path)
    shutil.copytree(os.path.join(BENCH, "lengths"), tmp_path / "lengths")
    (tmp_path / "lengths" / "fixed.py").write_text(
        "def at_quantiles(spec, q):\n    return [spec['value']] * len(q)\n")
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "traffic_gen_copy", tmp_path / "traffic_gen.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.quantile_lengths({"dist": "fixed", "value": 7}, 3).tolist() == [7] * 3
    with pytest.raises(ValueError, match="unknown length distribution"):
        gen.quantile_lengths({"dist": "fixed", "value": 7}, 3)


def test_traffic_lengths_keep_to_the_mix():
    plan = gen.serving_requests(MIX, 503, 3)
    for c in plan:
        for r in c:
            assert 8 <= len(r["prompt"]) <= 100
            assert 4 <= r["max_new_tokens"] <= 16
            assert r["prompt"].min() >= 0 and r["prompt"].max() < 503


def test_training_batches_seeded_and_rows_differ():
    a = next(gen.training_batches(4, 32, 503, 9))
    b = next(gen.training_batches(4, 32, 503, 9))
    c = next(gen.training_batches(4, 32, 503, 10))
    assert a.shape == (4, 33) and (a == b).all() and not (a == c).all()
    assert len({tuple(r) for r in a.tolist()}) == 4


HAND = {"n_embd": 8, "n_layer": 2, "n_head": 2, "vocab_size": 11,
        "n_positions": 16, "n_inner": None}


def test_flops_equal_a_hand_count():
    # per token, per layer: qkv 2*8*24, out 2*8*8, up 2*8*32, down 2*32*8
    block = 2 * (384 + 128 + 512 + 512)
    assert flops.block_flops_per_token(HAND) == block == 3072
    assert flops.head_flops(HAND) == 2 * 11 * 8 == 176
    # T=4 positions: 1+2+3+4 = 10 key-contexts, QK^T and PV 2*8 each, 2 layers
    attn = 10 * 2 * (2 * 8 + 2 * 8)
    assert flops.attn_flops(HAND, 10) == attn == 640
    assert flops.train_flops_per_step(HAND, 3, 4) == \
        3 * 3 * (4 * (3072 + 176) + 640)
    assert flops.prefill_flops(HAND, 4) == 4 * 3072 + 640 + 176
    assert flops.decode_flops(HAND, 5) == 3072 + 5 * 64 + 176
    assert flops.served_token_flops(HAND, 4, 0) == flops.prefill_flops(HAND, 4)
    assert flops.served_token_flops(HAND, 4, 1) == flops.decode_flops(HAND, 5)
    # parameters: emb 88 + pos 128 + 2 * (216 + 72 + 288 + 264 + 32) + 16
    assert flops.n_params(HAND) == 88 + 128 + 2 * 872 + 16


def test_published_sizes_give_the_published_parameter_counts():
    count = lambda f: flops.n_params(json.load(open(
        os.path.join(BENCH, "configs", f))))
    assert count("gpt2-large.json") == 774_030_080
    assert count("gpt2-medium.json") == 354_823_168


@pytest.fixture(scope="module")
def program():
    from deeplearning4j_tpu.models.gpt import CausalLM
    from deeplearning4j_tpu.models.transformer import TransformerConfig

    cfg = tiny_cfg()
    model = CausalLM(TransformerConfig(
        vocab_size=cfg["vocab_size"], max_len=cfg["n_positions"],
        d_model=cfg["n_embd"], n_layers=cfg["n_layer"],
        n_heads=cfg["n_head"], d_ff=4 * cfg["n_embd"], dropout=0.0,
        eps=1e-5), compute_dtype=jnp.float32)
    ids = jax.random.randint(jax.random.key(1), (3, 33), 0, cfg["vocab_size"])
    return cfg, model, ids


def test_both_layouts_hold_the_same_weights(program):
    cfg, _, _ = program
    big = 2**31 + 12345                  # more than 32 signed bits hold
    st = ref.make_params(cfg, big)
    pr = ref.make_params(cfg, big, layout="program")
    assert len(pr["layers"]) == cfg["n_layer"]
    np.testing.assert_array_equal(st["layers"]["wqkv"][1], pr["layers"][1]["wqkv"])
    np.testing.assert_array_equal(st["tok_emb"], pr["tok_emb"])
    other = ref.make_params(cfg, big + 1)
    assert not np.array_equal(st["tok_emb"], other["tok_emb"])


def test_reference_forward_agrees_with_the_program_in_f32(program):
    cfg, model, ids = program
    want = ref.logits(ref.make_params(cfg, 4), ids[:, :-1], cfg)
    got = model.forward(ref.make_params(cfg, 4, layout="program"), ids[:, :-1])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_reference_loss_and_gradients_agree_with_the_program_in_f32(program):
    cfg, model, ids = program
    st = ref.make_params(cfg, 4)
    pr = ref.make_params(cfg, 4, layout="program")
    lw, gw = jax.value_and_grad(lambda p: ref.loss(p, ids, cfg, remat=True))(st)
    lg, gg = jax.value_and_grad(lambda p: model.lm_loss(p, ids, False))(pr)
    assert float(lg) == pytest.approx(float(lw), rel=1e-5)
    a = ref.leaf_norms_program(gg, cfg)
    b = ref.leaf_norms_stacked(gw, cfg)
    assert list(a) == list(b)
    gap, at = ref.worst_leaf_gap(a, b)
    assert gap < 1e-3, at
    np.testing.assert_allclose(gg["layers"][1]["w1"], gw["layers"]["w1"][1],
                               atol=1e-6, rtol=1e-3)


def test_key_bias_is_a_leaf_of_its_own_and_has_no_gradient(program):
    cfg, _, ids = program
    g = jax.grad(lambda p: ref.loss(p, ids, cfg))(ref.make_params(cfg, 4))
    n = ref.leaf_norms_stacked(g, cfg)
    assert n["layers.0.bqkv.k"] < 1e-3 * n["layers.0.bqkv.v"]


def test_recorded_trace_gives_the_known_numbers():
    trace = json.load(open(os.path.join(BENCH, "data", "recorded_trace.json")))
    known = trace.pop("known")
    got = tr.reduce(trace)
    assert got["busy_s"] == pytest.approx(known["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(known["window_s"], rel=1e-9)
    # an independent count: mark every nanosecond... at the trace's own
    # resolution, by sweeping the sorted edges
    lo, hi = known["window_ns"]
    for plane, evs in trace["device"].items():
        edges = sorted({lo, hi} | {min(max(t, lo), hi) for _, s, d in evs
                                   for t in (s, s + d)})
        busy = sum(b - a for a, b in zip(edges, edges[1:])
                   if any(s <= a and b <= s + d for _, s, d in evs))
        assert busy / 1e9 == pytest.approx(got["busy_s"] * got["chips"], rel=1e-9)
    assert sum(got["per_name"].values()) == pytest.approx(
        got["busy_s"] * got["chips"], rel=1e-6)
    assert [n for n, _ in got["device_ops"][:3]] == known["top_ops"]
    # the breakdown adds the unrolled layers' copies of one operation up
    assert sum(s for _, s in tr.reduce(trace, top=10**6)["device_ops"]) == \
        pytest.approx(got["busy_s"] * got["chips"], rel=1e-6)
    assert len(tr.reduce(trace, top=10**6)["device_ops"]) < len(got["per_name"])
    idle = got["window_s"] - got["busy_s"]
    assert sum(s for _, s in tr.reduce(trace, top=10**6)["idle_gaps"]) == \
        pytest.approx(idle, rel=1e-6)
    assert got["idle_gaps"][0][0] == known["top_gap"]


def test_trace_arithmetic_on_a_hand_made_trace():
    trace = {"device": {"/device:TPU:0": [
        ("a", 0, 100), ("while", 200, 300), ("b", 250, 100), ("c", 400, 50),
        ("a", 700, 100)]},
        "host": [("bench:trace_window", 0, 1000), ("bench:x", 90, 120),
                 ("bench:y", 480, 300)]}
    got = tr.reduce(trace)
    assert got["busy_s"] == pytest.approx(500e-9)
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["per_name"]["while"] == pytest.approx(150e-9)   # less b and c
    assert dict(map(tuple, got["idle_gaps"])) == pytest.approx(
        {"bench:x": 100e-9, "bench:y": 200e-9, "unannotated": 200e-9})
    with pytest.raises(ValueError):
        tr.reduce({"device": {}, "host": []})


def test_operations_are_grouped_by_kind_and_result_shape():
    text = ('%closed_call.12 = bf16[8,20,1,64]{3,2,1,0:T(8,128)(2,1)} '
            'custom-call(bf16[8,20,1,64]{3,2,1,0} %x), '
            'custom_call_target="tpu_custom_call", operand_layout=x')
    assert tr.op_name(text) == "closed_call.12[tpu_custom_call]"
    assert tr.op_kind(text) == "closed_call[tpu_custom_call] bf16[8,20,1,64]"
    assert tr.op_kind("%fusion.3 = (f32[4,1024]{1,0}, bf16[2]{0}) fusion(%a)") \
        == "fusion f32[4,1024]"
    assert tr.op_kind("copy.7") == "copy"
