"""LFM2-MoE on the CPU at a small size (hidden 64, 8 experts top-2, 1
dense + 4 expert layers ``conv | A c c c``, 4 query / 2 KV heads),
seeded random weights from the benchmark's plain reference: the model's
forward against the reference's logits, the engine's prefill-then-decode
(pages and conv state) against the reference's full forward, the
router, the grouped product, grouped-query paged attention, and the
engine options the model's per-slot state makes it refuse."""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeLM
from deeplearning4j_tpu.ops.grouped_matmul_pallas import (grouped_matmul,
                                                          visits)
from deeplearning4j_tpu.ops.paged_attention_pallas import paged_attention
from deeplearning4j_tpu.serving import kv_pages
from deeplearning4j_tpu.serving.engine import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 5
CFG = dict(vocab_size=131, hidden_size=64, intermediate_size=96,
           moe_intermediate_size=32, num_hidden_layers=5,
           layer_types=["conv", "full_attention", "conv", "conv", "conv"],
           num_dense_layers=1, num_experts=8, num_experts_per_tok=2,
           num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3,
           norm_eps=1e-5, rope_parameters={"rope_theta": 1e6},
           norm_topk_prob=True, use_expert_bias=True,
           routed_scaling_factor=1, initializer_range=0.3,
           expert_bias_range=0.3, max_position_embeddings=128)


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("reference/lfm2_moe.py", "lfm2_reference_for_tests")


@pytest.fixture(scope="module")
def params(ref):
    """The reference's bf16 values, and the same in float32."""
    p = ref.make_params(CFG, SEED)
    return p, jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)


def _model(dtype=jnp.float32):
    keys = {k: v for k, v in CFG.items()
            if k not in ("rope_parameters", "expert_bias_range")}
    return Lfm2MoeLM(Lfm2MoeConfig(rope_theta=1e6, **keys), dtype)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, 131, n).astype(np.int32)


# ------------------------------------------------------------ the model
@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_forward_matches_the_reference(ref, params, mode):
    p16, p32 = params
    ids = _ids(24)
    with jax.default_matmul_precision("highest"):
        got, experts = _model().forward(p32, jnp.asarray(ids[None]),
                                        return_aux=True, mode=mode)
        want = ref.logits(CFG, SEED, ids, params=p16)
        chosen = ref.routing(CFG, SEED, ids, params=p16)
    assert float(jnp.max(jnp.abs(want))) > 1.0       # the blocks decide
    np.testing.assert_allclose(got[0], want, atol=1e-4)
    for e, li in zip(experts, sorted(chosen)):
        np.testing.assert_array_equal(np.sort(e[0], -1),
                                      np.sort(chosen[li], -1))


def test_cache_spec_is_attention_layers_and_conv_state():
    spec = _model().cache_spec()
    assert spec == {"kv_layers": 1, "kv_heads": 2, "head_dim": 16,
                    "state": (4, 3, 64)}


def test_parameters_are_created_in_the_serving_dtype():
    p = _model(jnp.bfloat16).init_params(jax.random.key(1))
    assert {a.dtype for a in jax.tree_util.tree_leaves(p)} \
        == {jnp.dtype(jnp.bfloat16)}
    jaxpr = str(jax.make_jaxpr(lambda q, i: _model(jnp.bfloat16).forward(
        q, i, mode="xla"))(p, jnp.zeros((1, 8), jnp.int32)))
    # no weight is cast inside a call: nothing of a weight's shape is
    # converted (activations are, for the norms and the router)
    for shape in ("bf16[64,192]", "bf16[8,64,32]", "bf16[131,64]",
                  "bf16[64,96]"):
        assert f"convert_element_type[new_dtype=float32 weak_type=False] " \
               f"{shape}" not in jaxpr


@pytest.mark.parametrize("t0", [1, 2, 3, 7, 15])
def test_prefill_then_decode_is_the_full_forward(params, t0):
    """State and pages taken at the last REAL position ``t0`` of a
    bucket of 16 (prompts shorter than the filter included), then a
    token at a time: the logits of the full forward."""
    _, p32 = params
    m, ps, S = _model(), 8, 2
    ids = _ids(24, seed=t0)
    with jax.default_matmul_precision("highest"):
        full = m.forward(p32, jnp.asarray(ids[None]), mode="xla")[0]
        prompt = np.zeros((1, 16), np.int32)
        prompt[0, :t0] = ids[:t0]
        ks, vs, st, last, counts = m.prefill(
            p32, jnp.asarray(prompt), jnp.asarray(t0, jnp.int32), mode="xla")
        np.testing.assert_allclose(last, full[t0 - 1], atol=1e-4)
        # counted over real positions only: 2 experts a token
        assert np.asarray(counts)[:, 0].tolist() == [2 * t0] * 4
        pool = kv_pages.PagePool(1, 2, ps, 16, 1 + 4, dtype=jnp.float32)
        kv = kv_pages.commit_prefill(pool.tree(), ks, vs,
                                     jnp.asarray([1, 2], jnp.int32), ps,
                                     n_valid=t0)
        state = jnp.zeros((4, S, 3, 64), jnp.float32).at[:, 0].set(st)
        tables = jnp.asarray([[1, 2, 3, 4], [0, 0, 0, 0]], jnp.int32)
        live = jnp.asarray([True, False])
        for t in range(t0, 24):
            kv, state, lg, counts = m.decode_step(
                p32, kv, state, tables, jnp.asarray([t, 0], jnp.int32),
                jnp.asarray([ids[t], 0], jnp.int32), live, ps, mode="xla")
            np.testing.assert_allclose(lg[0], full[t], atol=1e-4)
        # the dead slot went to no expert
        assert np.asarray(counts).tolist() == [[2, 2, 1]] * 4


# ------------------------------------------------------------ the router
def _router_case(scores, bias):
    """A one-layer stand-in whose router reads ``scores`` off the
    input: x = logit(scores) through an identity router."""
    E = len(bias)
    cfg = Lfm2MoeConfig(
        vocab_size=8, hidden_size=E, intermediate_size=8,
        moe_intermediate_size=8, num_hidden_layers=1, layer_types=["conv"],
        num_dense_layers=0, num_experts=E, num_experts_per_tok=4,
        num_attention_heads=1, num_key_value_heads=1)
    s = np.asarray(scores, np.float32)
    lp = {"router": jnp.eye(E, dtype=jnp.float32),
          "router_bias": jnp.asarray(bias, jnp.float32)}
    return Lfm2MoeLM(cfg, jnp.float32).route(
        lp, jnp.asarray(np.log(s / (1 - s))))


def test_bias_changes_the_selection_and_not_the_weight():
    s = [[0.9, 0.8, 0.7, 0.6, 0.5, 0.4, 0.3, 0.2]]
    idx0, w0 = _router_case(s, [0.0] * 8)
    assert sorted(np.asarray(idx0)[0]) == [0, 1, 2, 3]
    np.testing.assert_allclose(np.asarray(w0)[0].sum(), 1.0, atol=1e-5)
    idx, w = _router_case(s, [0, 0, 0, 0, 0, 0, 0, 0.45])   # 0.2 -> 0.65
    assert sorted(np.asarray(idx)[0]) == [0, 1, 2, 7]
    got = dict(zip(np.asarray(idx)[0].tolist(), np.asarray(w)[0].tolist()))
    # the weight is the unbiased score over the selected scores' sum
    total = 0.9 + 0.8 + 0.7 + 0.2 + 1e-6
    for e, score in ((0, 0.9), (1, 0.8), (2, 0.7), (7, 0.2)):
        assert got[e] == pytest.approx(score / total, rel=1e-5)


def test_ties_take_the_lower_index_as_the_reference_does(ref):
    s = [[0.5] * 8]
    idx, w = _router_case(s, [0.0] * 8)
    assert sorted(np.asarray(idx)[0]) == [0, 1, 2, 3]
    np.testing.assert_allclose(np.asarray(w)[0], 0.25, atol=1e-5)
    z = {"k": 4, "bias": True, "norm_topk": True, "scale": 1.0}
    x = jnp.zeros((1, 8), jnp.float32)       # sigmoid(0) = 0.5 for all
    ridx, rw = ref.route({"router": jnp.eye(8), "router_bias": jnp.zeros(8)},
                         x, z)
    np.testing.assert_array_equal(np.sort(ridx, -1), np.sort(idx, -1))
    np.testing.assert_allclose(rw, w, atol=1e-6)


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_every_token_gets_its_experts_with_16_tokens_on_one(mode):
    """No capacity: 16 tokens all routed to expert 3 (and each to one
    more) all get both, and the output is the plain sum."""
    m = _model()
    lp = m.init_params(jax.random.key(2))["layers"][1]
    lp = dict(lp, router_bias=jnp.zeros(8).at[3].set(10.0))
    x = jax.random.normal(jax.random.key(3), (16, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, counts, idx = m.experts(lp, x, mode=mode)
        idx, w = m.route(lp, x)
        assert (np.asarray(idx) == 3).any(-1).all()
        want = jnp.zeros_like(x)
        for j in range(2):
            for t in range(16):
                e = int(idx[t, j])
                h = jax.nn.silu(x[t] @ lp["ew1"][e]) * (x[t] @ lp["ew3"][e])
                want = want.at[t].add(w[t, j] * (h @ lp["ew2"][e]))
    np.testing.assert_allclose(y, want, atol=1e-4)
    a, touched, load_max = np.asarray(counts).tolist()
    assert (a, load_max) == (32, 16) and touched >= 2


@pytest.mark.parametrize("sizes", [[3, 0, 5, 2], [0, 0, 0, 0], [16, 0, 0, 0],
                                   [1, 1, 1, 1], [0, 7, 0, 9]])
def test_grouped_matmul_kernel_is_the_ragged_dot(sizes):
    rng = np.random.default_rng(sum(sizes))
    lhs = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(4, 32, 24)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    n = sum(sizes)
    with jax.default_matmul_precision("highest"):
        want = grouped_matmul(lhs, rhs, gs, mode="xla")
        got = jax.jit(lambda a, b, c: grouped_matmul(
            a, b, c, mode="interpret"))(lhs, rhs, gs)
    np.testing.assert_allclose(got[:n], want[:n], atol=1e-5)
    _, gid, tile, count = visits(gs, 16, 16)
    assert int(count[0]) == sum(s > 0 for s in sizes)
    assert np.asarray(gid)[:int(count[0])].tolist() == [
        g for g, s in enumerate(sizes) if s > 0]


def test_grouped_matmul_walks_row_tiles_and_groups_in_row_order():
    gs = jnp.asarray([100, 0, 0, 28, 1, 127, 0, 0], jnp.int32)
    _, gid, tile, count = visits(gs, 256, 128)
    n = int(count[0])
    assert n == 4
    assert list(zip(np.asarray(gid)[:n].tolist(),
                    np.asarray(tile)[:n].tolist())) == [
        (0, 0), (3, 0), (4, 1), (5, 1)]
    # visits past the last repeat it: they move no block
    assert set(np.asarray(gid)[n:].tolist()) == {5}
    assert set(np.asarray(tile)[n:].tolist()) == {1}


# ------------------------------------------------- grouped-query pages
def _paged_case(H, KV, Q, seed=0):
    rng = np.random.default_rng(seed)
    N, P, ps, hd = 2, 3, 4, 8
    kv = {k: jnp.asarray(rng.normal(size=(1, 1 + N * P, ps, KV * hd)),
                         jnp.float32) for k in ("k", "v")}
    q = jnp.asarray(rng.normal(size=(N, Q, H, hd)), jnp.float32)
    tables = jnp.asarray(1 + np.arange(N * P).reshape(N, P), jnp.int32)
    qbase = jnp.asarray([5, 9 - Q], jnp.int32)
    return q, kv, tables, qbase


@pytest.mark.parametrize("H,KV,Q", [(4, 2, 1), (4, 1, 1), (8, 2, 3),
                                    (4, 4, 1), (4, 4, 3)])
def test_paged_attention_modes_agree_at_fewer_kv_heads(H, KV, Q):
    q, kv, tables, qbase = _paged_case(H, KV, Q)
    with jax.default_matmul_precision("highest"):
        want = paged_attention(q, kv, 0, tables, qbase, mode="xla")
        got = paged_attention(q, kv, 0, tables, qbase, mode="interpret")
        # and both are plain attention over the query's group's KV head
        G = H // KV
        flat = lambda pool, n: pool[0][tables[n]].reshape(-1, KV, 8)
        for n in range(2):
            k, v = flat(kv["k"], n), flat(kv["v"], n)    # [P * ps, KV, hd]
            for h in range(H):
                for i in range(Q):
                    upto = int(qbase[n]) + i + 1
                    s = (k[:upto, h // G] @ q[n, i, h]) / np.sqrt(8.0)
                    ctx = jax.nn.softmax(s) @ v[:upto, h // G]
                    np.testing.assert_allclose(want[n, i, h], ctx, atol=1e-5)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_one_query_head_a_kv_head_lowers_to_the_program_it_was():
    """``G = 1`` adds no instruction: no division in the mask, and no
    transpose but the einsum pair's own (the group's regrouping is one
    on the way in and one on the way out)."""
    q, kv, tables, qbase = _paged_case(4, 4, 1)
    text = jax.jit(lambda *a: paged_attention(*a[:2], 0, *a[2:], mode="xla")) \
        .lower(q, kv, tables, qbase).as_text()
    int_div = re.compile(r"stablehlo\.divide[^\n]*xi32>")
    assert not int_div.search(text)
    q2, kv2, t2, b2 = _paged_case(4, 2, 1)
    grouped = jax.jit(lambda *a: paged_attention(
        *a[:2], 0, *a[2:], mode="xla")).lower(q2, kv2, t2, b2).as_text()
    assert int_div.search(grouped)
    assert grouped.count("stablehlo.transpose") \
        == text.count("stablehlo.transpose") + 2


def test_query_heads_must_divide_into_the_kv_heads():
    q, kv, tables, qbase = _paged_case(3, 2, 1)
    with pytest.raises(ValueError, match="do not divide"):
        paged_attention(q, kv, 0, tables, qbase, mode="xla")


# ------------------------------------------------------------ the engine
def _serve(model, p, requests, **engine):
    engine = dict(dict(slots=4, page_size=8, max_context=64,
                       attn_mode="xla", max_chunk=4), **engine)
    with DecodeEngine(model, p, **engine) as eng:
        handles = [eng.submit(pr, n) for pr, n in requests]
        outs = [h.result(timeout=300) for h in handles]
        stats = eng.stats()
    return outs, stats


REQUESTS = [(1, 20), (2, 9), (3, 30), (15, 11), (16, 7), (33, 20), (7, 5),
            (9, 12)]


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_engine_serves_what_the_reference_puts_first(ref, params, mode):
    """Tokens through pages and conv state, 8 requests over 4 slots
    (joins, evictions, reused slots, prompts of 1, 2, 3, a bucket less
    one and a bucket): every served token is the reference's best."""
    p16, p32 = params
    reqs = [(_ids(n, seed=n), k) for n, k in REQUESTS]
    outs, stats = _serve(_model(), p32, reqs, attn_mode=mode)
    got = ref.check_served(CFG, SEED, [(pr, o) for (pr, _), o in
                                       zip(reqs, outs)], params=p16)
    assert got["compared"] == sum(k for _, k in REQUESTS)
    assert got["widest_gap"] < 1e-4 and got["mismatch_share"] == 0.0
    # the cumulative forms of the new span attributes
    steps = stats["expert_layer_steps"]
    assert steps >= 4 * len(REQUESTS)          # 4 expert layers a prefill
    assert stats["expert_assignments"] >= stats["experts_touched"] >= steps
    assert stats["expert_load_max"] >= steps
    assert stats["state_bytes"] == 4 * 4 * 3 * 64 * 4
    assert stats["kv_pages"]["page_bytes"] == 2 * 1 * 2 * 8 * 16 * 4


@pytest.mark.parametrize("chunk", [1, 2, 8])
def test_chunk_sizes_give_the_same_tokens(params, chunk):
    _, p32 = params
    reqs = [(_ids(n, seed=n), k) for n, k in REQUESTS[:5]]
    base, _ = _serve(_model(), p32, reqs, max_chunk=4)
    outs, _ = _serve(_model(), p32, reqs, max_chunk=chunk)
    for a, b in zip(base, outs):
        np.testing.assert_array_equal(a, b)


def test_a_reused_slot_gives_what_a_fresh_engine_gives(params):
    """One slot: the second request runs where a long one just ran."""
    _, p32 = params
    long_req, short = (_ids(30, seed=1), 30), (_ids(2, seed=2), 12)
    (_, after), _ = _serve(_model(), p32, [long_req, short], slots=1)
    (fresh,), _ = _serve(_model(), p32, [short], slots=1)
    np.testing.assert_array_equal(after, fresh)


def test_spans_carry_the_expert_counts(params):
    from deeplearning4j_tpu.profiler import telemetry

    _, p32 = params
    t0 = __import__("time").perf_counter()
    _serve(_model(), p32, [(_ids(5), 9), (_ids(6, seed=1), 9)])
    syncs = telemetry.spans_between(t0, float("inf"), "engine.sync")
    pre = telemetry.spans_between(t0, float("inf"), "engine.prefill")
    assert syncs and pre
    for e in syncs + pre:
        a = e["args"]
        assert a["expert_assignments"] >= a["experts_touched"] \
            >= a["expert_layer_steps"] > 0
        assert a["expert_load_max"] >= a["expert_layer_steps"]
    # a prompt's counts are of its real positions: 5 tokens x 2 x 4 layers
    assert sorted(e["args"]["expert_assignments"] for e in pre) == [40, 48]


@pytest.mark.parametrize("option", [
    {"prefix_cache": True}, {"session_capacity": 2}, {"spec_decode": 2},
    {"quantization": "int8"}, {"kv_dtype": "fp8_e4m3"},
    {"handoff_threshold": 16}])
def test_engine_refuses_what_it_cannot_honour_by_name(params, option):
    _, p32 = params
    with pytest.raises(ValueError, match=next(iter(option))):
        DecodeEngine(_model(), p32, slots=2, page_size=8, max_context=64,
                     warm_start=False, **option)
