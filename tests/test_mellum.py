"""Mellum 2 on the CPU at a small size (hidden 64, 4 layers ``S S S F``
with a window of 8 in rings of 3 pages of 4 against contexts of 40, all
8 experts held top-2, 4 query / 2 KV heads of 16, 48 vocabulary rows,
the published rotations), seeded random weights from the benchmark's
plain reference: the model's forward against the reference's logits,
the engine's prefill (full layers' pages and window rings) then paged
decode against the reference's full forward (logits, not tokens),
YaRN's frequencies at the published sizes, planted faults, the ring at
the cell's window and buckets, and the engine's spans.

Tolerances. Program and reference both run in float32 at ``highest``
precision here; they differ in the ORDER of their sums only (a paged
online softmax against a whole one, sorted rows against a loop over
experts): 1e-4 absolute on logits that reach 5-10 is many times what
is seen (about 5e-5) and a thousand times under what a planted fault
gives (0.1 .. 3)."""

import importlib.util
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import sparse_decoder
from deeplearning4j_tpu.models.decoder_ops import rope, rope_frequencies
from deeplearning4j_tpu.models.mellum import (ROPE_PARAMETERS, MellumConfig,
                                              MellumLM)
from deeplearning4j_tpu.ops.paged_attention_pallas import paged_attention
from deeplearning4j_tpu.serving.engine import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 13
V = 48
S, F = "sliding_attention", "full_attention"
CFG = dict(vocab_size=V, hidden_size=64, moe_intermediate_size=32,
           num_hidden_layers=4, layer_types=[S, S, S, F],
           mlp_layer_types=["sparse"] * 4,
           sliding_window=8, num_experts=8, num_experts_per_tok=2,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           rms_norm_eps=1e-6, rope_parameters=ROPE_PARAMETERS,
           norm_topk_prob=True, initializer_range=0.3,
           max_position_embeddings=128)
ATOL = 1e-4
PS = 4           # positions a page, pool and ring alike


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("reference/mellum.py", "mellum_reference_for_tests")


@pytest.fixture(scope="module")
def params(ref):
    """The reference's bf16 values, and the same in float32."""
    p = ref.make_params(CFG, SEED)
    return p, jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)


def _model(dtype=jnp.float32, **over):
    return MellumLM(MellumConfig(window_page_size=PS, **dict(CFG, **over)),
                    dtype)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, V, n).astype(np.int32)


def _rotations(**full):
    """The published rotations with the full layers' entry changed."""
    return {S: ROPE_PARAMETERS[S], F: dict(ROPE_PARAMETERS[F], **full)}


# ------------------------------------------------------------ the model
@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_forward_matches_the_reference(ref, params, mode):
    """40 positions, five windows long: the band mask, a rotation for
    each kind of layer, the softmax router, every expert held."""
    p16, p32 = params
    ids = _ids(40)
    with jax.default_matmul_precision("highest"):
        got, experts = _model().forward(p32, jnp.asarray(ids[None]),
                                        return_aux=True, mode=mode)
        want = ref.logits(CFG, SEED, ids, params=p16)
        chosen = ref.routing(CFG, SEED, ids, params=p16)
    assert float(jnp.max(jnp.abs(want))) > 1.0       # the blocks decide
    np.testing.assert_allclose(got[0], want, atol=ATOL)
    for e, li in zip(experts, sorted(chosen)):
        np.testing.assert_array_equal(np.sort(e[0], -1),
                                      np.sort(chosen[li], -1))


def test_attention_in_query_blocks_is_the_attention(params):
    """A long prefill's attention a block of queries at a time (window
    layers over the keys a block can reach only) gives what the whole
    does."""
    _, p32 = params
    ids = jnp.asarray(_ids(32, seed=3)[None])
    whole, blocks = _model(), _model()
    blocks.query_block = 8
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(blocks.forward(p32, ids),
                                   whole.forward(p32, ids), atol=ATOL)


def test_cache_spec_names_two_kinds():
    spec = _model().cache_spec()
    ring = (3, 3, PS, 32)        # 3 window layers, 8 / 4 + 1 pages
    assert spec == {"kv_layers": 1, "kv_heads": 2, "head_dim": 16,
                    "state": {"k": ring, "v": ring}, "window": 8}


def test_parameters_are_created_in_the_serving_dtype():
    p = _model(dtype=jnp.bfloat16).init_params(jax.random.key(0))
    assert {a.dtype for a in jax.tree_util.tree_leaves(p)} \
        == {jnp.dtype(jnp.bfloat16)}
    lp = p["layers"][3]
    assert lp["router"].shape == (64, 8) and lp["ew1"].shape == (8, 64, 32)
    assert p["head"].shape == (64, V)
    # no shared expert, no q/k norm, no dense layer, no selection bias
    assert not {"sw1", "q_norm", "k_norm", "w1", "router_bias"} & set(lp)


@pytest.mark.parametrize("bad", [
    {"mlp_layer_types": ["dense", "sparse", "sparse", "sparse"]},
    {"attention_bias": True}, {"tie_word_embeddings": True},
    {"use_sliding_window": False}, {"rope_parameters": {S: {
        "rope_type": "default", "rope_theta": 1e4}}},
    {"layer_types": [S, S, "conv", F]}, {"num_key_value_heads": 3}])
def test_config_refuses_what_is_not_implemented(bad):
    with pytest.raises(ValueError):
        _model(**bad)


def test_an_unknown_rotation_is_refused():
    with pytest.raises(ValueError, match="rope_type"):
        rope_frequencies({"rope_type": "longrope", "rope_theta": 1e4}, 16)


# ------------------------------------------------------ the rotations
def test_yarn_at_the_published_sizes(ref):
    """Head 128, base 500,000, factor 16 over 8,192 original positions:
    the ramp runs between frequency dims 18 (beta_fast 32) and 35
    (beta_slow 1); below it the frequencies are the base's, above it
    divided by the factor; cos and sin carry the attention factor."""
    yarn = ROPE_PARAMETERS[F]
    inv, scale = rope_frequencies(yarn, 128)
    base, _ = rope_frequencies(ROPE_PARAMETERS[S], 128)
    inv, base = np.asarray(inv), np.asarray(base)
    assert scale == 1.2772588722239782 == pytest.approx(
        0.1 * math.log(16) + 1)
    assert inv[0] == 1.0
    np.testing.assert_allclose(inv[63], 1 / (16 * 500000 ** (126 / 128)),
                               rtol=1e-6)
    # the ramp: untouched up to dim 18, divided by 16 from dim 35
    np.testing.assert_array_equal(inv[:19], base[:19])
    np.testing.assert_allclose(inv[35:], base[35:] / 16, rtol=1e-6)
    mid = inv[19:35] / base[19:35]
    assert np.all(np.diff(mid) < 0) and 1 / 16 < mid.min() < mid.max() < 1
    np.testing.assert_allclose(mid, 1 - np.arange(1, 17) / 17 * (15 / 16),
                               rtol=1e-5)
    # the reference's own frequencies agree
    ref_inv, ref_scale = ref.inv_freq(yarn, 128)
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-6)
    assert ref_scale == scale


def test_the_attention_factor_scales_cos_and_sin():
    """At position 0 the rotation is the factor alone; at any position
    it turns each pair and stretches it by the factor, so q . k scales
    by its square."""
    x = jax.random.normal(jax.random.key(1), (1, 3, 2, 128), jnp.float32)
    pos = jnp.asarray([[0, 700, 9000]], jnp.int32)
    yarn, plain = ROPE_PARAMETERS[F], ROPE_PARAMETERS[S]
    got, unscaled = rope(x, pos, yarn), rope(
        x, pos, dict(yarn, attention_factor=1.0))
    f = yarn["attention_factor"]
    np.testing.assert_allclose(got[0, 0], f * x[0, 0], rtol=1e-6)
    np.testing.assert_allclose(got, f * unscaled, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1),
                               f * jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # the default rotation keeps lengths, and the base alone is the old
    # call's
    np.testing.assert_allclose(jnp.linalg.norm(rope(x, pos, plain), axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    np.testing.assert_array_equal(rope(x, pos, plain), rope(x, pos, 500000.0))


# ---------------------------------- through the engine's cache, logits
def _engine(model, p, mode="xla", slots=2, **kw):
    return DecodeEngine(model, p, slots=slots, page_size=PS,
                        max_context=64, prefill_buckets=[8, 16, 32],
                        attn_mode=mode, max_chunk=4, warm_start=False, **kw)


def _admit(eng, p, ids, t0, slot):
    """What ``DecodeEngine._admit`` does on the device: the engine's own
    prefill program writes the prompt's pages and the slot's rings."""
    bucket = next(b for b in eng.prefill_buckets if b >= t0)
    prompt = np.zeros((1, bucket), np.int32)
    prompt[0, :t0] = ids[:t0]
    P = eng.pages_per_slot
    rows = np.arange(1 + slot * P, 1 + (slot + 1) * P, dtype=np.int32)
    page_row = np.zeros((bucket // PS,), np.int32)
    n_real = -(-t0 // PS)
    page_row[:n_real] = rows[:n_real]
    cache, last, _ = eng._prefill_jit(
        p, eng._cache(), jnp.asarray(prompt), jnp.asarray(page_row),
        jnp.asarray(t0, jnp.int32), jnp.asarray(slot, jnp.int32))
    eng._rebind(cache)
    return rows, np.asarray(last)


def _decode_logits(eng, p, ids, t0, slot, rows, upto):
    """Tokens ``ids[t0:upto]`` a step at a time through the model's
    ``decode_step`` over the engine's cache -> logits ``[upto - t0,
    V]``."""
    m, n = eng.model, eng.slots
    tables = np.zeros((n, eng.pages_per_slot), np.int32)
    tables[slot] = rows
    live = np.zeros((n,), bool)
    live[slot] = True
    out = []
    for t in range(t0, upto):
        pos, tok = np.zeros((2, n), np.int32)
        pos[slot], tok[slot] = t, ids[t]
        kv, state = eng._cache()
        kv, state, lg, _ = m.decode_step(
            p, kv, state, jnp.asarray(tables), jnp.asarray(pos),
            jnp.asarray(tok), jnp.asarray(live), PS, mode=eng._attn_mode)
        eng._rebind((kv, state))
        out.append(np.asarray(lg[slot]))
    return np.stack(out)


def _served_logits(model, p, ids, t0, mode="xla", slot=1):
    eng = _engine(model, p, mode)
    rows, last = _admit(eng, p, ids, t0, slot)
    return last, _decode_logits(eng, p, ids, t0, slot, rows, len(ids))


@pytest.mark.parametrize("t0,mode", [
    (3, "xla"), (8, "xla"), (13, "xla"), (16, "xla"), (31, "xla"),
    (5, "interpret"), (13, "interpret")])
def test_prefill_then_paged_decode_is_the_full_forward(ref, params, t0,
                                                       mode):
    """A prompt shorter than the window (3), of a window (8), ending
    mid-page (13), filling its bucket (16) and several windows long
    (31), then a token at a time to position 40: every step's logits
    are the reference's full forward's. The rings wrap (3 pages of 4
    against 40 positions, past their first lap), the full layer's pages
    do not."""
    p16, p32 = params
    ids = _ids(40, seed=t0)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(CFG, SEED, ids, params=p16))
        last, got = _served_logits(_model(), p32, ids, t0, mode)
    np.testing.assert_allclose(last, want[t0 - 1], atol=ATOL)
    np.testing.assert_allclose(got, want[t0:], atol=ATOL)


def _fault(name):
    """A model with one planted fault, by the name the cell uses."""
    return {"plain_rope_on_full": dict(rope_parameters=_rotations(
                rope_type="default")),
            "no_attention_factor": dict(rope_parameters=_rotations(
                attention_factor=1.0)),
            "window_one_short": dict(sliding_window=7),
            "router_not_renormalised": dict(norm_topk_prob=False)}[name]


@pytest.mark.parametrize("fault", [
    "plain_rope_on_full", "no_attention_factor", "window_one_short",
    "router_not_renormalised"])
def test_a_planted_fault_leaves_the_reference(ref, params, fault):
    """The same comparison, with the program holding one fault: its
    logits part from the reference's by far more than the tolerance,
    in the forward and through the cache alike."""
    p16, p32 = params
    ids = _ids(40, seed=6)
    bad = _model(**_fault(fault))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(CFG, SEED, ids, params=p16))
        full = np.asarray(bad.forward(p32, jnp.asarray(ids[None]))[0])
        _, got = _served_logits(bad, p32, ids, 10)
    assert float(np.max(np.abs(full - want))) > 100 * ATOL
    assert float(np.max(np.abs(got - want[10:]))) > 100 * ATOL


# ------------------------------------------- the cell's window and buckets
@pytest.mark.parametrize("t0", [1, 1023, 1024, 1040, 5000, 8192])
def test_the_ring_of_a_long_prompt_holds_its_last_window(t0):
    """At the cell's sizes (a window of 1,024 in 65 pages of 16, a
    prompt in a bucket of 8,192): after prefill, ring entry ``(p // 16)
    % 65`` holds position ``p`` for every ``p`` the window reaches, and
    no page past the prompt's last one (the bucket's padding) is ever
    taken."""
    B, R, page = 8192, 65, 16
    ring = sparse_decoder._Prefill(1024, 256, t0, R, page)._ring(
        jnp.arange(B, dtype=jnp.float32).reshape(1, B, 1, 1) + 1)
    ring = np.asarray(ring)[..., 0] - 1           # position, or -1: empty
    assert ring.max() < -(-t0 // page) * page     # no padding page
    for p in range(max(0, t0 - 1024), t0):
        assert ring[(p // page) % R, p % page] == p


def test_the_ring_walk_at_the_cells_window():
    """The paged kernel's ring walk at a window of 1,024 over 65 pages
    of 16 (a group of 8 query heads on one KV head of 128): short
    sequences, exactly a window, and several laps round the ring."""
    rng = np.random.default_rng(0)
    H, KV, hd, window, R, ps = 8, 1, 128, 1024, 65, 16
    pos = [5, 1023, 1024, 3 * 1024 + 15]
    N, W = len(pos), KV * hd
    q = rng.standard_normal((N, 1, H, hd)).astype(np.float32)
    pool = {n: np.zeros((1, N * R, ps, W), np.float32) for n in "kv"}
    want = np.zeros((N, 1, H, hd), np.float32)
    for n, p in enumerate(pos):
        k, v = (rng.standard_normal((p + 1, KV, hd)).astype(np.float32)
                for _ in "kv")
        t = np.arange(p + 1)
        pages = n * R + (t // ps) % R
        pool["k"][0, pages, t % ps] = k.reshape(p + 1, W)
        pool["v"][0, pages, t % ps] = v.reshape(p + 1, W)
        lo = max(0, p - window + 1)
        for h in range(H):
            s = k[lo:, 0] @ q[n, 0, h] / np.sqrt(hd)
            a = np.exp(s - s.max())
            want[n, 0, h] = (a / a.sum()) @ v[lo:, 0]
    tables = np.arange(N * R, dtype=np.int32).reshape(N, R)
    got = paged_attention(jnp.asarray(q), {n: jnp.asarray(a) for n, a in
                                           pool.items()}, 0,
                          jnp.asarray(tables), jnp.asarray(pos, jnp.int32),
                          mode="xla", window=window)
    np.testing.assert_allclose(got, want, atol=2e-5)


# ------------------------------------------------------------ the engine
def _serve(model, p, requests, **engine):
    engine = dict(dict(slots=4, page_size=PS, max_context=64,
                       prefill_buckets=[8, 16, 32, 64], attn_mode="xla",
                       max_chunk=4), **engine)
    with DecodeEngine(model, p, **engine) as eng:
        handles = [eng.submit(pr, n) for pr, n in requests]
        outs = [h.result(timeout=300) for h in handles]
        stats = eng.stats()
    return outs, stats


REQUESTS = [(1, 20), (3, 30), (8, 24), (13, 40), (16, 7), (33, 30), (7, 5),
            (9, 12)]


def test_engine_serves_what_the_reference_puts_first(ref, params):
    """``submit`` / ``result`` over both kinds of cache, 8 requests over
    4 slots (joins, reused slots, prompts of 1, 3, a window, mid-page, a
    bucket and past it, contexts to 63): every served token is the
    reference's best."""
    p16, p32 = params
    reqs = [(_ids(n, seed=n), k) for n, k in REQUESTS]
    outs, stats = _serve(_model(), p32, reqs)
    got = ref.check_served(CFG, SEED, [(pr, o) for (pr, _), o in
                                       zip(reqs, outs)], params=p16)
    assert got["compared"] == sum(k for _, k in REQUESTS)
    assert got["widest_gap"] < ATOL and got["mismatch_share"] == 0.0
    kv = stats["kv_pages"]
    assert kv["page_bytes"] == 2 * 1 * PS * 32 * 4
    assert kv["window_bytes"] == stats["state_bytes"] \
        == 2 * 3 * 4 * 3 * PS * 32 * 4
    # every expert is held: every assignment routed is one here
    assert stats["expert_assignments"] \
        == stats["expert_assignments_routed"] > 0
    assert 0 < stats["experts_touched"] <= 8 * stats["expert_layer_steps"]
    assert stats["expert_load_max"] * 8 >= stats["expert_assignments"]


def test_spans_carry_the_window_and_the_expert_sums(params):
    from deeplearning4j_tpu.profiler import telemetry

    _, p32 = params
    t0 = time.perf_counter()
    _serve(_model(), p32, [(_ids(5), 9), (_ids(20, seed=1), 9)])
    inf = float("inf")
    syncs = telemetry.spans_between(t0, inf, "engine.sync")
    pre = telemetry.spans_between(t0, inf, "engine.prefill")
    calls = telemetry.spans_between(t0, inf, "engine.dispatch")
    assert syncs and pre and calls
    for e in syncs + pre:
        a = e["args"]
        assert a["expert_assignments_routed"] == a["expert_assignments"] > 0
        assert a["expert_layer_steps"] > 0
        assert a["expert_load_max"] >= 1 and a["experts_touched"] >= 1
    # a prompt's counts are of its real positions: x 2 x 4 sparse layers
    assert sorted(e["args"]["expert_assignments_routed"] for e in pre) \
        == [40, 160]
    for e in calls:
        a = e["args"]
        # each live lane's positions cut to the window of 8
        assert 0 < a["ctx_window_tokens"] <= min(
            a["ctx_tokens"], 8 * a["live"] * a["k"])
    assert any(e["args"]["ctx_window_tokens"] < e["args"]["ctx_tokens"]
               for e in calls)


@pytest.mark.parametrize("option", [
    {"prefix_cache": True}, {"session_capacity": 2}, {"spec_decode": 2},
    {"quantization": "int8"}, {"kv_dtype": "fp8_e4m3"},
    {"handoff_threshold": 16}])
def test_engine_refuses_what_it_cannot_honour_by_name(params, option):
    _, p32 = params
    with pytest.raises(ValueError, match=next(iter(option))):
        DecodeEngine(_model(), p32, slots=2, page_size=PS, max_context=64,
                     warm_start=False, **option)
