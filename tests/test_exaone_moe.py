"""EXAONE-MoE on the CPU at a small size (hidden 64, 1 dense + 4 sparse
layers ``L L L G L`` with a window of 8 against contexts of 40, 2 of 16
routed experts held top-2 beside a shared expert, 4 query / 2 KV heads,
24 of 192 vocabulary rows), seeded random weights from the benchmark's
plain reference: the model's forward against the reference's logits,
the engine's prefill (global pages and window rings) then paged decode
against the reference's full forward (logits, not tokens), planted
window faults, the share test of the model-configs guide (eight shares
add up to the uncut layer, eight vocabulary slices to the uncut
logits), the ring walk of the paged kernel, and the engine options a
cache of two kinds makes it refuse.

Tolerances. Program and reference both run in float32 at ``highest``
precision here; they differ in the ORDER of their sums only (a paged
online softmax against a whole one, sorted rows against a loop over
experts): 1e-4 absolute on logits that reach 5-10 is 20 times what was
seen (5e-6 .. 1e-5) and a hundred times under what a window off by one
position or a ring filled from the wrong place gives (0.01 .. 3)."""

import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import exaone_moe
from deeplearning4j_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                                  ExaoneMoeLM)
from deeplearning4j_tpu.models.routed_experts import routed_experts
from deeplearning4j_tpu.ops.paged_attention_pallas import paged_attention
from deeplearning4j_tpu.serving.engine import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 11
SHARES, HELD, SLICE = 8, 2, 24
L, G = "sliding_attention", "full_attention"
CFG = dict(vocab_size=SLICE, vocab_offset=2 * SLICE, hidden_size=64,
           intermediate_size=96, moe_intermediate_size=32,
           num_hidden_layers=5, layer_types=[L, L, L, G, L],
           mlp_layer_types=["dense"] + ["sparse"] * 4, sliding_window=8,
           num_experts=HELD, n_routed_experts=SHARES * HELD,
           expert_offset=2 * HELD, num_experts_per_tok=2,
           num_shared_experts=1, num_attention_heads=4,
           num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-5,
           rope_parameters={"rope_theta": 1e6}, norm_topk_prob=True,
           routed_scaling_factor=2.5, initializer_range=0.3,
           expert_bias_range=0.3, max_position_embeddings=128)
#: the same model whole: every expert, every row
UNCUT = dict(CFG, num_experts=SHARES * HELD, expert_offset=0,
             vocab_size=SHARES * SLICE, vocab_offset=0)
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
    return _load("reference/exaone_moe.py", "exaone_reference_for_tests")


@pytest.fixture(scope="module")
def params(ref):
    """The reference's bf16 values, and the same in float32."""
    p = ref.make_params(CFG, SEED)
    return p, jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)


def _model(cfg=CFG, dtype=jnp.float32, **over):
    keys = {k: v for k, v in cfg.items() if k not in (
        "rope_parameters", "expert_bias_range", "vocab_offset")}
    keys.update(over)
    return ExaoneMoeLM(ExaoneMoeConfig(rope_theta=1e6, window_page_size=PS,
                                       **keys), dtype)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, SLICE, n).astype(np.int32)


# ------------------------------------------------------------ the model
@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_forward_matches_the_reference(ref, params, mode):
    """40 positions, five windows long: the band mask, rotary positions
    on the window layers only, the shared expert, the held experts."""
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
    # the router chooses among all 16; most choices are held elsewhere
    assert int(np.max(experts[0])) >= HELD * 3


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
                                   whole.forward(p32, ids), atol=1e-5)


def test_cache_spec_names_two_kinds():
    spec = _model().cache_spec()
    ring = (4, 3, PS, 32)        # 4 window layers, 8 / 4 + 1 pages
    assert spec == {"kv_layers": 1, "kv_heads": 2, "head_dim": 16,
                    "state": {"k": ring, "v": ring}, "window": 8}


def test_parameters_are_created_in_the_serving_dtype():
    p = _model(dtype=jnp.bfloat16).init_params(jax.random.key(0))
    assert {a.dtype for a in jax.tree_util.tree_leaves(p)} \
        == {jnp.dtype(jnp.bfloat16)}
    lp = p["layers"][1]
    assert lp["router"].shape == (64, 16) and lp["ew1"].shape == (2, 64, 32)
    assert p["head"].shape == (64, SLICE) and "w1" in p["layers"][0]


@pytest.mark.parametrize("bad", [
    {"scoring_func": "softmax"}, {"n_group": 2},
    {"num_nextn_predict_layers": 1}, {"tie_word_embeddings": True},
    {"expert_offset": 15}, {"layer_types": [L, L, "conv", G, L]}])
def test_config_refuses_what_is_not_implemented(bad):
    with pytest.raises(ValueError):
        _model(**bad)


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
    m, S = eng.model, eng.slots
    tables = np.zeros((S, eng.pages_per_slot), np.int32)
    tables[slot] = rows
    live = np.zeros((S,), bool)
    live[slot] = True
    out = []
    for t in range(t0, upto):
        pos, tok = np.zeros((2, S), np.int32)
        pos[slot], tok[slot] = t, ids[t]
        kv, state = eng._cache()
        kv, state, lg, _ = m.decode_step(
            p, kv, state, jnp.asarray(tables), jnp.asarray(pos),
            jnp.asarray(tok), jnp.asarray(live), PS, mode=eng._attn_mode)
        eng._rebind((kv, state))
        out.append(np.asarray(lg[slot]))
    return np.stack(out)


def _served_logits(model, p, ids, t0, mode="xla", slot=1, upto=None):
    eng = _engine(model, p, mode)
    rows, last = _admit(eng, p, ids, t0, slot)
    return last, _decode_logits(eng, p, ids, t0, slot, rows,
                                upto or len(ids))


@pytest.mark.parametrize("t0,mode", [
    (3, "xla"), (8, "xla"), (13, "xla"), (16, "xla"), (31, "xla"),
    (3, "interpret"), (13, "interpret")])
def test_prefill_then_paged_decode_is_the_full_forward(ref, params, t0,
                                                       mode):
    """A prompt shorter than the window (3), of a window (8), ending
    mid-page (13), filling its bucket (16) and several windows long
    (31), then a token at a time to position 40: every step's logits
    are the reference's full forward's. The rings wrap (3 pages of 4
    against 40 positions), the global layer's pages do not."""
    p16, p32 = params
    ids = _ids(40, seed=t0)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(CFG, SEED, ids, params=p16))
        last, got = _served_logits(_model(), p32, ids, t0, mode)
    np.testing.assert_allclose(last, want[t0 - 1], atol=ATOL)
    np.testing.assert_allclose(got, want[t0:], atol=ATOL)


def test_a_reused_slot_never_reads_its_predecessor(ref, params):
    """Slot 0 serves 36 positions, then a prompt of 5: the second
    request's logits are the reference's, though every ring entry and
    page of the slot still holds the first one's rows."""
    p16, p32 = params
    first, second = _ids(36, seed=1), _ids(20, seed=2)
    eng = _engine(_model(), p32, slots=1)
    with jax.default_matmul_precision("highest"):
        rows, _ = _admit(eng, p32, first, 9, 0)
        _decode_logits(eng, p32, first, 9, 0, rows, 36)
        rows, last = _admit(eng, p32, second, 5, 0)
        got = _decode_logits(eng, p32, second, 5, 0, rows, 20)
        want = np.asarray(ref.logits(CFG, SEED, second, params=p16))
    np.testing.assert_allclose(last, want[4], atol=ATOL)
    np.testing.assert_allclose(got, want[5:], atol=ATOL)


def test_a_ring_filled_from_the_buckets_end_fails(ref, params,
                                                  monkeypatch):
    """The planted fault: the rings taken at the padded bucket's last
    position instead of the prompt's. The same comparison then fails by
    far more than its tolerance."""
    p16, p32 = params
    ids = _ids(24, seed=5)
    t0 = 9                              # in a bucket of 16
    real = exaone_moe._Prefill._ring

    def at_the_buckets_end(self, c):
        self.t0 = c.shape[1]
        return real(self, c)

    monkeypatch.setattr(exaone_moe._Prefill, "_ring", at_the_buckets_end)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(CFG, SEED, ids, params=p16))
        _, got = _served_logits(_model(), p32, ids, t0)
    assert float(np.max(np.abs(got - want[t0:]))) > 100 * ATOL


def test_a_window_of_127_for_128_fails(ref, params):
    """The planted fault of a window one position short (7 for 8), in
    the forward and through the cache alike."""
    p16, p32 = params
    ids = _ids(24, seed=6)
    short = _model(sliding_window=7)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(CFG, SEED, ids, params=p16))
        full = np.asarray(short.forward(p32, jnp.asarray(ids[None]))[0])
        _, got = _served_logits(short, p32, ids, 10)
    assert float(np.max(np.abs(full - want))) > 100 * ATOL
    assert float(np.max(np.abs(got - want[10:]))) > 100 * ATOL


# ------------------------------------------------- the share (guide §4)
def _share(i):
    return dict(CFG, expert_offset=i * HELD, vocab_offset=i * SLICE)


def test_a_share_is_a_slice_of_the_uncut_model(ref):
    """Expert ``e`` and vocabulary row ``r`` have the same values in
    whichever share holds them."""
    whole_g, whole_l = ref.make_globals(UNCUT, SEED), \
        ref.make_layer(UNCUT, SEED, 2)
    for i in (0, 3, 7):
        g, lp = ref.make_globals(_share(i), SEED), \
            ref.make_layer(_share(i), SEED, 2)
        rows = slice(i * SLICE, (i + 1) * SLICE)
        np.testing.assert_array_equal(g["tok_emb"], whole_g["tok_emb"][rows])
        np.testing.assert_array_equal(g["head"], whole_g["head"][:, rows])
        held = slice(i * HELD, (i + 1) * HELD)
        for name in ("ew1", "ew3", "ew2"):
            np.testing.assert_array_equal(lp[name], whole_l[name][held])
        for name in ("router", "router_bias", "sw1", "wq"):
            np.testing.assert_array_equal(lp[name], whole_l[name])


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_eight_shares_add_up_to_the_uncut_layer(ref, mode):
    """The routed parts of all 8 shares plus what every chip computes
    alike (the attention and the shared expert), counted once, are the
    uncut reference's layer."""
    li = 3                                   # sparse, global attention
    f32 = lambda t: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), t)
    x = jax.random.normal(jax.random.key(4), (1, 12, 64), jnp.float32)
    pos = jnp.arange(12, dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        want = ref.layer(f32(ref.make_layer(UNCUT, SEED, li)), x[0], li,
                         ref.sizes(UNCUT))
        total = None
        for i in range(SHARES):
            m = _model(_share(i))
            lp = f32(ref.make_layer(_share(i), SEED, li))
            aux = {"stats": [], "experts": []}
            dense = exaone_moe._Dense(8, 256)
            # the share's whole layer: x + attention + shared + routed_i
            out = m._block(li, lp, x, pos, dense, None, mode, aux)[0]
            # what every chip computes alike, for taking it off again
            after = x + m._attn(li, lp, m._rms(x, lp["in_norm"]), dense, pos)
            h = m._rms(after, lp["post_norm"])[0]
            routed = out - after[0] - m._swiglu(h, lp["sw1"], lp["sw3"],
                                                lp["sw2"])
            total = out if total is None else total + routed
            # a share counts its own assignments, and all 24 routed
            assert int(aux["stats"][0][3]) == 12 * 2
            assert int(aux["stats"][0][0]) < 12 * 2
    np.testing.assert_allclose(total, want, atol=ATOL)


def test_eight_vocabulary_slices_are_the_uncut_logits(ref):
    x = jax.random.normal(jax.random.key(5), (6, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        g = ref.make_globals(UNCUT, SEED)
        want = ref._rms(x, g["final_norm"].astype(jnp.float32), 1e-5) \
            @ g["head"].astype(jnp.float32)
        got = []
        for i in range(SHARES):
            m = _model(_share(i))
            p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                       ref.make_globals(_share(i), SEED))
            got.append(m._head(m._rms(x, p["final_norm"]), p))
    np.testing.assert_allclose(jnp.concatenate(got, -1), want, atol=ATOL)


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_an_absent_expert_is_no_group(mode):
    """Held experts 4..5 of 8: assignments to the others, and dead
    rows, add nothing; the held ones add their SwiGLU times the
    weight."""
    k = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(k[0], (6, 16), jnp.float32)
    ew1, ew3 = (jax.random.normal(k[i], (2, 16, 8), jnp.float32)
                for i in (1, 2))
    ew2 = jax.random.normal(k[3], (2, 8, 16), jnp.float32)
    idx = jnp.asarray([[4, 0], [5, 4], [1, 2], [7, 5], [4, 5], [5, 4]])
    w = jax.random.uniform(k[4], (6, 2), jnp.float32)
    live = jnp.asarray([True] * 5 + [False])
    with jax.default_matmul_precision("highest"):
        got, counts = routed_experts(x, idx, w, ew1, ew3, ew2, first=4,
                                     routed=8, live=live, mode=mode)
        want = np.zeros((6, 16), np.float32)
        for r in range(5):
            for j in range(2):
                e = int(idx[r, j]) - 4
                if 0 <= e < 2:
                    want[r] += float(w[r, j]) * np.asarray(
                        (jax.nn.silu(x[r] @ ew1[e]) * (x[r] @ ew3[e]))
                        @ ew2[e])
    np.testing.assert_allclose(got, want, atol=1e-4)
    assert counts.tolist() == [3, 3]


# -------------------------------------------------- the kernel's ring
def _ring_case(H, KV, hd, window, R, ps, pos, seed=0):
    """``N`` sequences at ``pos``, each with its own ring of ``R``
    pages filled as a decode would have (position ``p`` in entry ``(p
    // ps) % R``, older laps overwritten) and the plain attention over
    the window for comparison."""
    rng = np.random.default_rng(seed)
    N, W = len(pos), KV * hd
    q = rng.standard_normal((N, 1, H, hd)).astype(np.float32)
    pool = {n: np.zeros((1, N * R, ps, W), np.float32) for n in "kv"}
    want = np.zeros((N, 1, H, hd), np.float32)
    for n, p in enumerate(pos):
        k = rng.standard_normal((p + 1, KV, hd)).astype(np.float32)
        v = rng.standard_normal((p + 1, KV, hd)).astype(np.float32)
        for t in range(p + 1):
            page = n * R + (t // ps) % R
            pool["k"][0, page, t % ps] = k[t].reshape(-1)
            pool["v"][0, page, t % ps] = v[t].reshape(-1)
        lo = max(0, p - window + 1)
        for h in range(H):
            s = k[lo:, h // (H // KV)] @ q[n, 0, h] / np.sqrt(hd)
            a = np.exp(s - s.max())
            want[n, 0, h] = (a / a.sum()) @ v[lo:, h // (H // KV)]
    tables = np.arange(N * R, dtype=np.int32).reshape(N, R)
    return (jnp.asarray(q), {n: jnp.asarray(a) for n, a in pool.items()},
            jnp.asarray(tables), jnp.asarray(pos, jnp.int32), want)


@pytest.mark.parametrize("mode", ["xla", "interpret"])
@pytest.mark.parametrize("H,KV,hd,window,R,ps", [
    (4, 2, 16, 8, 3, 4),          # the tests' model
    (8, 1, 128, 32, 3, 16),       # a group of 8, a head of 128 lanes
    (16, 2, 128, 128, 9, 16)])    # the published window and ring
def test_the_ring_walk_is_the_windows_attention(H, KV, hd, window, R, ps,
                                                mode):
    """Sequences shorter than the window, of exactly a window, at a
    page's last offset, and many laps round the ring."""
    pos = [0, window // 2, window - 1, window, 3 * window + ps - 1,
           5 * window + 3]
    q, kv, tables, qbase, want = _ring_case(H, KV, hd, window, R, ps, pos)
    got = paged_attention(q, kv, 0, tables, qbase, mode=mode, window=window)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_ring_too_short_for_its_window_is_refused():
    q, kv, tables, qbase, _ = _ring_case(4, 2, 16, 8, 2, 4, [3])
    with pytest.raises(ValueError, match="ring"):
        paged_attention(q, kv, 0, tables, qbase, mode="xla", window=8)


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


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_engine_serves_what_the_reference_puts_first(ref, params, mode):
    """``submit`` / ``result`` over both kinds of cache, 8 requests over
    4 slots (joins, evictions, reused slots, prompts of 1, 3, a window,
    mid-page, a bucket and past it, contexts to 63): every served token
    is the reference's best."""
    p16, p32 = params
    reqs = [(_ids(n, seed=n), k) for n, k in REQUESTS]
    outs, stats = _serve(_model(), p32, reqs, attn_mode=mode)
    got = ref.check_served(CFG, SEED, [(pr, o) for (pr, _), o in
                                       zip(reqs, outs)], params=p16)
    assert got["compared"] == sum(k for _, k in REQUESTS)
    assert got["widest_gap"] < ATOL and got["mismatch_share"] == 0.0
    # the cache by kind: one global layer's pages, four layers' rings
    kv = stats["kv_pages"]
    assert kv["page_bytes"] == 2 * 1 * PS * 32 * 4
    assert kv["window_bytes"] == stats["state_bytes"] \
        == 2 * 4 * 4 * 3 * PS * 32 * 4
    assert kv["window_high_water_bytes"] == kv["window_bytes"]
    # an eighth of the experts is held: about an eighth of what is routed
    assert 0 < stats["expert_assignments"] \
        < stats["expert_assignments_routed"] / 3
    assert stats["expert_load_max"] >= stats["experts_touched"] > 0


@pytest.mark.parametrize("chunk", [1, 2, 8])
def test_chunk_sizes_give_the_same_tokens(params, chunk):
    _, p32 = params
    reqs = [(_ids(n, seed=n), k) for n, k in REQUESTS[:5]]
    base, _ = _serve(_model(), p32, reqs, max_chunk=4)
    outs, _ = _serve(_model(), p32, reqs, max_chunk=chunk)
    for a, b in zip(base, outs):
        np.testing.assert_array_equal(a, b)


def test_the_window_store_does_not_grow_with_the_context(params):
    """Twice the context is twice the pool's pages and the same rings."""
    _, p32 = params
    sizes = []
    for ctx in (64, 128):
        eng = DecodeEngine(_model(), p32, slots=2, page_size=PS,
                           max_context=ctx, warm_start=False)
        sizes.append((eng.pool.capacity, eng.stats()["kv_pages"]
                      ["window_bytes"]))
    assert sizes[1][0] == 2 * sizes[0][0] and sizes[1][1] == sizes[0][1]
    assert sizes[0][1] == 2 * 2 * 4 * 3 * PS * 32 * 4


def test_spans_carry_the_window_and_the_routed_counts(params):
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
        assert a["expert_assignments_routed"] > a["expert_assignments"]
        assert a["expert_assignments_routed"] % 2 == 0
        assert a["expert_layer_steps"] > 0
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
