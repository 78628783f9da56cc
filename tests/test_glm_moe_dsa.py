"""GLM-MoE-DSA on the CPU at a small size (hidden 64, 1 dense + 4 sparse
layers with indexers ``full shared shared shared full``, ``index_topk``
16 against contexts of 24-120, 4 heads of 16 + 8 over a latent of 32 +
8, an indexer of 4 heads of 16, 2 of 8 routed experts held top-2 beside
a shared expert, 12 of 96 vocabulary rows), seeded random weights from
the benchmark's plain reference: the model's forward against the
reference's logits, the engine's prefill (latent rows and indexer keys
into one pool of two stores) then paged decode against the reference's
full forward (logits, not tokens), planted faults of the selection and
of the latent row, the share test of the model-configs guide (sixteen
expert shares add up to the uncut layer, eight vocabulary slices to the
uncut logits), the two device stages in their three forms, and the
engine options a pool of the model's own stores makes it refuse.

Tolerances. Program and reference both run in float32 at ``highest``
precision here; they differ in the ORDER of their sums only (an online
softmax over gathered rows against a whole masked one, the absorbed
product against the per-head one, sorted rows against a loop over
experts): 2e-4 absolute on logits that reach 5-12 is ten times what was
seen (1e-5 .. 2e-5) and far under what a planted fault gives (0.02 ..
3)."""

import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import glm_moe_dsa
from deeplearning4j_tpu.models.glm_moe_dsa import (GlmMoeDsaConfig,
                                                   GlmMoeDsaLM)
from deeplearning4j_tpu.ops.paged_attention_pallas import paged_attention
from deeplearning4j_tpu.ops import sparse_latent_attention_pallas as stages
from deeplearning4j_tpu.ops.sparse_latent_attention_pallas import (
    gather_rows, index_select, selected_rows, sparse_latent_attention)
from deeplearning4j_tpu.serving.engine import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 13
HELD, SLICE, TOP = 2, 12, 16
F, S = "full", "shared"
CFG = dict(vocab_size=SLICE, vocab_offset=2 * SLICE, hidden_size=64,
           intermediate_size=96, moe_intermediate_size=32,
           num_hidden_layers=5, indexer_types=[F, S, S, S, F],
           mlp_layer_types=["dense"] + ["sparse"] * 4,
           num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32,
           qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
           index_n_heads=4, index_head_dim=16, index_topk=TOP,
           num_experts=HELD, n_routed_experts=8, expert_offset=2,
           num_experts_per_tok=2, n_shared_experts=1, rms_norm_eps=1e-5,
           rope_parameters={"rope_theta": 8e6}, norm_topk_prob=True,
           routed_scaling_factor=2.5, initializer_range=0.3,
           expert_bias_range=0.3, max_position_embeddings=128)
ATOL = 2e-4
PS = 4           # positions a page


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("reference/glm_moe_dsa.py", "glm_reference_for_tests")


@pytest.fixture(scope="module")
def params(ref):
    """The reference's bf16 values, and the same in float32."""
    p = ref.make_params(CFG, SEED)
    return p, jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)


def _config(cfg=CFG, **over):
    keys = {k: v for k, v in cfg.items() if k not in (
        "rope_parameters", "expert_bias_range", "vocab_offset")}
    keys.update(over)
    return GlmMoeDsaConfig(rope_theta=8e6, **keys)


def _model(cfg=CFG, dtype=jnp.float32, cls=GlmMoeDsaLM, **over):
    return cls(_config(cfg, **over), dtype)


def _ids(n, seed=0):
    return np.random.default_rng(seed).integers(0, SLICE, n).astype(np.int32)


# ------------------------------------------------------------ the model
@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_forward_matches_the_reference(ref, params, mode):
    """100 positions, six times ``index_topk``: the selection as a
    mask, interleaved rotary positions, the latent's two forms of K and
    V, the shared expert, the held experts."""
    p16, p32 = params
    ids = _ids(100)
    with jax.default_matmul_precision("highest"):
        got, experts = _model().forward(p32, jnp.asarray(ids[None]),
                                        return_aux=True, mode=mode)
        want = ref.logits(CFG, SEED, ids, params=p16)
    assert float(jnp.max(jnp.abs(want))) > 1.0       # the blocks decide
    np.testing.assert_allclose(got[0], want, atol=ATOL)
    # the router chooses among all 8; most choices are held elsewhere
    assert int(np.max(experts[0])) >= HELD * 3


def test_selection_and_attention_in_query_blocks_are_the_whole(ref, params):
    """The program's and the reference's block paths (a block of 8 and
    of 16 queries over 64 positions) give what one block does."""
    p16, p32 = params
    ids = _ids(64, seed=3)
    whole, blocks = _model(), _model()
    blocks.query_block = 8
    with jax.default_matmul_precision("highest"):
        base = whole.forward(p32, jnp.asarray(ids[None]))
        np.testing.assert_allclose(
            blocks.forward(p32, jnp.asarray(ids[None])), base, atol=1e-5)
        was = ref.QUERY_BLOCK
        try:
            ref.QUERY_BLOCK = 16
            ref._pre_fn.cache_clear()
            cut = ref.logits(CFG, SEED, ids, params=p16)
        finally:
            ref.QUERY_BLOCK = was
            ref._pre_fn.cache_clear()
    np.testing.assert_allclose(cut, base[0], atol=ATOL)


def test_the_selection_is_the_topk_and_shared_layers_reuse_it(ref, params):
    """The reference's own account of who reads whom: at most
    ``index_topk`` causal positions a query, every causal one while the
    context is short, a shared layer's mask the full layer's before it,
    the second full layer's another."""
    p16, _ = params
    sel = ref.selections(CFG, SEED, _ids(60, seed=4), params=p16)
    rows = sel[0].sum(-1)
    np.testing.assert_array_equal(rows, np.minimum(np.arange(60) + 1, TOP))
    assert not np.triu(sel[0], 1).any()
    for li in (1, 2, 3):
        np.testing.assert_array_equal(sel[li], sel[0])
    assert (sel[4] != sel[0]).any()
    np.testing.assert_array_equal(sel[4].sum(-1), rows)


def test_indexer_types_follow_offset_and_frequency(ref):
    """``index_skip_topk_offset`` 3 and ``index_topk_freq`` 4 spell the
    published list; the cut is its layers 2-6."""
    import json

    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "glm-5.2.json")))
    published = ref.indexer_pattern(78, cfg["index_skip_topk_offset"],
                                    cfg["index_topk_freq"])
    assert published[:8] == [F, F, F, S, S, S, F, S]
    assert published.count(F) == 21 and published[-3:] == [S, S, S]
    assert cfg["indexer_types"] == published[2:7]


def test_cache_spec_names_two_stores():
    assert _model().cache_spec() == {
        "stores": {"latent": (5, 128), "index_k": (2, 16)},
        "state": None, "selected": TOP}
    full = GlmMoeDsaLM(GlmMoeDsaConfig(
        num_hidden_layers=5, indexer_types=[F, S, S, S, F],
        mlp_layer_types=["dense"] + ["sparse"] * 4, num_experts=16))
    assert full.cache_spec()["stores"] == {"latent": (5, 640),
                                           "index_k": (2, 128)}


def test_parameters_are_created_in_the_serving_dtype():
    p = _model(dtype=jnp.bfloat16).init_params(jax.random.key(0))
    assert {a.dtype for a in jax.tree_util.tree_leaves(p)} \
        == {jnp.dtype(jnp.bfloat16)}
    assert "iwq" in p["layers"][0] and "iwq" in p["layers"][4]
    assert not any(k.startswith("i") and k != "in_norm"
                   for k in p["layers"][2])
    assert p["layers"][1]["ew1"].shape == (2, 64, 32)
    assert p["layers"][1]["router"].shape == (64, 8)


@pytest.mark.parametrize("bad", [
    {"scoring_func": "softmax"}, {"n_group": 2}, {"topk_method": "greedy"},
    {"num_nextn_predict_layers": 1}, {"tie_word_embeddings": True},
    {"rope_interleave": False}, {"expert_offset": 7},
    {"indexer_types": [S, S, S, S, F]},
    {"indexer_types": [F, S, "sparse", S, F]}])
def test_config_refuses_what_is_not_implemented(bad):
    with pytest.raises(ValueError):
        _model(**bad)


# ---------------------------------- through the engine's cache, logits
def _engine(model, p, mode="xla", slots=2, **kw):
    return DecodeEngine(model, p, slots=slots, page_size=PS,
                        max_context=128, prefill_buckets=[8, 16, 32, 64],
                        attn_mode=mode, max_chunk=4, warm_start=False, **kw)


def _admit(eng, p, ids, t0, slot):
    """What ``DecodeEngine._admit`` does on the device: the engine's own
    prefill program writes the prompt's rows into both stores."""
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
    m, S_ = eng.model, eng.slots
    tables = np.zeros((S_, eng.pages_per_slot), np.int32)
    tables[slot] = rows
    live = np.zeros((S_,), bool)
    live[slot] = True
    step = jax.jit(lambda kv, pos, tok: m.decode_step(
        p, kv, None, jnp.asarray(tables), pos, tok, jnp.asarray(live), PS,
        mode=eng._attn_mode))
    out = []
    for t in range(t0, upto):
        pos, tok = np.zeros((2, S_), np.int32)
        pos[slot], tok[slot] = t, ids[t]
        kv, state, lg, _ = step(eng._cache()[0], jnp.asarray(pos),
                                jnp.asarray(tok))
        eng._rebind((kv, state))
        out.append(np.asarray(lg[slot]))
    return np.stack(out)


def _served_logits(model, p, ids, t0, mode="xla", slot=1, upto=None):
    eng = _engine(model, p, mode)
    rows, last = _admit(eng, p, ids, t0, slot)
    return last, _decode_logits(eng, p, ids, t0, slot, rows,
                                upto or len(ids))


@pytest.mark.parametrize("t0,n,mode", [
    (3, 24, "xla"), (13, 40, "xla"), (16, 40, "xla"), (37, 120, "xla"),
    (61, 80, "xla"), (3, 24, "interpret"), (37, 64, "interpret")])
def test_prefill_then_paged_decode_is_the_full_forward(ref, params, t0, n,
                                                       mode):
    """A prompt under ``index_topk`` (3, 13: everything is selected,
    then the context passes 16 while decoding), of exactly it (16),
    ending mid-page and several times it (37, 61), then a token at a
    time: every step's logits are the reference's full forward's."""
    p16, p32 = params
    ids = _ids(n, seed=t0)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(CFG, SEED, ids, params=p16))
        last, got = _served_logits(_model(), p32, ids, t0, mode)
    np.testing.assert_allclose(last, want[t0 - 1], atol=ATOL)
    np.testing.assert_allclose(got, want[t0:], atol=ATOL)


def test_a_reused_slot_never_reads_its_predecessor(ref, params):
    """Slot 0 serves 60 positions, then a prompt of 5: the second
    request's logits are the reference's, though every page of the slot
    still holds the first one's rows in both stores."""
    p16, p32 = params
    first, second = _ids(60, seed=1), _ids(30, seed=2)
    eng = _engine(_model(), p32, slots=1)
    with jax.default_matmul_precision("highest"):
        rows, _ = _admit(eng, p32, first, 21, 0)
        _decode_logits(eng, p32, first, 21, 0, rows, 60)
        rows, last = _admit(eng, p32, second, 5, 0)
        got = _decode_logits(eng, p32, second, 5, 0, rows, 30)
        want = np.asarray(ref.logits(CFG, SEED, second, params=p16))
    np.testing.assert_allclose(last, want[4], atol=ATOL)
    np.testing.assert_allclose(got, want[5:], atol=ATOL)


# ------------------------------------------------------ planted faults
class _UnrotatedKey(GlmMoeDsaLM):
    """The planted fault of a latent row whose ``k_r`` was never
    rotated."""

    def _attn(self, li, lp, x, cache, pos):
        real = cache.attend
        raw = (x @ lp["wkv_a"])[..., self.cfg.kv_lora_rank:]

        def attend(li, lp, q_nope, q_rope, c_kv, k_r, index, pos):
            return real(li, lp, q_nope, q_rope, c_kv, raw, index, pos)

        cache.attend = attend
        try:
            return super()._attn(li, lp, x, cache, pos)
        finally:
            del cache.attend


def _with_layer_4s_indexer(p):
    """Layers 1-3 given layer 4's indexer: a shared layer that computes
    a selection of its own."""
    own = {k: v for k, v in p["layers"][4].items()
           if k in ("iwq", "iwk", "ik_gain", "ik_bias", "iww")}
    return dict(p, layers=[dict(lp, **own) if li in (1, 2, 3) else lp
                           for li, lp in enumerate(p["layers"])])


FAULTS = {
    "attends_every_position": dict(over={"index_topk": 10 ** 6}),
    "shared_layer_selects_for_itself": dict(
        over={"indexer_types": [F] * 5}, params=_with_layer_4s_indexer),
    "stale_selection": dict(over={"indexer_types": [F, S, S, S, S]}),
    "topk_one_short": dict(over={"index_topk": TOP - 1}),
    "unrotated_k_r": dict(cls=_UnrotatedKey),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails(ref, params, fault):
    """Each fault, in the forward and through the cache alike, misses
    the reference by far more than the tolerance."""
    p16, p32 = params
    spec = FAULTS[fault]
    ids = _ids(48, seed=6)
    faulty = _model(cls=spec.get("cls", GlmMoeDsaLM), **spec.get("over", {}))
    p = spec.get("params", lambda t: t)(p32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(CFG, SEED, ids, params=p16))
        full = np.asarray(faulty.forward(p, jnp.asarray(ids[None]))[0])
        _, got = _served_logits(faulty, p, ids, 21)
    assert float(np.max(np.abs(full - want))) > 100 * ATOL
    assert float(np.max(np.abs(got - want[21:]))) > 100 * ATOL


# ------------------------------------------------- the share (guide §4)
SHARES = 16
#: one expert a share of 16, an eighth of 96 rows a slice
ONE = dict(CFG, num_experts=1, n_routed_experts=SHARES, expert_offset=0,
           vocab_offset=0)
UNCUT = dict(ONE, num_experts=SHARES, vocab_size=8 * SLICE)


def _share(i):
    return dict(ONE, expert_offset=i, vocab_offset=(i % 8) * SLICE)


def test_a_share_is_a_slice_of_the_uncut_model(ref):
    whole_g, whole_l = ref.make_globals(UNCUT, SEED), \
        ref.make_layer(UNCUT, SEED, 4)
    for i in (0, 3, 15):
        g, lp = ref.make_globals(_share(i), SEED), \
            ref.make_layer(_share(i), SEED, 4)
        rows = slice((i % 8) * SLICE, (i % 8 + 1) * SLICE)
        np.testing.assert_array_equal(g["tok_emb"], whole_g["tok_emb"][rows])
        np.testing.assert_array_equal(g["head"], whole_g["head"][:, rows])
        for name in ("ew1", "ew3", "ew2"):
            np.testing.assert_array_equal(lp[name], whole_l[name][i:i + 1])
        for name in ("router", "router_bias", "sw1", "wkv_a", "iwq", "iww"):
            np.testing.assert_array_equal(lp[name], whole_l[name])


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_sixteen_shares_add_up_to_the_uncut_layer(ref, mode):
    """The routed parts of all 16 shares plus what every chip computes
    alike (attention, indexer and shared expert), counted once, are the
    uncut reference's layer (layer 4: sparse, a full indexer; 24
    positions, so the selection bites)."""
    li = 4
    f32 = lambda t: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), t)
    x = jax.random.normal(jax.random.key(4), (1, 24, 64), jnp.float32)
    pos = jnp.arange(24, dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        want, _ = ref.layer(f32(ref.make_layer(UNCUT, SEED, li)), x[0],
                            None, li, ref.sizes(UNCUT))
        total = None
        for i in range(SHARES):
            m = _model(_share(i))
            lp = f32(ref.make_layer(_share(i), SEED, li))
            aux = {"stats": [], "experts": []}
            dense = glm_moe_dsa._Dense(m.cfg, 256)
            out = m._block(li, lp, x, pos, dense, None, mode, aux)[0]
            after = x + m._attn(li, lp, m._rms(x, lp["in_norm"]), dense, pos)
            h = m._rms(after, lp["post_norm"])[0]
            routed = out - after[0] - m._swiglu(h, lp["sw1"], lp["sw3"],
                                                lp["sw2"])
            total = out if total is None else total + routed
            assert int(aux["stats"][0][3]) == 24 * 2
            assert int(aux["stats"][0][0]) < 24 * 2
    np.testing.assert_allclose(total, want, atol=ATOL)


def test_eight_vocabulary_slices_are_the_uncut_logits(ref):
    x = jax.random.normal(jax.random.key(5), (6, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        g = ref.make_globals(UNCUT, SEED)
        want = ref._rms(x, g["final_norm"].astype(jnp.float32), 1e-5) \
            @ g["head"].astype(jnp.float32)
        got = []
        for i in range(8):
            m = _model(_share(i))
            p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                       ref.make_globals(_share(i), SEED))
            got.append(m._head(m._rms(x, p["final_norm"]), p))
    np.testing.assert_allclose(jnp.concatenate(got, -1), want, atol=ATOL)


# ------------------------------------------------ the two device stages
def _stage_case(S_, H, W, dv, D, Hi, P, ps, pos, seed=0, dtype=np.float32):
    """Stores filled as a decode would have left them (slot ``s`` holds
    ``pos[s] + 1`` positions through its own pages, the rest of the
    pool noise another request left), and the step's queries."""
    rng = np.random.default_rng(seed)
    n_pages = 1 + S_ * P
    latent = rng.standard_normal((2, n_pages, ps, W)).astype(dtype)
    index_k = rng.standard_normal((2, n_pages, ps, D)).astype(dtype)
    tables = 1 + np.arange(S_ * P, dtype=np.int32).reshape(S_, P)
    q = rng.standard_normal((S_, H, W)).astype(dtype)
    qi = rng.standard_normal((S_, Hi, D)).astype(dtype)
    w = rng.standard_normal((S_, Hi)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(qi), jnp.asarray(w),
            jnp.asarray(latent), jnp.asarray(index_k), jnp.asarray(tables),
            jnp.asarray(pos, jnp.int32))


def _plain_scores(qi, w, index_k, layer, tables, pos):
    out = []
    for s in range(len(pos)):
        k = np.asarray(index_k)[layer][np.asarray(tables)[s]] \
            .reshape(-1, index_k.shape[-1])[:int(pos[s]) + 1]
        out.append((np.maximum(k @ np.asarray(qi)[s].T, 0)
                    * np.asarray(w)[s]).sum(-1))
    return out


@pytest.mark.parametrize("mode", ["xla", "interpret"])
@pytest.mark.parametrize("D,Hi,P,ps,top", [(16, 4, 10, 4, 16),
                                           (128, 32, 24, 16, 128)])
def test_index_select_is_the_exact_topk(D, Hi, P, ps, top, mode):
    """Contexts under ``top`` (everything), of exactly ``top``,
    mid-page and filling the table: the selection is the ``top`` best
    of the positions held, handed over in order of position."""
    pos = [0, top // 2, top - 1, top, P * ps - ps - 2, P * ps - 1]
    _, qi, w, _, index_k, tables, qpos = _stage_case(
        len(pos), 4, 128, 64, D, Hi, P, ps, pos)
    with jax.default_matmul_precision("highest"):
        sel, n_sel = index_select(qi, w, index_k, 1, tables, qpos, top,
                                  mode=mode)
    want = _plain_scores(qi, w, index_k, 1, tables, pos)
    for s, p in enumerate(pos):
        n = min(p + 1, top)
        assert int(n_sel[s]) == n
        got = np.asarray(sel[s, :n])
        assert (np.diff(got) > 0).all() and got.max() <= p
        best = np.sort(want[s])[::-1][:n]
        np.testing.assert_allclose(np.sort(want[s][got])[::-1], best,
                                   rtol=1e-5, atol=1e-5)


def test_index_select_breaks_ties_to_the_lower_position():
    """Keys that score alike: the lower positions are taken."""
    sel, n_sel = index_select(*_tie_case(), mode="xla")
    assert int(n_sel[0]) == 8
    np.testing.assert_array_equal(np.sort(np.asarray(sel[0])), np.arange(8))


TIES = dict(ps=4, P=6, D=16, pos=20, top=8)


def _tie_case():
    """Keys that score alike, 21 of them held."""
    ps, P, D = TIES["ps"], TIES["P"], TIES["D"]
    index_k = jnp.ones((1, 1 + P, ps, D), jnp.float32)
    tables = jnp.arange(1, 1 + P, dtype=jnp.int32)[None]
    qi, w = jnp.ones((1, 2, D), jnp.float32), jnp.ones((1, 2), jnp.float32)
    return qi, w, index_k, 0, tables, jnp.asarray([TIES["pos"]], jnp.int32), \
        TIES["top"]


@pytest.mark.parametrize("case,mode", [
    ((16, 4, 10, 4, 16), "xla"), ((16, 4, 10, 4, 16), "interpret"),
    ((128, 32, 24, 16, 128), "xla"), ((128, 32, 24, 16, 128), "interpret"),
    ("ties", "xla"), ("ties", "interpret")],
    ids=["toy-xla", "toy-interpret", "published-xla", "published-interpret",
         "ties-xla", "ties-interpret"])
def test_the_mask_is_the_selection(case, mode):
    """The mask ``index_select`` makes from the scores (a threshold and
    a running count, no scatter) names exactly ``sel[s, :n_sel[s]]``,
    on the exact top-k's cases and where every key scores alike; the
    scatter of the list, which the stage falls back to, names them
    too."""
    if case == "ties":
        args = _tie_case()
    else:
        D, Hi, P, ps, top = case
        pos = [0, top // 2, top - 1, top, P * ps - ps - 2, P * ps - 1]
        _, qi, w, _, index_k, tables, qpos = _stage_case(
            len(pos), 4, 128, 64, D, Hi, P, ps, pos)
        args = (qi, w, index_k, 1, tables, qpos, top)
    with jax.default_matmul_precision("highest"):
        sel, n_sel, mask = index_select(*args, mode=mode, with_mask=True)
        plain = index_select(*args, mode=mode)
    np.testing.assert_array_equal(plain[0], sel)
    np.testing.assert_array_equal(plain[1], n_sel)
    cells = args[4].shape[1] * args[2].shape[2]
    assert mask.shape == (len(n_sel), cells) and mask.dtype == bool
    scattered = np.asarray(stages.selection_mask(sel, n_sel, cells))
    for s in range(len(n_sel)):
        want = np.asarray(sel[s, :int(n_sel[s])])
        np.testing.assert_array_equal(np.flatnonzero(np.asarray(mask[s])),
                                      want)
        np.testing.assert_array_equal(np.flatnonzero(scattered[s]), want)
    if case == "ties":
        np.testing.assert_array_equal(np.flatnonzero(np.asarray(mask[0])),
                                      np.arange(TIES["top"]))


#: pages a visit of the walk: contexts "several visits long" are
#: measured in it
WALK = stages._PAGES_A_WALK
#: H, W, dv, P, ps, top, positions (None: 0, top - 1, top, near the end)
STAGE_CASES = {
    "toy": (4, 128, 64, 10, 4, 16, None),
    # G = 64 over a row of 576 in 640
    "published-row": (64, 640, 512, 24, 16, 128, None),
    # three and four visits of 64 pages; the contexts end on a visit's
    # last row, on its first, mid-page and mid-visit, and inside the
    # first visit beside slots that go on
    "toy-many-visits": (4, 128, 64, 4 * WALK, 4, 16,
                        [2 * WALK * 4 - 1, 2 * WALK * 4, 3 * WALK * 4 + 6,
                         4 * WALK * 4 - 1, 9]),
    "published-row-many-visits": (64, 640, 512, 2 * WALK + 9, 16, 128,
                                  [WALK * 16 + 7, 2 * WALK * 16 + 100, 130,
                                   2 * WALK * 16 - 1]),
}


@pytest.mark.parametrize("fill", ["as-left", "unselected-1e4"])
@pytest.mark.parametrize("null_slot", [False, True],
                         ids=["live", "beside-a-null-table"])
@pytest.mark.parametrize("shape", sorted(STAGE_CASES))
def test_sparse_latent_attention_forms_agree_and_read_only_the_selected(
        shape, null_slot, fill):
    """The walk over the pages held against the gather of the selected
    rows, and both against plain arithmetic over ``sel``'s rows.
    ``beside-a-null-table``: one more slot at position 0 whose table is
    all the null page (an evicted slot) between the live ones.
    ``unselected-1e4``: every row of the store the selection does not
    name, pages the slots hold and the null page included, is 10,000: a
    masked row is read and weighs nothing."""
    H, W, dv, P, ps, top, pos = STAGE_CASES[shape]
    pos = list(pos or [0, top - 1, top, P * ps - 3])
    q, qi, w, latent, index_k, tables, qpos = _stage_case(
        len(pos) + null_slot, H, W, dv, 16, 4, P, ps, pos + [0] * null_slot,
        seed=2)
    if null_slot:
        # the evicted slot second, so that the walk passes through it
        order = [0, len(pos)] + list(range(1, len(pos)))
        q, qi, w, tables, qpos = (a[jnp.asarray(order)]
                                  for a in (q, qi, w, tables, qpos))
        tables = tables.at[1].set(0)
    scale = 0.125
    with jax.default_matmul_precision("highest"):
        sel, n_sel = index_select(qi, w, index_k, 0, tables, qpos, top,
                                  mode="xla")
        if fill == "unselected-1e4":
            named = np.zeros(latent.shape[1] * ps, bool)
            named[np.asarray(selected_rows(tables, sel, ps))[
                np.arange(sel.shape[1])[None] < np.asarray(n_sel)[:, None]]] \
                = True
            latent = latent.at[1].set(jnp.where(
                named.reshape(-1, ps)[..., None], latent[1], 1e4))
        got = {mode: np.asarray(sparse_latent_attention(
            q, latent, 1, tables, sel, n_sel, dv=dv, scale=scale,
            mode=mode)) for mode in ("xla", "interpret")}
    np.testing.assert_allclose(got["interpret"], got["xla"], atol=2e-5)
    rows = np.asarray(gather_rows(latent, 1, selected_rows(tables, sel, ps)))
    for s in range(len(n_sel)):
        r = rows[s, :int(n_sel[s])]
        a = np.asarray(q)[s] @ r.T * scale
        a = np.exp(a - a.max(-1, keepdims=True))
        want = (a / a.sum(-1, keepdims=True)) @ r[:, :dv]
        np.testing.assert_allclose(got["xla"][s], want, atol=2e-5)
        assert np.abs(got["interpret"][s]).max() < 100


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_under_topk_it_is_plain_paged_attention(mode):
    """A context under ``index_topk`` selects everything: the sparse
    stage then equals the paged kernel's attention over the same store
    read as one KV head (64 query heads in one group, the row both key
    and value)."""
    H, W, P, ps, top = 64, 640, 12, 16, 2048
    pos = [0, 17, 100, P * ps - 1]
    q, qi, w, latent, index_k, tables, qpos = _stage_case(
        len(pos), H, W, 512, 128, 32, P, ps, pos, seed=3)
    scale = 1.0 / np.sqrt(W)               # the paged kernel's own
    with jax.default_matmul_precision("highest"):
        sel, n_sel = index_select(qi, w, index_k, 0, tables, qpos, top,
                                  mode=mode)
        got = sparse_latent_attention(q, latent, 0, tables, sel, n_sel,
                                      dv=512, scale=float(scale), mode=mode)
        want = paged_attention(q[:, None], {"k": latent, "v": latent}, 0,
                               tables, qpos, mode="xla")[:, 0, :, :512]
    np.testing.assert_allclose(got, want, atol=2e-5)


# ------------------------------------------------------------ the engine
def _serve(model, p, requests, **engine):
    engine = dict(dict(slots=4, page_size=PS, max_context=128,
                       prefill_buckets=[8, 16, 32, 64], attn_mode="xla",
                       max_chunk=4), **engine)
    with DecodeEngine(model, p, **engine) as eng:
        handles = [eng.submit(pr, n) for pr, n in requests]
        outs = [h.result(timeout=600) for h in handles]
        stats = eng.stats()
    return outs, stats


REQUESTS = [(1, 20), (3, 30), (16, 24), (13, 60), (37, 7), (61, 40), (7, 5),
            (9, 12)]


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_engine_serves_what_the_reference_puts_first(ref, params, mode):
    """``submit`` / ``result`` over the pool of two stores, 8 requests
    over 4 slots (joins, evictions, reused slots, prompts of 1, 3,
    ``index_topk``, mid-page and several times it, contexts to 101):
    every served token is the reference's best."""
    p16, p32 = params
    reqs = [(_ids(n, seed=n), k) for n, k in REQUESTS]
    outs, stats = _serve(_model(), p32, reqs, attn_mode=mode)
    got = ref.check_served(CFG, SEED, [(pr, o) for (pr, _), o in
                                       zip(reqs, outs)], params=p16)
    assert got["compared"] == sum(k for _, k in REQUESTS)
    assert got["widest_gap"] < ATOL and got["mismatch_share"] == 0.0
    kv = stats["kv_pages"]
    n_pages = 1 + 4 * 32
    assert kv["store_bytes"] == {"latent": 5 * n_pages * PS * 128 * 4,
                                 "index_k": 2 * n_pages * PS * 16 * 4}
    assert kv["page_bytes"] == (5 * 128 + 2 * 16) * PS * 4
    assert stats["state_bytes"] == 0
    assert 0 < stats["expert_assignments"] \
        < stats["expert_assignments_routed"] / 2


@pytest.mark.parametrize("chunk", [1, 8])
def test_chunk_sizes_give_the_same_tokens(params, chunk):
    _, p32 = params
    reqs = [(_ids(n, seed=n), k) for n, k in REQUESTS[:5]]
    base, _ = _serve(_model(), p32, reqs, max_chunk=4)
    outs, _ = _serve(_model(), p32, reqs, max_chunk=chunk)
    for a, b in zip(base, outs):
        np.testing.assert_array_equal(a, b)


def test_a_kv_model_reports_its_pools_by_name():
    """GPT-2 keeps ``{"k", "v"}``: the same stats, a store each."""
    from deeplearning4j_tpu.models.gpt import CausalLM
    from deeplearning4j_tpu.models.transformer import tiny_config

    cfg = tiny_config(vocab=64, max_len=64, d_model=32, n_layers=2,
                      n_heads=2, d_ff=64)
    model = CausalLM(cfg)
    eng = DecodeEngine(model, model.init_params(jax.random.key(0)), slots=2,
                       page_size=4, max_context=64, warm_start=False)
    kv = eng.stats()["kv_pages"]
    assert set(kv["store_bytes"]) == {"k", "v"}
    assert sum(kv["store_bytes"].values()) \
        == kv["page_bytes"] * eng.pool.n_pages
    assert list(eng.pool.tree()) == ["k", "v"]


def test_spans_carry_the_selected_and_the_scored_counts(params):
    from deeplearning4j_tpu.profiler import telemetry

    _, p32 = params
    t0 = time.perf_counter()
    _serve(_model(), p32, [(_ids(5), 9), (_ids(40, seed=1), 30)])
    calls = telemetry.spans_between(t0, float("inf"), "engine.dispatch")
    assert calls
    for e in calls:
        a = e["args"]
        # each live lane's context cut to index_topk, at each step
        assert 0 < a["ctx_selected_tokens"] <= min(
            a["ctx_tokens"], TOP * a["live"] * a["k"])
        assert a["ctx_index_tokens"] == a["ctx_tokens"]
        assert "ctx_window_tokens" not in a
    assert any(e["args"]["ctx_selected_tokens"] < e["args"]["ctx_tokens"]
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
