"""The tree a served model's programs take (docs/SERVING.md, "What a
served model brings": ``serving_params``).

``CausalLM`` casts a parameter to its compute dtype at every point of
use, so an engine handed float32 masters used to cast all of them
inside every dispatch (PERF.md, PR 33). ``DecodeEngine`` now holds the
copy ``serving_params`` makes once: the same values, hence the same
tokens, and half the bytes. A model that brings no such method, or
whose leaves already rest in its compute dtype, is served from the
very arrays it was given. ``_AsBefore`` hides the method: an engine
built around it is the engine this repository had before."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                                  ExaoneMoeLM)
from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.nn.precision import is_int8, quantized_bytes
from deeplearning4j_tpu.serving import DecodeEngine

VOCAB = 61
leaves = jax.tree_util.tree_leaves


def _gpt(dtype=jnp.bfloat16):
    cfg = tiny_config(vocab=VOCAB, max_len=64, d_model=32, n_layers=2,
                      n_heads=4, d_ff=64)
    cfg.dropout = 0.0
    return CausalLM(cfg, compute_dtype=dtype)


def _lfm2():
    return Lfm2MoeLM(Lfm2MoeConfig(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=3,
        layer_types=["conv", "full_attention", "conv"],
        num_dense_layers=1, num_experts=4, num_experts_per_tok=2,
        num_attention_heads=4, num_key_value_heads=2, conv_L_cache=3,
        max_position_embeddings=64))


def _exaone():
    L, G = "sliding_attention", "full_attention"
    return ExaoneMoeLM(ExaoneMoeConfig(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=3,
        layer_types=[L, G, L], mlp_layer_types=["dense", "sparse", "sparse"],
        sliding_window=8, num_experts=2, n_routed_experts=4,
        num_experts_per_tok=2, num_shared_experts=1,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        max_position_embeddings=64, window_page_size=8))


class _AsBefore:
    """The model with no ``serving_params``: what PR 32 served."""

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        if name == "serving_params":
            raise AttributeError(name)
        return getattr(self._model, name)


@pytest.fixture(scope="module")
def model():
    return _gpt()


@pytest.fixture(scope="module")
def masters(model):
    # wide enough that bf16 rounding never decides a token
    return jax.tree_util.tree_map(
        lambda a: a * 8 if a.ndim == 2 else a,
        model.init_params(jax.random.key(3)))


def _engine(model, tree, **kw):
    kw.setdefault("warm_start", False)
    return DecodeEngine(model, tree, slots=2, page_size=8, max_chunk=4,
                        prefill_buckets=[16, 32], **kw)


def _prompts(n=3):
    rng = np.random.default_rng(0)
    return [rng.integers(0, VOCAB, t).astype(np.int32)
            for t in (5, 11, 19)[:n]]


def _serve(model, tree, temperature=0.0, new=12, **kw):
    with _engine(model, tree, **kw) as eng:
        # one at a time: a sampled request's key follows its ordinal
        return [eng.submit(p, new, temperature).result(timeout=300)
                for p in _prompts()]


# ------------------------------------------------------------ the method
@pytest.mark.parametrize("case", [
    "every_floating_leaf_is_cast", "the_values_are_the_point_of_use_cast",
    "idempotent", "a_leaf_at_rest_is_the_same_array",
    "codes_and_scales_are_left_alone", "an_integer_leaf_is_left_alone",
    "a_float32_model_keeps_its_tree"])
def test_serving_params(model, masters, case):
    bf16 = jnp.dtype(jnp.bfloat16)
    copy = model.serving_params(masters)
    if case == "every_floating_leaf_is_cast":
        assert jax.tree_util.tree_structure(copy) \
            == jax.tree_util.tree_structure(masters)
        assert {a.dtype for a in leaves(masters)} == {jnp.dtype("float32")}
        assert {a.dtype for a in leaves(copy)} == {bf16}
    elif case == "the_values_are_the_point_of_use_cast":
        for a, b in zip(leaves(masters), leaves(copy)):
            np.testing.assert_array_equal(np.asarray(a.astype(bf16)),
                                          np.asarray(b))
    elif case == "idempotent":
        again = model.serving_params(copy)
        assert all(a is b for a, b in zip(leaves(copy), leaves(again)))
    elif case == "a_leaf_at_rest_is_the_same_array":
        mixed = dict(masters, tok_emb=copy["tok_emb"])
        got = model.serving_params(mixed)
        assert got["tok_emb"] is copy["tok_emb"]
        assert got["pos_emb"].dtype == bf16
    elif case == "codes_and_scales_are_left_alone":
        q = model.quantize_decode_params(masters)
        got = model.serving_params(q)
        for have, want in ((got["tok_emb"], q["tok_emb"]),
                           (got["layers"][1]["w2"], q["layers"][1]["w2"])):
            assert is_int8(have)
            assert have["q"] is want["q"] and have["s"] is want["s"]
            assert have["s"].dtype == jnp.float32
        assert got["layers"][0]["b1"].dtype == bf16
        assert got["pos_emb"].dtype == bf16
    elif case == "an_integer_leaf_is_left_alone":
        steps = jnp.arange(3)
        assert model.serving_params({"steps": steps})["steps"] is steps
    else:
        f32 = _gpt(jnp.float32)
        got = f32.serving_params(masters)
        assert all(a is b for a, b in zip(leaves(got), leaves(masters)))


# ------------------------------------------------------------ the wiring
def test_engine_keeps_the_copy_and_not_the_masters(model, masters):
    eng = _engine(model, masters)
    assert eng._decode_params is eng.params
    assert {a.dtype for a in leaves(eng.params)} \
        == {jnp.dtype(jnp.bfloat16)}
    held = {id(a) for a in leaves((eng.params, eng._decode_params))}
    assert not held & {id(a) for a in leaves(masters)}


@pytest.mark.parametrize("temperature", [0.0, 0.9],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("given", ["float32", "cast_before"])
def test_the_served_tokens_are_the_same(model, masters, given, temperature):
    """Float32 masters or the copy made by the caller: the tokens of
    the engine of PR 32 (float32 kept, cast in every program); greedy,
    they are ``generate()``'s."""
    want = _serve(_AsBefore(model), masters, temperature)
    tree = model.serving_params(masters) if given == "cast_before" \
        else masters
    got = _serve(model, tree, temperature)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert len({tuple(map(int, g)) for g in got}) > 1
    if temperature == 0.0:
        for p, g in zip(_prompts(), got):
            solo = model.generate(masters, jnp.asarray(p[None]), len(g))
            np.testing.assert_array_equal(np.asarray(solo)[0], g)


@pytest.mark.parametrize("temperature", [0.0, 0.9],
                         ids=["greedy", "sampled"])
def test_an_int8_engine_quantises_the_tree_as_given(model, masters,
                                                    temperature):
    """Codes and scales come from the float32 tree, as before; only the
    leaves that stay float rest in the compute dtype."""
    want = model.quantize_decode_params(masters)
    eng = _engine(model, masters, quantization="int8")
    got = eng._decode_params
    codes = lambda t: [x for x in jax.tree_util.tree_leaves(
        t, is_leaf=is_int8) if is_int8(x)]
    assert len(codes(got)) == len(codes(want)) == 1 + 4 * 2
    for g, w in zip(codes(got), codes(want)):
        np.testing.assert_array_equal(np.asarray(g["q"]), np.asarray(w["q"]))
        np.testing.assert_array_equal(np.asarray(g["s"]), np.asarray(w["s"]))
    assert quantized_bytes(codes(got)) == quantized_bytes(codes(want))
    assert got["pos_emb"].dtype == jnp.bfloat16
    # prefill stays in full precision: the copy, not the codes
    assert not any(map(is_int8, jax.tree_util.tree_leaves(
        eng.params, is_leaf=is_int8)))
    old = _serve(_AsBefore(model), masters, temperature,
                 quantization="int8")
    new = _serve(model, masters, temperature, quantization="int8")
    for g, w in zip(new, old):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("build", [_lfm2, _exaone, lambda: _gpt(jnp.float32)],
                         ids=["lfm2", "exaone", "gpt2_float32_compute"])
def test_a_model_at_rest_is_served_from_the_tree_it_gave(build):
    model = build()
    tree = model.init_params(jax.random.key(0))
    eng = _engine(model, tree)
    assert eng._decode_params is eng.params
    assert jax.tree_util.tree_structure(eng.params) \
        == jax.tree_util.tree_structure(tree)
    assert all(a is b for a, b in zip(leaves(eng.params), leaves(tree)))
    assert eng.stats()["weight_bytes"] == quantized_bytes(tree)


@pytest.mark.parametrize("case", ["float32_given", "cast_before",
                                  "as_before", "int8"])
def test_weight_bytes(model, masters, case):
    """The counter that says the copy engaged: the bytes of the arrays
    the programs take, halved for float32 GPT-2 and not otherwise."""
    f32 = quantized_bytes(masters)
    if case == "float32_given":
        got = _engine(model, masters).stats()["weight_bytes"]
        assert got * 2 == f32
    elif case == "cast_before":
        copy = model.serving_params(masters)
        got = _engine(model, copy).stats()["weight_bytes"]
        assert got == quantized_bytes(copy) == f32 // 2
    elif case == "as_before":
        got = _engine(_AsBefore(model), masters).stats()["weight_bytes"]
        assert got == f32
    else:
        eng = _engine(model, masters, quantization="int8")
        both = {id(a): a for a in leaves((eng.params, eng._decode_params))}
        got = eng.stats()["weight_bytes"]
        assert got == quantized_bytes(list(both.values()))
        # the prefill's copy and the decode tree beside it
        assert f32 // 2 < got < f32
