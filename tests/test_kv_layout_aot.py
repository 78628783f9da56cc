"""The page pool's layout contract, checked with no chip
(serving/kv_pages.py module docstring, "Layout contract").

The serving programs are compiled for a DESCRIBED TPU v5e (libtpu's
compiler, no device attached) and the optimised HLO is searched for a
``copy`` whose result has the pool's shape. Two ways a pool gets
copied, both found on the chip first (PERF.md section 6):

- *Inside a program* (PR 27: 72 copies of 379 MB a decode step). The
  paged kernel takes its operands row-major; a write whose update
  window has an indexed dimension between its own makes XLA keep the
  pools in another layout and copy each whole pool, every layer, to
  bridge the two. The ``pinned`` cases hold every array row-major at
  entry and exit and so see the writes alone; the control compiles
  the same program around such a write and must find those copies —
  it proves the search can see the fault.
- *At a program's boundary* (PR 31: four copies a dispatch, 26% of the
  GPT-2 serving window). At rest an array has the device's own layout
  for its shape; where that is not the one the program computes in,
  the program re-lays the pool out at entry and at exit. The
  ``at_rest`` cases pin nothing, so the compiler gives every argument
  and result the layout it has at rest: a page ``[page_size, H * hd]``
  whose rows are whole lane tiles rests row-major and is never copied;
  the control finds the four copies around the page this repo had
  before, ``[H, page_size, hd]`` with ``hd`` half a lane tile.

The same compile answers a second question (PR 33: 21% of the GPT-2
serving window): does a program cast a WEIGHT? ``CausalLM`` casts each
parameter to its compute dtype where it is used; an engine that held
float32 masters compiled to programs that read and rewrote all of them
once a dispatch. The engine now holds ``serving_params``' copy, so its
programs hold no ``convert`` with a weight's shape; the controls hand
the same programs, and ``decode_step`` itself, the float32 tree.

The kernel's grid is (KV head blocks, live visits): a visit holds every
KV head of up to 64 pages of one sequence, the heads along the lanes,
fetched by the kernel itself from pools left in HBM, and the visits are
as many as the pages the sequences hold; the last tests compile it at
the cells' shapes.

The topology is described inside a fixture, never at import (one
process a time may load libtpu; xdist workers all import this file).
"""

import functools
import re

import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.serving import DecodeEngine
from deeplearning4j_tpu.serving import kv_pages

LAYERS, SLOTS, CHUNK, SPEC_K, BUCKET = 2, 4, 4, 3, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@functools.lru_cache(maxsize=None)
def _engine(kv_dtype=None):
    # never started, only traced: one a pool dtype serves every case
    cfg = tiny_config(vocab=512, max_len=1024, d_model=256,
                      n_layers=LAYERS, n_heads=4, d_ff=1024)
    cfg.dropout = 0.0
    model = CausalLM(cfg, compute_dtype=jnp.bfloat16)
    return DecodeEngine(
        model, model.init_params(jax.random.key(0)), slots=SLOTS,
        page_size=16, max_context=1024, attn_mode="pallas",
        max_chunk=CHUNK, warm_start=False, prefix_cache=True,
        spec_decode={"k": SPEC_K}, kv_dtype=kv_dtype)


def _programs(eng):
    """name -> (the engine's own jitted program, its abstract
    arguments as ``_aot_warmup`` spells them, shapes only)."""
    S, P, kw = eng.slots, eng.pages_per_slot, eng._kd_width
    i32, u32, f32 = jnp.int32, jnp.uint32, jnp.float32
    # the one cache tree every program carries: (pool tree, state)
    cache, dec, par = eng._cache(), eng._decode_params, eng.params
    slot = [((S, P), i32), ((S,), i32), ((S,), bool), ((S,), i32)]
    tail = [((S, kw), u32), ((S,), f32)]
    out = {
        "chunk": (eng._decode_jits[CHUNK], [dec, cache, *slot, *tail]),
        "prefill": (eng._prefill_jit, [
            par, cache, ((1, BUCKET), i32),
            ((BUCKET // eng.page_size,), i32), ((), i32), ((), i32)]),
    }
    if eng._spec is not None:
        out["verify"] = (eng._verify_jit, [
            dec, cache, *slot, ((S, SPEC_K), i32), ((S,), i32), *tail])
    if eng._reuse:
        out["suffix_prefill"] = (eng._prefix_prefill_jit, [
            par, cache, ((BUCKET,), i32), ((P,), i32), ((), i32),
            ((), i32)])
    return out


def _compiled_text(jitted, args, one_chip, layouts) -> str:
    """Optimised HLO of ``jitted`` (KV tree donated, as the engine
    does) for the described chip: ``pinned``, every array row-major in
    and out; ``at_rest``, every array in the layout the device gives
    its shape, as the engine's arrays are between programs."""
    from jax.experimental.layout import Format, Layout

    def placed(ndim):
        if layouts == "at_rest":
            return one_chip
        return Format(Layout(tuple(range(ndim))), one_chip)

    def abstract(a):
        shape, dtype = (a.shape, a.dtype) if hasattr(a, "shape") else a
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=placed(len(shape)))

    # a (shape, dtype) pair is a leaf; the cache's pair is not
    args = jax.tree_util.tree_map(
        abstract, args, is_leaf=lambda a: isinstance(a, tuple)
        and isinstance(a[0], tuple))
    fn = jitted.__wrapped__
    outs = jax.tree_util.tree_map(lambda a: placed(a.ndim),
                                  jax.eval_shape(fn, *args))
    return jax.jit(fn, donate_argnums=(1,), out_shardings=outs) \
        .lower(*args).compile().as_text()


def pool_copies(hlo: str, pool) -> dict:
    """Pool-shaped ``copy`` instructions of an optimised HLO module:
    ``{"loop": inside a while body, "all": anywhere}``."""
    dims = ",".join(map(str, pool.shape))
    pat = re.compile(r"= \w+\[%s\]\S* copy\(" % re.escape(dims))
    comps = {}
    for block in re.split(r"\n\n+", hlo):
        m = re.match(r"\s*(?:ENTRY )?%?([\w.\-]+) \(", block)
        if m:
            comps[m.group(1)] = block
    bodies = set(re.findall(r"\bbody=%?([\w.\-]+)", hlo))
    return {"loop": sum(len(pat.findall(comps[b])) for b in bodies),
            "all": len(pat.findall(hlo))}


@pytest.mark.parametrize("layouts", ["pinned", "at_rest"])
@pytest.mark.parametrize("kv_dtype", [None, "fp8_e4m3"],
                         ids=["bf16", "fp8"])
@pytest.mark.parametrize(
    "program", ["chunk", "verify", "suffix_prefill", "prefill"])
def test_serving_program_never_copies_a_pool(one_chip, program,
                                             kv_dtype, layouts):
    """Neither a write inside the program (``pinned``) nor the
    program's boundary (``at_rest``) copies a pool. The fp8 pools
    (1-byte elements, tiles of 32 sublanes, pages of 16 rows) are found
    copy-free too."""
    eng = _engine(kv_dtype)
    assert eng.pool.k.ndim == 4
    jitted, args = _programs(eng)[program]
    hlo = _compiled_text(jitted, args, one_chip, layouts)
    assert "tpu_custom_call" in hlo or program == "prefill"
    assert pool_copies(hlo, eng.pool.k) == {"loop": 0, "all": 0}


# ------------------------------ the weights rest in the compute dtype
def weight_casts(hlo: str, eng) -> dict:
    """``convert`` instructions of an optimised HLO module whose result
    is a bf16 array of a matrix parameter's shape, by parameter name
    (``pos_emb`` [1024, 256] counts under ``w2``, whose shape it has)."""
    lp = eng.params["layers"][0]
    shapes = {"tok_emb": eng.params["tok_emb"].shape,
              **{k: lp[k].shape for k in ("wqkv", "wo", "w1", "w2")}}
    return {name: len(re.findall(
        r"= bf16\[%s\]\S* convert\(" % ",".join(map(str, shape)), hlo))
        for name, shape in shapes.items()}


@pytest.mark.parametrize("program,given", [
    ("chunk", "served"), ("prefill", "served"),
    ("chunk", "float32"), ("prefill", "float32"),
    ("decode_step", "float32")])
def test_serving_program_casts_no_weight(one_chip, program, given):
    """An engine built from FLOAT32 parameters: its chunk program and a
    prefill bucket take the tree it holds and cast none of it. The
    controls: the same two programs handed the float32 tree (the engine
    before PR 33), and ``CausalLM.decode_step`` on it directly, hold a
    cast of every matrix of every layer and of the embedding."""
    eng = _engine(None)
    masters = jax.tree_util.tree_map(
        lambda a: (a.shape, jnp.float32), eng.params)
    if program == "decode_step":
        model, ps = eng.model, eng.page_size

        def decode_step(params, kv, tables, pos, tok, active):
            kv, _, logits, _ = model.decode_step(
                params, kv, None, tables, pos, tok, active, ps,
                mode="pallas")
            return kv, logits

        S, P, i32 = eng.slots, eng.pages_per_slot, jnp.int32
        jitted, args = jax.jit(decode_step), [
            masters, eng.pool.tree(), ((S, P), i32), ((S,), i32),
            ((S,), i32), ((S,), bool)]
    else:
        jitted, args = _programs(eng)[program]
        assert {a.dtype for a in jax.tree_util.tree_leaves(args[0])} \
            == {jnp.dtype(jnp.bfloat16)}
        if given == "float32":
            args = [masters, *args[1:]]
    found = weight_casts(_compiled_text(jitted, args, one_chip, "at_rest"),
                         eng)
    if given == "served":
        assert found == dict.fromkeys(found, 0)
    else:
        assert found["tok_emb"] >= 1
        assert all(found[k] >= LAYERS for k in ("wqkv", "wo", "w1", "w2"))


# ------------------------------------------- the controls: the old page
#: the page this repo had until PR 31, at the test engine's sizes:
#: layers, pages, KV heads, page size, head width
OLD_POOL = (LAYERS, 1 + SLOTS * 64, 4, 16, 64)


def _old_page_program(write):
    """What a decode chunk did to pools of the old page ``[H, ps,
    hd]``: every step of a loop, every layer, ``write`` puts a
    position's K and V into both pools and a Mosaic kernel reads page
    blocks ``(1, 1, H, ps, hd)`` of them through a prefetched layer,
    as the paged kernel of PR 29 did. Returns the jitted program and
    its abstract arguments."""
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, n_pages, H, ps, hd = OLD_POOL

    def read(k, v, layer):
        def kernel(layer_ref, k_ref, v_ref, o_ref):
            o_ref[...] = k_ref[0].astype(jnp.float32) \
                + v_ref[0].astype(jnp.float32)

        page = pl.BlockSpec((1, 1, H, ps, hd),
                            lambda i, ly: (ly[0], i + 1, 0, 0, 0))
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(2,), in_specs=[page, page],
                out_specs=pl.BlockSpec((1, H, ps, hd),
                                       lambda i, ly: (i, 0, 0, 0))),
            out_shape=jax.ShapeDtypeStruct((2, H, ps, hd), jnp.float32),
        )(jnp.full((1,), layer, jnp.int32), k, v)

    def program(x, pools, page_idx, offset):
        def step(pools, _):
            k, v = pools
            seen = 0.0
            for layer in range(L):
                k = write(k, layer, page_idx, offset, x)
                v = write(v, layer, page_idx, offset, x)
                seen = seen + jnp.sum(read(k, v, layer))
            return (k, v), seen

        return lax.scan(step, pools, None, length=CHUNK)

    return jax.jit(program), [
        ((SLOTS, H, hd), jnp.bfloat16), (_old_pool(), _old_pool()),
        ((SLOTS,), jnp.int32), ((SLOTS,), jnp.int32)]


def _write_by_head(pool, layer, page_idx, offset, x):
    """PR 27's per-position write into the old page: the head index
    spelled out, so the update window is the trailing ``hd``."""
    heads = jnp.arange(pool.shape[2], dtype=page_idx.dtype)
    return pool.at[layer, page_idx[..., None], heads,
                   offset[..., None]].set(x.astype(pool.dtype))


def _old_pool():
    return jax.ShapeDtypeStruct(OLD_POOL, jnp.bfloat16)


def test_detector_sees_the_old_write(one_chip):
    """The control of the ``pinned`` cases: around the write this repo
    had before PR 27 (update window ``[H, hd]``, the indexed
    ``page_size`` between them) the loop holds one copy per pool per
    layer; around PR 27's write it holds none."""
    def old_write(pool, layer, page_idx, offset, x):
        return pool.at[layer, page_idx, :, offset].set(
            x.astype(pool.dtype))

    found = pool_copies(_compiled_text(
        *_old_page_program(old_write), one_chip, "pinned"), _old_pool())
    assert found["loop"] == 2 * LAYERS
    assert found["all"] >= found["loop"]
    assert pool_copies(_compiled_text(
        *_old_page_program(_write_by_head), one_chip, "pinned"),
        _old_pool()) == {"loop": 0, "all": 0}


def test_detector_sees_the_old_page_at_rest(one_chip):
    """The control of the ``at_rest`` cases: pools of the old page rest
    with the page dimension minor-most, so the same program, its
    writes as PR 27 left them, lays both pools out anew at entry and
    at exit."""
    found = pool_copies(_compiled_text(
        *_old_page_program(_write_by_head), one_chip, "at_rest"),
        _old_pool())
    assert found == {"loop": 0, "all": 4}


#: the pools of the two serving cells (BENCHMARK.json): layers, pages,
#: page size, KV heads x head width; and the query heads over them
GPT2_LARGE = ((36, 1 + 4 * 64, 16, 20 * 64), 20)
LFM2_24B = ((2, 1 + 16 * 64, 16, 8 * 64), 32)


@pytest.mark.parametrize("shape,dtype,row_major", [
    (GPT2_LARGE[0], jnp.bfloat16, True), (LFM2_24B[0], jnp.bfloat16, True),
    (GPT2_LARGE[0], jnp.float8_e4m3fn, True),
    ((36, 257, 20, 16, 64), jnp.bfloat16, False),   # the old page
    ((2, 33, 16, 32), jnp.bfloat16, False)],        # a toy row: 32 lanes
    ids=["gpt2-large", "lfm2-24b-a2b", "gpt2-large-fp8", "old-page",
         "narrow-row"])
def test_how_a_pool_rests(one_chip, shape, dtype, row_major):
    """The layout the device gives an array of a pool's shape: a page
    whose rows are whole lane tiles rests row-major, as the kernel and
    the writes take it."""
    rests = jax.jit(lambda x: x).lower(jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)).compile().input_formats[0][0]
    order = tuple(rests.layout.major_to_minor)
    assert (order == tuple(range(len(shape)))) == row_major


# ----------------- the kernels LFM2-24B-A2B serves through, at its widths
def _sds(shape, dtype, chip):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


@pytest.mark.parametrize("rows,k,n", [(64, 2048, 1536), (1024, 2048, 1536),
                                      (64, 1536, 2048)],
                         ids=["decode-up", "prefill-up", "decode-down"])
def test_grouped_matmul_compiles_at_published_widths(one_chip, rows, k, n):
    """16 slots x 4 experts a token (or a bucket of 256) over 64
    experts of 2048 x 1536: the chip's compiler takes the blocks."""
    from deeplearning4j_tpu.ops.grouped_matmul_pallas import grouped_matmul

    bf16 = jnp.bfloat16
    hlo = jax.jit(lambda a, b, c: grouped_matmul(a, b, c, mode="pallas")) \
        .lower(_sds((rows, k), bf16, one_chip),
               _sds((64, k, n), bf16, one_chip),
               _sds((64,), jnp.int32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo and "moe_experts" in hlo
    # no copy of the 64 experts' matrices on the way in
    assert not re.search(r"bf16\[64,%d,%d\][^\n]* copy\(" % (k, n), hlo)


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.float8_e4m3fn],
                         ids=["bf16", "fp8"])
@pytest.mark.parametrize("cell,slots,queries", [
    (GPT2_LARGE, 4, 1), (LFM2_24B, 16, 1),      # the cells' decode steps
    (GPT2_LARGE, 4, 4),                         # a verify call, k = 3
    (GPT2_LARGE, 1, 256), (LFM2_24B, 1, 256),   # a suffix-prefill bucket
    (GPT2_LARGE, 1, 1024)],
    ids=["gpt2-decode", "lfm2-decode", "gpt2-verify", "gpt2-suffix256",
         "lfm2-suffix256", "gpt2-suffix1024"])
def test_paged_kernel_compiles_at_published_widths(one_chip, cell, slots,
                                                   queries, kv_dtype):
    """The chip's compiler takes the walk at the shapes the cells run:
    every KV head of the pages a visit takes, the heads along the lanes
    and a lane tile's heads stacked on the query axis, the visits as
    many as the pages held (a dynamic grid), fewer heads a visit where
    the rows are many, the group of 4 on the query axis; and the pools
    go in as they rest, with no copy of one."""
    from deeplearning4j_tpu.ops.paged_attention_pallas import paged_attention

    (L, n_pages, ps, W), H = cell
    Hkv, hd = W // 64, 64
    bf16, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    pool = _sds((L, n_pages, ps, W), kv_dtype, one_chip)
    kv = {"k": pool, "v": pool}
    if kv_dtype != bf16:
        kv.update(k_scale=_sds((L, n_pages, Hkv), f32, one_chip),
                  v_scale=_sds((L, n_pages, Hkv), f32, one_chip))
    hlo = jax.jit(lambda q, kv, t, b: paged_attention(
        q, kv, 1, t, b, mode="pallas")) \
        .lower(_sds((slots, queries, H, hd), bf16, one_chip), kv,
               _sds((slots, 64), i32, one_chip),
               _sds((slots,), i32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo and "paged_attention" in hlo
    assert pool_copies(hlo, pool)["all"] == 0


# ------------- a cache of two kinds: K-EXAONE-236B-A23B's pool and rings
#: the cell's stores (BENCHMARK.json): the global layer's pool, and the
#: window layers' rings ``[layers, slots, ring pages, page, KV x head]``
EXAONE_POOL = (1, 1 + 64 * 320, 16, 8 * 128)
EXAONE_RINGS = (4, 64, 9, 16, 8 * 128)


@functools.lru_cache(maxsize=None)
def _two_kind_engine():
    """Never started, only traced: window (32) and global layers, a
    group of 2 query heads on 2 KV heads of 64 (rows of 128 lanes, the
    least that rest row-major), 2 of 8 experts held."""
    from deeplearning4j_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                                      ExaoneMoeLM)

    L, G = "sliding_attention", "full_attention"
    model = ExaoneMoeLM(ExaoneMoeConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        moe_intermediate_size=128, num_hidden_layers=5,
        layer_types=(L, L, L, G, L),
        mlp_layer_types=("dense",) + ("sparse",) * 4, sliding_window=32,
        num_experts=2, n_routed_experts=8, expert_offset=2,
        num_experts_per_tok=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=64, max_position_embeddings=1024),
        jnp.bfloat16)
    return DecodeEngine(
        model, model.init_params(jax.random.key(0)), slots=SLOTS,
        page_size=16, max_context=1024, attn_mode="pallas",
        max_chunk=CHUNK, warm_start=False)


@pytest.mark.parametrize("layouts", ["pinned", "at_rest"])
@pytest.mark.parametrize("program", ["chunk", "prefill"])
def test_two_kind_cache_is_never_copied(one_chip, program, layouts):
    """The decode chunk and the prefill of a model with window and
    global layers copy neither store: not the global layers' pool, not
    the window layers' rings, nor the rings seen as a pool (a merge of
    leading dimensions). The window layers' attention is the paged
    kernel, one trace of it for each kind of layer."""
    eng = _two_kind_engine()
    jitted, args = _programs(eng)[program]
    hlo = _compiled_text(jitted, args, one_chip, layouts)
    rings = eng._state["k"]
    Lw, S, R, ps, W = rings.shape
    assert (Lw, S, R, ps, W) == (4, SLOTS, 3, 16, 128)
    for store in (eng.pool.k, rings,
                  jax.ShapeDtypeStruct((Lw, S * R, ps, W), rings.dtype)):
        assert pool_copies(hlo, store) == {"loop": 0, "all": 0}
    if program == "chunk":
        assert "paged_attention" in hlo and "moe_experts" in hlo


@pytest.mark.parametrize("shape", [EXAONE_POOL, EXAONE_RINGS],
                         ids=["global-pool", "window-rings"])
def test_both_stores_of_the_two_kind_cache_rest_row_major(one_chip, shape):
    rests = jax.jit(lambda x: x).lower(jax.ShapeDtypeStruct(
        shape, jnp.bfloat16, sharding=one_chip)) \
        .compile().input_formats[0][0]
    assert tuple(rests.layout.major_to_minor) == tuple(range(len(shape)))


@pytest.mark.parametrize("window", [None, 128], ids=["global", "window"])
def test_paged_kernel_compiles_for_a_group_of_8_heads_of_128(one_chip,
                                                             window):
    """K-EXAONE-236B-A23B's decode step: 64 slots, 64 query heads on 8
    KV heads of 128 (a row of 1,024 lanes), through a table of 320
    pages for the global layer and through a ring of 9 for a window
    layer (``window=128``: the walk starts at the window's first page);
    the stores go in as they rest."""
    from deeplearning4j_tpu.ops.paged_attention_pallas import paged_attention

    bf16, i32 = jnp.bfloat16, jnp.int32
    Lw, S, R, ps, W = EXAONE_RINGS
    shape = EXAONE_POOL if window is None else (Lw, S * R, ps, W)
    pool = _sds(shape, bf16, one_chip)
    hlo = jax.jit(lambda q, kv, t, b: paged_attention(
        q, kv, 0, t, b, mode="pallas", window=window)) \
        .lower(_sds((S, 1, 64, 128), bf16, one_chip),
               {"k": pool, "v": pool},
               _sds((S, 320 if window is None else R), i32, one_chip),
               _sds((S,), i32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo and "paged_attention" in hlo
    assert pool_copies(hlo, pool)["all"] == 0


@pytest.mark.parametrize("rows,k,n", [(512, 6144, 2048), (512, 2048, 6144),
                                      (16384, 6144, 2048)],
                         ids=["decode-up", "decode-down", "prefill-up"])
def test_grouped_matmul_compiles_for_16_held_experts(one_chip, rows, k, n):
    """64 slots x 8 experts a token (or a bucket of 2,048) over the 16
    experts of 6144 x 2048 a chip holds: rows past the held experts'
    belong to no group."""
    from deeplearning4j_tpu.ops.grouped_matmul_pallas import grouped_matmul

    bf16 = jnp.bfloat16
    hlo = jax.jit(lambda a, b, c: grouped_matmul(a, b, c, mode="pallas")) \
        .lower(_sds((rows, k), bf16, one_chip),
               _sds((16, k, n), bf16, one_chip),
               _sds((16,), jnp.int32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo and "moe_experts" in hlo
    assert not re.search(r"bf16\[16,%d,%d\][^\n]* copy\(" % (k, n), hlo)


# ---------- a pool of a model's own stores: GLM-5.2's latent rows and keys
#: the cell's stores (BENCHMARK.json): every layer's latent rows, 576
#: numbers a position resting in 640 lanes, and the two full-indexer
#: layers' keys; 1 + 32 slots x 896 pages
GLM_LATENT = (5, 1 + 32 * 896, 16, 640)
GLM_INDEX_K = (2, 1 + 32 * 896, 16, 128)


@pytest.mark.parametrize("shape,row_major", [
    (GLM_LATENT, True), (GLM_INDEX_K, True),
    (GLM_LATENT[:3] + (576,), False),    # the row as the mathematics has it
    (GLM_LATENT[:3] + (512,), True),     # the latent alone ...
    (GLM_LATENT[:3] + (64,), False)],    # ... and its rotary key alone
    ids=["latent-640", "index-k", "latent-576", "c-kv-512", "k-rope-64"])
def test_how_a_latent_store_rests(one_chip, shape, row_major):
    """Why the latent row rests in 640 lanes: a row of 576 (four and a
    half lane tiles) rests with the PAGE dimension minor-most, as the
    page of PR 31's fault did, and so would a store of the 64 rotary
    lanes alone; 640, 512 and the indexer's 128 rest row-major. (Two
    stores, 512 and 64 padded to 128, would hold the same 640 lanes a
    position behind two gathers.)"""
    rests = jax.jit(lambda x: x).lower(jax.ShapeDtypeStruct(
        shape, jnp.bfloat16, sharding=one_chip)).compile().input_formats[0][0]
    order = tuple(rests.layout.major_to_minor)
    assert (order == tuple(range(len(shape)))) == row_major


@functools.lru_cache(maxsize=None)
def _latent_engine(attn_mode="pallas"):
    """Never started, only traced: MLA over a latent of 64 + 32 (a row
    of 128 lanes, the least that rests row-major), an indexer of 4
    heads of 128 selecting 128 positions (of the 64 pages a slot's table
    holds: no other look-up of the program has as many entries as the
    selection), full and shared layers, 2 of 8 experts held."""
    from deeplearning4j_tpu.models.glm_moe_dsa import (GlmMoeDsaConfig,
                                                       GlmMoeDsaLM)

    model = GlmMoeDsaLM(GlmMoeDsaConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        moe_intermediate_size=128, num_hidden_layers=5,
        indexer_types=("full", "shared", "shared", "shared", "full"),
        mlp_layer_types=("dense",) + ("sparse",) * 4,
        num_attention_heads=4, q_lora_rank=128, kv_lora_rank=64,
        qk_nope_head_dim=32, qk_rope_head_dim=32, v_head_dim=32,
        index_n_heads=4, index_head_dim=128, index_topk=128,
        num_experts=2, n_routed_experts=8, expert_offset=2,
        num_experts_per_tok=2, max_position_embeddings=1024), jnp.bfloat16)
    return DecodeEngine(
        model, model.init_params(jax.random.key(0)), slots=SLOTS,
        page_size=16, max_context=1024, attn_mode=attn_mode,
        max_chunk=CHUNK, warm_start=False)


@pytest.mark.parametrize("layouts", ["pinned", "at_rest"])
@pytest.mark.parametrize("program", ["chunk", "prefill"])
def test_a_pool_of_two_stores_is_never_copied(one_chip, program, layouts):
    """The decode chunk and the prefill of a model whose pool is a
    latent store and an indexer-key store under one page table copy
    neither, nor either seen as rows (the gather's view: a merge of
    leading dimensions); both new stages are kernels in the chunk."""
    eng = _latent_engine()
    stores = eng.pool.tree()
    assert {n: a.shape for n, a in stores.items()} == {
        "latent": (5, 1 + SLOTS * 64, 16, 128),
        "index_k": (2, 1 + SLOTS * 64, 16, 128)}
    jitted, args = _programs(eng)[program]
    hlo = _compiled_text(jitted, args, one_chip, layouts)
    for a in stores.values():
        L, n_pages, ps, W = a.shape
        for store in (a, jax.ShapeDtypeStruct((L * n_pages * ps, W),
                                              a.dtype)):
            assert pool_copies(hlo, store) == {"loop": 0, "all": 0}
    if program == "chunk":
        assert "sparse_latent_attention" in hlo and "index_scores" in hlo
        assert "moe_experts" in hlo


def test_index_scores_kernel_compiles_at_published_widths(one_chip):
    """GLM-5.2's selection: 32 slots, 32 indexer heads of 128 against
    the paged keys through a table of 896 pages, eight pages a visit,
    the visits as many as the pages held; the store goes in as it
    rests."""
    from deeplearning4j_tpu.ops.sparse_latent_attention_pallas import \
        index_select

    bf16, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    store = _sds(GLM_INDEX_K, bf16, one_chip)
    hlo = jax.jit(lambda q, w, st, t, p: index_select(
        q, w, st, 1, t, p, 2048, mode="pallas")) \
        .lower(_sds((32, 32, 128), bf16, one_chip),
               _sds((32, 32), f32, one_chip), store,
               _sds((32, 896), i32, one_chip),
               _sds((32,), i32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo and "index_scores" in hlo
    assert pool_copies(hlo, store)["all"] == 0


def row_gathers(hlo: str, rows: int, width: int, dtype="bf16") -> dict:
    """The gather of ``rows`` single rows of ``width`` lanes as a device
    trace lists it: a gather fusion whose result is ``[rows, width]``
    (``fusion bf16[65536,640]`` in the GLM-5.2 cell until PR 36: 32
    slots x 2,048 selected rows, 99 GB/s), and the fusions that make
    its ``rows`` addresses (``fusion s32[65536]``)."""
    def fusions(shape):
        return len(re.findall(
            r"= %s\S* fusion\([^\n]*op_name=\"[^\"]*gather" % re.escape(shape),
            hlo))

    return {"rows": fusions(f"{dtype}[{rows},{width}]"),
            "addresses": fusions(f"s32[{rows}]")}


@pytest.mark.parametrize("mode", ["pallas", "xla"])
def test_sparse_latent_kernel_compiles_at_published_widths(one_chip, mode):
    """GLM-5.2's attention: 64 query heads against ONE shared row of
    640 lanes a position (576 used), 32 slots, tables 896 wide, values
    the row's first 512 lanes. ``pallas``: the walk over the pages held
    compiles, the store goes in as it rests and stays in HBM, and no
    row is gathered. ``xla``, the control: the detector finds the
    gather of 65,536 rows and their addresses."""
    from deeplearning4j_tpu.ops.sparse_latent_attention_pallas import \
        sparse_latent_attention

    bf16, i32 = jnp.bfloat16, jnp.int32
    store = _sds(GLM_LATENT, bf16, one_chip)
    hlo = jax.jit(lambda q, st, t, sel, n: sparse_latent_attention(
        q, st, 3, t, sel, n, dv=512, scale=1 / 16, mode=mode)) \
        .lower(_sds((32, 64, 640), bf16, one_chip), store,
               _sds((32, 896), i32, one_chip),
               _sds((32, 2048), i32, one_chip),
               _sds((32,), i32, one_chip)).compile().as_text()
    found = row_gathers(hlo, 32 * 2048, 640)
    if mode == "xla":
        assert found["rows"] >= 1 and found["addresses"] >= 1
        return
    assert "tpu_custom_call" in hlo and "sparse_latent_attention" in hlo
    assert found == {"rows": 0, "addresses": 0}
    assert pool_copies(hlo, store)["all"] == 0
    L, n_pages, ps, W = GLM_LATENT
    assert pool_copies(hlo, jax.ShapeDtypeStruct(
        (L * n_pages * ps, W), bf16))["all"] == 0


@pytest.mark.parametrize("mode", ["pallas", "xla"])
def test_the_decode_chunk_gathers_no_row(one_chip, mode):
    """The decode chunk program of the latent engine (4 slots selecting
    128 positions: 512 rows of 128 lanes a layer-step): served through
    the kernels it walks pages and gathers no row and looks no address
    up; the ``xla`` form is the control in which the detector finds
    both."""
    eng = _latent_engine(mode)
    jitted, args = _programs(eng)["chunk"]
    # as served: the suite's "highest" is refused by Mosaic for the bf16
    # products XLA's own expert product lowers to in the control
    with jax.default_matmul_precision("default"):
        hlo = _compiled_text(jitted, args, one_chip, "at_rest")
    found = row_gathers(hlo, SLOTS * 128, 128)
    if mode == "xla":
        assert found["rows"] >= 1 and found["addresses"] >= 1
    else:
        assert "sparse_latent_attention" in hlo
        assert found == {"rows": 0, "addresses": 0}


# ------- Mellum2-12B-A2.5B: a window of 1,024 in rings, 64 experts whole
#: the cell's stores (BENCHMARK.json): the two full layers' pool (1 + 64
#: slots x 768 pages) and the six window layers' rings of 65 pages,
#: 4 KV heads x 128 a row
MELLUM_POOL = (2, 1 + 64 * 768, 16, 4 * 128)
MELLUM_RINGS = (6, 64, 65, 16, 4 * 128)


@pytest.mark.parametrize("shape", [MELLUM_POOL, MELLUM_RINGS],
                         ids=["full-pool", "window-rings"])
def test_the_stores_of_a_window_of_1024_rest_row_major(one_chip, shape):
    rests = jax.jit(lambda x: x).lower(jax.ShapeDtypeStruct(
        shape, jnp.bfloat16, sharding=one_chip)) \
        .compile().input_formats[0][0]
    assert tuple(rests.layout.major_to_minor) == tuple(range(len(shape)))


@pytest.mark.parametrize("window", [None, 1024], ids=["full", "window"])
def test_paged_kernel_compiles_for_32_heads_on_4_of_128(one_chip, window):
    """Mellum2's decode step: 64 slots, 32 query heads on 4 KV heads of
    128 (a row of 512 lanes), through a table of 768 pages for a full
    layer and through a ring of 65 for a window layer (``window=1024``);
    the stores go in as they rest."""
    from deeplearning4j_tpu.ops.paged_attention_pallas import paged_attention

    bf16, i32 = jnp.bfloat16, jnp.int32
    Lw, S, R, ps, W = MELLUM_RINGS
    shape = MELLUM_POOL if window is None else (Lw, S * R, ps, W)
    pool = _sds(shape, bf16, one_chip)
    hlo = jax.jit(lambda q, kv, t, b: paged_attention(
        q, kv, 0, t, b, mode="pallas", window=window)) \
        .lower(_sds((S, 1, 32, 128), bf16, one_chip), {"k": pool, "v": pool},
               _sds((S, 768 if window is None else R), i32, one_chip),
               _sds((S,), i32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo and "paged_attention" in hlo
    assert pool_copies(hlo, pool)["all"] == 0


@pytest.mark.parametrize("rows,k,n", [(512, 2304, 896), (512, 896, 2304),
                                      (65536, 2304, 896)],
                         ids=["decode-up", "decode-down", "prefill-up"])
def test_grouped_matmul_compiles_for_64_experts_of_896(one_chip, rows, k, n):
    """64 slots x 8 experts a token (or a bucket of 8,192) over all 64
    experts of 2304 x 896."""
    from deeplearning4j_tpu.ops.grouped_matmul_pallas import grouped_matmul

    bf16 = jnp.bfloat16
    hlo = jax.jit(lambda a, b, c: grouped_matmul(a, b, c, mode="pallas")) \
        .lower(_sds((rows, k), bf16, one_chip),
               _sds((64, k, n), bf16, one_chip),
               _sds((64,), jnp.int32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo and "moe_experts" in hlo


# ------------- the walk fetches its own pages: the visit the shapes choose
#: the served decode shapes: pool, queries ``[N, Q, H, hd]``, the table's
#: width, the window, and the table slots a visit takes
WALKS = {
    "mellum2-ring": ((MELLUM_RINGS[0], 64 * 65, 16, 512), (64, 1, 32, 128),
                     65, 1024, 33),
    "mellum2-full": (MELLUM_POOL, (64, 1, 32, 128), 768, None, 64),
    "k-exaone-ring": ((EXAONE_RINGS[0], 64 * 9, 16, 1024), (64, 1, 64, 128),
                      9, 128, 9),
    "gpt2-large": (GPT2_LARGE[0], (4, 1, 20, 64), 64, None, 32),
    "lfm2-24b-a2b": (LFM2_24B[0], (16, 1, 32, 64), 64, None, 64),
}


def _walk_hlo(one_chip, name):
    from deeplearning4j_tpu.ops.paged_attention_pallas import paged_attention

    shape, (S, Q, H, hd), P, window, _ = WALKS[name]
    bf16, i32 = jnp.bfloat16, jnp.int32
    pool = _sds(shape, bf16, one_chip)
    hlo = jax.jit(lambda q, kv, t, b: paged_attention(
        q, kv, 0, t, b, mode="pallas", window=window)) \
        .lower(_sds((S, Q, H, hd), bf16, one_chip), {"k": pool, "v": pool},
               _sds((S, P), i32, one_chip), _sds((S,), i32, one_chip)) \
        .compile().as_text()
    return hlo, pool


@pytest.mark.parametrize("name", list(WALKS))
def test_the_walk_compiles_with_the_visit_its_shapes_choose(one_chip, name):
    """Every KV head and the most pages whose two buffers fit beside the
    working set (``_visit_shape``), the table spread evenly over its
    visits: 64 pages of 16 KB, 32 of 32 or 40 KB, a ring of 65 as 33 +
    32 and one of 9 in one visit. The chip's compiler places the
    buffers in the scoped VMEM or refuses the kernel (the control
    below), and the pools stay in HBM as they rest, with no copy."""
    from deeplearning4j_tpu.ops import paged_attention_pallas as pa

    shape, (S, Q, H, hd), P, _, B = WALKS[name]
    Hkv = shape[3] // hd
    assert pa._visit_shape(Hkv, Q * H // Hkv, hd, shape[2], P, 2, 2,
                           False) == (Hkv, B)
    hlo, pool = _walk_hlo(one_chip, name)
    assert "tpu_custom_call" in hlo and "paged_attention" in hlo
    assert pool_copies(hlo, pool)["all"] == 0


def test_a_visit_past_the_scoped_vmem_is_refused(one_chip, monkeypatch):
    """The control: 256 pages of 16 KB a visit, two buffers of K and of
    V (16 MiB), do not fit, and the compiler says so."""
    from deeplearning4j_tpu.ops import paged_attention_pallas as pa

    monkeypatch.setattr(pa, "_MOST_PAGES_A_VISIT", 256)
    monkeypatch.setattr(pa, "_VMEM_BUDGET", 64 << 20)
    pa._pallas_paged_attention.clear_cache()
    try:
        with pytest.raises(Exception, match="vmem"):
            _walk_hlo(one_chip, "mellum2-full")
    finally:
        pa._pallas_paged_attention.clear_cache()
