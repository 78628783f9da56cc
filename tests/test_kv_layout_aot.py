"""The page pool's layout contract, checked with no chip
(serving/kv_pages.py module docstring, "Layout contract").

The serving programs are compiled for a DESCRIBED TPU v5e (libtpu's
compiler, no device attached) with the pools row-major at entry and
exit, and the optimised HLO is searched for a ``copy`` whose result
has the pool's shape. (On the chip the pools rest in the device's own
layout for their shape, and every program holds four more copies at
its boundary; they are no write's doing and not this file's subject.) The paged kernel takes its
operands row-major; a write whose update window is wider than the
trailing ``hd`` makes XLA keep the pools in another layout and copy
each whole pool, every layer, to bridge the two (PERF.md section 6,
PR 27: 72 copies of 379 MB a decode step). The kernel's grid is (KV
head blocks, live visits): a visit holds every KV head of eight pages
of one sequence, and the visits are as many as the pages the
sequences hold (PR 29); the last tests compile it at the cells' shapes. The control case compiles
the same program around that old write and must find those copies —
it proves the search can see the fault.

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
    return {
        "chunk": (eng._decode_jits[CHUNK], [dec, cache, *slot, *tail]),
        "verify": (eng._verify_jit, [
            dec, cache, *slot, ((S, SPEC_K), i32), ((S,), i32), *tail]),
        "suffix_prefill": (eng._prefix_prefill_jit, [
            par, cache, ((BUCKET,), i32), ((P,), i32), ((), i32),
            ((), i32)]),
        "prefill": (eng._prefill_jit, [
            par, cache, ((1, BUCKET), i32),
            ((BUCKET // eng.page_size,), i32), ((), i32), ((), i32)]),
    }


def _compiled_text(jitted, args, one_chip) -> str:
    """Optimised HLO of ``jitted`` (KV tree donated, as the engine
    does) for the described chip, every array row-major in and out."""
    from jax.experimental.layout import Format, Layout

    def row_major(ndim):
        return Format(Layout(tuple(range(ndim))), one_chip)

    def pinned(a):
        shape, dtype = (a.shape, a.dtype) if hasattr(a, "shape") else a
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=row_major(len(shape)))

    # a (shape, dtype) pair is a leaf; the cache's pair is not
    args = jax.tree_util.tree_map(
        pinned, args, is_leaf=lambda a: isinstance(a, tuple)
        and isinstance(a[0], tuple))
    fn = jitted.__wrapped__
    outs = jax.tree_util.tree_map(lambda a: row_major(a.ndim),
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


@pytest.mark.parametrize("kv_dtype", [None, "fp8_e4m3"],
                         ids=["bf16", "fp8"])
@pytest.mark.parametrize(
    "program", ["chunk", "verify", "suffix_prefill", "prefill"])
def test_serving_program_never_copies_a_pool(one_chip, program,
                                             kv_dtype):
    eng = _engine(kv_dtype)
    assert eng.pool.k.ndim == 5
    jitted, args = _programs(eng)[program]
    hlo = _compiled_text(jitted, args, one_chip)
    assert "tpu_custom_call" in hlo or program == "prefill"
    assert pool_copies(hlo, eng.pool.k) == {"loop": 0, "all": 0}


def test_detector_sees_the_old_write(one_chip, monkeypatch):
    """The control: the chunk program around the write this repo had
    before PR 27 (update window ``[H, hd]``, the indexed ``page_size``
    between them) holds one copy per pool per layer in its loop."""
    def old_write(pool, layer, page_idx, offset, x):
        return pool.at[layer, page_idx, :, offset].set(
            x.astype(pool.dtype))

    monkeypatch.setattr(kv_pages, "_write_rows", old_write)
    eng = _engine()
    jitted, args = _programs(eng)["chunk"]
    found = pool_copies(_compiled_text(jitted, args, one_chip),
                        eng.pool.k)
    assert found["loop"] == 2 * LAYERS
    assert found["all"] >= found["loop"]


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


#: the pools of the two serving cells (BENCHMARK.json): layers, pages,
#: KV heads, page size, head width; and the query heads over them
GPT2_LARGE = ((36, 1 + 4 * 64, 20, 16, 64), 20)
LFM2_24B = ((2, 1 + 16 * 64, 8, 16, 64), 32)


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
    every KV head of eight pages a visit, the visits as many as the
    pages held (a dynamic grid), few rows a head on the VPU and many on
    the MXU with fewer heads a visit, the group of 4 on the query axis;
    and the pools go in as they rest, with no copy of one."""
    from deeplearning4j_tpu.ops.paged_attention_pallas import paged_attention

    from jax.experimental.layout import Format, Layout

    (L, n_pages, Hkv, ps, hd), H = cell
    bf16, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    # row-major, as the serving programs hold the pools (above)
    pool = _sds((L, n_pages, Hkv, ps, hd), kv_dtype,
                Format(Layout(tuple(range(5))), one_chip))
    kv = {"k": pool, "v": pool}
    if kv_dtype != bf16:
        kv.update(k_scale=_sds((L, n_pages, Hkv), f32, one_chip),
                  v_scale=_sds((L, n_pages, Hkv), f32, one_chip))
    hlo = jax.jit(lambda q, kv, t, b: paged_attention(
        q, kv, 1, t, b, mode="pallas")) \
        .lower(_sds((slots, H, queries, hd), bf16, one_chip), kv,
               _sds((slots, 64), i32, one_chip),
               _sds((slots,), i32, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo and "paged_attention" in hlo
    assert pool_copies(hlo, pool)["all"] == 0
