"""Paged-attention Pallas kernel: the serving engine's per-layer page
gather + masked softmax + weighted sum in ONE pass over the page pool.

Reference role: the cuDNN fuse-the-memory-bound-chain playbook
(Chetlur et al., arXiv:1410.0759) applied to the DECODE loop, exactly
as ``ops/fused_update_pallas.py`` applied it to the optimizer. The
roofline registry (profiler/programs.py) classifies ``serving_decode``
as memory-bound: every decode step streams the whole paged KV cache
through the einsum pair

    logits = einsum("nhqd,nphod->nhqpo", q, gather(kpool, tables))
    ctx    = einsum("nhqpo,nphod->nhqd", softmax(logits), gather(vpool))

materializing (a) the gathered page copies and (b) the full
``[n,h,q,p,o]`` logits tensor in HBM between the two contractions.
This kernel instead walks the slot's page table via scalar prefetch —
the table lookup happens in the BlockSpec index_map, so each K/V page
block is DMA'd from the pool into VMEM directly, no gathered copy —
and runs an online-softmax (flash-style) accumulation per slot: a
running max ``m``, running sum ``l`` and context accumulator carried
in VMEM scratch across the page-walk grid dimension. The logits
tensor never exists; pages are read once.

Layout contract (kv_pages.py): pools are ``[L, n_pages, H, ps, hd]``
with page 0 the never-read null page; ``tables`` rows are page ids in
position order, so flat position ``p*ps + o`` of sequence ``n`` lives
at ``pool[layer, tables[n, p], :, o]`` and the causal mask is a plain
``flat <= qpos``. Queries are ``[N, H, Q, hd]`` where query ``i`` of
sequence ``n`` sits at absolute position ``qbase[n] + i`` — Q=1 with
per-slot positions for the decode step, N=1 with consecutive suffix
positions for the prefix-prefill program.

Grouped-query attention: the pools hold ``Hkv`` heads and the queries
``H = Hkv * G``; query head ``h`` reads KV head ``h // G``. The ``G``
query heads of a group ride the kernel's QUERY axis (``[N, Hkv, Q * G,
hd]``, row ``i`` = query ``i // G`` of head ``i % G`` of the group), so
a page is read once a group and the grid is ``(N, Hkv, P)``; row ``i``
is masked at position ``qbase + i // G``. ``G = 1`` is the kernel as it
was, instruction for instruction.

fp8 KV (``kv_dtype="fp8_e4m3"``): the pools store float8_e4m3fn with
per-page-per-head fp32 scale planes ``[L, n_pages, H]`` beside them;
the kernel dequantizes each page block in VMEM (one scalar multiply
per block) so HBM traffic stays fp8 — the other half of the
bytes/step reduction.

Dispatch (``paged_attention_mode()``), mirroring
``DL4J_TPU_FUSED_UPDATE``:
- ``pallas``    — real TPU backend: the kernel above.
- ``interpret`` — forced via ``DL4J_TPU_PAGED_ATTN=interpret``: the
  same kernel through the Pallas interpreter (CPU-testable path; what
  the CI token-identity gate runs).
- ``xla``       — everything else (CPU/GPU, or
  ``DL4J_TPU_PAGED_ATTN=xla``): the exact einsum pair above, verbatim
  — the serving engine built in this mode is program-for-program
  identical to the pre-kernel engine.

Numerics: the xla path IS the reference (bit-identical to the decode
core it replaced). The kernel is float-equivalent but not
bit-identical (online softmax reduces in a different order, f32
accumulation); the greedy TOKEN-identity gate at f32 — the same
contract the engine already holds against ``generate()`` — is what
tests and run_tests.sh pin.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.ops.registry import register_op

#: f32 mask value. Masked scores sit at this floor; the online-softmax
#: update zeroes their exp() contribution explicitly (``where(valid)``)
#: so an all-masked page leaves (m, l, acc) untouched.
_MASK_MIN = float(np.finfo(np.float32).min)


def paged_attention_mode() -> str:
    """'pallas' | 'interpret' | 'xla' — see module docstring."""
    env = os.environ.get("DL4J_TPU_PAGED_ATTN", "auto").strip().lower()
    if env in ("pallas", "interpret", "xla"):
        return env
    return "pallas" if jax.default_backend() == "tpu" else "xla"


# ------------------------------------------------------- xla reference
def _xla_paged_attention(q, kv, layer, tables, qbase, group=1):
    """The exact einsum pair from the pre-kernel decode core /
    prefix-prefill program (serving/engine.py PR 8-9 lineage). This is
    the dispatch target when the kernel is off, so it must stay
    op-for-op what those programs inlined — the engine's greedy
    bit-identity to ``CausalLM.generate()`` rests on it."""
    N, H, Q, hd = q.shape
    ck = kv["k"][layer][tables]           # [N, P, H, ps, hd]
    cv = kv["v"][layer][tables]
    if "k_scale" in kv:
        cd = q.dtype
        ck = ck.astype(cd) * kv["k_scale"][layer][tables][
            ..., None, None].astype(cd)
        cv = cv.astype(cd) * kv["v_scale"][layer][tables][
            ..., None, None].astype(cd)
    P, ps = ck.shape[1], ck.shape[3]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, q.dtype))
    qi = jnp.arange(Q, dtype=jnp.int32)
    if group > 1:
        qi = qi // group
    qpos = qbase[:, None] + qi[None, :]
    # page-major contraction: (p, o) together are the flat key axis
    logits = jnp.einsum("nhqd,nphod->nhqpo", q, ck) \
        .reshape(N, H, Q, P * ps) * scale
    neg = jnp.asarray(jnp.finfo(logits.dtype).min, logits.dtype)
    valid = (jnp.arange(P * ps)[None, None, None, :]
             <= qpos[:, None, :, None])
    logits = jnp.where(valid, logits, neg)
    w = jax.nn.softmax(logits, axis=-1).reshape(N, H, Q, P, ps)
    return jnp.einsum("nhqpo,nphod->nhqd", w, cv)


# -------------------------------------------------------------- kernel
def _kernel(tables_ref, qbase_ref, q_ref, k_ref, v_ref, *rest,
            layer, page_size, sm_scale, fp8, group):
    """One (sequence n, head h, page p) grid step of the online-softmax
    walk. Scratch (m, l, acc) persists across the sequential innermost
    page dimension; initialized at p == 0, finalized into the output
    block at the last page."""
    from jax.experimental import pallas as pl

    if fp8:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    n = pl.program_id(0)
    p = pl.program_id(2)
    last = pl.num_programs(2) - 1

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _MASK_MIN, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32)          # [Q, hd]
    k = k_ref[0, 0, 0].astype(jnp.float32)       # [ps, hd]
    v = v_ref[0, 0, 0].astype(jnp.float32)
    if fp8:
        k = k * ks_ref[0, 0, 0]
        v = v * vs_ref[0, 0, 0]
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * sm_scale
    # causal over flat positions: key at flat p*ps + o is admitted by
    # query i iff it is <= qbase[n] + i (2-D iotas per the TPU rule)
    qi = lax.broadcasted_iota(jnp.int32, s.shape, 0)
    oi = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if group > 1:
        qi = qi // group        # row i is query i // G of its head
    valid = (p * page_size + oi) <= (qbase_ref[n] + qi)
    s = jnp.where(valid, s, _MASK_MIN)
    m_prev = m_ref[...]                          # [Q, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # explicit zero for masked lanes: an all-masked page would
    # otherwise contribute exp(MASK_MIN - MASK_MIN) == 1 per lane
    pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_new = alpha * l_ref[...] + jnp.sum(pexp, axis=-1, keepdims=True)
    acc_new = acc_ref[...] * alpha + lax.dot_general(
        pexp, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(p == last)
    def _finish():
        # every query admits flat position 0 (qpos >= 0 always), so
        # l >= exp(0) == 1 at the end of the walk — safe division
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _pallas_paged_attention(q, kv, layer, tables, qbase, interpret,
                            group=1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, H, Q, hd = q.shape
    P = tables.shape[1]
    ps = kv["k"].shape[3]
    fp8 = "k_scale" in kv
    grid = (N, H, P)

    # scalar-prefetch index maps: the page-table lookup IS the
    # BlockSpec index, so each page block DMAs straight from the pool
    # (index_map args: grid indices, then the prefetched scalar refs)
    q_spec = pl.BlockSpec((1, 1, Q, hd),
                          lambda n, h, p, t, b: (n, h, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, 1, ps, hd),
                           lambda n, h, p, t, b: (layer, t[n, p], h,
                                                  0, 0))
    out_spec = pl.BlockSpec((1, 1, Q, hd),
                            lambda n, h, p, t, b: (n, h, 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [q, kv["k"], kv["v"]]
    if fp8:
        # one scale per (layer, page, head): viewed as [L, n_pages, H,
        # 1, 1] so the block's last two dims are the array's (Mosaic
        # refuses a (1, 1) block over the [n_pages, H] plane itself)
        sc_spec = pl.BlockSpec((1, 1, 1, 1, 1),
                               lambda n, h, p, t, b: (layer, t[n, p],
                                                      h, 0, 0))
        in_specs += [sc_spec, sc_spec]
        args += [kv["k_scale"][..., None, None],
                 kv["v_scale"][..., None, None]]
    kernel = functools.partial(
        _kernel, layer=layer, page_size=ps,
        sm_scale=float(1.0 / np.sqrt(np.float32(hd))), fp8=fp8,
        group=group)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=[pltpu.VMEM((Q, 1), jnp.float32),
                            pltpu.VMEM((Q, 1), jnp.float32),
                            pltpu.VMEM((Q, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((N, H, Q, hd), q.dtype),
        interpret=interpret,
        # what a device trace calls the kernel (PERF.md section 3)
        name="paged_attention",
    )(tables.astype(jnp.int32), qbase.astype(jnp.int32), *args)


# ------------------------------------------------------------ dispatch
def paged_attention(q, kv, layer, tables, qbase, *, mode=None):
    """Per-layer paged attention over the serving engine's KV tree.

    Parameters
    ----------
    q : ``[N, H, Q, hd]`` queries in the compute dtype.
    kv : the page-pool tree (``kv_pages.PagePool.tree()``): ``"k"`` /
        ``"v"`` pools ``[L, n_pages, Hkv, ps, hd]``, plus ``"k_scale"``
        / ``"v_scale"`` planes ``[L, n_pages, Hkv]`` when the pool is
        fp8. ``H`` is a multiple of ``Hkv`` (module docstring).
    layer : static layer index (the engine's layer loop is unrolled).
    tables : ``[N, P]`` int32 page tables, rows in position order.
    qbase : ``[N]`` int32; query ``i`` of row ``n`` sits at absolute
        position ``qbase[n] + i``.
    mode : overrides :func:`paged_attention_mode` (tests/benches).

    Returns ``[N, H, Q, hd]`` context in ``q.dtype``.
    """
    mode = mode or paged_attention_mode()
    if mode not in ("xla", "pallas", "interpret"):
        raise ValueError(
            f"unknown paged-attention mode {mode!r} (expected 'pallas',"
            " 'interpret' or 'xla')")
    N, H, Q, hd = q.shape
    Hkv = kv["k"].shape[2]
    G, rem = divmod(H, Hkv)
    if rem:
        raise ValueError(f"{H} query heads do not divide into the "
                         f"pool's {Hkv} KV heads")
    if G > 1:
        # the group's heads onto the query axis, query-major
        q = q.reshape(N, Hkv, G, Q, hd).transpose(0, 1, 3, 2, 4) \
             .reshape(N, Hkv, Q * G, hd)
    if mode == "xla":
        out = _xla_paged_attention(q, kv, layer, tables, qbase, G)
    else:
        out = _pallas_paged_attention(q, kv, layer, tables, qbase,
                                      interpret=(mode == "interpret"),
                                      group=G)
    if G > 1:
        out = out.reshape(N, Hkv, Q, G, hd).transpose(0, 1, 3, 2, 4) \
                 .reshape(N, H, Q, hd)
    return out


@register_op("paged_attention")
def _op(q, kv, layer, tables, qbase, mode=None):
    return paged_attention(q, kv, int(layer), tables, qbase, mode=mode)
