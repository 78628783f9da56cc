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
This kernel instead walks each sequence's page table via scalar
prefetch — the lookup happens in the BlockSpec index_map, so each K/V
page block is DMA'd from the pool into VMEM directly, no gathered copy
— and runs an online-softmax (flash-style) accumulation per sequence:
a running max ``m``, running sum ``l`` and context accumulator carried
in VMEM scratch across the sequence's visits. The logits tensor never
exists; pages are read once.

Layout contract (kv_pages.py): pools are ``[L, n_pages, Hkv, ps, hd]``
with page 0 the never-read null page; ``tables`` rows are page ids in
position order, so flat position ``p*ps + o`` of sequence ``n`` lives
at ``pool[layer, tables[n, p], :, o]`` and the causal mask is a plain
``flat <= qpos``. Queries are ``[N, H, Q, hd]`` where query ``i`` of
sequence ``n`` sits at absolute position ``qbase[n] + i`` — Q=1 with
per-slot positions for the decode step, N=1 with consecutive suffix
positions for the prefix-prefill program, Q=k+1 with per-slot positions
for the speculative verify call.

The walk (PR 29; PERF.md section 6). A VISIT is one grid step: ``B``
(8) consecutive table slots of one sequence, every KV head of each
page in one block ``(1, 1, Hkv, ps, hd)`` — the slab lies contiguous in
the pool as it rests — as ``B`` K and ``B`` V operands over the same
pool array, each with its own index map. The grid is ``(KV head
blocks, visits)`` and its visit axis is DYNAMIC: ``_live_visits``
computes, from the scalars the call already has, the pages each
sequence HOLDS (up to the page of its last query, ``(qbase + Q - 1) //
ps``), lays the sequences' visits end to end and hands the kernel that
schedule (sequence, visit and the ``B`` page ids of every step) as
prefetched scalars. So the steps, the DMAs and the work of a call
follow the context held, not slots x heads x table width: a sequence
of 235 positions in a table of 64 slots is 2 visits, not 20 x 64 grid
steps. The slots of a sequence's final visit past its last live page
name that page again (the clamp) and are masked; no table entry past
the last live page is ever read, so what evicted or shorter sequences
leave there cannot matter (``engine._evict``: position 0 and an
all-null table is one visit of the null page). A first form kept the
grid static, ``(N, P / B)``, clamped the index maps and skipped the
body past the last live page: on the chip its dead steps still cost
2 us each (GPT-2-large's shapes), 65 of a call's 76 us.

Inside a visit the form follows the static shape, not a switch. Few
query rows a KV head (``Q * G <= 5``: decode, verify): every head at
once on the VPU, scores as a broadcast multiply of ``[Hkv, B * ps,
hd]`` by the query row and a lane reduction, the context as a multiply
by the weights and a sublane reduction — ``Hkv x B`` products with M <=
5 on the MXU are latency-bound (the old kernel was 5,120 of them).
Many rows (a suffix prefill's bucket): the ``dot_general`` pair a head,
in a loop over the visit's heads, and fewer heads a visit where the
f32 scratch, the double-buffered pages and a head's scores would pass
the VMEM budget (``_heads_a_visit``; the head blocks are the grid's
outer axis). K, V, scores, max, sum and accumulator are f32 in both.

Grouped-query attention: the pools hold ``Hkv`` heads and the queries
``H = Hkv * G``; query head ``h`` reads KV head ``h // G``. The ``G``
query heads of a group ride the kernel's QUERY axis (``[N, Hkv, Q * G,
hd]``, row ``i`` = query ``i // G`` of head ``i % G`` of the group), so
a page is read once a group; row ``i`` is masked at position ``qbase +
i // G``. ``G = 1`` is no longer the PR 8 kernel instruction for
instruction: its one head and one page a grid step are now every head
and eight pages, and its M = 1 products are VPU reductions (float-
equivalent, another order of summation).

fp8 KV (``kv_dtype="fp8_e4m3"``): the pools store float8_e4m3fn with
per-page-per-head fp32 scale planes ``[L, n_pages, H]`` beside them;
the kernel dequantizes each page block in VMEM (one multiply by its
heads' scales per block) so HBM traffic stays fp8 — the other half of the
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
#: page-table slots a visit (one grid step) brings into VMEM
_PAGES_A_VISIT = 8
#: at most this many query rows a KV head (``Q * G``) are scored on the
#: VPU (broadcast multiply + lane reduction), whose time grows with the
#: rows; more go to the MXU, whose time does not. On a v5e the two
#: cross between 5 and 8 rows at both cells' widths (PERF.md, PR 29)
_VPU_ROWS = 5
#: what a visit's blocks, scratch and temporaries may take of the
#: scoped VMEM (16 MiB on a v5e): sets the KV heads a visit
_VMEM_BUDGET = 8 << 20


def _heads_a_visit(Hkv, rows, hd, ps, B, itemsize, vpu, fp8):
    """Most KV heads (a divisor of ``Hkv``) whose visit fits the VMEM
    budget: double-buffered K and V pages (and, fp8, their scales, a
    tile a head), the query and output blocks,
    the f32 scratch, and the f32 working set (every head's keys and
    values on the VPU path, one head's and its scores and weights on
    the MXU path). Minor dimensions count as the tiles they occupy
    (``hd`` to 128 lanes)."""
    lanes = -(-hd // 128) * 128
    r8 = -(-rows // 8) * 8
    T = B * ps
    pages = 2 * 2 * B * max(ps, 32 // itemsize) * lanes * itemsize
    if fp8:
        pages += 2 * 2 * B * 8 * 128 * 4
    qo = 2 * 2 * r8 * lanes * itemsize
    scratch = r8 * (lanes + 2 * 128) * 4
    kv = 2 * T * lanes * 4                   # a head's f32 K and V
    head = pages + qo + scratch + (kv if vpu else 0)
    once = 0 if vpu else kv + 2 * r8 * max(T, 128) * 4
    for h in range(Hkv, 0, -1):
        if Hkv % h == 0 and h * head + once <= _VMEM_BUDGET:
            return h
    return 1


def _live_visits(tables, qbase, n_queries, ps, B):
    """The walk's schedule, from the scalars in hand: grid step ``g`` is
    visit ``visit[g]`` of sequence ``lane[g]`` and brings the ``B`` pool
    pages ``pages[g * B:(g + 1) * B]``; ``total`` steps cover every page
    the sequences hold and nothing else. A sequence holds the pages up
    to ``last[n]``, the page of its last query; the slots of its final
    visit past that page name it again (the kernel masks them), so no
    table entry beyond a sequence's last live page is ever read."""
    N, P = tables.shape
    i32 = jnp.int32
    last = jnp.clip((qbase + n_queries - 1) // ps, 0, P - 1)       # [N]
    visits = last // B + 1
    ends = jnp.cumsum(visits)
    g = jnp.arange(N * -(-P // B), dtype=i32)
    # steps past the total (never run) fall to the last sequence
    lane = jnp.minimum(jnp.sum(g[:, None] >= ends[None, :], axis=1),
                       N - 1)
    visit = g - (ends - visits)[lane]
    slot = jnp.minimum(visit[:, None] * B + jnp.arange(B, dtype=i32),
                       last[lane][:, None])
    pages = tables[lane[:, None], slot].reshape(-1)
    return tuple(a.astype(i32)
                 for a in (lane, visit, pages, last, ends[-1]))


def _kernel(layer_ref, lane_ref, visit_ref, page_ref, qbase_ref, last_ref,
            q_ref, *rest, page_size, sm_scale, fp8, group, pages, vpu):
    """One visit (grid step ``g``) of the online-softmax walk: ``pages``
    table slots of sequence ``lane[g]``, every KV head of the block at
    once. Scratch (m, l, acc) persists across a sequence's consecutive
    visits; initialized at its first, finalized into the output block
    at its last."""
    from jax.experimental import pallas as pl

    del layer_ref, page_ref          # read by the index maps
    B, ps = pages, page_size
    k_refs, v_refs, rest = rest[:B], rest[B:2 * B], rest[2 * B:]
    ks_refs = vs_refs = (None,) * B
    if fp8:
        ks_refs, vs_refs, rest = rest[:B], rest[B:2 * B], rest[2 * B:]
    o_ref, m_ref, l_ref, acc_ref = rest
    g = pl.program_id(1)
    n, j = lane_ref[g], visit_ref[g]
    qbase, last = qbase_ref[n], last_ref[n]
    heads, rows = q_ref.shape[1], q_ref.shape[2]
    T = B * ps
    # a key at flat position j*T + t is admitted by query row i iff it
    # is <= qbase[n] + i // G (causal) and lies on a page the sequence
    # holds (a slot past the last live page names that page again)
    held = (last + 1) * ps

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _MASK_MIN, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def visit_of(refs, scales, h):
        """The visit's ``B`` pages end to end in f32 (``ps`` is whole
        sublane tiles): ``[heads, T, hd]``, or head ``h``'s ``[T, hd]``."""
        out = []
        for ref, scale in zip(refs, scales):
            x = ref[0, 0, h].astype(jnp.float32)
            if fp8:
                x = x * scale[0, 0, h]
            out.append(x)
        return out[0] if B == 1 else jnp.concatenate(out, axis=-2)

    def fold(at, s, valid, axis, context):
        """The online-softmax update of the state at ``at`` with the
        visit's scores ``s`` (keys along ``axis``)."""
        s = jnp.where(valid, s, _MASK_MIN)
        m_prev = m_ref[at]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=axis, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # explicit zero for masked keys: an all-masked row would
        # otherwise contribute exp(MASK_MIN - MASK_MIN) == 1 a key
        pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[at] = alpha * l_ref[at] \
            + jnp.sum(pexp, axis=axis, keepdims=True)
        acc_ref[at] = acc_ref[at] * alpha + context(pexp)
        m_ref[at] = m_new

    if vpu:
        # few rows a head: products with so small an M keep the MXU
        # waiting. Every head at once on the VPU: scores as a multiply
        # by the query row and a lane reduction, the context as a
        # multiply by the weights and a sublane reduction
        q = q_ref[0].astype(jnp.float32)                # [heads, rows, hd]
        k = visit_of(k_refs, ks_refs, slice(None))      # [heads, T, hd]
        v = visit_of(v_refs, vs_refs, slice(None))
        pos = j * T + lax.broadcasted_iota(jnp.int32, (1, T, 1), 1)
        for r in range(rows):
            row = (slice(None), slice(r, r + 1), slice(None))
            s = jnp.sum(k * q[row], axis=-1, keepdims=True) * sm_scale
            valid = pos <= jnp.minimum(qbase + r // group, held - 1)
            fold(row, s, valid, 1,                      # s [heads, T, 1]
                 lambda p: jnp.sum(p * v, axis=1, keepdims=True))
    else:
        def head(h, carry):
            q = q_ref[0, h].astype(jnp.float32)         # [rows, hd]
            k = visit_of(k_refs, ks_refs, h)            # [T, hd]
            v = visit_of(v_refs, vs_refs, h)
            s = lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            # 2-D iotas per the TPU rule
            qi = lax.broadcasted_iota(jnp.int32, s.shape, 0)
            pos = j * T + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            if group > 1:
                qi = qi // group    # row i is query i // G of its head
            valid = (pos <= qbase + qi) & (pos < held)
            fold(h, s, valid, -1,                       # s [rows, T]
                 lambda p: lax.dot_general(
                     p, v, (((1,), (0,)), ((), ())),
                     preferred_element_type=jnp.float32))
            return carry

        lax.fori_loop(0, heads, head, 0)

    @pl.when(j == last // B)
    def _finish():
        # every query admits flat position 0 (qpos >= 0 always), so
        # l >= exp(0) == 1 at the end of the walk: safe division
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "group"))
def _pallas_paged_attention(q, kv, layer, tables, qbase, interpret,
                            group=1):
    """``layer`` is a traced ``[1]`` array and the function is jitted
    so that the layers of a program share ONE trace and one lowering of
    the kernel: traced per layer, its body cost a served model's
    set-up seconds at every start, warm cache or not (PERF.md, PR 29)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, H, rows, hd = q.shape           # H KV heads, rows = Q * G
    P = tables.shape[1]
    ps = kv["k"].shape[3]
    fp8 = "k_scale" in kv
    B = min(_PAGES_A_VISIT, P)
    vpu = rows <= _VPU_ROWS
    Hh = _heads_a_visit(H, rows, hd, ps, B, kv["k"].dtype.itemsize, vpu,
                        fp8)
    qbase = qbase.astype(jnp.int32)
    lane, visit, pages, last, total = _live_visits(
        tables.astype(jnp.int32), qbase, rows // group, ps, B)
    # the walk is as long as the pages held: the visit axis is dynamic
    grid = (H // Hh, total)

    # scalar-prefetch index maps: the schedule's page id IS the
    # BlockSpec index, so each page block DMAs straight from the pool
    # (index_map args: grid indices, then the prefetched scalar refs)
    def page_map(i):
        return lambda h, g, ly, ln, vi, pg, *_: (ly[0], pg[g * B + i],
                                                 h, 0, 0)

    q_spec = pl.BlockSpec((1, Hh, rows, hd),
                          lambda h, g, ly, ln, *_: (ln[g], h, 0, 0))
    kv_specs = [pl.BlockSpec((1, 1, Hh, ps, hd), page_map(i))
                for i in range(B)]
    in_specs = [q_spec] + kv_specs + kv_specs
    args = [q] + [kv["k"]] * B + [kv["v"]] * B
    if fp8:
        # one scale per (layer, page, head): viewed as [L, n_pages, H,
        # 1, 1] so the block's last two dims are the array's (Mosaic
        # refuses a (1, 1) block over the [n_pages, H] plane itself)
        sc_specs = [pl.BlockSpec((1, 1, Hh, 1, 1), page_map(i))
                    for i in range(B)]
        in_specs += sc_specs + sc_specs
        args += [kv["k_scale"][..., None, None]] * B \
            + [kv["v_scale"][..., None, None]] * B
    kernel = functools.partial(
        _kernel, page_size=ps,
        sm_scale=float(1.0 / np.sqrt(np.float32(hd))), fp8=fp8,
        group=group, pages=B, vpu=vpu)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=grid,
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((Hh, rows, 1), jnp.float32),
                            pltpu.VMEM((Hh, rows, 1), jnp.float32),
                            pltpu.VMEM((Hh, rows, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((N, H, rows, hd), q.dtype),
        interpret=interpret,
        # what a device trace calls the kernel (PERF.md section 3)
        name="paged_attention",
    )(layer, lane, visit, pages, qbase, last, *args)


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
        out = _pallas_paged_attention(
            q, kv, jnp.full((1,), layer, jnp.int32), tables, qbase,
            interpret=(mode == "interpret"), group=G)
    if G > 1:
        out = out.reshape(N, Hkv, Q, G, hd).transpose(0, 1, 3, 2, 4) \
                 .reshape(N, H, Q, hd)
    return out


@register_op("paged_attention")
def _op(q, kv, layer, tables, qbase, mode=None):
    return paged_attention(q, kv, int(layer), tables, qbase, mode=mode)
