"""Paged-attention Pallas kernel: the serving engine's per-layer page
gather + masked softmax + weighted sum in ONE pass over the page pool.

Reference role: the cuDNN fuse-the-memory-bound-chain playbook
(Chetlur et al., arXiv:1410.0759) applied to the DECODE loop, exactly
as ``ops/fused_update_pallas.py`` applied it to the optimizer. The
roofline registry (profiler/programs.py) classifies ``serving_decode``
as memory-bound: every decode step streams the whole paged KV cache
through the einsum pair

    logits = einsum("nqhd,npohd->nhqpo", q, gather(kpool, tables))
    ctx    = einsum("nhqpo,npohd->nqhd", softmax(logits), gather(vpool))

materializing (a) the gathered page copies and (b) the full
``[n,h,q,p,o]`` logits tensor in HBM between the two contractions.
This kernel instead walks each sequence's page table, prefetched into
scalar memory, and fetches each page it needs from the pool into VMEM
itself, no gathered copy; it runs an online-softmax (flash-style)
accumulation per sequence: a running max ``m``, running sum ``l`` and
context accumulator carried in VMEM scratch across the sequence's
visits. The logits tensor never exists; pages are read once.

Layout contract (kv_pages.py): pools are ``[L, n_pages, ps, Hkv *
hd]`` with page 0 the never-read null page: a page is ``ps`` rows, a
row one position's K (or V) with every KV head side by side, head
``h`` on lanes ``[h * hd, (h + 1) * hd)`` — the projection's own output
row, and the shape the device keeps row-major at rest, so no program
copies a pool to hand it over (PERF.md section 6, PR 31). ``tables``
rows are page ids in position order, so flat position ``p*ps + o`` of
sequence ``n`` lives at ``pool[layer, tables[n, p], o]`` and the
causal mask is a plain ``flat <= qpos``. Queries are ``[N, Q, H, hd]``
where query ``i`` of sequence ``n`` sits at absolute position
``qbase[n] + i`` — Q=1 with per-slot positions for the decode step,
N=1 with consecutive suffix positions for the prefix-prefill program,
Q=k+1 with per-slot positions for the speculative verify call.

The walk (PERF.md section 6). A VISIT is one grid step: up to ``B``
consecutive table slots of one sequence, every KV head of each page (a
page ``[ps, Hkv * hd]`` as it lies in the pool, no padding). The grid is ``(KV head blocks, visits)`` and its visit axis
is DYNAMIC: ``_live_visits`` computes, from the scalars the call
already has, the pages each sequence HOLDS (up to the page of its last
query, ``(qbase + Q - 1) // ps``), lays the sequences' visits end to
end and hands the kernel that schedule (sequence and visit of every
step) with the page tables as prefetched scalars. So the steps, the
DMAs and the work of a call follow the context held, not slots x heads
x table width: a sequence of 235 positions in a table of 64 slots is
one visit, not 20 x 64 grid steps.

The pools stay in HBM (``memory_space=ANY``) and the kernel fetches its
own pages: a page's K and its V a DMA each into one of two buffers
``[B, ps, W]``, step ``g + 1``'s pages sent for before step ``g``'s are
waited on, page by page, so a visit's fetch runs under the visit
before it. Only the live slots are fetched or waited on: a slot past a
sequence's last live page costs no byte, and its rows keep what they
held (zeros, or a page of this call: the value buffers start a call as
zeros, since unwritten VMEM may hold NaN and 0 x NaN is NaN in the
product with the weights) and are masked. No table entry past the last
live page is ever read, so what evicted or shorter sequences leave
there cannot matter (``engine._evict``: position 0 and an all-null
table is one visit of the null page). The fetch loops run in turns of
8 slots laid out flat.

A visit's size follows the call's shapes (``_visit_shape``): the most
pages, up to 64, whose two buffers of K and V fit the VMEM budget
beside the scratch and the working set, spread evenly over the table,
so that a walk of the table's width does not end in a near-empty
visit: 64 pages of 16 KB (Mellum2's and LFM2's full layers), 32 of 32
or 40 KB (K-EXAONE's global layer, GPT-2-large), a ring of 65 as 33 +
32 and one of 9 in one visit.

History. The walk before this one handed the pipeline each visit's 8
pages as 8 K and 8 V ``BlockSpec`` operands, each with its own index
map, and clamped the slots past the last live page to it: bound by the
pipeline's 45-60 ns a page fetch (measured on GLM's latent pages, whose
walk fetches its own pages too) it read Mellum2's caches at 30.7% of
the roofline, a quarter of that cell's device time (PERF.md section
6). A first form before it kept the grid
static, ``(N, P / B)``, clamped the index maps and skipped the body
past the last live page: on the chip its dead steps still cost 2 us
each (GPT-2-large's shapes), 65 of a call's 76 us.

Inside a visit the heads lie along the lanes, and both products run
on the MXU a LANE TILE at a time (``_lane_tile``): 128 lanes of whole
heads (two of 64), a head of whole tiles, or — a row that is neither,
toy sizes on a CPU — the row. A tile's heads are STACKED on the query
axis: stacked row ``i`` is query row ``i % rows`` with every lane but
those of head ``i // rows`` zeroed, so ONE product of the stacked rows
``[hpt * rows, 128]`` with the tile's keys ``[B * ps, 128]``,
contracted over the tile's lanes, scores every head of the tile, and
one product of the weights with the tile's values gives each stacked
row its head's context on its head's lanes; the other lanes of a row
are dropped at the end. The products run a tile at a time on the
pages as they lie in their buffer (no copy of a visit), and the online
softmax between them works on ``[tiles, hpt * rows, B * ps]``. bf16
pages under bf16 queries go to the MXU as they are stored (their
products are exact in the f32 it accumulates in; no page is
converted), the f32 weights as two bf16 halves over one pass of the
values; f32 or fp8 pools are worked on in f32. Scores, max, sum and accumulator are f32 always. One
form for one query row a head and for a suffix prefill's bucket: with
many rows, fewer heads a visit where the scores, the weights and the
scratch would pass the VMEM budget (``_visit_shape``; the head
blocks are the grid's outer axis, whole lane tiles each). (PR 29's
kernel kept the heads on a leading axis, scored few rows on the VPU —
a lane reduction a head and key, an ``exp`` over vregs one lane full —
and many rows a head at a time on the MXU; the stacked form is 1.9x
and 3.2x faster at the two cells' decode shapes, PERF.md section 6,
PR 31.)

Grouped-query attention: the pools hold ``Hkv`` heads and the queries
``H = Hkv * G``; query head ``h`` reads KV head ``h // G``. The ``G``
query heads of a group ride the kernel's QUERY axis (``[N, Q * G, Hkv
* hd]``, row ``i`` = query ``i // G`` of head ``i % G`` of each
group), so a page is read once a group; row ``i`` is masked at
position ``qbase + i // G``. With ``G = 1`` a decode step's query is
the projection's row as it stands.

A window's ring (PR 32; ``window=``): a sliding-window layer keeps,
for each sequence, a RING of ``R`` pages whatever the context: the
table is ``[N, R]``, page NUMBER ``n`` (positions ``[n * ps, (n + 1) *
ps)``) lives in table entry ``n % R``, and a query at position ``p``
attends ``p - window + 1 .. p``. ``R * ps >= window + ps - 1``, so the
pages a window straddles never share an entry. The walk starts at the
page of the first admitted position instead of the sequence's first
and is at most ``R`` pages long; a row's mask adds ``position > p -
window``. What an earlier lap of the ring left in an entry lies, by
this arithmetic, at positions past the query's and is masked like any
other future position. One kernel, one layout: the window is a static
argument, and without it the program is the one above, op for op.

fp8 KV (``kv_dtype="fp8_e4m3"``): the pools store float8_e4m3fn with
per-page-per-head fp32 scale planes ``[L, n_pages, H]`` beside them;
the pages are fetched as the others, and the scales of a visit's pages,
gathered by the schedule, come as one block a step; the kernel
dequantizes each page in VMEM (one multiply by its heads' scales, each
spread over its head's lanes) so HBM traffic stays fp8 — the other
half of the bytes/step reduction.

Dispatch (``paged_attention_mode()``), mirroring
``DL4J_TPU_FUSED_UPDATE``:
- ``pallas``    — real TPU backend: the kernel above.
- ``interpret`` — forced via ``DL4J_TPU_PAGED_ATTN=interpret``: the
  same kernel through the Pallas interpreter (CPU-testable path; what
  the CI token-identity gate runs).
- ``xla``       — everything else (CPU/GPU, or
  ``DL4J_TPU_PAGED_ATTN=xla``): the einsum pair above over the page
  rows — the pre-kernel engine's program.

Numerics: the xla path IS the reference (the decode core it
replaced). The kernel is float-equivalent but not
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
def _ring_positions(qbase, n_queries, P, ps):
    """The absolute position each cell of a ring table holds, ``[N, P *
    ps]``: entry ``r`` holds the newest page number ``<=`` the last
    query's whose remainder by ``P`` is ``r`` (negative where the
    sequence has not reached it; what an older lap left there reads as
    a position past every query)."""
    last = (qbase + n_queries - 1) // ps                        # [N]
    r = jnp.arange(P, dtype=jnp.int32)
    pn = last[:, None] - (last[:, None] - r[None, :]) % P       # [N, P]
    return (pn[:, :, None] * ps
            + jnp.arange(ps, dtype=jnp.int32)[None, None, :]) \
        .reshape(-1, P * ps)


def _xla_paged_attention(q, kv, layer, tables, qbase, group=1,
                         window=None):
    """The einsum pair of the pre-kernel decode core / prefix-prefill
    program (serving/engine.py PR 8-9 lineage) over page rows: ``q`` is
    ``[N, rows, Hkv, hd]`` (row ``i`` = query ``i // group``). This is
    the dispatch target when the kernel is off and the tests'
    reference — the engine's greedy identity to
    ``CausalLM.generate()`` rests on it. With ``window`` the table is
    a ring (module docstring)."""
    N, Q, H, hd = q.shape

    def pages(name):                      # [N, P, ps, H, hd]
        c = kv[name][layer][tables]
        c = c.reshape(*c.shape[:3], H, hd)
        if name + "_scale" in kv:
            sc = kv[name + "_scale"][layer][tables]        # [N, P, H]
            c = c.astype(q.dtype) \
                * sc[:, :, None, :, None].astype(q.dtype)
        return c

    ck, cv = pages("k"), pages("v")
    P, ps = ck.shape[1], ck.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, q.dtype))
    qi = jnp.arange(Q, dtype=jnp.int32)
    if group > 1:
        qi = qi // group
    qpos = qbase[:, None] + qi[None, :]
    # page-major contraction: (p, o) together are the flat key axis
    logits = jnp.einsum("nqhd,npohd->nhqpo", q, ck) \
        .reshape(N, H, Q, P * ps) * scale
    neg = jnp.asarray(jnp.finfo(logits.dtype).min, logits.dtype)
    if window is None:
        valid = (jnp.arange(P * ps)[None, None, None, :]
                 <= qpos[:, None, :, None])
    else:
        kpos = _ring_positions(qbase, Q // group, P, ps)[:, None, None, :]
        valid = (kpos <= qpos[:, None, :, None]) & (kpos >= 0) \
            & (kpos > qpos[:, None, :, None] - window)
    logits = jnp.where(valid, logits, neg)
    w = jax.nn.softmax(logits, axis=-1).reshape(N, H, Q, P, ps)
    return jnp.einsum("nhqpo,npohd->nqhd", w, cv)


# -------------------------------------------------------------- kernel
#: the most table slots a visit brings into VMEM (on the chip GLM's
#: walk took 0.69 / 0.54 / 0.48 / 0.47 ms a call at 16 / 32 / 64 / 128
#: pages a visit: PERF.md section 6)
_MOST_PAGES_A_VISIT = 64
#: table slots a turn of the kernel's fetch loops sends for or waits on
_PAGES_A_TURN = 8
#: what a visit's buffers, scratch and temporaries may take of the
#: scoped VMEM (16 MiB on a v5e): sets the KV heads and pages a visit
_VMEM_BUDGET = 8 << 20


def _lane_tile(W, hd):
    """Lanes the kernel works on at once: a 128-lane tile of whole
    heads (two of 64), a head that is whole tiles, or — a row that is
    neither, toy sizes — the row."""
    if W % 128 == 0 and 128 % hd == 0:
        return 128
    return hd if hd % 128 == 0 else W


def _visit_shape(Hkv, rows, hd, ps, P, itemsize, q_itemsize, fp8):
    """``(heads, pages)`` a visit takes, from the call's shapes. Heads:
    the most (a divisor of ``Hkv`` whose lanes are whole lane tiles)
    whose visit of 8 pages fits the VMEM budget; pages: the most, a
    power of two up to ``_MOST_PAGES_A_VISIT``, whose visit with those
    heads fits. A visit holds two buffers of K and of V pages (and,
    fp8, their scales ``[B, 1, heads]``), the query and output
    blocks, the f32 scratch, and the working set (the stacked queries,
    the scores, the weights and their two halves, the products; the
    keys and values too where they are not fed to the MXU as stored).
    Rows count as the sublane tiles they occupy; lanes are real, a
    page row has no padding. Then the table's ``P`` slots are spread
    evenly over the visits they need, so that no walk of the table's
    width (a ring's ``R`` or ``R - 1`` pages) ends in a near-empty
    visit: a ring of 65 is 2 visits of 33, not 64 + 1."""
    tw = _lane_tile(Hkv * hd, hd)
    Rp = -(-(tw // hd) * rows // 8) * 8      # stacked query rows a tile
    r8 = -(-rows // 8) * 8
    as_stored = itemsize == 2 and q_itemsize == 2

    def size(h, B):
        W, T = h * hd, max(B * ps, 128)
        tiles = W // tw
        pages = 2 * 2 * B * max(ps, 32 // itemsize) * W * itemsize
        if fp8:
            pages += 2 * 2 * B * -(-h // 128) * 8 * 128 * 4
        qo = 2 * 2 * r8 * W * q_itemsize
        scratch = tiles * Rp * (tw + 2 * 128) * 4
        work = tiles * Rp * (4 * T + 3 * tw) * 4
        if not as_stored:
            work += 2 * B * ps * W * 4
        return pages + qo + scratch + work

    fits = [h for h in range(Hkv, 0, -1)
            if Hkv % h == 0 and (h * hd) % tw == 0]
    heads = next((h for h in fits if size(h, 8) <= _VMEM_BUDGET), fits[-1])
    B = _MOST_PAGES_A_VISIT
    while B > 8 and size(heads, B) > _VMEM_BUDGET:
        B //= 2
    visits = -(-P // B)
    return heads, -(-P // visits)


def _live_visits(tables, qbase, n_queries, ps, B, window=None):
    """The walk's schedule, from the scalars in hand: grid step ``g`` is
    visit ``visit[g]`` of sequence ``lane[g]`` and brings the ``B`` pool
    pages ``pages[g * B:(g + 1) * B]``; ``total`` steps cover every page
    the sequences hold and nothing else. A sequence holds the pages up
    to ``last[n]``, the page of its last query; the slots of its final
    visit past that page name it again, so no table entry beyond a
    sequence's last live page is ever read. With ``window`` the table
    is a ring: the walk starts at ``first[n]``, the page of the first
    position the first query's window admits, ``last`` is a page
    NUMBER, and page number ``n`` is table entry ``n % P``. (The paged
    kernel reads the table itself, and ``pages`` only for fp8 scales.)"""
    N, P = tables.shape
    i32 = jnp.int32
    if window is None:
        last = jnp.clip((qbase + n_queries - 1) // ps, 0, P - 1)   # [N]
        visits = last // B + 1
    else:
        last = jnp.maximum(qbase + n_queries - 1, 0) // ps
        first = jnp.maximum(qbase - (window - 1), 0) // ps
        visits = (last - first) // B + 1
    ends = jnp.cumsum(visits)
    g = jnp.arange(N * -(-P // B), dtype=i32)
    # steps past the total (never run) fall to the last sequence
    lane = jnp.minimum(jnp.sum(g[:, None] >= ends[None, :], axis=1),
                       N - 1)
    visit = g - (ends - visits)[lane]
    slot = visit[:, None] * B + jnp.arange(B, dtype=i32)
    if window is not None:
        slot = slot + first[lane][:, None]
    slot = jnp.minimum(slot, last[lane][:, None])
    if window is not None:
        slot = slot % P
    pages = tables[lane[:, None], slot].reshape(-1)
    return tuple(a.astype(i32)
                 for a in (lane, visit, pages, last, ends[-1]))


def _kernel(layer_ref, lane_ref, visit_ref, table_ref, qbase_ref, last_ref,
            total_ref, q_ref, k_hbm, v_hbm, *rest, page_size, head_dim,
            sm_scale, fp8, group, window=None):
    """One visit (grid step ``g``) of the online-softmax walk: the
    ``B`` table slots from visit ``visit[g]`` on of sequence
    ``lane[g]``, the ``W`` lanes of KV head block ``h`` of each page.
    The pools stay in HBM; the kernel fetches a page's K and its V a
    DMA each into ``buf[g % 2]`` ``[B, ps, W]``, sending for step ``g +
    1``'s pages before it waits, page by page, for step ``g``'s. Only
    slots up to the sequence's last live page are fetched or waited
    on; the rows of the others keep what they held, zeros or a page of
    this call. fp8 scales come as a block ``[B, 1, W / hd]`` a step.
    The block's ``W`` lanes are ``tiles`` lane tiles of ``hpt`` whole
    heads each (``_lane_tile``). A tile's heads are STACKED on the
    query axis: stacked row ``i`` of a tile is query row ``i % rows``
    with every lane but those of head ``i // rows`` zeroed, so one
    product of the stacked rows with the tile's keys, contracted over
    the tile's lanes, scores every head of the tile, and one product of
    the weights with the tile's values gives each stacked row its
    head's context on its head's lanes. Scratch (``m``, ``l`` ``[tiles,
    Rp, 1]``, ``acc`` ``[tiles, Rp, tw]``, ``Rp`` the stacked rows in
    whole sublane tiles) persists across a sequence's consecutive
    visits; initialized at its first, finalized into the output block
    at its last."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ks_ref = vs_ref = None
    if fp8:
        ks_ref, vs_ref, *rest = rest
    o_ref, k_buf, v_buf, sem, m_ref, l_ref, acc_ref = rest
    B, ps, hd = k_buf.shape[1], page_size, head_dim
    P = table_ref.shape[1]
    h, g = pl.program_id(0), pl.program_id(1)
    n, j = lane_ref[g], visit_ref[g]
    qbase, last = qbase_ref[n], last_ref[n]
    rows = q_ref.shape[1]
    tiles, Rp, tw = acc_ref.shape
    hpt, T, W = tw // hd, B * ps, tiles * tw
    half = g % 2
    f32, bf16 = jnp.float32, jnp.bfloat16
    # bf16 queries over bf16 pages go to the MXU as they are stored:
    # their products are exact in the f32 it accumulates in
    as_stored = q_ref.dtype == bf16 and k_buf.dtype == bf16
    mm = bf16 if as_stored else f32
    # Mosaic refuses bf16 operands under a caller's "highest"
    precision = lax.Precision.DEFAULT if as_stored else None

    def first_page(lane):
        """The page NUMBER a sequence's walk starts at: its first, or,
        under a window, the page of the first position the first
        query's window admits."""
        if window is None:
            return 0
        return jnp.maximum(qbase_ref[lane] - (window - 1), 0) // ps

    # a page's lanes of head block ``h``
    lanes = (slice(None) if W == k_hbm.shape[3]
             else pl.ds(pl.multiple_of(h * W, W), W))

    def each_page(step, into, do):
        """``do`` the fetch of each live page of grid step ``step`` into
        ``buf[into]``: a rolled loop over turns of ``_PAGES_A_TURN``
        slots, each turn laid out flat by the lowering (on the chip, at
        Mellum2's full layer, 831 us a call with all ``B`` slots laid
        out flat, 757 in turns of 8, 914 rolled page by page; unrolled
        in Python the loops cost seconds of every warm start: PERF.md
        section 6)."""
        lane = lane_ref[step]
        base = first_page(lane) + visit_ref[step] * B
        live = jnp.minimum(last_ref[lane] - base + 1, B)

        def one(i, carry):
            @pl.when(i < live)
            def _live():
                slot = base + i if window is None else (base + i) % P
                page = table_ref[lane, slot]
                for pool, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                    do(pltpu.make_async_copy(
                        pool.at[layer_ref[0], page, :, lanes],
                        buf.at[into, i], sem.at[into]))
            return carry

        def turn(t, carry):
            return lax.fori_loop(
                0, _PAGES_A_TURN, lambda u, c: one(t * _PAGES_A_TURN + u, c),
                carry, unroll=True)

        lax.fori_loop(0, (live + _PAGES_A_TURN - 1) // _PAGES_A_TURN, turn,
                      0)

    start, wait = lambda c: c.start(), lambda c: c.wait()

    @pl.when(g == 0)
    def _first():
        # the weights of a slot that is not fetched are 0, but 0 x NaN
        # is NaN in the product: the values' rows start as zeros
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
        each_page(0, 0, start)                 # once a call

    @pl.when(g + 1 < total_ref[0])
    def _ahead():
        each_page(g + 1, 1 - half, start)

    each_page(g, half, wait)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _MASK_MIN, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    sub = lax.broadcasted_iota(jnp.int32, (1, Rp, 1), 1)
    head_of_lane = lax.broadcasted_iota(jnp.int32, (1, 1, tw), 2) // hd

    def tile_of(buf, scales, c):
        """Lane tile ``c`` of the visit's pages end to end, ``[T,
        tw]``; an fp8 page in f32, times its heads' scales, each on its
        head's lanes."""
        x = buf[half, :, :, c * tw:(c + 1) * tw].astype(mm)
        if fp8:
            sc = scales[0, 0, :, :, c * hpt:(c + 1) * hpt]  # [B, 1, hpt]
            by_lane = sc[:, :, 0:1]
            for hh in range(1, hpt):
                by_lane = jnp.where(head_of_lane == hh, sc[:, :, hh:hh + 1],
                                    by_lane)
            x = x * by_lane
        return x.reshape(T, tw)

    k_of = lambda c: tile_of(k_buf, ks_ref, c)
    v_of = lambda c: tile_of(v_buf, vs_ref, c)
    stack = lambda f: jnp.stack([f(c) for c in range(tiles)])

    # the stacked queries [tiles, Rp, tw] (laid out in f32: its masks
    # are whole sublane tiles)
    q = stack(lambda c: q_ref[0, :, c * tw:(c + 1) * tw].astype(f32))
    if rows % 8 == 0:
        q = jnp.concatenate([q] * hpt, axis=1)
    else:
        q, each = jnp.zeros((tiles, Rp, tw), f32), q
        for i in range(hpt * rows):
            q = jnp.where(sub == i, each[:, i % rows:i % rows + 1], q)
    q = jnp.where(head_of_lane == sub // rows, q, 0.0).astype(mm)

    # a tile at a time: the pages are read from their buffer, no copy
    s = stack(lambda c: lax.dot_general(
        q[c], k_of(c), (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=f32)) * sm_scale        # [tiles, Rp, T]
    # a key at flat position p is admitted by stacked row i (query
    # (i % rows) // G of its head) iff p <= qbase[n] + that (causal)
    # and lies on a page the sequence holds (the slots past the last
    # live page were not fetched)
    first = first_page(n)
    pos = (first + j * B) * ps + lax.broadcasted_iota(jnp.int32, (1, 1, T), 2)
    valid = (pos <= qbase + (sub % rows) // group) \
        & (pos < (last + 1) * ps)                       # [1, Rp, T]
    if window is not None:
        # a row admits its own window only
        valid = valid & (pos > qbase + (sub % rows) // group - window)
    s = jnp.where(valid, s, _MASK_MIN)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # explicit zero for masked keys: an all-masked row would
    # otherwise contribute exp(MASK_MIN - MASK_MIN) == 1 a key
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    weigh = lambda w: stack(lambda c: lax.dot_general(
        w[c], v_of(c), (((1,), (0,)), ((), ())), precision=precision,
        preferred_element_type=f32))
    if as_stored:
        # the f32 weights as two bf16 halves (16 bits of mantissa
        # between them), one product over the values as stored
        hi = p.astype(bf16).astype(f32)
        both = weigh(jnp.concatenate([hi, p - hi], axis=1).astype(bf16))
        ctx = both[:, :Rp] + both[:, Rp:]
    else:
        ctx = weigh(p)
    acc_ref[...] = acc_ref[...] * alpha + ctx

    @pl.when(j == (last - first) // B)
    def _finish():
        # every query admits flat position 0 (qpos >= 0 always; under
        # a window, its own position), so l >= exp(0) == 1 at the end
        # of the walk: safe division
        full = acc_ref[...] / l_ref[...]
        out = full[:, :rows]
        for hh in range(1, hpt):
            out = jnp.where(head_of_lane == hh,
                            full[:, hh * rows:(hh + 1) * rows], out)
        for c in range(tiles):
            o_ref[0, :, c * tw:(c + 1) * tw] = out[c].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "group", "window"))
def _pallas_paged_attention(q, kv, layer, tables, qbase, interpret,
                            group=1, window=None):
    """``q`` is ``[N, rows, Hkv, hd]`` (row ``i`` = query ``i //
    group``). ``layer`` is a traced ``[1]`` array and the function is
    jitted so that the layers of a program share ONE trace and one
    lowering of the kernel: traced per layer, its body cost a served
    model's set-up seconds at every start, warm cache or not (PERF.md,
    PR 29)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, rows, H, hd = q.shape           # H KV heads, rows = Q * G
    P = tables.shape[1]
    ps = kv["k"].shape[2]
    fp8 = "k_scale" in kv
    Hh, B = _visit_shape(H, rows, hd, ps, P, kv["k"].dtype.itemsize,
                         q.dtype.itemsize, fp8)
    tw = _lane_tile(H * hd, hd)
    W, tiles, hpt = Hh * hd, Hh * hd // tw, tw // hd
    Rp = -(-hpt * rows // 8) * 8
    qbase = qbase.astype(jnp.int32)
    tables = tables.astype(jnp.int32)
    # the kernel looks a visit's pages up in the table as it sends for
    # them; the schedule's own page list serves fp8 scales alone (XLA
    # drops it unused)
    lane, visit, pages, last, total = _live_visits(
        tables, qbase, rows // group, ps, B, window)
    # the walk is as long as the pages held: the visit axis is dynamic
    grid = (H // Hh, total)
    q_spec = pl.BlockSpec((1, rows, W),
                          lambda h, g, ly, ln, *_: (ln[g], 0, h))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [q_spec, hbm, hbm]
    args = [q.reshape(N, rows, H * hd), kv["k"], kv["v"]]
    if fp8:
        # the scales of a step's pages, gathered by the schedule (a
        # slot past the last live page has that page's): one block
        # ``[B, 1, Hh]`` a step and head block, the array's last two dims
        steps = pages.shape[0] // B
        view = lambda sc: sc[layer[0]][pages] \
            .reshape(steps, B, H // Hh, 1, Hh).transpose(0, 2, 1, 3, 4)
        in_specs += [pl.BlockSpec((1, 1, B, 1, Hh),
                                  lambda h, g, *_: (g, h, 0, 0, 0))] * 2
        args += [view(kv["k_scale"]), view(kv["v_scale"])]
    kernel = functools.partial(
        _kernel, page_size=ps, head_dim=hd,
        sm_scale=float(1.0 / np.sqrt(np.float32(hd))), fp8=fp8,
        group=group, window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=grid,
            # the pools stay in HBM: the kernel fetches its own pages
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((2, B, ps, W), kv["k"].dtype),
                pltpu.VMEM((2, B, ps, W), kv["v"].dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((tiles, Rp, 1), jnp.float32),
                pltpu.VMEM((tiles, Rp, 1), jnp.float32),
                pltpu.VMEM((tiles, Rp, tw), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((N, rows, H * hd), q.dtype),
        interpret=interpret,
        # what a device trace calls the kernel (PERF.md section 3)
        name="paged_attention",
    )(layer, lane, visit, tables, qbase, last, total.reshape(1), *args)
    return out.reshape(N, rows, H, hd)


# ------------------------------------------------------------ dispatch
def paged_attention(q, kv, layer, tables, qbase, *, mode=None,
                    window=None):
    """Per-layer paged attention over the serving engine's KV tree.

    Parameters
    ----------
    q : ``[N, Q, H, hd]`` queries in the compute dtype: a position's
        projected row, split into its heads.
    kv : the page-pool tree (``kv_pages.PagePool.tree()``): ``"k"`` /
        ``"v"`` pools ``[L, n_pages, ps, Hkv * hd]``, plus
        ``"k_scale"`` / ``"v_scale"`` planes ``[L, n_pages, Hkv]`` when
        the pool is fp8. ``H`` is a multiple of ``Hkv`` (module
        docstring).
    layer : the pool's layer, a Python int. The kernel takes it as a
        prefetched scalar, so a program's layers share one trace of it.
    tables : ``[N, P]`` int32 page tables, rows in position order.
    qbase : ``[N]`` int32; query ``i`` of row ``n`` sits at absolute
        position ``qbase[n] + i``.
    mode : overrides :func:`paged_attention_mode` (tests/benches).
    window : a sliding window's width: query at position ``p`` attends
        positions ``p - window + 1 .. p``, and ``tables`` is a RING
        ``[N, R]`` (module docstring, "A window's ring").

    Returns ``[N, Q, H, hd]`` context in ``q.dtype``.
    """
    mode = mode or paged_attention_mode()
    if mode not in ("xla", "pallas", "interpret"):
        raise ValueError(
            f"unknown paged-attention mode {mode!r} (expected 'pallas',"
            " 'interpret' or 'xla')")
    N, Q, H, hd = q.shape
    W = kv["k"].shape[3]
    Hkv = W // hd
    if W % hd or not Hkv or H % Hkv:
        raise ValueError(
            f"{H} query heads of width {hd} do not divide into the "
            f"pool's rows of {W}")
    G = H // Hkv
    if G > 1:
        # the group's heads onto the query axis, query-major: row
        # ``i`` is query ``i // G`` of head ``i % G`` of its group
        q = q.reshape(N, Q, Hkv, G, hd).transpose(0, 1, 3, 2, 4) \
             .reshape(N, Q * G, Hkv, hd)
    ps = kv["k"].shape[2]
    if window is not None and tables.shape[1] * ps < window + Q + ps - 2:
        raise ValueError(
            f"a ring of {tables.shape[1]} pages of {ps} cannot hold "
            f"{Q} queries' window of {window} from any offset")
    if mode == "xla":
        out = _xla_paged_attention(q, kv, layer, tables, qbase, G, window)
    else:
        out = _pallas_paged_attention(
            q, kv, jnp.full((1,), layer, jnp.int32), tables, qbase,
            interpret=(mode == "interpret"), group=G, window=window)
    if G > 1:
        out = out.reshape(N, Q, G, Hkv, hd).transpose(0, 1, 3, 2, 4) \
                 .reshape(N, Q, H, hd)
    return out


@register_op("paged_attention")
def _op(q, kv, layer, tables, qbase, mode=None):
    return paged_attention(q, kv, int(layer), tables, qbase, mode=mode)
