"""Learned sparse attention over a paged LATENT cache: the two device
stages a decode step of ``models/glm_moe_dsa.py`` adds to the paged
ones (DeepSeek-V3.2's sparse attention: a lightning indexer picks the
positions, multi-head latent attention reads only those).

1. :func:`index_select`. A cheap indexer scores EVERY position a slot
   holds against the step's one query, ``I_s = sum_i w_i relu(q_i .
   k_s)`` over ``Hi`` small heads, and the ``top`` largest are the
   positions the attention may read: ``sel [S, top]`` (ties at rank
   ``top`` go to the lower position; handed over in order of position,
   and, where asked for, as a mask over the table's cells) and ``n_sel
   [S] = min(pos + 1, top)`` of them real. A context of at most ``top``
   positions selects all of itself. The keys ``k_s`` (one row of
   ``index_head_dim`` a position) lie in a paged store of their own
   under the slot's page table. The top-k is exact (``lax.top_k``, no ``approx_max_k``).
2. :func:`sparse_latent_attention`. All ``H`` query heads read ONE
   shared row a position, ``[c_kv | k_rope | padding]`` (``kv_pages``:
   the latent store; the row rests in whole lane tiles): the score is
   the query's whole row against the stored row (the query side
   carries the absorbed ``q_nope W_kvb^K`` on the latent's lanes, the
   rotated ``q_rope`` on the rope's and zeros on the padding's), the
   value the row's first ``dv`` lanes. Only the selected rows are
   attended: row ``j`` of slot ``s`` is ``store[layer, tables[s, sel[s,
   j] // ps], sel[s, j] % ps]``.

Forms (``mode``, as ``ops/paged_attention_pallas.py``):

- ``xla``: the reference, and a CPU's path. Stage 1 gathers the slot's
  pages and scores them with one einsum; stage 2 gathers the selected
  rows (:func:`gather_rows`, an XLA gather over the store seen as rows)
  and runs the einsum pair with a plain softmax between.
- ``pallas`` / ``interpret``: both stages WALK the pages each slot
  holds through its page table, on the schedule
  ``paged_attention_pallas._live_visits`` makes (a dynamic grid ``(1,
  visits)``, a slot's visits end to end: the work follows the contexts
  held), the store and the layer a prefetched scalar, so a program's
  layers share one trace of each kernel.

  Stage 1's scores come from ``index_scores`` (the name in a device
  trace), ``_PAGES_A_VISIT`` pages a visit; the top-k stays XLA's.

  Stage 2 gathers nothing. A store rests in tiles of several rows x 128
  lanes, so ONE row of 640 lanes is a piece of each of five tiles:
  XLA's gather fetched row by row at 13 ns a row whatever its bytes
  (99 GB/s), and Mosaic refuses to fetch a single row at all (a slice
  of a tiled dimension has to be whole tiles). What the chip reads well
  is a whole page, 20 KB of whole tiles in one run. So the kernel
  (``sparse_latent_attention`` in a trace) reads EVERY page a slot
  holds up to its last selected position, ``_PAGES_A_WALK`` pages a
  visit, and masks the rows that were not selected: a visit is ``[B *
  ps, W]`` rows and its slice ``[1, B * ps]`` of the selection AS A
  MASK; scores ``q . rows^T`` on the MXU, set to ``_MASK_MIN`` where
  the mask is off, an online softmax (max, sum and accumulator ``[H,
  dv]`` float32 in scratch, set at a slot's first visit and divided out
  at its last), the weights of masked rows set to 0 before the second
  product. Masked rows are READ: a pool row is zeros or a request's
  finite leftovers. The store stays in HBM and the kernel fetches for
  itself, a page a DMA into one of two buffers, the next visit's pages
  sent for before this visit's are worked on: handed to the pipeline
  as ``B`` operands a visit the same walk was bound by the pipeline's
  45-60 ns a page fetch (PERF.md section 6, PR 36).

  The mask is made without a scatter (65,536 ones into ``[S, cells]``
  is the class of operation the gather's address look-up was):
  :func:`index_select` has the scores and ``top_k``'s ``K``-th value,
  so the mask is ``score > kth``, plus the ties at ``kth`` in order of
  position until ``K`` are on, elementwise and one running count a row.
  It is made once a selection and shared by the layers under it
  (:func:`selection_reads`, ``reads=``); a program that hands it over
  never uses the list ``sel``, and XLA drops the list's sort. Called
  with the list alone, :func:`sparse_latent_attention` scatters it into
  a mask itself: slow and exact.

  The walk's bytes grow with the context and the gather's did not: at
  the GLM-5.2 cell's contexts (mean 5.6 K) a call takes 0.47 ms against
  the gather's 1.08, with every slot at 14 K 1.08 against 1.09. There
  is one Pallas form all the same; contexts of 32-128 K have a cell of
  their own to come (ROADMAP W13), with those figures on record.

Numerics: float-equivalent across forms, not bit-identical (the online
softmax reduces in another order). Where two positions' index scores
lie within rounding of each other at rank ``top``, bf16 arithmetic may
select the other one than float32 would; the attention over either set
is exact for the set it was given.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.ops.paged_attention_pallas import (
    _MASK_MIN, _live_visits, paged_attention_mode)

#: pages a visit of the scoring kernel brings into VMEM: a page of 16
#: keys of 128 is 4 KB, so a visit moves 128 KB (at 8 pages a visit the
#: kernel read 91 GB/s, bound by its 1,500 grid steps a call: PERF.md
#: section 6, PR 34)
_PAGES_A_VISIT = 32
#: pages a visit of the attention's walk brings into VMEM: a page of 16
#: latent rows of 640 is 20 KB, so a visit moves 1.3 MB into one of two
#: buffers (on the chip 16 / 32 / 64 / 128 pages a visit took 0.69 / 0.54
#: / 0.48 / 0.47 ms a call at the GLM-5.2 cell's contexts; a slot's last
#: visit is half padding on average, more of it the longer the visit:
#: PERF.md section 6, PR 36)
_PAGES_A_WALK = 64


def _check_mode(mode):
    mode = mode or paged_attention_mode()
    if mode not in ("xla", "pallas", "interpret"):
        raise ValueError(
            f"unknown mode {mode!r} (expected 'pallas', 'interpret' or "
            "'xla')")
    return mode


# ------------------------------------------------------ stage 1: select
def _xla_index_scores(qi, w, store, layer, tables):
    """``I [S, P * ps]`` float32 of every table cell, masked by no
    one: the slot's pages gathered, one einsum."""
    k = store[layer][tables]                          # [S, P, ps, D]
    S, P, ps, _ = k.shape
    s = jnp.einsum("shd,spod->shpo", qi, k,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w[:, :, None, None], axis=1) \
        .reshape(S, P * ps)


def _scores_kernel(layer_ref, lane_ref, visit_ref, page_ref, q_ref, w_ref,
                   *rest, pages):
    """One visit: ``pages`` pages of one slot end to end ``[T, D]``
    against the slot's ``Hi`` queries -> the visit's ``T`` scores."""
    del layer_ref, lane_ref, visit_ref, page_ref      # the index maps'
    k_refs, o_ref = rest[:pages], rest[pages]
    k = [r[0, 0] for r in k_refs]
    k = k[0] if pages == 1 else jnp.concatenate(k, axis=0)     # [T, D]
    bf16 = q_ref.dtype == jnp.bfloat16 and k.dtype == jnp.bfloat16
    s = lax.dot_general(
        q_ref[0], k, (((1,), (1,)), ((), ())),
        precision=lax.Precision.DEFAULT if bf16 else None,
        preferred_element_type=jnp.float32)                   # [Hi, T]
    o_ref[0, 0] = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0], axis=0,
                          keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_index_scores(qi, w, store, layer, tables, pos, interpret):
    """``layer`` is a traced ``[1]`` array, so a program's indexer
    layers share one trace of the kernel. Cells of pages a slot does
    not hold come back unwritten: the caller masks by position."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, Hi, D = qi.shape
    P = tables.shape[1]
    ps = store.shape[2]
    B = min(_PAGES_A_VISIT, P)
    NV = -(-P // B)
    lane, visit, pages, _, total = _live_visits(
        tables.astype(jnp.int32), pos.astype(jnp.int32), 1, ps, B)

    def page_map(i):
        return lambda _, g, ly, ln, vi, pg: (ly[0], pg[g * B + i], 0, 0)

    by_lane = lambda _, g, ly, ln, vi, pg: (ln[g], 0, 0)
    out = pl.pallas_call(
        functools.partial(_scores_kernel, pages=B),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(1, total),
            in_specs=[pl.BlockSpec((1, Hi, D), by_lane),
                      pl.BlockSpec((1, Hi, 1), by_lane)]
            + [pl.BlockSpec((1, 1, ps, D), page_map(i)) for i in range(B)],
            out_specs=pl.BlockSpec(
                (1, 1, 1, B * ps),
                lambda _, g, ly, ln, vi, pg: (ln[g], vi[g], 0, 0))),
        out_shape=jax.ShapeDtypeStruct((S, NV, 1, B * ps), jnp.float32),
        interpret=interpret,
        # what a device trace calls the kernel (PERF.md section 3)
        name="index_scores",
    )(layer, lane, visit, pages, qi, w[..., None], *[store] * B)
    return out.reshape(S, NV * B * ps)[:, :P * ps]


def index_select(qi, w, store, layer, tables, pos, top, *, mode=None,
                 with_mask=False):
    """The positions each slot's step may attend.

    ``qi [S, Hi, D]`` the indexer's queries of the one position a slot
    decodes, ``w [S, Hi]`` float32 their weights, ``store [layers,
    n_pages, ps, D]`` the paged indexer keys (the step's own already
    written), ``layer`` a Python int, ``tables [S, P]``, ``pos [S]`` the
    step's position. -> ``(sel [S, K] int32, n_sel [S] int32)`` with ``K
    = min(top, P * ps)``: the ``K`` best-scored positions ``<= pos`` in
    order of position, the first ``n_sel = min(pos + 1, K)`` of them
    real. ``with_mask``: and, third, the same selection as a mask ``[S,
    P * ps]`` bool (what :func:`selection_reads` takes): the scores
    above the ``K``-th largest, and of those equal to it the lowest
    positions until ``K`` are on, elementwise and a running count along
    a row, no scatter."""
    mode = _check_mode(mode)
    if mode == "xla":
        scores = _xla_index_scores(qi, w, store, layer, tables)
    else:
        scores = _pallas_index_scores(
            qi, w, store, jnp.full((1,), layer, jnp.int32), tables, pos,
            interpret=(mode == "interpret"))
    cells = scores.shape[1]
    K = min(int(top), cells)
    held = jnp.arange(cells, dtype=jnp.int32)[None, :] <= pos[:, None]
    # a cell past the position is unwritten or another request's: never
    # a number, whatever lies there
    scores = jnp.where(held, scores, -jnp.inf)
    kth, sel = lax.top_k(scores, K)
    n_sel = jnp.minimum(pos.astype(jnp.int32) + 1, K)
    sel = _by_position(sel.astype(jnp.int32), n_sel, cells)
    if not with_mask:
        return sel, n_sel
    # under K positions held the K-th value is -inf: everything held is
    # above it
    kth = kth[:, -1:]
    above, tie = scores > kth, scores == kth
    room = K - jnp.sum(above, axis=-1, keepdims=True)
    return sel, n_sel, held & (
        above | (tie & (jnp.cumsum(tie, axis=-1) <= room)))


def _by_position(sel, n_sel, cells):
    """The selection in order of POSITION, the real entries first: the
    rows the attention gathers then lie in address order a page (as
    ``top_k`` leaves them, by falling score, every row is a jump in
    HBM). Entries past ``n_sel`` name the table's last cell."""
    real = jnp.arange(sel.shape[1], dtype=jnp.int32)[None, :] \
        < n_sel[:, None]
    return jnp.sort(jnp.where(real, sel, cells - 1), axis=-1)


# ------------------------------------------------------ stage 2: attend
def selected_rows(tables, sel, page_size):
    """Where the selected positions lie in a layer of a store: row
    ``page * page_size + offset`` of the layer's ``n_pages * page_size``
    rows, ``[S, K]``. The same for every store and layer under the
    table, so a selection shared by several layers computes it once."""
    page = jnp.take_along_axis(tables, sel // page_size, axis=1)
    return page * page_size + sel % page_size


def gather_rows(store, layer, rows):
    """Rows ``rows [S, K]`` (:func:`selected_rows`) of layer ``layer``
    of ``store [layers, n_pages, ps, W]`` -> ``[S, K, W]``: the store
    seen as rows (a merge of leading dimensions, no data moves) and one
    gather by flat row number."""
    L, n_pages, ps, W = store.shape
    return store.reshape(L * n_pages * ps, W)[layer * n_pages * ps + rows]


def _xla_attend(q, rows, n_sel, dv, scale):
    K = rows.shape[1]
    s = jnp.einsum("shw,skw->shk", q, rows,
                   preferred_element_type=jnp.float32) * scale
    ok = jnp.arange(K, dtype=jnp.int32)[None, None, :] \
        < n_sel[:, None, None]
    p = jax.nn.softmax(jnp.where(ok, s, _MASK_MIN), axis=-1)
    return jnp.einsum("shk,skv->shv", p.astype(rows.dtype),
                      rows[..., :dv]).astype(q.dtype)


def selection_mask(sel, n_sel, cells):
    """The list as a mask ``[S, cells]`` bool: one scatter of the real
    entries (slow and exact; a program has :func:`index_select`'s own,
    made without one)."""
    S, K = sel.shape
    real = jnp.arange(K, dtype=jnp.int32)[None, :] < n_sel[:, None]
    return jnp.zeros((S, cells), bool).at[
        jnp.arange(S)[:, None], jnp.where(real, sel, cells)].set(
            True, mode="drop")


def _masked_walk(tables, mask, page_size):
    """The walk over the pages a selection reaches: the mask a visit at
    a time ``[S, visits, 1, B * ps]`` float32, ``_live_visits``'
    schedule (which slot and which of its visits a grid step is) up to
    the page of each slot's last selected position (nothing selected
    lies beyond) and the tables themselves: the kernel looks a visit's
    pages up as it sends for them (the schedule's own list of them, a
    look-up of ``S * P`` scalars in the table, is dropped by XLA
    unused: 0.15 ms a selection on the chip)."""
    S, P = tables.shape
    B = min(_PAGES_A_WALK, P)
    cells = -(-P // B) * B * page_size
    tables = tables.astype(jnp.int32)
    last = jnp.max(jnp.where(
        mask, jnp.arange(mask.shape[1], dtype=jnp.int32)[None, :], 0), axis=1)
    on = jnp.pad(mask.astype(jnp.float32),
                 ((0, 0), (0, cells - mask.shape[1])))
    lane, visit, _, last, total = _live_visits(tables, last, 1, page_size, B)
    return (on.reshape(S, -1, 1, B * page_size), lane, visit, tables, last,
            total.reshape(1))


def selection_reads(tables, sel, n_sel, page_size, *, mode=None, mask=None):
    """What every layer under one selection reads by, made once a
    selection and handed to :func:`sparse_latent_attention` as
    ``reads=``: the rows' addresses (``xla``: :func:`selected_rows`) or
    the masked walk (the kernel's). ``mask`` is :func:`index_select`'s
    where the caller has it; without it the list is scattered into
    one."""
    if _check_mode(mode) == "xla":
        return selected_rows(tables, sel, page_size)
    if mask is None:
        mask = selection_mask(sel, n_sel, tables.shape[1] * page_size)
    return _masked_walk(tables, mask, page_size)


def _walk_kernel(layer_ref, lane_ref, visit_ref, table_ref, last_ref,
                 total_ref, q_ref, on_ref, store_ref, o_ref, buf, sem, m_ref,
                 l_ref, acc_ref, *, pages, scale, dv):
    """Visit ``j`` of slot ``n`` (grid step ``g``): ``pages`` whole
    pages of the store end to end in ``buf[g % 2]`` ``[T, W]``, fetched
    a page a DMA while the step before computed (the store stays in
    HBM; this step sends for the next one's pages first), the visit's
    slice of the mask ``[1, T]``; online softmax over the rows that are
    on. The slots of a slot's final visit past its last live page name
    that page again, and the mask is off there (those cells lie past
    the last selected position, or past the table). ``m``, ``l`` ``[H,
    1]`` and ``acc`` ``[H, dv]`` persist over a slot's consecutive
    visits: set at its first, divided out at its last."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g = pl.program_id(1)
    n, j = lane_ref[g], visit_ref[g]
    ps = buf.shape[1] // pages
    half = g % 2
    f32 = jnp.float32

    def each_page(step, into, do, unroll):
        """``do`` the fetch of each page of grid step ``step`` into
        ``buf[into]``. The loop is traced once and, where ``unroll``,
        laid out flat by the lowering (a rolled loop's visit took 0.77
        ms a call for 0.45; 192 fetches traced one by one cost a served
        model 2.7 s of every start: PERF.md section 6, PR 36)."""
        lane = lane_ref[step]
        first, last = visit_ref[step] * pages, last_ref[lane]

        def one(i, carry):
            do(pltpu.make_async_copy(
                store_ref.at[layer_ref[0],
                             table_ref[lane, jnp.minimum(first + i, last)]],
                buf.at[into, pl.ds(pl.multiple_of(i * ps, ps), ps)],
                sem.at[into]))
            return carry

        lax.fori_loop(0, pages, one, 0, unroll=unroll)

    start, wait = lambda c: c.start(), lambda c: c.wait()

    @pl.when(g == 0)
    def _first():
        each_page(0, 0, start, False)          # once a call

    @pl.when(g + 1 < total_ref[0])
    def _ahead():
        each_page(g + 1, 1 - half, start, True)

    each_page(g, half, wait, True)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _MASK_MIN, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    q, r = q_ref[0], buf[half]                       # [H, W], [T, W]
    bf16 = q.dtype == jnp.bfloat16 and r.dtype == jnp.bfloat16
    precision = lax.Precision.DEFAULT if bf16 else None
    s = lax.dot_general(q, r, (((1,), (1,)), ((), ())),
                        precision=precision,
                        preferred_element_type=f32) * scale
    # a row that is not selected was read all the same: a pool row is
    # zeros or somebody's finite leftovers, and weighs nothing
    on = on_ref[0, 0] > 0.0                          # [1, T]
    s = jnp.where(on, s, _MASK_MIN)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(on, jnp.exp(s - m_new), 0.0)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, -1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
        p.astype(r.dtype), r[:, :dv], (((1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=f32)

    @pl.when(j == last_ref[n] // pages)
    def _finish():
        # n_sel >= 1 (a slot holds its step's own position at least),
        # and the walk ends at the last selected one: l > 0
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("dv", "scale", "interpret"))
def _pallas_attend(q, store, layer, walk, dv, scale, interpret):
    """``layer`` is a traced ``[1]`` array: a program's layers share one
    trace of the kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    on, lane, visit, tables, last, total = walk
    S, H, W = q.shape
    T = on.shape[-1]
    by_lane = lambda _, g, ly, ln, *rest: (ln[g], 0, 0)
    return pl.pallas_call(
        functools.partial(_walk_kernel, pages=T // store.shape[2],
                          scale=scale, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(1, total[0]),
            in_specs=[pl.BlockSpec((1, H, W), by_lane),
                      pl.BlockSpec(
                          (1, 1, 1, T),
                          lambda _, g, ly, ln, vi, *rest: (ln[g], vi[g],
                                                           0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, dv), by_lane),
            scratch_shapes=[pltpu.VMEM((2, T, W), store.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, dv), q.dtype),
        interpret=interpret,
        # what a device trace calls the kernel (PERF.md section 3)
        name="sparse_latent_attention",
    )(layer, lane, visit, tables, last, total, q, on, store)


def sparse_latent_attention(q, store, layer, tables, sel, n_sel, *, dv,
                            scale, mode=None, reads=None):
    """``q [S, H, W]`` (a slot's ``H`` query heads over the store's row:
    absorbed latent part, rotated rope part, zeros on the padding) over
    the rows ``sel [S, K]`` names of ``store [layers, n_pages, ps, W]``
    through ``tables [S, P]``, the first ``n_sel [S]`` of them real ->
    ``ctx [S, H, dv]``: ``softmax(q . row * scale)`` over the real rows,
    weighing each row's first ``dv`` lanes. ``layer`` is a Python int.
    ``reads`` is :func:`selection_reads` of the same selection and mode
    where the caller has it already (layers that share a selection)."""
    mode = _check_mode(mode)
    if q.shape[-1] != store.shape[-1] or dv > store.shape[-1]:
        raise ValueError(
            f"queries of {q.shape[-1]} lanes and values of {dv} over a "
            f"store whose rows have {store.shape[-1]}")
    if reads is None:
        reads = selection_reads(tables, sel, n_sel, store.shape[2],
                                mode=mode)
    if mode == "xla":
        return _xla_attend(q, gather_rows(store, layer, reads), n_sel, dv,
                           scale)
    return _pallas_attend(q, store, jnp.full((1,), layer, jnp.int32), reads,
                          dv=dv, scale=float(scale),
                          interpret=(mode == "interpret"))


__all__ = ["index_select", "sparse_latent_attention", "selection_reads",
           "selection_mask", "selected_rows", "gather_rows"]
