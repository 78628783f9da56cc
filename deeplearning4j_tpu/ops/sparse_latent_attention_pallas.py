"""Learned sparse attention over a paged LATENT cache: the two device
stages a decode step of ``models/glm_moe_dsa.py`` adds to the paged
ones (DeepSeek-V3.2's sparse attention: a lightning indexer picks the
positions, multi-head latent attention reads only those).

1. :func:`index_select`. A cheap indexer scores EVERY position a slot
   holds against the step's one query, ``I_s = sum_i w_i relu(q_i .
   k_s)`` over ``Hi`` small heads, and the ``top`` largest are the
   positions the attention may read: ``sel [S, top]`` (ties at rank
   ``top`` go to the lower position; handed over in order of position)
   and ``n_sel [S] = min(pos + 1, top)`` of them real. A context of at most ``top`` positions selects
   all of itself. The keys ``k_s`` (one row of ``index_head_dim`` a
   position) lie in a paged store of their own under the slot's page
   table. The top-k is exact (``lax.top_k``, no ``approx_max_k``).
2. :func:`sparse_latent_attention`. All ``H`` query heads read ONE
   shared row a position, ``[c_kv | k_rope | padding]`` (``kv_pages``:
   the latent store; the row rests in whole lane tiles): the score is
   the query's whole row against the stored row (the query side
   carries the absorbed ``q_nope W_kvb^K`` on the latent's lanes, the
   rotated ``q_rope`` on the rope's and zeros on the padding's), the
   value the row's first ``dv`` lanes. Only the selected rows are
   read: row ``j`` of slot ``s`` is ``store[layer, tables[s, sel[s, j]
   // ps], sel[s, j] % ps]``.

Forms (``mode``, as ``ops/paged_attention_pallas.py``):

- ``xla``: the reference. Stage 1 gathers the slot's pages and scores
  them with one einsum; stage 2 gathers the selected rows and runs the
  einsum pair with a plain softmax between.
- ``pallas`` / ``interpret``: stage 1's scores come from a kernel
  (``index_scores`` in a device trace) that walks the pages each slot
  HOLDS through the page table, ``_PAGES_A_VISIT`` pages a visit, the schedule
  ``paged_attention_pallas._live_visits`` makes (a dynamic grid: the
  work follows the contexts held); the top-k stays XLA's. Stage 2
  gathers the selected rows in front of the kernel
  (:func:`gather_rows`, an XLA gather over the store seen as rows) and
  the kernel (``sparse_latent_attention`` in a trace) makes ONE pass
  over them a block of ``_ROWS_A_BLOCK`` at a time with an online
  softmax: scores, max, sum and accumulator in float32, both products
  on the MXU over the rows as stored. Blocks past ``n_sel`` are
  skipped. (A kernel that fetches single rows itself is refused by
  Mosaic: a slice of a store's tiled page dimension has to be whole
  tiles of 8 rows; XLA's gather it is: PERF.md section 5, PR 34.)

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

#: selected rows a grid step of the attention kernel brings into VMEM
_ROWS_A_BLOCK = 512
#: pages a visit of the scoring kernel brings into VMEM: a page of 16
#: keys of 128 is 4 KB, so a visit moves 128 KB (at 8 pages a visit the
#: kernel read 91 GB/s, bound by its 1,500 grid steps a call: PERF.md
#: section 6, PR 34)
_PAGES_A_VISIT = 32


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


def index_select(qi, w, store, layer, tables, pos, top, *, mode=None):
    """The positions each slot's step may attend.

    ``qi [S, Hi, D]`` the indexer's queries of the one position a slot
    decodes, ``w [S, Hi]`` float32 their weights, ``store [layers,
    n_pages, ps, D]`` the paged indexer keys (the step's own already
    written), ``layer`` a Python int, ``tables [S, P]``, ``pos [S]`` the
    step's position. -> ``(sel [S, K] int32, n_sel [S] int32)`` with ``K
    = min(top, P * ps)``: the ``K`` best-scored positions ``<= pos`` in
    order of position, the first ``n_sel = min(pos + 1, K)`` of them
    real."""
    mode = _check_mode(mode)
    if mode == "xla":
        scores = _xla_index_scores(qi, w, store, layer, tables)
    else:
        scores = _pallas_index_scores(
            qi, w, store, jnp.full((1,), layer, jnp.int32), tables, pos,
            interpret=(mode == "interpret"))
    K = min(int(top), scores.shape[1])
    held = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :] \
        <= pos[:, None]
    # a cell past the position is unwritten or another request's: never
    # a number, whatever lies there
    _, sel = lax.top_k(jnp.where(held, scores, -jnp.inf), K)
    n_sel = jnp.minimum(pos.astype(jnp.int32) + 1, K)
    return _by_position(sel.astype(jnp.int32), n_sel, scores.shape[1]), \
        n_sel


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


def _attend_kernel(n_ref, q_ref, r_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   block, scale, dv):
    """Block ``j`` of slot ``s``'s selected rows, online softmax."""
    from jax.experimental import pallas as pl

    s_i, j = pl.program_id(0), pl.program_id(1)
    n = n_ref[s_i]
    f32 = jnp.float32

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _MASK_MIN, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    @pl.when(j * block < n)
    def _visit():
        q, r = q_ref[0], r_ref[0]                    # [H, W], [block, W]
        bf16 = q.dtype == jnp.bfloat16 and r.dtype == jnp.bfloat16
        precision = lax.Precision.DEFAULT if bf16 else None
        s = lax.dot_general(q, r, (((1,), (1,)), ((), ())),
                            precision=precision,
                            preferred_element_type=f32) * scale
        valid = j * block + lax.broadcasted_iota(
            jnp.int32, (1, block), 1) < n
        s = jnp.where(valid, s, _MASK_MIN)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, -1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
            p.astype(r.dtype), r[:, :dv], (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=f32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        # n_sel >= 1: the step's own position is always selected
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("dv", "scale", "interpret"))
def _pallas_attend(q, rows, n_sel, dv, scale, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, W = q.shape
    K = rows.shape[1]
    block = _ROWS_A_BLOCK if K % _ROWS_A_BLOCK == 0 else K
    return pl.pallas_call(
        functools.partial(_attend_kernel, block=block, scale=scale, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S, K // block),
            in_specs=[pl.BlockSpec((1, H, W), lambda s, j, n: (s, 0, 0)),
                      pl.BlockSpec((1, block, W),
                                   lambda s, j, n: (s, j, 0))],
            out_specs=pl.BlockSpec((1, H, dv), lambda s, j, n: (s, 0, 0)),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, dv), q.dtype),
        interpret=interpret,
        name="sparse_latent_attention",
    )(n_sel.astype(jnp.int32), q, rows)


def sparse_latent_attention(q, store, layer, tables, sel, n_sel, *, dv,
                            scale, mode=None, rows=None):
    """``q [S, H, W]`` (a slot's ``H`` query heads over the store's row:
    absorbed latent part, rotated rope part, zeros on the padding) over
    the rows ``sel [S, K]`` names of ``store [layers, n_pages, ps, W]``
    through ``tables [S, P]``, the first ``n_sel [S]`` of them real ->
    ``ctx [S, H, dv]``: ``softmax(q . row * scale)`` over the real rows,
    weighing each row's first ``dv`` lanes. ``layer`` is a Python int.
    ``rows`` is ``selected_rows(tables, sel, ps)`` where the caller has
    it already (layers that share a selection)."""
    mode = _check_mode(mode)
    if q.shape[-1] != store.shape[-1] or dv > store.shape[-1]:
        raise ValueError(
            f"queries of {q.shape[-1]} lanes and values of {dv} over a "
            f"store whose rows have {store.shape[-1]}")
    if rows is None:
        rows = selected_rows(tables, sel, store.shape[2])
    rows = gather_rows(store, layer, rows)
    if mode == "xla":
        return _xla_attend(q, rows, n_sel, dv, scale)
    return _pallas_attend(q, rows, n_sel, dv=dv, scale=float(scale),
                          interpret=(mode == "interpret"))


__all__ = ["index_select", "sparse_latent_attention", "selected_rows",
           "gather_rows"]
