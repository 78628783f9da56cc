"""Paged KV cache: fixed-size page pool + per-slot page tables.

Why pages: ``CausalLM.generate()`` allocates one dense
``[L, N, H, max_len, hd]`` cache per compiled ``(batch, prompt, new)``
shape — every distinct request geometry is a fresh multi-hundred-MB
allocation and a fresh executable. The serving engine instead owns ONE
pool of fixed-size pages shared by every slot:

- ``kv tree``: ``{"k", "v"}`` pools of shape
  ``[L, n_pages, page_size, H * hd]`` (a page is ``page_size`` rows,
  a row one position's K or V, every KV head side by side: the
  projection's own output row), allocated once at engine
  startup, plus — when the pool is fp8-quantized — ``"k_scale"`` /
  ``"v_scale"`` per-page-per-head fp32 scale planes ``[L, n_pages,
  H]`` stored beside them. Page 0 is the NULL page — a scratch target
  that absorbs writes from inactive slots and from the padded tail of
  prefill commits; it is never read through a valid attention
  position.
- a model that names its own ``stores`` (``cache_spec()``; PR 34:
  GLM-5.2's latent rows and indexer keys) gets ``{name: [layers,
  n_pages, page_size, row width]}`` instead, each store with its own
  layer count and row width, all under the one allocator and the one
  page table a slot; ``commit_rows`` / ``append_rows`` are its writers.
- per-slot page table: row ``j`` of a slot's table names the page
  holding absolute positions ``[j*page_size, (j+1)*page_size)`` of
  that slot's sequence. Unallocated tail entries point at the null
  page and are masked by the position check (attention only admits
  flat position ``<= pos``).
- ``PagePool`` is the HOST-side allocator (free list, REFERENCE
  COUNTS, utilization gauge); the device arrays thread functionally
  through the jitted prefill/decode steps as ONE pytree
  (``pool.tree()``) and are rebound by the engine
  (``pool.rebind(kv)``).

Reference counts are what make cross-request KV reuse safe
(serving/prefix_cache.py, serving/sessions.py): a page can be mapped
read-only into several slots' tables at once — ``alloc`` hands a page
out at refcount 1, ``share`` adds readers, ``free`` DECREMENTS and
only returns the page to the free list when the last reader is gone.
Writers must hold the only reference; a slot about to write into a
shared page takes a private copy first (``copy_page``, the
copy-on-write step) and swaps its table entry.

fp8 KV (``kv_dtype="fp8_e4m3"``, nn/precision.py helpers): pages
store float8_e4m3fn — HALF the bytes of bf16, so the same HBM budget
holds ~2x the KV positions and every decode step streams half the
cache bytes. Scales are per-page-per-head and follow the page's
lifecycle exactly: written at commit (prefill/handoff compute exact
per-page absmax over the REAL positions), frozen once a page has
entries (the decode append only mints a fresh scale at offset 0, so
earlier tokens in the page are never re-scaled under their feet),
carried by ``copy_page`` (a CoW clone keeps the source's scales), and
reclaimed implicitly with the page (scale rows of free pages are
garbage, exactly like their page contents). Scale planes initialize
to ONES so the zero-filled pools round-trip exactly and no division
ever sees zero.

Layout contract (PR 27, PR 31): the pools rest in the layout the
programs use, so no program copies one. A page is ``[page_size, H *
hd]``: with the minor dimension whole lane tiles (1,280 lanes at
GPT-2-large, 512 at LFM2-24B-A2B) the device keeps the array
row-major at rest, which is also how the Mosaic kernel
(ops/paged_attention_pallas.py) and the writes here take it. (The
page this file had before, ``[H, page_size, hd]`` with ``hd`` 64 half
a lane tile, rested with the PAGE dimension minor-most, and every
program re-laid both pools out at entry and at exit: four copies of
379 MB a dispatch, 26% of the GPT-2 serving window; PERF.md section 6,
PR 31. A row narrower than 128 lanes still rests otherwise: toy sizes
only.) Inside a program a write must keep that layout too: a scatter
whose update window has an INDEXED dimension between its own is given
another layout by XLA, and with it the whole decode loop's carry (72
copies of a pool a step, PR 27). So a per-position write indexes
``[layer, page, offset]`` and its update window is the trailing row,
one contiguous ``H * hd`` run (``_write_rows``, the one such site);
whole-page writes (``commit_prefill``, ``copy_page``: window
``[page_size, H * hd]``, nothing indexed inside it) are row-major as
they stand. Nothing is pinned (``jax.experimental.layout``): an
executable loaded back from the persistent compilation cache comes
without its pinned layouts (jax 0.9.0). ``tests/test_kv_layout_aot.py``
compiles the serving programs for a described v5e with the layouts the
arrays have at rest and fails on any pool-shaped ``copy``; it needs no
chip.

The jax functions here are pure and shape-static, so the engine's one
decode executable serves every mix of request lengths.
"""

from __future__ import annotations

import collections
import threading
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import precision as _precision
from deeplearning4j_tpu.profiler import telemetry as _telemetry


class PagePool:
    """Host-side refcounting page allocator over the device-resident
    K/V pools.

    ``n_pages`` INCLUDES the reserved null page 0, so the usable
    capacity is ``n_pages - 1`` pages. ``alloc`` returns None when the
    request cannot be satisfied — the scheduler keeps the request
    queued (head-of-line) until eviction frees pages.

    ``dtype`` is the engine's COMPUTE dtype; ``kv_dtype`` optionally
    quantizes the STORED pages (``"fp8_e4m3"``) with fp32 scale planes
    beside them — ``tree()`` then carries four leaves instead of two.

    Thread safety: the free list and refcounts are guarded by a lock —
    the scheduler thread allocates/frees, while session release and
    submit-time budget hints may touch refcounts from client threads.
    """

    def __init__(self, n_layers: int, n_heads: int, page_size: int,
                 head_dim: int, n_pages: int, dtype=jnp.bfloat16,
                 engine_id: str = "solo", device=None, kv_dtype=None,
                 stores: Optional[Dict[str, Sequence[int]]] = None):
        if page_size < 1 or n_pages < 2:
            raise ValueError(
                f"need page_size >= 1 and n_pages >= 2 (one null page "
                f"+ one usable), got {page_size}/{n_pages}")
        self.page_size = int(page_size)
        self.n_pages = int(n_pages)
        #: ``engine=`` label on the utilization gauges, so N pools in
        #: one process (a serving fleet) stay distinguishable series
        self.engine_id = str(engine_id)
        self.kv_dtype = _precision.resolve_kv_dtype(kv_dtype)
        store = (_precision.fp8_kv_dtype() if self.kv_dtype
                 else jnp.dtype(dtype))
        #: ``kv_dtype=`` label value on every SERVING_KV_* series
        self.dtype_label = self.kv_dtype or jnp.dtype(dtype).name
        if stores is not None and self.kv_dtype:
            raise ValueError("only K/V pools are quantized: a model that "
                             "names its own stores keeps them in its "
                             "compute dtype")
        # a page row is every KV head side by side, head ``h`` on
        # lanes ``[h * hd, (h + 1) * hd)`` (module docstring); a model
        # that names its stores gives each one's layers and row width
        if stores is None:
            stores = dict.fromkeys(("k", "v"),
                                   (n_layers, n_heads * head_dim))
        # allocated where they live (device=None is the default
        # device): a pool must never pass through another chip's HBM
        #: the device arrays by name, in the order the programs' tree
        #: has them: ``{"k", "v"}`` (+ scale planes), or the model's own
        self._arrays: Dict[str, jnp.ndarray] = {
            name: jnp.zeros((int(layers), n_pages, page_size, int(width)),
                            store, device=device)
            for name, (layers, width) in stores.items()}
        if self.kv_dtype:
            sshape = (n_layers, n_pages, n_heads)
            for name in ("k_scale", "v_scale"):
                self._arrays[name] = jnp.ones(sshape, jnp.float32,
                                              device=device)
        # LIFO free list: recently-freed pages are re-used first, which
        # keeps the hot working set of pages small and cache-friendly
        self._free: List[int] = list(range(n_pages - 1, 0, -1))
        #: page -> live reference count; absent means the page is free
        self._refs: Dict[int, int] = {}
        self._high_water = 0
        self._lock = threading.Lock()
        self._gauge()

    # ----------------------------------------------------- device tree
    def tree(self) -> Dict[str, jnp.ndarray]:
        """The device-side KV pytree threaded through jitted programs:
        ``{"k", "v"}`` (+ ``"k_scale"``/``"v_scale"`` when fp8). Dict
        insertion order is the flatten order, so a non-fp8 tree
        flattens to exactly the (kpool, vpool) pair the pre-tree
        programs took — both features off stays program-identical."""
        return dict(self._arrays)

    def rebind(self, kv: Dict[str, jnp.ndarray]) -> None:
        """Adopt the arrays a jitted program returned (the functional
        counterpart of ``tree()``; donation invalidated the old ones).
        """
        self._arrays = {name: kv[name] for name in self._arrays}

    # the K/V pools by their names (None where the pool is a model's
    # own stores, or not quantized)
    k = property(lambda self: self._arrays.get("k"))
    v = property(lambda self: self._arrays.get("v"))
    k_scale = property(lambda self: self._arrays.get("k_scale"))
    v_scale = property(lambda self: self._arrays.get("v_scale"))

    # ------------------------------------------------------- accounting
    @property
    def capacity(self) -> int:
        return self.n_pages - 1

    @property
    def allocated(self) -> int:
        return self.capacity - len(self._free)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def high_water(self) -> int:
        return self._high_water

    def utilization(self) -> float:
        return self.allocated / max(self.capacity, 1)

    def refcount(self, page: int) -> int:
        """Live references on ``page`` (0 when free)."""
        with self._lock:
            return self._refs.get(int(page), 0)

    def shared_pages(self) -> int:
        """Pages with MORE than one reader (prefix-cache hits mapped
        into live slots, cache+session double holds, ...)."""
        with self._lock:
            return sum(1 for r in self._refs.values() if r > 1)

    def bytes_per_page(self) -> int:
        # every store (k + v + scale planes, or the model's own), all
        # layers, one page
        return sum(self.store_bytes().values()) // self.n_pages

    def store_bytes(self) -> Dict[str, int]:
        """Bytes of each device array of the pool, by name."""
        return {name: int(a.nbytes) for name, a in self._arrays.items()}

    # ------------------------------------------------------- allocation
    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` pages at refcount 1 each, or None if the pool can't
        satisfy it (caller keeps the request queued)."""
        with self._lock:
            if n > len(self._free):
                return None
            pages = [self._free.pop() for _ in range(n)]
            for p in pages:
                self._refs[p] = 1
            self._high_water = max(self._high_water, self.allocated)
        self._gauge()
        return pages

    def share(self, pages: Sequence[int]) -> None:
        """Add one reference per listed page (a page listed twice gains
        two). Every page must currently be live — sharing a free page
        is a use-after-free and raises."""
        with self._lock:
            for p in pages:
                self._check_range(p)
                if int(p) not in self._refs:
                    raise ValueError(
                        f"cannot share free page {int(p)} (not "
                        "currently allocated)")
            for p in pages:
                self._refs[int(p)] += 1
        self._gauge()

    def free(self, pages: Sequence[int]) -> None:
        """Drop one reference per listed page; a page whose last
        reference drops returns to the free list.

        The whole call is validated BEFORE any mutation: out-of-range
        or null-page indices, frees of already-free pages, and
        DUPLICATES WITHIN ONE CALL that exceed the page's live count
        all raise with the free list untouched (a silent bad free is a
        corrupted allocator and, pages being shared now, somebody
        else's KV cache)."""
        with self._lock:
            demand = collections.Counter()
            for p in pages:
                self._check_range(p)
                demand[int(p)] += 1
            for p, n in demand.items():
                have = self._refs.get(p, 0)
                if have == 0:
                    raise ValueError(f"double free of page {p} "
                                     "(already on the free list)")
                if n > have:
                    raise ValueError(
                        f"over-free of page {p}: {n} frees in one call "
                        f"but only {have} live reference(s)")
            for p, n in demand.items():
                left = self._refs[p] - n
                if left == 0:
                    del self._refs[p]
                    self._free.append(p)
                else:
                    self._refs[p] = left
        self._gauge()

    def _check_range(self, p) -> None:
        if not isinstance(p, (int,)) and not hasattr(p, "__index__"):
            raise ValueError(f"page index {p!r} is not an integer")
        p = int(p)
        if not 0 < p < self.n_pages:
            raise ValueError(f"page {p} outside pool (null page 0 "
                             "is never allocated or freed)")

    def _gauge(self) -> None:
        if _telemetry.enabled():
            reg = _telemetry.MetricsRegistry.get_default()
            labels = dict(engine=self.engine_id,
                          kv_dtype=self.dtype_label)
            reg.gauge(
                _telemetry.SERVING_KV_PAGE_UTILIZATION,
                "fraction of KV-cache pages currently allocated to "
                "live requests").set(self.utilization(), **labels)
            reg.gauge(
                _telemetry.SERVING_SHARED_PAGES,
                "KV pages mapped by more than one reader (prefix-"
                "cache sharing)").set(self.shared_pages(), **labels)
            reg.gauge(
                _telemetry.SERVING_KV_PAGE_BYTES,
                "bytes per KV page (k + v + scale planes, all "
                "layers)").set(self.bytes_per_page(), **labels)


# ------------------------------------------------------- pure jax ops
def _is_fp8(kv) -> bool:
    return "k_scale" in kv


def commit_prefill(kv, ks, vs, page_row, page_size: int, n_valid=None):
    """Scatter one prompt's prefill K/V into its pages.

    ``ks``/``vs``: ``[L, 1, H, B, hd]`` from the parallel-prefill
    forward over the padded prompt (bucket width ``B``, a multiple of
    ``page_size``), laid into page rows ``[L, B // page_size,
    page_size, H * hd]`` here. ``page_row``: ``[B // page_size]`` page
    ids — real
    pages for chunks the slot owns, null page 0 for the padded tail
    (garbage written there is never read: positions beyond the true
    prompt length stay masked until the decode loop overwrites them).

    fp8 pools additionally compute the exact per-page-per-head absmax
    — over REAL positions only when ``n_valid`` (the true prompt
    length, a traced scalar) is given, so padded-tail garbage can't
    inflate a page's scale — and scatter the minted scales beside the
    quantized pages.
    """
    L, one, H, B, hd = ks.shape
    pb = B // page_size
    # [L, 1, H, B, hd] -> [L, B, H * hd]: position-major, the heads
    # along the row
    rows = lambda c: c[:, 0].transpose(0, 2, 1, 3).reshape(L, B, H * hd)
    if not _is_fp8(kv):
        return commit_rows(kv, {"k": rows(ks), "v": rows(vs)}, page_row,
                           page_size)
    ck, cv = (rows(c).reshape(L, pb, page_size, H * hd) for c in (ks, vs))
    out = dict(kv)

    def one(c):  # -> quantized rows, scales [L, pb, H]
        ch = _by_head(c.astype(jnp.float32), H)   # [L, pb, ps, H, hd]
        a = jnp.abs(ch)
        if n_valid is not None:
            flat = (jnp.arange(pb)[:, None] * page_size
                    + jnp.arange(page_size)[None, :])
            a = jnp.where((flat < n_valid)[None, :, :, None, None], a, 0.0)
        sc = _precision.fp8_scale(jnp.max(a, axis=(2, 4)))
        return _precision.quantize_fp8(
            ch, sc[:, :, None, :, None]).reshape(c.shape), sc

    (qk, ksc), (qv, vsc) = one(ck), one(cv)
    out["k"] = kv["k"].at[:, page_row].set(qk)
    out["v"] = kv["v"].at[:, page_row].set(qv)
    out["k_scale"] = kv["k_scale"].at[:, page_row].set(ksc)
    out["v_scale"] = kv["v_scale"].at[:, page_row].set(vsc)
    return out


def commit_rows(kv, rows, page_row, page_size: int):
    """Lay one prompt's rows into its pages, store by store (the one
    float write of a prefill: a pool of a model's own stores calls it
    with the rows its ``prefill`` returned, :func:`commit_prefill` with
    K and V): ``rows[name]`` is ``[layers, B, width]``, a row a position
    of the padded prompt as the store holds it, laid into whole pages
    ``[layers, B // page_size, page_size, width]`` at ``page_row`` (the
    null page for the padded tail)."""
    out = dict(kv)
    for name, r in rows.items():
        L, B, W = r.shape
        out[name] = kv[name].at[:, page_row].set(
            r.reshape(L, B // page_size, page_size, W)
            .astype(kv[name].dtype))
    return out


def append_rows(kv, name: str, layer: int, page_idx, offset, x):
    """Write one decode position's row ``x [S, width]`` of the store
    ``name``: lane ``s`` lands at ``(layer, page_idx[s], offset[s])``
    (what :func:`append_token` does for K and for V)."""
    out = dict(kv)
    out[name] = _write_rows(kv[name], layer, page_idx, offset, x)
    return out


def _write_rows(pool, layer: int, page_idx, offset, x):
    """``pool[layer, page_idx[i], offset[i]] = x[i]`` for every lane
    ``i`` of ``page_idx`` / ``offset`` (``[S]``, or ``[S, W]`` for a
    verify dispatch; ``x`` is ``[..., H * hd]``, a position's whole
    row), cast to the pool's dtype. THE per-position write into a
    pool: layer, page and offset are all indexed, so the scatter's
    update window is the trailing row alone, one contiguous run, and
    the pool keeps the row-major layout it rests in and the paged
    kernel reads (module docstring, "Layout contract")."""
    return pool.at[layer, page_idx, offset].set(x.astype(pool.dtype))


def _by_head(x, heads: int):
    """A row ``[..., H * hd]`` as its heads ``[..., H, hd]``."""
    return x.reshape(*x.shape[:-1], heads, x.shape[-1] // heads)


def append_token(kv, layer: int, page_idx, offset, k, v):
    """Write one DECODE position's K/V rows ``[S, H * hd]``, one per
    lane: lane ``s`` lands at ``(layer, page_idx[s], offset[s])``.
    Inactive slots' page_idx must already point at the null page.

    fp8 scale rule (frozen-at-page-start): a lane writing ``offset ==
    0`` is the first entry of a fresh page and mints the page's scale
    from its own absmax; every later offset REUSES the stored scale —
    rescaling a partially-filled page would corrupt the entries
    already quantized under the old scale. The absmax of one token
    only estimates the page's range, so later outlier tokens clip at
    ±448 (bounded error) instead of silently breaking earlier ones.
    """
    if not _is_fp8(kv):
        return append_rows(append_rows(kv, "k", layer, page_idx, offset, k),
                           "v", layer, page_idx, offset, v)
    out = dict(kv)
    fresh = (offset == 0)[:, None]

    def one(pool, scales, x):
        xf = _by_head(x.astype(jnp.float32), scales.shape[-1])
        cand = _precision.fp8_scale(jnp.max(jnp.abs(xf), axis=-1))
        sc = jnp.where(fresh, cand, scales[layer, page_idx])  # [S, H]
        q = _precision.quantize_fp8(xf, sc[..., None])   # [S, H, hd]
        return (_write_rows(pool, layer, page_idx, offset,
                            q.reshape(x.shape)),
                scales.at[layer, page_idx].set(sc))

    out["k"], out["k_scale"] = one(kv["k"], kv["k_scale"], k)
    out["v"], out["v_scale"] = one(kv["v"], kv["v_scale"], v)
    return out


def append_spec(kv, layer: int, page_idx, offset, k, v, *,
                chunk=None, real=None, tables=None):
    """Write ``W`` consecutive positions' K/V per slot (``[S, W]``
    index arrays, ``[S, W, H * hd]`` rows), padded/inactive lanes
    pointing at the null page: a VERIFY dispatch's ``W = k_drafts + 1``
    lanes a slot, or (``S = 1``) the suffix a warm-prefix prefill
    computes behind its cached pages. :func:`append_token` for float
    pools.

    Rewind contract (speculative decoding): lanes past the accepted
    prefix wrote K/V that the engine's position rollback
    (:func:`spec_rewind`) makes invisible — attention only admits flat
    position ``<= query pos`` — and the NEXT dispatch overwrites those
    exact (page, offset) cells because positions are consecutive. No
    device-side cleanup ever runs.

    fp8 scale composition with that rollback: ``chunk`` ([S, W], the
    lane's table row, or P for padded lanes), ``real`` ([S, W]) and
    ``tables`` ([S, P]) drive a per-(slot, page) segment-max absmax.
    A page whose offset-0 lane is real in this batch mints a fresh
    scale (exact over every lane it receives here; decode continues it
    frozen); a page entered mid-way (a resume's boundary page, already
    committed by the prefix-cache hit) keeps its stored scale. A scale
    minted partly from later-REJECTED lanes
    merely over-covers the values that replace them (bounded
    quantization error, the same ±448 clip bound as
    :func:`append_token`'s one-token mint) — and when the rollback
    lands back ON the page's offset 0, the overwriting dispatch
    re-mints the scale fresh, so rejected garbage never outlives the
    page's first committed entry. The ``tables`` scatter may carry the
    same page in several slots' rows (prefix-cache sharing); those
    duplicates are value-identical writes — shared pages are read-only
    for every slot (writers own their pages at refcount 1), so their
    scale rows always re-write the stored value."""
    if not _is_fp8(kv):
        return append_token(kv, layer, page_idx, offset, k, v)
    S, W = real.shape
    P = tables.shape[1]
    # per-(slot, page) segments: slot s's table row c -> s*(P+1) + c,
    # padded lanes -> the slot's trash segment s*(P+1) + P
    segf = (jnp.arange(S, dtype=jnp.int32)[:, None] * (P + 1)
            + chunk).reshape(-1)
    started = jax.ops.segment_max(
        jnp.where(real & (offset == 0), 1, 0).reshape(-1), segf,
        num_segments=S * (P + 1)).reshape(S, P + 1)[:, :P] > 0  # [S, P]
    out = dict(kv)

    def one(pool, scales, x):
        H = scales.shape[-1]
        xf = _by_head(x.astype(jnp.float32), H)        # [S, W, H, hd]
        am = jnp.where(real[..., None],
                       jnp.max(jnp.abs(xf), axis=-1), 0.0)  # [S, W, H]
        am_pg = jax.ops.segment_max(
            am.reshape(S * W, H), segf,
            num_segments=S * (P + 1)).reshape(S, P + 1, H)[:, :P]
        cur = scales[layer, tables]                        # [S, P, H]
        sc_pg = jnp.where(started[..., None],
                          _precision.fp8_scale(am_pg), cur)
        sc = jnp.take_along_axis(
            sc_pg, jnp.minimum(chunk, P - 1)[..., None], axis=1)
        sc = jnp.where(real[..., None], sc, 1.0)           # [S, W, H]
        q = _precision.quantize_fp8(xf, sc[..., None])
        return (_write_rows(pool, layer, page_idx, offset,
                            q.reshape(x.shape)),
                scales.at[layer, tables].set(sc_pg))

    out["k"], out["k_scale"] = one(kv["k"], kv["k_scale"], k)
    out["v"], out["v_scale"] = one(kv["v"], kv["v_scale"], v)
    return out


def spec_rewind(pos, n_acc):
    """Post-verify position rollback: the new committed extent after a
    verify dispatch accepted ``n_acc[s]`` tokens (accepted drafts + the
    correction) per slot. Positions ``>= pos + n_acc`` hold the
    REJECTED drafts' K/V — invisible to attention (flat position ``<=
    query pos``) and overwritten in place by the next dispatch, so the
    rollback is this one addition: no page is freed, no cell is
    cleared, CoW/prefix/session sharing is untouched (verify only ever
    writes pages the slot exclusively owns)."""
    return pos + n_acc


def gather_pages(pool, layer: int, tables) -> jnp.ndarray:
    """Each slot's pages in page-major layout ``[S, P, ps, H * hd]``:
    flat position ``p*page_size + o`` of slot ``s`` lives at
    ``[s, p, o]`` (table row order IS position order — what makes
    the position mask a plain ``<= pos``). Kept page-major so the
    attention einsums contract ``(p, o)`` directly instead of paying a
    transpose+reshape copy of the whole cache per layer per step."""
    return pool[layer][tables]


def copy_page(kv, src, dst):
    """Copy-on-write step: duplicate page ``src`` into ``dst`` across
    every layer of the whole KV tree — scale rows travel with their
    page, so a CoW clone of an fp8 page dequantizes identically to its
    source. ``src``/``dst`` are traced scalars so ONE compiled program
    serves every copy. The caller then swaps its page-table entry to
    ``dst`` and drops its reference on ``src`` — readers of ``src``
    never observe the writer's divergence."""
    out = {"k": kv["k"].at[:, dst].set(kv["k"][:, src]),
           "v": kv["v"].at[:, dst].set(kv["v"][:, src])}
    if _is_fp8(kv):
        out["k_scale"] = kv["k_scale"].at[:, dst].set(
            kv["k_scale"][:, src])
        out["v_scale"] = kv["v_scale"].at[:, dst].set(
            kv["v_scale"][:, src])
    return out


def handoff_commit(kv, ks, vs, page_row, page_size: int, n_valid=None):
    """Cross-pool page handoff: scatter K/V computed by ANOTHER
    executable stream (the fleet's disaggregated prefill lane) into
    this pool's pages. The lane runs the prompt forward on its own
    thread and hands over the raw per-layer stacks — immutable jax
    arrays, so the snapshot stays valid however far the lane has moved
    on — and the destination engine commits them between decode bursts
    with this one cheap scatter instead of re-running the bucket-padded
    prefill. Same layout contract as ``commit_prefill`` (real pages for
    owned chunks, null page 0 for the padded tail); the dtype cast —
    or fp8 quantization with freshly minted scales — to the
    destination pool's format happens inside."""
    return commit_prefill(kv, ks, vs, page_row, page_size,
                          n_valid=n_valid)


def pages_needed(total_positions: int, page_size: int) -> int:
    return -(-int(total_positions) // int(page_size))


__all__ = ["PagePool", "commit_prefill", "commit_rows", "append_token",
           "append_rows", "append_spec", "spec_rewind",
           "gather_pages", "copy_page", "handoff_commit",
           "pages_needed"]
