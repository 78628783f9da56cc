"""Pallas paged-attention kernel + fp8 KV cache
(ops/paged_attention_pallas.py, serving/kv_pages.py fp8 path,
nn/precision.py fp8 helpers).

The kernel runs under the Pallas INTERPRETER here (mode="interpret")
so CPU-only CI executes the same kernel body the TPU compiles —
shapes are kept tiny because interpret mode unrolls the grid at trace
time. Golden checks: kernel vs the XLA einsum pair vs a plain numpy
reference, across page counts, mid-page offsets, chunk widths,
null-page masking, and CoW-shared pages; fp8 round-trip error bounds,
frozen-at-page-start scale semantics, and engine-level greedy token
identity (xla vs interpret, including sticky-session resume and
prefix-cache hits) with pools draining to zero.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.nn import precision
from deeplearning4j_tpu.ops import paged_attention_pallas as pa
from deeplearning4j_tpu.ops.paged_attention_pallas import (
    paged_attention, paged_attention_mode,
)
from deeplearning4j_tpu.serving import DecodeEngine, PagePool
from deeplearning4j_tpu.serving import kv_pages


# ------------------------------------------------------- helpers
def _mk_kv(rng, L, n_pages, H, ps, hd, fp8=False):
    """A pool tree of pages ``[ps, H * hd]``: a row is one position,
    head ``h`` on lanes ``[h * hd, (h + 1) * hd)``."""
    k = rng.standard_normal((L, n_pages, ps, H, hd)).astype(np.float32)
    v = rng.standard_normal((L, n_pages, ps, H, hd)).astype(np.float32)
    rows = lambda x: jnp.asarray(x).reshape(L, n_pages, ps, H * hd)
    kv = {"k": rows(k), "v": rows(v)}
    if fp8:
        out = {}
        for name, x in (("k", k), ("v", v)):
            am = jnp.asarray(np.abs(x).max(axis=(2, 4)))
            sc = precision.fp8_scale(am)                 # [L, pages, H]
            out[name] = rows(precision.quantize_fp8(
                jnp.asarray(x), sc[:, :, None, :, None]))
            out[name + "_scale"] = sc
        kv = {"k": out["k"], "v": out["v"],
              "k_scale": out["k_scale"], "v_scale": out["v_scale"]}
    return kv


def _np_ref(q, kp, vp, tables, qbase):
    """Dense float32 reference over one layer's pages ``[n_pages, ps,
    H * hd]``; ``q`` and the result are ``[N, Q, H, hd]``."""
    N, Q, H, hd = q.shape
    out = np.zeros((N, Q, H, hd), np.float32)
    for n in range(N):
        keys = kp[tables[n]].reshape(-1, H, hd)          # [P * ps, H, hd]
        vals = vp[tables[n]].reshape(-1, H, hd)
        for qi in range(Q):
            valid = np.arange(keys.shape[0]) <= qbase[n] + qi
            s = np.einsum("hd,thd->ht", q[n, qi], keys) / np.sqrt(hd)
            s = np.where(valid[None, :], s, -np.inf)
            w = np.exp(s - s.max(-1, keepdims=True))
            w /= w.sum(-1, keepdims=True)
            out[n, qi] = np.einsum("ht,thd->hd", w, vals)
    return out


def _both(q, kv, layer, tables, qbase):
    ker = np.asarray(paged_attention(q, kv, layer, tables, qbase,
                                     mode="interpret"))
    xla = np.asarray(paged_attention(q, kv, layer, tables, qbase,
                                     mode="xla"))
    return ker, xla


# ------------------------------------------------------- kernel golden
class TestKernelGolden:
    @pytest.mark.parametrize("P", [1, 2, 4])
    def test_decode_matches_xla_across_page_counts(self, P):
        rng = np.random.default_rng(P)
        L, H, ps, hd, N = 2, 2, 4, 8, 2
        kv = _mk_kv(rng, L, 1 + N * P, H, ps, hd)
        tables = jnp.asarray(
            1 + np.arange(N * P).reshape(N, P), jnp.int32)
        # mid-page offsets on purpose: qbase not a page multiple
        qbase = jnp.asarray([P * ps - 2, max(ps - 3, 0)], jnp.int32)
        q = jnp.asarray(rng.standard_normal((N, 1, H, hd)), jnp.float32)
        for layer in range(L):
            ker, xla = _both(q, kv, layer, tables, qbase)
            np.testing.assert_allclose(ker, xla, atol=1e-5, rtol=1e-5)

    def test_matches_dense_numpy_reference(self):
        rng = np.random.default_rng(0)
        L, H, ps, hd, N, P = 1, 2, 4, 8, 3, 3
        kv = _mk_kv(rng, L, 12, H, ps, hd)
        tables = jnp.asarray(
            1 + np.arange(N * P).reshape(N, P), jnp.int32)
        qbase = jnp.asarray([1, 5, 10], jnp.int32)   # mid-page spread
        q = jnp.asarray(rng.standard_normal((N, 1, H, hd)), jnp.float32)
        ker, xla = _both(q, kv, 0, tables, qbase)
        ref = _np_ref(np.asarray(q), np.asarray(kv["k"][0]),
                      np.asarray(kv["v"][0]), np.asarray(tables),
                      np.asarray(qbase))
        np.testing.assert_allclose(ker, ref, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(xla, ref, atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("Q", [2, 4, 8])
    def test_prefill_chunk_widths(self, Q):
        """Q > 1 is the prefix-prefill geometry: the same kernel serves
        every chunk width with the causal mask sliding per row."""
        rng = np.random.default_rng(Q)
        L, H, ps, hd, P = 1, 2, 4, 8, 3
        kv = _mk_kv(rng, L, 6, H, ps, hd)
        tables = jnp.asarray([[1, 2, 3]], jnp.int32)
        qbase = jnp.asarray([3], jnp.int32)          # mid-page start
        q = jnp.asarray(rng.standard_normal((1, Q, H, hd)), jnp.float32)
        ker, xla = _both(q, kv, 0, tables, qbase)
        ref = _np_ref(np.asarray(q), np.asarray(kv["k"][0]),
                      np.asarray(kv["v"][0]), np.asarray(tables),
                      np.asarray(qbase))
        np.testing.assert_allclose(ker, xla, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(ker, ref, atol=1e-4, rtol=1e-4)

    def test_null_page_and_tail_garbage_masked(self):
        """Unallocated table rows point at null page 0 and positions
        beyond qpos may hold arbitrary garbage — neither may leak into
        the output of either implementation."""
        rng = np.random.default_rng(3)
        L, H, ps, hd, N, P = 1, 2, 4, 8, 2, 3
        kv = _mk_kv(rng, L, 8, H, ps, hd)
        # slot 0 owns one real page (positions 0..3), rows 1..2 -> null
        # page; slot 1 owns two pages, mid-page at position 5
        tables = jnp.asarray([[1, 0, 0], [2, 3, 0]], jnp.int32)
        qbase = jnp.asarray([2, 5], jnp.int32)
        q = jnp.asarray(rng.standard_normal((N, 1, H, hd)), jnp.float32)
        clean_k, clean_v = np.asarray(kv["k"]), np.asarray(kv["v"])

        dirty_k, dirty_v = clean_k.copy(), clean_v.copy()
        dirty_k[:, 0], dirty_v[:, 0] = 1e4, -1e4     # null page garbage
        dirty_k[:, 1, 3:], dirty_v[:, 1, 3:] = 1e4, -1e4    # > qpos
        dirty_k[:, 3, 2:], dirty_v[:, 3, 2:] = -1e4, 1e4    # > qpos
        dirty = {"k": jnp.asarray(dirty_k), "v": jnp.asarray(dirty_v)}

        ker_c, xla_c = _both(q, kv, 0, tables, qbase)
        ker_d, xla_d = _both(q, dirty, 0, tables, qbase)
        np.testing.assert_allclose(ker_d, ker_c, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(xla_d, xla_c, atol=1e-5, rtol=1e-5)

    def test_cow_shared_pages(self):
        """Two slots mapping the SAME physical page (a prefix-cache
        hit before divergence) read identically to two private copies
        of it."""
        rng = np.random.default_rng(4)
        L, H, ps, hd = 1, 2, 4, 8
        kv = _mk_kv(rng, L, 8, H, ps, hd)
        # page 1 shared; pages 2/3 private seconds; page 4 = copy of 1
        shared = jnp.asarray([[1, 2], [1, 3]], jnp.int32)
        kc = np.asarray(kv["k"]).copy()
        vc = np.asarray(kv["v"]).copy()
        kc[:, 4], vc[:, 4] = kc[:, 1], vc[:, 1]
        private = jnp.asarray([[1, 2], [4, 3]], jnp.int32)
        kv2 = {"k": jnp.asarray(kc), "v": jnp.asarray(vc)}
        qbase = jnp.asarray([6, 7], jnp.int32)
        q = jnp.asarray(rng.standard_normal((2, 1, H, hd)), jnp.float32)
        ker_s, xla_s = _both(q, kv, 0, shared, qbase)
        ker_p, xla_p = _both(q, kv2, 0, private, qbase)
        np.testing.assert_allclose(ker_s, ker_p, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(xla_s, xla_p, atol=1e-5, rtol=1e-5)

    def test_bad_mode_raises(self):
        rng = np.random.default_rng(5)
        kv = _mk_kv(rng, 1, 3, 2, 4, 8)
        q = jnp.zeros((1, 1, 2, 8), jnp.float32)
        with pytest.raises(ValueError, match="paged-attention mode"):
            paged_attention(q, kv, 0, jnp.asarray([[1]], jnp.int32),
                            jnp.asarray([0], jnp.int32), mode="cuda")

    def test_env_mode_resolution(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_PAGED_ATTN", "interpret")
        assert paged_attention_mode() == "interpret"
        monkeypatch.delenv("DL4J_TPU_PAGED_ATTN")
        # auto: pallas only when a TPU backend is live
        expect = ("pallas" if jax.default_backend() == "tpu"
                  else "xla")
        assert paged_attention_mode() == expect


# ------------------------------------------------------- the live walk
def _np_ref_grouped(q, kp, vp, tables, qbase, group):
    """``_np_ref`` with ``group`` query heads a KV head."""
    H = q.shape[2] // group
    wide = lambda x: np.repeat(
        x.reshape(*x.shape[:2], H, -1), group, axis=2) \
        .reshape(*x.shape[:2], -1)
    return _np_ref(q, wide(kp), wide(vp), tables, qbase)


def _walk_case(qbase, P, *, ps, hd, Hkv, Q=1, G=1, fp8=False,
               evicted=(), seed=0):
    """The kernel (interpreted) against ``_xla_paged_attention`` and
    the dense reference on pools of pages ``[ps, Hkv * hd]``. Every
    table slot past a lane's last live page (the page of its last
    query) points at a page filled with NaN: a finite output that
    equals the references proves that nothing past that page was
    read."""
    rng = np.random.default_rng(seed)
    N = len(qbase)
    nan_page = 1 + N * P
    kv = _mk_kv(rng, 2, nan_page + 1, Hkv, ps, hd, fp8=fp8)
    kv = {n: a.at[:, nan_page].set(jnp.nan) for n, a in kv.items()}
    assert all(bool(jnp.isnan(a[:, nan_page].astype(jnp.float32))
                    .all()) for a in kv.values())
    qbase = np.asarray(qbase, np.int32)
    last = np.minimum((qbase + Q - 1) // ps, P - 1)
    live = np.arange(P)[None, :] <= last[:, None]
    own = 1 + rng.permutation(N * P).reshape(N, P)
    own[list(evicted)] = 0            # an evicted lane: null table
    tables = np.where(live, own, nan_page).astype(np.int32)
    clean = np.where(live, own, 0).astype(np.int32)
    q = jnp.asarray(rng.standard_normal((N, Q, Hkv * G, hd)),
                    jnp.float32)
    ker = np.asarray(paged_attention(
        q, kv, 1, jnp.asarray(tables), jnp.asarray(qbase),
        mode="interpret"))
    xla = np.asarray(paged_attention(
        q, kv, 1, jnp.asarray(clean), jnp.asarray(qbase), mode="xla"))
    assert np.isfinite(ker).all()
    np.testing.assert_allclose(ker, xla, atol=2e-5, rtol=2e-5)
    if fp8:
        return
    # rows past the table's end (a padded suffix) see what the
    # table holds: the dense reference is for rows inside it
    ref = _np_ref_grouped(np.asarray(q), np.asarray(kv["k"][1]),
                          np.asarray(kv["v"][1]), clean, qbase, G)
    inside = (qbase[:, None] + np.arange(Q)[None, :]) < P * ps
    np.testing.assert_allclose(ker[inside], ref[inside],
                               atol=1e-4, rtol=1e-4)


class TestLiveWalk:
    """What a walk over live pages only, ``B`` table slots and every KV
    head a visit, can get wrong (``_walk_case``: every table slot past
    a lane's last live page names a page of NaN)."""

    PS, HD, HKV = 4, 8, 2
    B = pa._PAGES_A_VISIT                 # table slots a visit
    VISIT = B * PS                        # positions a visit

    def _case(self, qbase, P, **kw):
        _walk_case(qbase, P, ps=self.PS, hd=self.HD, Hkv=self.HKV, **kw)

    @pytest.mark.parametrize("fp8", [False, True], ids=["f32", "fp8"])
    def test_one_position_beside_a_full_table(self, fp8):
        P = 3 * self.B
        self._case([0, P * self.PS - 1, 5, self.VISIT + 1], P, fp8=fp8)

    @pytest.mark.parametrize("edge", [
        "page-1", "page", "page+1", "visit-1", "visit", "visit+1",
        "2visit-1", "2visit", "table-1"])
    @pytest.mark.parametrize("Q", [1, 4], ids=["decode", "verify"])
    def test_qbase_at_every_edge(self, edge, Q):
        """The last query on the last position of a page or a visit,
        on the first of the next, and one further."""
        P = 2 * self.B + 3
        at = {"page": self.PS, "visit": self.VISIT,
              "2visit": 2 * self.VISIT, "table": P * self.PS}
        name, _, d = edge.partition("-" if "-" in edge else "+")
        pos = at[name] + {"": 0, "1": -1 if "-" in edge else 1}[d]
        # ``pos`` is where the LAST query sits
        self._case([pos - (Q - 1), 2], P, Q=Q, seed=pos)

    @pytest.mark.parametrize("where", [0, 1, 2])
    def test_an_evicted_lane_beside_live_ones(self, where):
        """``engine._evict``: position 0 and an all-null table."""
        qbase = [self.VISIT + 3, 2 * self.VISIT, 9]
        qbase[where] = 0
        self._case(qbase, 2 * self.B + 1, evicted=[where], seed=where)

    @pytest.mark.parametrize("P", [1, 3, pa._PAGES_A_VISIT + 1,
                                   2 * pa._PAGES_A_VISIT + 5])
    def test_table_width_not_a_multiple_of_a_visit(self, P):
        top = P * self.PS - 1
        self._case([top, top // 2, 0], P, seed=P)

    @pytest.mark.parametrize("fp8", [False, True], ids=["f32", "fp8"])
    @pytest.mark.parametrize("Q", [1, 4, 8],
                             ids=["decode", "verify", "suffix"])
    @pytest.mark.parametrize("G", [1, 4])
    def test_groups_and_query_widths(self, G, Q, fp8):
        """1 to 32 rows a KV head: both the few-rows and the many-rows
        form of the visit, queries straddling a visit's edge, and (the
        last lane) a padded suffix running past the table's end."""
        P = 2 * self.B + 2
        self._case([self.VISIT - 2, 3, P * self.PS - 3], P, Q=Q, G=G,
                   fp8=fp8, seed=10 * G + Q)

    def test_the_schedule_covers_the_pages_held_and_nothing_else(self):
        ps, B, P = self.PS, self.B, 2 * self.B + 3
        qbase = np.asarray([0, ps, B * ps - 1, B * ps, P * ps - 1,
                            P * ps + 40], np.int32)
        tables = np.arange(qbase.size * P, dtype=np.int32) \
            .reshape(qbase.size, P) + 1
        for Q in (1, 5):
            lane, visit, pages, last, total = (np.asarray(a) for a in (
                pa._live_visits(jnp.asarray(tables), jnp.asarray(qbase),
                                Q, ps, B)))
            want_last = np.minimum((qbase + Q - 1) // ps, P - 1)
            np.testing.assert_array_equal(last, want_last)
            steps = [(n, j) for n in range(qbase.size)
                     for j in range(want_last[n] // B + 1)]
            assert total == len(steps) <= qbase.size * -(-P // B)
            assert list(zip(lane[:total], visit[:total])) == steps
            for g, (n, j) in enumerate(steps):
                slots = np.minimum(j * B + np.arange(B), want_last[n])
                np.testing.assert_array_equal(
                    pages[g * B:(g + 1) * B], tables[n, slots])
            # steps past the total (never run) still name pages held
            assert set(pages[total * B:]) <= set(
                tables[-1, :want_last[-1] + 1])

    @pytest.mark.parametrize("Hkv,rows,fp8,whole", [
        (20, 1, False, True),             # gpt2-large decode
        (8, 4, False, True),              # lfm2-24b-a2b decode
        (20, 4, True, True),              # a verify call, fp8 pools
        (20, 256, False, False),          # a suffix-prefill bucket
        (20, 1024, False, False),
        (64, 64, False, False)])
    def test_heads_a_visit_fit_the_budget(self, Hkv, rows, fp8, whole):
        """Whole lane tiles of heads (two heads of 64), every head
        where the visit fits."""
        h = pa._heads_a_visit(Hkv, rows, 64, 16, 8, 1 if fp8 else 2, 2,
                              fp8)
        assert Hkv % h == 0 and h % 2 == 0 and (h == Hkv) == whole


# --------------------------------------------- heads along the lanes
class TestPageRows:
    """A page is ``[ps, Hkv * hd]`` (PR 31): the kernel takes the heads
    of a row by lane, a lane tile at a time. The kernel (interpreted)
    against ``_xla_paged_attention`` at the shapes that decide how: two
    heads of 64 a 128-lane tile as both serving cells have them, a
    head that is a whole tile, a row that is neither (three heads of
    64) and a toy row narrower than a tile; one query head a KV head
    and four; one query a lane, a verify call's few, a suffix
    prefill's bucket; fp8 pools with their scale planes. The tables
    are wider than a visit, so every case walks several visits and
    ends on one whose last slots are clamped to the last live page
    (``_walk_case`` fills what lies past it with NaN)."""

    PS = 16
    B = pa._PAGES_A_VISIT

    #: name -> KV heads, head width, query heads a KV head, queries
    CASES = {
        "g1-decode": (4, 64, 1, 1),
        "g4-decode": (2, 64, 4, 1),
        "g1-verify": (4, 64, 1, 4),
        "g4-verify": (2, 64, 4, 3),
        "g1-suffix-bucket": (4, 64, 1, 16),
        "g4-suffix-bucket": (2, 64, 4, 8),
        "head-a-tile": (2, 128, 1, 1),
        "row-of-three-heads": (3, 64, 1, 2),
        "narrow-row": (2, 8, 1, 1),
        "narrow-row-g4-verify": (2, 8, 4, 4),
    }

    def _case(self, name, fp8=False):
        Hkv, hd, G, Q = self.CASES[name]
        P = self.B + 3                    # 2 visits, the last of 3 pages
        top = P * self.PS - Q
        lanes = [top, self.B * self.PS + 1, 5] if Q < 8 else [top - 7]
        _walk_case(lanes, P, ps=self.PS, hd=hd, Hkv=Hkv, Q=Q, G=G,
                   fp8=fp8, seed=len(name))

    @pytest.mark.parametrize("name", list(CASES))
    def test_kernel_matches_xla(self, name):
        self._case(name)

    @pytest.mark.parametrize("name", ["g1-decode", "g4-decode",
                                      "g1-verify", "g1-suffix-bucket",
                                      "narrow-row"])
    def test_kernel_matches_xla_on_fp8_pools(self, name):
        self._case(name, fp8=True)

    def test_a_clamped_last_visit_reads_nothing_past_the_last_page(self):
        """One lane whose last visit holds a single live page of
        ``B``: the other ``B - 1`` slots name that page again."""
        P = 2 * self.B
        _walk_case([self.B * self.PS + 2], P, ps=self.PS, hd=64, Hkv=4)

    @pytest.mark.parametrize("W,hd,tile", [
        (1280, 64, 128), (512, 64, 128), (256, 128, 128),
        (512, 256, 256), (192, 64, 192), (16, 8, 16), (128, 32, 128)])
    def test_lane_tile(self, W, hd, tile):
        assert pa._lane_tile(W, hd) == tile


# ------------------------------------------------------- fp8 numerics
class TestFp8:
    def test_roundtrip_error_bound(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((4, 16)) * 3, jnp.float32)
        am = jnp.max(jnp.abs(x), axis=-1)
        sc = precision.fp8_scale(am)
        deq = precision.dequantize_fp8(
            precision.quantize_fp8(x, sc[:, None]), sc[:, None],
            jnp.float32)
        # e4m3: 3 mantissa bits -> relative half-step 2**-4 of the
        # value, i.e. <= amax/16 absolute after scaling to +-448
        err = np.abs(np.asarray(deq) - np.asarray(x))
        bound = np.asarray(am)[:, None] / 16 + 1e-6
        assert (err <= bound).all()

    def test_scale_floor_handles_zero_pages(self):
        z = jnp.zeros((2, 8), jnp.float32)
        sc = precision.fp8_scale(jnp.max(jnp.abs(z), axis=-1))
        assert (np.asarray(sc) > 0).all()
        deq = precision.dequantize_fp8(
            precision.quantize_fp8(z, sc[:, None]), sc[:, None],
            jnp.float32)
        assert (np.asarray(deq) == 0).all()

    def test_kernel_matches_xla_on_fp8(self):
        rng = np.random.default_rng(1)
        L, H, ps, hd, N, P = 2, 2, 4, 8, 2, 2
        kv8 = _mk_kv(rng, L, 6, H, ps, hd, fp8=True)
        tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        qbase = jnp.asarray([5, 3], jnp.int32)
        q = jnp.asarray(rng.standard_normal((N, 1, H, hd)), jnp.float32)
        for layer in range(L):
            ker, xla = _both(q, kv8, layer, tables, qbase)
            np.testing.assert_allclose(ker, xla, atol=1e-5, rtol=1e-5)

    def test_fp8_close_to_float_within_quantization(self):
        rng = np.random.default_rng(2)
        L, H, ps, hd = 1, 2, 4, 8
        kvf = _mk_kv(rng, L, 6, H, ps, hd)
        kv8 = {"k": kvf["k"], "v": kvf["v"]}
        kv8 = _mk_kv(np.random.default_rng(2), L, 6, H, ps, hd,
                     fp8=True)
        tables = jnp.asarray([[1, 2]], jnp.int32)
        qbase = jnp.asarray([6], jnp.int32)
        q = jnp.asarray(rng.standard_normal((1, 1, H, hd)), jnp.float32)
        ref = np.asarray(paged_attention(q, kvf, 0, tables, qbase,
                                         mode="xla"))
        got = np.asarray(paged_attention(q, kv8, 0, tables, qbase,
                                         mode="interpret"))
        np.testing.assert_allclose(got, ref, atol=0.15)


# ------------------------------------------------- fp8 page semantics
class TestFp8Pages:
    def _pool_kv(self, L=1, H=2, ps=4, hd=8, n_pages=6):
        pool = PagePool(L, H, ps, hd, n_pages=n_pages,
                        dtype=jnp.float32, kv_dtype="fp8_e4m3")
        return pool, pool.tree()

    def test_commit_prefill_n_valid_masks_padded_tail(self):
        """Garbage past the true prompt length must not inflate a
        page's scale: scales with a huge padded tail equal scales with
        a zero tail."""
        _, kv = self._pool_kv()
        L, H, ps, hd, B = 1, 2, 4, 8, 8
        rng = np.random.default_rng(0)
        base = rng.standard_normal((L, 1, H, B, hd)).astype(np.float32)
        dirty = base.copy()
        dirty[:, :, :, 5:, :] = 1e3                    # padded tail
        clean = base.copy()
        clean[:, :, :, 5:, :] = 0.0
        row = jnp.asarray([1, 2], jnp.int32)
        out_d = kv_pages.commit_prefill(
            kv, jnp.asarray(dirty), jnp.asarray(dirty), row, ps,
            n_valid=jnp.asarray(5, jnp.int32))
        out_c = kv_pages.commit_prefill(
            kv, jnp.asarray(clean), jnp.asarray(clean), row, ps,
            n_valid=jnp.asarray(5, jnp.int32))
        np.testing.assert_array_equal(
            np.asarray(out_d["k_scale"]), np.asarray(out_c["k_scale"]))
        # valid positions round-trip within the e4m3 bound
        deq = precision.dequantize_fp8(
            out_d["k"][0, row[0]].reshape(ps, H, hd),
            out_d["k_scale"][0, row[0]][None, :, None], jnp.float32)
        ref = base[0, 0, :, :ps, :].transpose(1, 0, 2)   # [ps, H, hd]
        np.testing.assert_allclose(np.asarray(deq), ref, atol=0.26)

    def test_append_token_scale_frozen_after_page_start(self):
        """offset==0 mints the page scale; later offsets reuse it even
        for outlier tokens (which clip instead of re-scaling earlier
        entries under their feet)."""
        _, kv = self._pool_kv()
        page = jnp.asarray([1], jnp.int32)
        k0 = jnp.full((1, 2 * 8), 2.0, jnp.float32)     # a row: H * hd
        kv = kv_pages.append_token(kv, 0, page,
                                   jnp.asarray([0], jnp.int32), k0, k0)
        minted = np.asarray(kv["k_scale"][0, 1]).copy()
        k1 = jnp.full((1, 2 * 8), 400.0, jnp.float32)  # outlier
        kv = kv_pages.append_token(kv, 0, page,
                                   jnp.asarray([1], jnp.int32), k1, k1)
        np.testing.assert_array_equal(
            np.asarray(kv["k_scale"][0, 1]), minted)
        # the offset-0 entry still dequantizes to its original value
        deq = precision.dequantize_fp8(
            kv["k"][0, 1, 0].reshape(2, 8), kv["k_scale"][0, 1][:, None],
            jnp.float32)
        np.testing.assert_allclose(np.asarray(deq), 2.0, atol=0.2)

    def test_append_suffix_scale_semantics(self):
        """``append_spec`` with one slot, as a suffix prefill behind
        cached pages writes: a page whose offset-0 lane is in the
        suffix batch mints a fresh scale from the EXACT amax over every lane it receives; a
        page entered mid-way (the resume boundary) keeps its stored
        scale; untouched pages and padded lanes change nothing."""
        _, kv = self._pool_kv()
        ps, H, hd, P = 4, 2, 8, 3
        rng = np.random.default_rng(1)
        table = jnp.asarray([1, 2, 3], jnp.int32)
        # pre-commit page 2 positions 4..5 (the resumed boundary page)
        pre = jnp.full((1, H * hd), 2.0, jnp.float32)
        for off in (0, 1):
            kv = kv_pages.append_token(
                kv, 0, jnp.asarray([2], jnp.int32),
                jnp.asarray([off], jnp.int32), pre, pre)
        boundary_scale = np.asarray(kv["k_scale"][0, 2]).copy()
        # suffix covers positions 6..9: page 2 mid-way, page 3 fresh
        pos = np.arange(6, 10)
        B = 8
        ks = rng.standard_normal((B, H, hd)).astype(np.float32) * 5
        real = np.arange(B) < pos.size
        padded_pos = np.concatenate([pos, np.zeros(B - pos.size, int)])
        chunk = np.where(real, padded_pos // ps, P)
        page = np.where(real, np.asarray(table)[
            np.minimum(padded_pos // ps, P - 1)], 0)
        off = np.where(real, padded_pos % ps, 0)
        rows = jnp.asarray(ks).reshape(1, B, H * hd)
        out = kv_pages.append_spec(
            kv, 0, jnp.asarray(page, jnp.int32)[None],
            jnp.asarray(off, jnp.int32)[None], rows, rows,
            chunk=jnp.asarray(chunk, jnp.int32)[None],
            real=jnp.asarray(real)[None], tables=table[None])
        # boundary page keeps its frozen scale; fresh page 3 mints the
        # exact amax over its two lanes (positions 8, 9)
        np.testing.assert_array_equal(
            np.asarray(out["k_scale"][0, 2]), boundary_scale)
        want = precision.fp8_scale(jnp.max(jnp.abs(
            jnp.asarray(ks[2:4])), axis=(0, 2)))
        np.testing.assert_allclose(
            np.asarray(out["k_scale"][0, 3]), np.asarray(want),
            atol=1e-6)
        # untouched page 1 still at the init scale of 1
        np.testing.assert_array_equal(
            np.asarray(out["k_scale"][0, 1]), 1.0)
        # page-3 lanes round-trip within the e4m3 bound of their amax
        deq = precision.dequantize_fp8(
            out["k"][0, 3, 0:2].reshape(2, H, hd),
            out["k_scale"][0, 3][None, :, None], jnp.float32)
        ref = np.asarray(ks[2:4])                        # [2, H, hd]
        bound = np.asarray(want)[None, :, None] * 448 / 16 + 1e-6
        assert (np.abs(np.asarray(deq) - ref) <= bound).all()

    def test_copy_page_carries_scales(self):
        _, kv = self._pool_kv()
        page = jnp.asarray([1], jnp.int32)
        k0 = jnp.full((1, 2 * 8), 3.0, jnp.float32)
        kv = kv_pages.append_token(kv, 0, page,
                                   jnp.asarray([0], jnp.int32), k0, k0)
        out = kv_pages.copy_page(kv, jnp.asarray(1), jnp.asarray(4))
        np.testing.assert_array_equal(
            np.asarray(out["k_scale"][:, 4]),
            np.asarray(kv["k_scale"][:, 1]))
        np.testing.assert_array_equal(
            np.asarray(out["k"][:, 4]).view(np.uint8),
            np.asarray(kv["k"][:, 1]).view(np.uint8))

    def test_pool_bytes_capacity_and_gauge(self):
        from deeplearning4j_tpu.profiler import telemetry

        bf16 = PagePool(2, 4, 8, 16, n_pages=4, dtype=jnp.bfloat16,
                        engine_id="t_bf16")
        fp8 = PagePool(2, 4, 8, 16, n_pages=4, dtype=jnp.bfloat16,
                       kv_dtype="fp8_e4m3", engine_id="t_fp8")
        ratio = bf16.bytes_per_page() / fp8.bytes_per_page()
        assert ratio >= 1.8                     # the capacity claim
        assert fp8.dtype_label == "fp8_e4m3"
        assert bf16.dtype_label == "bfloat16"
        reg = telemetry.MetricsRegistry.get_default()
        g = reg.gauge(telemetry.SERVING_KV_PAGE_BYTES)
        assert g.value(engine="t_fp8", kv_dtype="fp8_e4m3") \
            == fp8.bytes_per_page()
        assert g.value(engine="t_bf16", kv_dtype="bfloat16") \
            == bf16.bytes_per_page()

    def test_bad_kv_dtype_raises(self):
        with pytest.raises(ValueError, match="kv_dtype"):
            PagePool(1, 2, 4, 4, n_pages=3, kv_dtype="int4")


# ------------------------------------------- per-position write sites
class TestPositionWrites:
    """kv_pages._write_rows behind its callers: the same values in the
    same cells as a plain loop, whatever spells the scatter."""

    L, NP, H, PS, HD, P = 3, 20, 3, 4, 8, 3
    LAYER = 1

    def _lanes(self, rng, writer):
        """Random DISTINCT live (page, offset) lanes plus lanes parked
        on the null page 0, in the index shapes ``writer`` takes, with
        the fp8 segment arguments consistent with them. Every writer
        is ``rows`` page tables of ``lanes`` positions each: a decode
        step is one position a slot, a suffix prefill ``append_spec``
        over one slot's positions."""
        P, ps = self.P, self.PS
        rows, lanes = {"append_token": (6, 1), "append_spec": (4, 3),
                       "append_spec_one_slot": (1, 6)}[writer]
        tables = rng.permutation(np.arange(1, self.NP))[:rows * P] \
            .reshape(rows, P)
        cells = np.stack([rng.permutation(P * ps)[:lanes]
                          for _ in range(rows)])        # distinct a row
        chunk, off = cells // ps, cells % ps
        real = rng.random((rows, lanes)) < 0.6
        real.flat[:2], real.flat[-2:] = True, False
        page = np.where(real, np.take_along_axis(tables, chunk, 1), 0)
        seg = np.where(real, chunk, P)
        idx = lambda a: jnp.asarray(a, jnp.int32)
        if writer == "append_token":
            return idx(page[:, 0]), idx(off[:, 0]), real[:, 0], {}
        return (idx(page), idx(off), real,
                dict(chunk=idx(seg), real=jnp.asarray(real),
                     tables=idx(tables)))

    @pytest.mark.parametrize("fp8", [False, True], ids=["bf16", "fp8"])
    @pytest.mark.parametrize(
        "writer", ["append_token", "append_spec_one_slot", "append_spec"])
    def test_matches_numpy_loop(self, writer, fp8):
        rng = np.random.default_rng(7)
        kv = _mk_kv(rng, self.L, self.NP, self.H, self.PS, self.HD,
                    fp8=fp8)
        if not fp8:
            kv = {n: a.astype(jnp.bfloat16) for n, a in kv.items()}
        page, off, real, extra = self._lanes(rng, writer)
        x = {n: rng.standard_normal(page.shape + (self.H * self.HD,))
             .astype(np.float32) * 3 for n in ("k", "v")}
        out = getattr(kv_pages, writer.removesuffix("_one_slot"))(
            kv, self.LAYER, page, off, jnp.asarray(x["k"]),
            jnp.asarray(x["v"]), **extra)
        for n in ("k", "v"):
            want = np.asarray(kv[n].astype(jnp.float32)).copy()
            for lane in np.ndindex(page.shape):
                pg, o = int(page[lane]), int(off[lane])
                xs = jnp.asarray(x[n][lane])
                if fp8:   # under the scale the page ends up with
                    sc = out[n + "_scale"][self.LAYER, pg]
                    xs = precision.quantize_fp8(
                        xs.reshape(self.H, self.HD), sc[:, None]) \
                        .reshape(-1)
                want[self.LAYER, pg, o] = np.asarray(
                    xs.astype(kv[n].dtype).astype(jnp.float32))
            got = np.asarray(out[n].astype(jnp.float32))
            assert out[n].dtype == kv[n].dtype
            # page 0 takes the parked lanes' writes: unordered, unread
            np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
            assert not np.array_equal(got[:, 1:], np.asarray(
                kv[n].astype(jnp.float32))[:, 1:])


# ------------------------------------------------- engine token identity
VOCAB = 13


@pytest.fixture(scope="module")
def model():
    cfg = tiny_config(vocab=VOCAB, max_len=48, d_model=32, n_layers=2,
                      n_heads=4, d_ff=64)
    cfg.dropout = 0.0
    return CausalLM(cfg, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params(model):
    return model.init_params(jax.random.key(1))


def _engine(model, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_context", 32)   # interpret unrolls the grid:
    kw.setdefault("max_chunk", 4)      # keep slots*H*pages tiny
    kw.setdefault("prefill_buckets", [8, 16])
    return DecodeEngine(model, params, **kw)


def _serve(eng, jobs):
    """jobs: list of (prompt, new, session_id|None) -> token arrays."""
    try:
        outs = []
        for p, n, sid in jobs:
            r = eng.submit(p, n, session_id=sid)
            outs.append(np.asarray(r.result(timeout=300)))
        drained = eng.pool.allocated if eng._sessions is None else None
        stats = eng.stats()
    finally:
        eng.shutdown()
    return outs, drained, stats


class TestEngineTokenIdentity:
    def _jobs(self, seed=0):
        rng = np.random.default_rng(seed)
        mk = lambda n: rng.integers(0, VOCAB, (n,)).astype(np.int32)
        shared = mk(10)
        return [
            (mk(6), 6, None),
            (np.concatenate([shared, mk(3)]), 5, None),
            (mk(9), 6, "conv"),                 # session open
            (np.concatenate([shared, mk(2)]), 5, None),  # prefix hit
            (mk(4), 4, "conv"),                 # session RESUME
            (mk(11), 6, None),
        ]

    def test_interpret_token_identical_to_xla(self, model, params):
        """The CI-facing identity claim: same greedy tokens from the
        kernel engine and the einsum engine, across prefix-cache hits
        and a sticky-session resume, with zero warm-pool misses."""
        jobs = self._jobs()
        a, _, sa = _serve(_engine(model, params, prefix_cache=True,
                                  session_capacity=2,
                                  attn_mode="xla"), jobs)
        b, _, sb = _serve(_engine(model, params, prefix_cache=True,
                                  session_capacity=2,
                                  attn_mode="interpret"), jobs)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
        assert sa["warm_pool"]["misses"] == 0
        assert sb["warm_pool"]["misses"] == 0
        assert sb["attn_mode"] == "interpret"

    def test_fp8_agreement_and_drain(self, model, params):
        """fp8 is agreement-gated, not identity-gated; pools (and with
        them the scale planes) must drain to zero when no sessions pin
        pages."""
        jobs = [(p, n, None) for p, n, _ in self._jobs(1)]
        ref, d0, _ = _serve(_engine(model, params, attn_mode="xla"),
                            jobs)
        f8, d1, st = _serve(_engine(model, params,
                                    attn_mode="interpret",
                                    kv_dtype="fp8_e4m3"), jobs)
        agree = np.mean([np.array_equal(x, y)
                         for x, y in zip(ref, f8)])
        assert agree >= 0.75
        assert d0 == 0 and d1 == 0
        assert st["kv_dtype"] == "fp8_e4m3"
        assert st["kv_pages"]["page_bytes"] < 2048  # < bf16 full page

    def test_bad_engine_args_raise(self, model, params):
        with pytest.raises(ValueError, match="attn_mode"):
            DecodeEngine(model, params, slots=2, page_size=8,
                         max_context=16, attn_mode="rocm")
        with pytest.raises(ValueError, match="kv_dtype"):
            DecodeEngine(model, params, slots=2, page_size=8,
                         max_context=16, kv_dtype="fp4")
