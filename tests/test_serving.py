"""Continuous-batching decode engine (serving/engine.py, kv_pages.py):
greedy token-parity against solo generate() with requests joining and
leaving mid-flight, AOT warm-pool zero-trace contract, int8 weight-only
decode tolerance, paged-KV allocator, HTTP front-end, telemetry."""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.nn.precision import (
    dequantize_int8, int8_matmul, quantize_int8, quantized_bytes,
)
from deeplearning4j_tpu.profiler import telemetry
from deeplearning4j_tpu.serving import DecodeEngine, PagePool
from deeplearning4j_tpu.serving.kv_pages import pages_needed


VOCAB = 13


def _model():
    cfg = tiny_config(vocab=VOCAB, max_len=48, d_model=32, n_layers=2,
                      n_heads=4, d_ff=64)
    cfg.dropout = 0.0
    return CausalLM(cfg, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def params(model):
    return model.init_params(jax.random.key(1))


def _solo(model, params, prompt, new):
    return np.asarray(model.generate(
        params, jnp.asarray(np.asarray(prompt)[None, :], jnp.int32),
        new))[0]


# ------------------------------------------------------------ kv pages
class TestPagePool:
    def test_alloc_free_roundtrip_and_utilization(self):
        pool = PagePool(2, 4, 8, 8, n_pages=9, dtype=jnp.float32)
        assert pool.capacity == 8
        a = pool.alloc(3)
        b = pool.alloc(5)
        assert sorted(a + b) == list(range(1, 9))  # null page 0 never
        assert pool.alloc(1) is None               # exhausted -> None
        assert pool.utilization() == 1.0
        pool.free(a)
        assert pool.allocated == 5
        assert pool.high_water == 8
        c = pool.alloc(3)
        assert sorted(c) == sorted(a)

    def test_double_free_and_null_page_guarded(self):
        pool = PagePool(1, 2, 4, 4, n_pages=4, dtype=jnp.float32)
        pages = pool.alloc(2)
        pool.free(pages)
        with pytest.raises(ValueError, match="double free"):
            pool.free([pages[0]])
        with pytest.raises(ValueError, match="null page"):
            pool.free([0])

    def test_pages_needed(self):
        assert pages_needed(1, 8) == 1
        assert pages_needed(8, 8) == 1
        assert pages_needed(9, 8) == 2
        assert pages_needed(48, 8) == 6


# ----------------------------------------------------- greedy parity
class TestEngineGreedyParity:
    def test_mixed_length_concurrent_requests_match_solo(self, model,
                                                         params):
        """The acceptance contract: every request decoded through the
        engine — joining/leaving mid-flight next to other requests —
        is token-identical to a solo generate() call."""
        rng = np.random.default_rng(0)
        specs = [(5, 6), (9, 3), (3, 12), (12, 1), (7, 9), (4, 4),
                 (10, 7), (6, 2), (8, 8), (5, 11)]
        prompts = [rng.integers(0, VOCAB, (t0,)).astype(np.int32)
                   for t0, _ in specs]
        with DecodeEngine(model, params, slots=3, page_size=8) as eng:
            with ThreadPoolExecutor(max_workers=8) as ex:
                handles = list(ex.map(
                    lambda pn: eng.submit(pn[0], pn[1]),
                    zip(prompts, [n for _, n in specs])))
            outs = [h.result(timeout=120) for h in handles]
            assert eng.stats()["completed"] == len(specs)
        for p, (_, new), got in zip(prompts, specs, outs):
            np.testing.assert_array_equal(got, _solo(model, params, p,
                                                     new))

    def test_staggered_join_next_to_inflight_requests(self, model,
                                                      params):
        """A request admitted while another is mid-decode must not
        perturb either (slot math is row-independent)."""
        rng = np.random.default_rng(1)
        long_p = rng.integers(0, VOCAB, (4,)).astype(np.int32)
        short_p = rng.integers(0, VOCAB, (6,)).astype(np.int32)
        with DecodeEngine(model, params, slots=2, page_size=8) as eng:
            # an unreachable eos_id forces single-chunk dispatches
            # (completion is unpredictable to the scheduler), so the
            # request is observably mid-flight between bursts
            long_req = eng.submit(long_p, 14, eos_id=VOCAB)
            # wait until the long request is visibly mid-flight
            for _ in range(500):
                if len(long_req.tokens) >= 2:
                    break
                time.sleep(0.01)
            assert not long_req.done
            short_out = eng.submit(short_p, 3).result(timeout=60)
            long_out = long_req.result(timeout=60)
        np.testing.assert_array_equal(
            long_out, _solo(model, params, long_p, 14))
        np.testing.assert_array_equal(
            short_out, _solo(model, params, short_p, 3))

    def test_eos_stops_early_and_matches_solo_prefix(self, model,
                                                     params):
        p = np.asarray([1, 2, 3, 4], np.int32)
        full = _solo(model, params, p, 10)
        eos = int(full[3])     # force a stop after 4 tokens
        with DecodeEngine(model, params, slots=2, page_size=8) as eng:
            req = eng.submit(p, 10, eos_id=eos)
            got = req.result(timeout=60)
            assert req.finish_reason == "eos"
        stop = int(np.flatnonzero(full == eos)[0])
        np.testing.assert_array_equal(got, full[:stop + 1])

    def test_single_token_request(self, model, params):
        p = np.asarray([2, 5, 7], np.int32)
        with DecodeEngine(model, params, slots=2, page_size=8) as eng:
            got = eng.generate(p, 1)
        np.testing.assert_array_equal(got, _solo(model, params, p, 1))

    def test_streaming_yields_the_same_tokens(self, model, params):
        p = np.asarray([3, 1, 4, 1, 5], np.int32)
        with DecodeEngine(model, params, slots=2, page_size=8) as eng:
            req = eng.submit(p, 6)
            streamed = list(req.stream())
        np.testing.assert_array_equal(
            np.asarray(streamed, np.int32), _solo(model, params, p, 6))

    def test_page_pool_smaller_than_traffic_queues_and_completes(
            self, model, params):
        """More concurrent requests than the KV pool can hold at once:
        the surplus queues head-of-line and completes correctly after
        evictions free pages."""
        rng = np.random.default_rng(2)
        specs = [(6, 8), (9, 5), (4, 10), (7, 7), (5, 4), (8, 6)]
        prompts = [rng.integers(0, VOCAB, (t0,)).astype(np.int32)
                   for t0, _ in specs]
        # 2 slots x 2 pages-worth of pool: at most ~2 requests resident
        with DecodeEngine(model, params, slots=2, page_size=8,
                          n_pages=1 + 4) as eng:
            handles = [eng.submit(p, n)
                       for p, (_, n) in zip(prompts, specs)]
            outs = [h.result(timeout=120) for h in handles]
            assert eng.pool.allocated == 0
        for p, (_, new), got in zip(prompts, specs, outs):
            np.testing.assert_array_equal(
                got, _solo(model, params, p, new))


# ------------------------------------- a burst's first chunk sent ahead
class TestRunAhead:
    """Once a burst's tokens are read, the next burst's first chunk goes
    to the device BEFORE the tokens are handed to the callers
    (``DecodeEngine._run_ahead``). What that must not change: any
    request's tokens, whoever joins, ends or is cancelled while the
    chunk is in flight."""

    @staticmethod
    def _watch(eng):
        """Record every burst ``_run_ahead`` begins."""
        begun, inner = [], eng._run_ahead

        def run_ahead(read, on_device):
            b = inner(read, on_device)
            if b is not None:
                begun.append(b)
            return b

        eng._run_ahead = run_ahead
        return begun

    def test_callers_that_wait_for_each_reply_match_solo(self, model,
                                                         params):
        """Closed-loop callers (as many as slots): a request ends, the
        next chunk is already out for the others, the caller's next
        request joins while it is in flight."""
        rng = np.random.default_rng(5)
        plans = [[(int(rng.integers(3, 12)), int(n)) for n in ns]
                 for ns in ([9, 4, 13], [5, 11, 7], [14, 3, 6])]
        prompts = [[rng.integers(0, VOCAB, (t0,)).astype(np.int32)
                    for t0, _ in plan] for plan in plans]
        outs = [[None] * 3 for _ in plans]
        with DecodeEngine(model, params, slots=3, page_size=8) as eng:
            begun = self._watch(eng)

            def caller(c):
                for i, (_, n) in enumerate(plans[c]):
                    outs[c][i] = eng.submit(prompts[c][i], n).result(
                        timeout=120)

            threads = [threading.Thread(target=caller, args=(c,))
                       for c in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
            assert eng.stats()["completed"] == 9
            assert eng.pool.allocated == 0
        # the path under test ran, with and without a request ending
        assert any(b.after_end for b in begun)
        for c, plan in enumerate(plans):
            for i, (_, n) in enumerate(plan):
                np.testing.assert_array_equal(
                    outs[c][i], _solo(model, params, prompts[c][i], n))

    def test_cancelled_with_a_chunk_in_flight_and_its_slot_reused(
            self, model, params):
        """A request cancelled while the chunk sent ahead still decodes
        for it: its tokens of that chunk are dropped, the request that
        takes its slot at the same pass keeps the state its admission
        gave the slot, the neighbour is not disturbed."""
        rng = np.random.default_rng(6)
        p_cancel, p_stay, p_next = (
            rng.integers(0, VOCAB, (t0,)).astype(np.int32)
            for t0 in (5, 7, 6))
        with DecodeEngine(model, params, slots=2, page_size=8,
                          max_chunk=2) as eng:
            inner, state = eng._run_ahead, {}

            def run_ahead(read, on_device):
                b = inner(read, on_device)
                if b is not None and "next" not in state \
                        and len(state["cancel"].tokens) >= 3:
                    # the chunk is out for both slots: cancel one and
                    # queue its successor before the scheduler's next
                    # pass (aborts first, then admissions, then the
                    # burst is taken up)
                    assert state["cancel"].cancel()
                    state["next"] = eng.submit(p_next, 9)
                return b

            eng._run_ahead = run_ahead
            state["cancel"] = eng.submit(p_cancel, 30)
            stay = eng.submit(p_stay, 25)
            got_stay = stay.result(timeout=120)
            got_cancel = state["cancel"].result(timeout=120)
            got_next = state["next"].result(timeout=120)
            assert state["cancel"].finish_reason == "cancelled"
            assert eng.pool.allocated == 0
        full = _solo(model, params, p_cancel, 30)
        assert 3 <= len(got_cancel) < 30
        np.testing.assert_array_equal(got_cancel, full[:len(got_cancel)])
        np.testing.assert_array_equal(
            got_stay, _solo(model, params, p_stay, 25))
        np.testing.assert_array_equal(
            got_next, _solo(model, params, p_next, 9))

    def test_a_submit_asks_nothing_of_the_device(self, model, params,
                                                 monkeypatch):
        """The device works through its calls in order: a submit that
        folded its sampling key there would wait for the chunk in
        flight, and the slot its request is to take would stand empty
        meanwhile. The keys come from the host's copy of a block, and
        are the ones the fold gives."""
        with DecodeEngine(model, params, slots=2, page_size=8,
                          seed=7) as eng:
            for n in (0, 5, 1023, 1024, 3000, 1023):
                np.testing.assert_array_equal(
                    eng._sample_keydata(n),
                    np.asarray(jax.random.key_data(jax.random.fold_in(
                        jax.random.key(7), n))))

            def no_fold(*a, **k):
                raise AssertionError("a submit folded a key on the device")

            monkeypatch.setattr(jax.random, "fold_in", no_fold)
            p = np.asarray([1, 2, 3, 4], np.int32)
            got = eng.submit(p, 5).result(timeout=60)
        np.testing.assert_array_equal(got, _solo(model, params, p, 5))

    def test_the_chunk_around_a_freed_slot_is_short(self, model, params):
        """The chunk sent ahead when a request has just ended is
        JOIN_CHUNK steps at most, whatever the others have left."""
        rng = np.random.default_rng(8)
        p_short, p_long = (rng.integers(0, VOCAB, (5,)).astype(np.int32)
                           for _ in range(2))
        with DecodeEngine(model, params, slots=2, page_size=8) as eng:
            begun = self._watch(eng)
            long_req = eng.submit(p_long, 40)
            eng.submit(p_short, 4).result(timeout=60)
            long_req.result(timeout=60)
        after = [b for b in begun if b.after_end]
        assert after and all(
            int(b.chunks[0].shape[1]) <= DecodeEngine.JOIN_CHUNK
            for b in after)

    def test_not_where_the_roster_is_not_known_ahead(self, model, params):
        """A request that may end on an eos_id, or a speculating engine:
        the next roster is known only from the tokens, so nothing is
        sent ahead."""
        p = np.asarray([1, 2, 3, 4], np.int32)
        with DecodeEngine(model, params, slots=2, page_size=8) as eng:
            begun = self._watch(eng)
            eng.submit(p, 40, eos_id=VOCAB).result(timeout=60)
            assert not begun
            # 39 decode steps: a burst of four chunks of 8, then more
            eng.submit(p, 40).result(timeout=60)
            assert begun
        with DecodeEngine(model, params, slots=2, page_size=8,
                          spec_decode=2) as eng:
            begun = self._watch(eng)
            eng.submit(p, 40, spec_decode=False).result(timeout=60)
            assert not begun

    def test_a_queued_request_joins_before_anything_is_sent_ahead(
            self, model, params):
        """More callers than slots: when a request ends and another
        waits, the admission goes first, as before."""
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, VOCAB, (6,)).astype(np.int32)
                   for _ in range(5)]
        with DecodeEngine(model, params, slots=1, page_size=8) as eng:
            begun = self._watch(eng)
            reqs = [eng.submit(q, 6) for q in prompts]
            outs = [r.result(timeout=120) for r in reqs]
        # a request's own bursts may run ahead; none was begun with the
        # slot just freed and a request waiting for it
        assert not any(b.after_end for b in begun)
        for q, got in zip(prompts, outs):
            np.testing.assert_array_equal(got, _solo(model, params, q, 6))


# ------------------------------------------------------- AOT warm pool
class TestWarmPool:
    def _compiles(self, site):
        return telemetry.MetricsRegistry.get_default().counter(
            telemetry.JIT_COMPILES).value(site=site)

    def test_first_request_zero_trace_after_warm_start(self, model,
                                                       params):
        d0 = self._compiles("serving_decode")
        p0 = self._compiles("serving_prefill")
        with DecodeEngine(model, params, slots=2, page_size=8) as eng:
            eng.generate(np.asarray([1, 2, 3], np.int32), 4)
            stats = eng.stats()
        assert self._compiles("serving_decode") == d0, \
            "decode went through the compiling jit path"
        assert self._compiles("serving_prefill") == p0, \
            "prefill went through the compiling jit path"
        # 1 prefill + the decode chunks covering 3 post-first tokens
        assert stats["warm_pool"]["hits"] >= 3
        assert stats["warm_pool"]["misses"] == 0

    def test_out_of_bucket_prompt_falls_back_and_stays_correct(
            self, model, params):
        p0 = self._compiles("serving_prefill")
        p = np.arange(11, dtype=np.int32) % VOCAB
        # buckets cover only width 8; an 11-token prompt must take the
        # compiling fallback (padded to the page-size multiple 16)
        with DecodeEngine(model, params, slots=2, page_size=8,
                          prefill_buckets=[8]) as eng:
            got = eng.generate(p, 3)
            assert eng.stats()["warm_pool"]["misses"] >= 1
        assert self._compiles("serving_prefill") > p0
        np.testing.assert_array_equal(got, _solo(model, params, p, 3))

    def test_warm_start_false_compiles_lazily_but_serves(self, model,
                                                         params):
        d0 = self._compiles("serving_decode")
        with DecodeEngine(model, params, slots=2, page_size=8,
                          warm_start=False) as eng:
            got = eng.generate(np.asarray([4, 2], np.int32), 5)
            assert eng.stats()["warm_pool"]["hits"] == 0
            assert eng.stats()["warm_pool"]["misses"] >= 2
        assert self._compiles("serving_decode") >= d0 + 1
        np.testing.assert_array_equal(
            got, _solo(model, params, np.asarray([4, 2], np.int32), 5))


# ------------------------------------------------------------ sampling
class TestSampling:
    def test_sampled_decode_deterministic_per_seed(self, model, params):
        p = np.asarray([1, 2, 3], np.int32)
        with DecodeEngine(model, params, slots=2, page_size=8) as eng:
            a = eng.submit(p, 6, temperature=1.0,
                           sample_seed=7).result(60)
            b = eng.submit(p, 6, temperature=1.0,
                           sample_seed=7).result(60)
            c = eng.submit(p, 6, temperature=1.0,
                           sample_seed=8).result(60)
        np.testing.assert_array_equal(a, b)
        assert a.min() >= 0 and a.max() < VOCAB
        assert not np.array_equal(a, c) or True  # seeds may collide;
        # the hard guarantee is same-seed determinism above

    def test_mixed_greedy_and_sampled_slots_keep_greedy_exact(
            self, model, params):
        """A sampled request decoding in the neighboring slot must not
        perturb a greedy request."""
        rng = np.random.default_rng(3)
        p = rng.integers(0, VOCAB, (6,)).astype(np.int32)
        with DecodeEngine(model, params, slots=2, page_size=8) as eng:
            g = eng.submit(p, 8)
            eng.submit(p, 8, temperature=1.3, sample_seed=1)
            got = g.result(timeout=60)
        np.testing.assert_array_equal(got, _solo(model, params, p, 8))


# ---------------------------------------------------------------- int8
class TestInt8Preset:
    def test_quantize_roundtrip_error_bounded(self):
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(0, 0.05, (32, 48)), jnp.float32)
        wq = quantize_int8(w, axis=1)
        deq = dequantize_int8(wq)
        # symmetric rounding: per-channel error <= scale/2
        err = np.abs(np.asarray(deq - w))
        bound = np.asarray(wq["s"])[None, :] * 0.5 + 1e-7
        assert (err <= bound).all()
        assert wq["q"].dtype == jnp.int8
        # int8 storage is ~4x smaller than the f32 original
        assert quantized_bytes(wq) < quantized_bytes(w) / 3

    def test_int8_matmul_matches_dequantized(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
        w = jnp.asarray(rng.normal(0, 0.05, (32, 16)), jnp.float32)
        wq = quantize_int8(w, axis=1)
        np.testing.assert_allclose(
            np.asarray(int8_matmul(x, wq, jnp.float32)),
            np.asarray(x @ dequantize_int8(wq)), rtol=1e-5, atol=1e-5)
        # plain arrays pass through
        np.testing.assert_allclose(
            np.asarray(int8_matmul(x, w, jnp.float32)),
            np.asarray(x @ w), rtol=1e-6, atol=1e-6)

    def test_logits_tolerance_and_loss_parity_vs_reference(self, model,
                                                           params):
        """int8 weight-only decode weights must stay within the same
        quality neighborhood as a bf16 cast of the model (the serving
        preset it substitutes for)."""
        from deeplearning4j_tpu.nn.precision import cast_tree

        rng = np.random.default_rng(2)
        ids = jnp.asarray(rng.integers(0, VOCAB, (4, 16)), jnp.int32)

        def q_tree(p):
            out = {"tok_emb": dequantize_int8(quantize_int8(
                       p["tok_emb"], 0)),
                   "pos_emb": p["pos_emb"], "ln_f": p["ln_f"],
                   "layers": []}
            for lp in p["layers"]:
                nl = dict(lp)
                for k in ("wqkv", "wo", "w1", "w2"):
                    nl[k] = dequantize_int8(quantize_int8(lp[k], 1))
                out["layers"].append(nl)
            return out

        full = np.asarray(model.forward(params, ids), np.float32)
        int8 = np.asarray(model.forward(q_tree(params), ids),
                          np.float32)
        bf16 = np.asarray(model.forward(
            cast_tree(params, jnp.bfloat16), ids), np.float32)
        spread = np.abs(full).max()
        int8_err = np.abs(int8 - full).max()
        bf16_err = np.abs(bf16 - full).max()
        assert int8_err < 0.05 * spread, (int8_err, spread)
        # same neighborhood as the bf16 cast (weight-only int8 is
        # usually BETTER than casting activations+weights to bf16)
        assert int8_err < 4 * bf16_err + 1e-3, (int8_err, bf16_err)

        l_full = float(model.lm_loss(params, ids, train=False))
        l_int8 = float(model.lm_loss(q_tree(params), ids, train=False))
        assert abs(l_int8 - l_full) / abs(l_full) < 0.02

    def test_int8_engine_first_token_exact_and_decode_in_vocab(
            self, model, params):
        """Prefill stays full-precision under the int8 preset, so the
        FIRST generated token is exact; decode tokens must be valid."""
        p = np.asarray([1, 2, 3, 4, 5], np.int32)
        with DecodeEngine(model, params, slots=2, page_size=8,
                          quantization="int8") as eng:
            got = eng.generate(p, 6)
            assert eng.stats()["quantization"] == "int8"
        want = _solo(model, params, p, 6)
        assert got[0] == want[0]
        assert got.min() >= 0 and got.max() < VOCAB

    def test_unknown_quantization_rejected(self, model, params):
        with pytest.raises(ValueError, match="quantization"):
            DecodeEngine(model, params, quantization="fp4")


# ------------------------------------------------------- validation
class TestValidation:
    def test_submit_rejects_bad_requests(self, model, params):
        eng = DecodeEngine(model, params, slots=2, page_size=8,
                           warm_start=False)
        try:
            with pytest.raises(ValueError, match="empty"):
                eng.submit(np.zeros((0,), np.int32), 4)
            with pytest.raises(ValueError, match="max_new_tokens"):
                eng.submit([1, 2], 0)
            with pytest.raises(ValueError, match="max_context"):
                eng.submit(np.zeros((40,), np.int32), 20)
            # batched prompts must be rejected, not silently
            # concatenated into one sequence
            with pytest.raises(ValueError, match="ONE sequence"):
                eng.submit(np.zeros((2, 5), np.int32), 4)
            # ... but the [1, t0] convenience shape is accepted
            assert eng.submit(np.asarray([[1, 2, 3]], np.int32),
                              1).result(60).shape == (1,)
        finally:
            eng.shutdown()

    def test_request_larger_than_pool_rejected_up_front(self, model,
                                                        params):
        eng = DecodeEngine(model, params, slots=2, page_size=8,
                           n_pages=3, warm_start=False)
        try:
            with pytest.raises(ValueError, match="KV pages"):
                eng.submit(np.zeros((20,), np.int32), 10)
        finally:
            eng.shutdown()

    def test_submit_after_shutdown_raises(self, model, params):
        eng = DecodeEngine(model, params, slots=2, page_size=8,
                           warm_start=False)
        eng.start()
        eng.shutdown()
        with pytest.raises(RuntimeError, match="shut down"):
            eng.submit([1, 2], 3)

    def test_shutdown_fails_pending_requests_not_strands_them(
            self, model, params):
        eng = DecodeEngine(model, params, slots=2, page_size=8).start()
        req = eng.submit(np.asarray([1, 2, 3], np.int32), 12)
        eng.shutdown()
        assert req.done
        if req.finish_reason == "error":
            with pytest.raises(RuntimeError):
                req.result(timeout=1)

    def test_engine_thread_joined_on_shutdown(self, model, params):
        with DecodeEngine(model, params, slots=2, page_size=8,
                          warm_start=False) as eng:
            eng.generate([1, 2], 2)
        assert not any(t.name == "ServingEngine" and t.is_alive()
                       for t in threading.enumerate())


# ---------------------------------------------------- front-ends
class TestGenerativeInference:
    def test_parity_and_stats(self, model, params):
        from deeplearning4j_tpu.parallel.wrapper import (
            GenerativeInference,
        )

        p = np.asarray([2, 4, 6], np.int32)
        with GenerativeInference(model, params, slots=2,
                                 page_size=8) as gi:
            out = gi.output(p, 5)
            out2 = gi.output(p[None, :], 5)     # [1, t0] also accepted
            with pytest.raises(ValueError, match="ONE sequence"):
                gi.output(np.zeros((2, 3), np.int32), 4)
            assert gi.n_requests == 2
            assert gi.n_dispatches >= 1
            assert gi.stats()["decode_steps"] >= 8
            assert gi.stats()["completed"] == 2
        np.testing.assert_array_equal(out, _solo(model, params, p, 5))
        np.testing.assert_array_equal(out2, out)


class TestHttpServing:
    def test_generate_endpoint_parity_info_stats(self, model, params):
        from deeplearning4j_tpu.remote.server import (
            JsonModelServer, JsonRemoteInference,
        )

        eng = DecodeEngine(model, params, slots=2, page_size=8)
        srv = JsonModelServer(engine=eng)
        port = srv.start()
        try:
            cli = JsonRemoteInference(f"http://127.0.0.1:{port}")
            p = np.asarray([1, 3, 5, 7], np.int32)
            got = cli.generate(p, 6)
            np.testing.assert_array_equal(
                got, _solo(model, params, p, 6))
            # concurrent HTTP clients share the engine's slots
            with ThreadPoolExecutor(max_workers=4) as ex:
                outs = list(ex.map(lambda _: cli.generate(p, 6),
                                   range(4)))
            for o in outs:
                np.testing.assert_array_equal(o, got)
            import json
            import urllib.request
            info = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/serving/info",
                timeout=10).read())
            assert info["engine"]["slots"] == 2
            stats = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/v1/serving/stats",
                timeout=10).read())
            assert stats["completed"] == 5
        finally:
            srv.stop()
            eng.shutdown()

    def test_server_requires_model_or_engine(self):
        from deeplearning4j_tpu.remote.server import JsonModelServer

        with pytest.raises(ValueError, match="model"):
            JsonModelServer()


# ------------------------------------------------------------ telemetry
class TestServingTelemetry:
    def test_gauges_histograms_counters_populated(self, model, params):
        reg = telemetry.MetricsRegistry.get_default()
        with DecodeEngine(model, params, slots=2, page_size=8) as eng:
            eid = eng.engine_id      # fresh per engine: counts start 0
            eng.generate(np.asarray([1, 2, 3], np.int32), 5)
            eng.generate(np.asarray([4, 5], np.int32), 3)
            occ = reg.gauge(telemetry.SERVING_SLOT_OCCUPANCY).value(
                engine=eid)
            assert 0 <= occ <= 1
            # all pages freed -> utilization gauge back to 0
            assert reg.gauge(
                telemetry.SERVING_KV_PAGE_UTILIZATION).value(
                engine=eid) == 0.0
            snap = telemetry.serving_snapshot()
            for key in ("request_latency", "ttft", "slot_occupancy",
                        "queue_depth", "kv_page_utilization",
                        "tokens_total"):
                assert key in snap, key
            # per-engine label sets fold into fleet-level aggregates
            assert eid in snap["engines"]
            assert snap["aggregate"]["requests_total"] >= 2
            assert "serving" in telemetry.snapshot()
        # cumulative history survives shutdown...
        lat = reg.histogram(telemetry.SERVING_REQUEST_LATENCY)
        assert lat.count(reason="length", engine=eid) == 2
        pct = lat.percentiles(reason="length", engine=eid)
        assert pct["p50"] > 0 and pct["p99"] >= pct["p50"]
        assert reg.histogram(telemetry.SERVING_TTFT).count(
            engine=eid) == 2
        # ...but the engine's GAUGE series are retired (stale-series
        # expiry: no ghost engine frozen at its last reading) and it
        # leaves the live-engine roster while aggregates keep its
        # traffic
        snap = telemetry.serving_snapshot()
        assert eid not in snap["engines"]
        assert snap["aggregate"]["requests_total"] >= 2
        occ_series = reg.gauge(telemetry.SERVING_SLOT_OCCUPANCY).values()
        assert (("engine", eid),) not in occ_series

    def test_two_engines_are_distinguishable_series(self, model,
                                                    params):
        """The fleet-correctness contract: two engines in one process
        must NOT merge their metrics into one series."""
        reg = telemetry.MetricsRegistry.get_default()
        a = DecodeEngine(model, params, slots=2, page_size=8,
                         prefill_buckets=[8], max_chunk=2)
        b = DecodeEngine(model, params, slots=2, page_size=8,
                         prefill_buckets=[8], max_chunk=2,
                         warm_source=a)
        a.start()          # warm a first so b can adopt its programs
        assert a.engine_id != b.engine_id
        try:
            a.generate(np.asarray([1, 2], np.int32), 2)
            a.generate(np.asarray([2, 3], np.int32), 2)
            b.generate(np.asarray([1, 2], np.int32), 2)
        finally:
            a.shutdown()
            b.shutdown()
        req = reg.counter(telemetry.SERVING_REQUESTS)
        assert req.value(engine=a.engine_id) == 2
        assert req.value(engine=b.engine_id) == 1
        lat = reg.histogram(telemetry.SERVING_REQUEST_LATENCY)
        assert lat.count(reason="length", engine=a.engine_id) == 2
        assert lat.count(reason="length", engine=b.engine_id) == 1

    def test_dashboard_has_serving_card(self):
        from deeplearning4j_tpu.ui.server import _DASHBOARD_HTML

        assert "Serving (continuous-batching decode engine)" \
            in _DASHBOARD_HTML
        assert "dl4j_tpu_serving_request_latency_seconds" \
            in _DASHBOARD_HTML


# ------------------------------------- the engine names no model
class _ToyLM:
    """What a served model brings (docs/SERVING.md) and nothing else:
    one attention layer with names of its own, no norm, no MLP, no
    ``paged_rows``, no ``quantize_decode_params``."""

    class cfg:
        max_len = 32

    V, D, H, MAX = 17, 16, 2, cfg.max_len
    _cdtype = jnp.float32

    def weights(self, key):
        names = ("book", "where", "ask", "key", "val", "back")
        shapes = [(self.V, self.D), (self.MAX, self.D)] + 4 * [(self.D,) * 2]
        return {n: jax.random.normal(k, s, jnp.float32) * 0.5
                for n, k, s in zip(names, jax.random.split(key, 6), shapes)}

    def cache_spec(self):
        return {"kv_layers": 1, "kv_heads": self.H,
                "head_dim": self.D // self.H, "state": None}

    def _qkv(self, w, x):               # [..., t, D] -> 3 x [..., H, t, hd]
        return (jnp.swapaxes((x @ w[n]).reshape(
            *x.shape[:-1], self.H, self.D // self.H), -2, -3)
            for n in ("ask", "key", "val"))

    def dense(self, w, ids):
        """ids [t] -> (logits [t, V], k, v [H, t, hd]), recomputing
        everything: the rollout the engine is held to."""
        t = ids.shape[0]
        x = w["book"][ids] + w["where"][:t]
        q, k, v = self._qkv(w, x)
        s = jnp.einsum("hqd,hkd->hqk", q, k) / (self.D // self.H) ** 0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
        ctx = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), v)
        x = x + jnp.swapaxes(ctx, 0, 1).reshape(t, self.D) @ w["back"]
        return x @ w["book"].T, k, v

    def prefill(self, w, prompt, t0, mode=None):
        logits, k, v = self.dense(w, prompt[0])
        return k[None, None], v[None, None], None, logits[t0 - 1], None

    def decode_step(self, w, kv, state, tables, pos, tok, active,
                    page_size, mode=None):
        from deeplearning4j_tpu.ops.paged_attention_pallas import \
            paged_attention
        from deeplearning4j_tpu.serving import kv_pages

        S = tok.shape[0]
        x = w["book"][tok] + w["where"][pos]
        # a projected row is a page row: the heads side by side
        q, k, v = (x @ w[n] for n in ("ask", "key", "val"))   # [S, D]
        kv = kv_pages.append_token(
            kv, 0, tables[jnp.arange(S), pos // page_size],
            pos % page_size, k, v)
        ctx = paged_attention(q.reshape(S, 1, self.H, self.D // self.H),
                              kv, 0, tables, pos, mode=mode)
        x = x + ctx.reshape(S, self.D) @ w["back"]
        return kv, None, x @ w["book"].T, None


class TestServedModelProtocol:
    def test_a_model_of_its_own_is_served_token_identically(self):
        """``cache_spec`` / ``prefill`` / ``decode_step`` are all the
        engine asks: a model it has never heard of, requests joining
        and leaving beside each other, each greedy continuation equal
        to the model's own recompute-everything rollout."""
        toy = _ToyLM()
        w = toy.weights(jax.random.key(3))
        dense = jax.jit(toy.dense)

        def rollout(prompt, new):
            ids = list(prompt)
            for _ in range(new):
                # padded to one width: one trace; causal, so exact
                pad = np.zeros(toy.MAX, np.int32)
                pad[:len(ids)] = ids
                logits, _, _ = dense(w, jnp.asarray(pad))
                ids.append(int(np.argmax(np.asarray(logits[len(ids) - 1]))))
            return np.asarray(ids[len(prompt):], np.int32)

        rng = np.random.default_rng(5)
        work = [(rng.integers(0, toy.V, n).astype(np.int32), new)
                for n, new in [(3, 9), (11, 5), (6, 14), (1, 7), (9, 12)]]
        with DecodeEngine(toy, w, slots=2, page_size=4,
                          max_chunk=4) as eng:
            reqs = [eng.submit(p, new) for p, new in work]
            got = [r.result(timeout=120) for r in reqs]
            assert eng._warm.misses == 0
        for (p, new), out in zip(work, got):
            np.testing.assert_array_equal(out, rollout(p, new))

    @pytest.mark.parametrize("option,method", [
        ({"prefix_cache": True}, "paged_rows"),
        ({"session_capacity": 2}, "paged_rows"),
        ({"spec_decode": 2}, "paged_rows"),
        ({"quantization": "int8"}, "quantize_decode_params")])
    def test_options_follow_from_what_the_model_brings(self, option,
                                                       method):
        toy = _ToyLM()
        with pytest.raises(ValueError,
                           match=f"{next(iter(option))}.*{method}"):
            DecodeEngine(toy, toy.weights(jax.random.key(3)), slots=2,
                         page_size=4, warm_start=False, **option)
