"""Speculative decoding (serving/spec_decode.py + the engine's verify
path): rejection-sampling acceptance golden vs a dense per-slot numpy
reference, distribution preservation at temperature > 0, greedy
engine-level token identity vs spec-off across mid-flight joins,
sessions resume (with forced rejected-token rewind), prefix-cache CoW
sharers, per-seed determinism, warm-pool zero-miss / zero-compile
contracts, and off-mode inertness (spec_decode=None builds nothing)."""

import re
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.gpt import CausalLM
from deeplearning4j_tpu.models.transformer import tiny_config
from deeplearning4j_tpu.profiler import telemetry
from deeplearning4j_tpu.serving import (
    DecodeEngine, NGramDraft, SpecConfig,
)
from deeplearning4j_tpu.serving.spec_decode import accept_tokens

VOCAB = 13
PS = 8


def _model():
    cfg = tiny_config(vocab=VOCAB, max_len=64, d_model=32, n_layers=2,
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


def _engine(model, params, spec=4, **kw):
    kw.setdefault("slots", 3)
    kw.setdefault("page_size", PS)
    kw.setdefault("max_chunk", 4)
    kw.setdefault("prefill_buckets", [8, 16, 32])
    return DecodeEngine(model, params, spec_decode=spec, **kw)


# ------------------------------------------------------- n-gram draft
class TestNGramDraft:
    def test_proposes_continuation_of_trailing_ngram(self):
        d = NGramDraft(match_len=3)
        h = np.asarray([1, 2, 3, 9, 1, 2, 3], np.int32)
        # trailing [1,2,3] occurred at position 0; what followed is
        # [9, 1, 2]
        np.testing.assert_array_equal(d.propose(h, 3), [9, 1, 2])

    def test_prefers_longest_match_and_most_recent_occurrence(self):
        d = NGramDraft(match_len=2)
        # [5, 6] occurs twice before the tail; the LATER one (followed
        # by 8) must win over the earlier (followed by 7)
        h = np.asarray([5, 6, 7, 5, 6, 8, 5, 6], np.int32)
        np.testing.assert_array_equal(d.propose(h, 1), [8])

    def test_fallback_repeats_last_token(self):
        d = NGramDraft(match_len=3)
        h = np.asarray([3, 4, 5], np.int32)   # no repeated n-gram
        np.testing.assert_array_equal(d.propose(h, 4), [5, 5, 5, 5])

    def test_short_continuation_padded_to_k(self):
        d = NGramDraft(match_len=2)
        h = np.asarray([1, 2, 3, 1, 2], np.int32)
        # match at 0, continuation [3, 1, 2] then padded with 2
        np.testing.assert_array_equal(d.propose(h, 5), [3, 1, 2, 2, 2])

    def test_always_returns_exactly_k_int32(self):
        d = NGramDraft()
        for k in (1, 3, 8):
            out = d.propose(np.asarray([0, 1, 0, 1, 0], np.int32), k)
            assert out.shape == (k,) and out.dtype == np.int32

    def test_match_len_validated(self):
        with pytest.raises(ValueError, match="match_len"):
            NGramDraft(match_len=0)


# -------------------------------------------------------- SpecConfig
class TestSpecConfig:
    def test_resolve_forms(self):
        assert SpecConfig.resolve(None) is None
        assert SpecConfig.resolve(False) is None
        assert SpecConfig.resolve(True).k == 4
        assert SpecConfig.resolve(6).k == 6
        assert SpecConfig.resolve("ngram").draft == "ngram"
        c = SpecConfig.resolve({"k": 2, "match_len": 1})
        assert c.k == 2 and c.match_len == 1
        cfg = SpecConfig(k=3)
        assert SpecConfig.resolve(cfg) is cfg

    def test_resolve_rejects_unknowns(self):
        with pytest.raises(ValueError, match="unknown spec_decode"):
            SpecConfig.resolve("medusa")
        with pytest.raises(ValueError, match="k must be >= 1"):
            SpecConfig.resolve(0)
        with pytest.raises(ValueError):
            SpecConfig.resolve(3.5)

    def test_make_draft_custom_object(self):
        class Custom:
            def propose(self, history, k):
                return np.zeros((k,), np.int32)

        d = Custom()
        assert SpecConfig(k=2, draft=d).make_draft() is d
        with pytest.raises(ValueError, match="propose"):
            SpecConfig(k=2, draft=object()).make_draft()


# ---------------------------------------------- acceptance math golden
def _ref_accept(logits, drafts, n_draft, keydata, temps):
    """Dense per-slot python reference of accept_tokens: the same
    jax.random primitives applied one slot / one position at a time,
    with the acceptance loop written as the textbook sequential
    algorithm. The fixed-shape vectorized version must agree exactly."""
    S, W, V = logits.shape
    K = W - 1
    outs, naccs, carries = [], [], []
    for s in range(S):
        kk = jax.random.wrap_key_data(jnp.asarray(keydata[s]))
        nk = jax.random.split(kk, 2 * K + 2)
        carries.append(np.asarray(jax.random.key_data(nk[0])))
        lg = np.asarray(logits[s], np.float32)
        t = float(temps[s])
        if t > 0:
            scaled = lg / t
            p = np.asarray(jax.nn.softmax(jnp.asarray(scaled[:K]),
                                          axis=-1))
            m = 0
            while m < n_draft[s]:
                u = float(jax.random.uniform(nk[1 + m]))
                if u < p[m, drafts[s, m]]:
                    m += 1
                else:
                    break
            if m < n_draft[s]:
                resid = scaled[m].copy()
                resid[drafts[s, m]] = -np.inf
                corr = int(jax.random.categorical(
                    nk[K + 1 + m], jnp.asarray(resid)))
            else:
                corr = int(jax.random.categorical(
                    nk[2 * K + 1], jnp.asarray(scaled[int(n_draft[s])])))
        else:
            greedy = lg.argmax(-1)
            m = 0
            while m < n_draft[s] and drafts[s, m] == greedy[m]:
                m += 1
            corr = int(greedy[m])
        outs.append(list(drafts[s, :m]) + [corr])
        naccs.append(m + 1)
    return outs, naccs, np.stack(carries)


class TestAcceptTokens:
    def _case(self, seed, S=5, K=4, V=VOCAB, temps=None):
        rng = np.random.default_rng(seed)
        logits = rng.normal(0, 2, (S, K + 1, V)).astype(np.float32)
        drafts = rng.integers(0, V, (S, K)).astype(np.int32)
        n_draft = rng.integers(0, K + 1, (S,)).astype(np.int32)
        n_draft[0] = K            # always cover the all-real case
        keydata = np.stack([
            np.asarray(jax.random.key_data(jax.random.key(seed * 100
                                                          + s)))
            for s in range(S)])
        if temps is None:
            temps = np.zeros((S,), np.float32)
        return logits, drafts, n_draft, keydata, temps

    def test_greedy_matches_sequential_reference(self):
        for seed in range(4):
            lg, dr, nd, kd, tm = self._case(seed)
            out, nacc, new_kd = jax.tree_util.tree_map(
                np.asarray, accept_tokens(jnp.asarray(lg),
                                          jnp.asarray(dr),
                                          jnp.asarray(nd),
                                          jnp.asarray(kd),
                                          jnp.asarray(tm)))
            ref_out, ref_n, ref_kd = _ref_accept(lg, dr, nd, kd, tm)
            np.testing.assert_array_equal(nacc, ref_n)
            np.testing.assert_array_equal(new_kd, ref_kd)
            for s in range(lg.shape[0]):
                np.testing.assert_array_equal(out[s, :nacc[s]],
                                              ref_out[s])

    def test_greedy_is_longest_prefix_plus_argmax_correction(self):
        """Constructed case: drafts agree with the target argmax for
        exactly m positions -> emit those m + the argmax at m."""
        V, K = 7, 3
        logits = np.full((1, K + 1, V), -5.0, np.float32)
        argmaxes = [2, 5, 1, 6]
        for i, a in enumerate(argmaxes):
            logits[0, i, a] = 5.0
        drafts = np.asarray([[2, 5, 3]], np.int32)   # mismatch at i=2
        kd = np.asarray(jax.random.key_data(jax.random.key(0)))[None]
        out, nacc, _ = accept_tokens(
            jnp.asarray(logits), jnp.asarray(drafts),
            jnp.asarray([K], jnp.int32), jnp.asarray(kd),
            jnp.zeros((1,), jnp.float32))
        assert int(nacc[0]) == 3
        np.testing.assert_array_equal(np.asarray(out)[0, :3], [2, 5, 1])

    def test_sampled_matches_sequential_reference(self):
        for seed in range(4):
            lg, dr, nd, kd, _ = self._case(seed)
            tm = np.full((lg.shape[0],), 0.7, np.float32)
            tm[0] = 0.0           # mixed greedy/sampled roster
            out, nacc, new_kd = jax.tree_util.tree_map(
                np.asarray, accept_tokens(jnp.asarray(lg),
                                          jnp.asarray(dr),
                                          jnp.asarray(nd),
                                          jnp.asarray(kd),
                                          jnp.asarray(tm)))
            ref_out, ref_n, ref_kd = _ref_accept(lg, dr, nd, kd, tm)
            np.testing.assert_array_equal(nacc, ref_n)
            np.testing.assert_array_equal(new_kd, ref_kd)
            for s in range(lg.shape[0]):
                np.testing.assert_array_equal(out[s, :nacc[s]],
                                              ref_out[s])

    def test_nacc_bounds_and_zero_draft_slots(self):
        lg, dr, nd, kd, tm = self._case(9)
        nd[:] = [4, 0, 2, 0, 1]
        out, nacc, _ = accept_tokens(
            jnp.asarray(lg), jnp.asarray(dr), jnp.asarray(nd),
            jnp.asarray(kd), jnp.asarray(tm))
        nacc = np.asarray(nacc)
        assert ((nacc >= 1) & (nacc <= nd + 1)).all()
        # n_draft = 0 lanes are op-for-op a plain greedy step
        assert nacc[1] == 1 and nacc[3] == 1
        assert int(np.asarray(out)[1, 0]) == int(lg[1, 0].argmax())

    def test_key_advance_independent_of_acceptance(self):
        """The carry key must advance identically no matter what was
        drafted or accepted — replays stay deterministic per seed."""
        lg, dr, nd, kd, _ = self._case(3)
        tm = np.full((lg.shape[0],), 0.9, np.float32)
        _, _, kd_a = accept_tokens(
            jnp.asarray(lg), jnp.asarray(dr), jnp.asarray(nd),
            jnp.asarray(kd), jnp.asarray(tm))
        rng = np.random.default_rng(99)
        other = rng.integers(0, VOCAB, dr.shape).astype(np.int32)
        _, _, kd_b = accept_tokens(
            jnp.asarray(-lg), jnp.asarray(other),
            jnp.asarray(np.zeros_like(nd)), jnp.asarray(kd),
            jnp.asarray(tm))
        np.testing.assert_array_equal(np.asarray(kd_a),
                                      np.asarray(kd_b))

    def test_first_token_marginal_is_target_distribution(self):
        """Rejection sampling with a deterministic draft preserves the
        target law: over many keys, the FIRST emitted token's empirical
        distribution matches softmax(logits / T) even though the draft
        always proposes the same token."""
        V, K, N, T = 5, 1, 4000, 0.8
        rng = np.random.default_rng(0)
        row = rng.normal(0, 1, (V,)).astype(np.float32)
        logits = np.tile(row, (N, K + 1, 1))
        drafts = np.full((N, K), 3, np.int32)   # fixed draft token
        kd = np.asarray(jax.vmap(jax.random.key_data)(
            jax.vmap(jax.random.key)(jnp.arange(N))))
        out, _, _ = accept_tokens(
            jnp.asarray(logits), jnp.asarray(drafts),
            jnp.full((N,), K, jnp.int32), jnp.asarray(kd),
            jnp.full((N,), T, jnp.float32))
        first = np.asarray(out)[:, 0]
        want = np.asarray(jax.nn.softmax(jnp.asarray(row) / T))
        got = np.bincount(first, minlength=V) / N
        np.testing.assert_allclose(got, want, atol=0.05)


# -------------------------------------------------- engine: greedy id
class _WrongDraft:
    """Adversarial draft proposing guaranteed-mismatching tokens
    (argmax + 1 mod V of nothing — just a constant stream shifted off
    the history), so every dispatch exercises the rejected-token KV
    rewind path."""

    def propose(self, history, k):
        h = np.asarray(history, np.int32)
        return ((h[-1] + 5 + np.arange(k)) % VOCAB).astype(np.int32)


class TestEngineSpecGreedyIdentity:
    def test_mixed_length_concurrent_requests_match_solo(self, model,
                                                         params):
        """The tentpole acceptance contract: spec-on greedy decoding is
        token-identical to solo generate() for every request, with
        requests joining and leaving mid-flight."""
        rng = np.random.default_rng(0)
        specs = [(5, 6), (9, 3), (3, 12), (12, 1), (7, 9), (4, 4),
                 (10, 7), (6, 2)]
        prompts = [rng.integers(0, VOCAB, (t0,)).astype(np.int32)
                   for t0, _ in specs]
        with _engine(model, params, spec=4) as eng:
            with ThreadPoolExecutor(max_workers=8) as ex:
                handles = list(ex.map(
                    lambda pn: eng.submit(pn[0], pn[1]),
                    zip(prompts, [n for _, n in specs])))
            outs = [h.result(timeout=120) for h in handles]
            st = eng.stats()
            assert st["completed"] == len(specs)
            assert st["spec"]["proposed"] > 0
            assert st["spec"]["verify_dispatches"] > 0
            assert st["warm_pool"]["misses"] == 0
        assert eng.pool.allocated == 0
        for p, (_, new), got in zip(prompts, specs, outs):
            np.testing.assert_array_equal(
                got, _solo(model, params, p, new))

    def test_staggered_join_next_to_inflight_request(self, model,
                                                     params):
        rng = np.random.default_rng(1)
        long_p = rng.integers(0, VOCAB, (4,)).astype(np.int32)
        short_p = rng.integers(0, VOCAB, (6,)).astype(np.int32)
        with _engine(model, params, spec=2, slots=2) as eng:
            long_req = eng.submit(long_p, 14, eos_id=VOCAB)
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

    def test_all_rejected_drafts_still_token_identical(self, model,
                                                       params):
        """An adversarial always-wrong draft forces a rejection (and a
        KV position rewind) on EVERY verify dispatch; output identity
        proves rejected lanes leave no trace in the cache."""
        rng = np.random.default_rng(2)
        prompts = [rng.integers(0, VOCAB, (n,)).astype(np.int32)
                   for n in (5, 9, 13)]
        cfg = SpecConfig(k=3, draft=_WrongDraft())
        with _engine(model, params, spec=cfg, slots=2) as eng:
            outs = [eng.submit(p, 10).result(timeout=120)
                    for p in prompts]
            st = eng.stats()["spec"]
            assert st["proposed"] > 0
            assert st["acceptance"] == 0.0   # every draft rejected
        for p, got in zip(prompts, outs):
            np.testing.assert_array_equal(
                got, _solo(model, params, p, 10))

    def test_session_resume_after_rejected_rewinds(self, model,
                                                   params):
        """A session's pinned pages were written THROUGH the verify
        path (including rejected, rewound positions past each turn's
        end); the resumed turn must still be token-identical."""
        rng = np.random.default_rng(3)
        p = rng.integers(0, VOCAB, (9,)).astype(np.int32)
        cfg = SpecConfig(k=3, draft=_WrongDraft())
        with _engine(model, params, spec=cfg, slots=2,
                     prefix_cache=True, session_capacity=2) as eng:
            o1 = eng.submit(p, 6, session_id="s").result(timeout=120)
            t2 = np.concatenate([p, o1])
            r2 = eng.submit(t2, 6, session_id="s")
            o2 = r2.result(timeout=120)
            assert r2.cache_hit_tokens == t2.size - 1
        np.testing.assert_array_equal(o1, _solo(model, params, p, 6))
        np.testing.assert_array_equal(o2, _solo(model, params, t2, 6))

    def test_prefix_cache_cow_sharers_token_identical(self, model,
                                                      params):
        """Two requests sharing cached prefix pages read-only while
        the verify program appends their divergent suffixes: CoW must
        isolate them exactly as on the plain path."""
        rng = np.random.default_rng(4)
        sys_p = rng.integers(0, VOCAB, (16,)).astype(np.int32)
        pa = np.concatenate([sys_p, rng.integers(0, VOCAB, (4,))
                             .astype(np.int32)])
        pb = np.concatenate([sys_p, rng.integers(0, VOCAB, (6,))
                             .astype(np.int32)])
        with _engine(model, params, spec=4, slots=2,
                     prefix_cache=True) as eng:
            eng.submit(sys_p, 1).result(120)    # populate the cache
            with ThreadPoolExecutor(max_workers=2) as ex:
                ha = ex.submit(lambda: eng.submit(pa, 10).result(120))
                hb = ex.submit(lambda: eng.submit(pb, 10).result(120))
                out_a, out_b = ha.result(), hb.result()
        np.testing.assert_array_equal(out_a,
                                      _solo(model, params, pa, 10))
        np.testing.assert_array_equal(out_b,
                                      _solo(model, params, pb, 10))

    def test_per_request_opt_out_rides_along(self, model, params):
        """spec_decode=False requests share the roster with drafting
        neighbors as plain lanes: identical output, zero spec stats."""
        rng = np.random.default_rng(5)
        pa = rng.integers(0, VOCAB, (6,)).astype(np.int32)
        pb = rng.integers(0, VOCAB, (8,)).astype(np.int32)
        with _engine(model, params, spec=4, slots=2) as eng:
            with ThreadPoolExecutor(max_workers=2) as ex:
                ha = ex.submit(lambda: eng.submit(pa, 9))
                hb = ex.submit(lambda: eng.submit(pb, 9,
                                                  spec_decode=False))
                ra, rb = ha.result(), hb.result()
            out_a = ra.result(timeout=120)
            out_b = rb.result(timeout=120)
            assert rb.spec_proposed == 0 and rb.spec_accepted == 0
        np.testing.assert_array_equal(out_a,
                                      _solo(model, params, pa, 9))
        np.testing.assert_array_equal(out_b,
                                      _solo(model, params, pb, 9))

    def test_fp8_kv_with_spec_completes_and_drains(self, model,
                                                   params):
        """fp8 pages + the verify path's segment-max scale minting:
        requests complete with the right token counts and every page
        refcount returns to zero (numeric identity is not the fp8
        contract — quantization moves logits by design)."""
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, VOCAB, (n,)).astype(np.int32)
                   for n in (5, 11)]
        with _engine(model, params, spec=3, slots=2,
                     kv_dtype="fp8_e4m3") as eng:
            outs = [eng.submit(p, 8).result(timeout=120)
                    for p in prompts]
            assert eng.stats()["spec"]["proposed"] > 0
        assert all(o.size == 8 for o in outs)
        assert eng.pool.allocated == 0


# ------------------------------------------- determinism + telemetry
class TestSpecDeterminismAndTelemetry:
    def test_sampling_deterministic_per_seed(self, model, params):
        """Same seeds, fresh engines: identical sampled outputs AND
        identical acceptance counters (the n-gram draft and the fixed
        key schedule are both deterministic)."""
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, VOCAB, (n,)).astype(np.int32)
                   for n in (6, 10)]

        def run():
            with _engine(model, params, spec=4, slots=2) as eng:
                outs = [eng.submit(p, 12, temperature=0.8,
                                   sample_seed=40 + i).result(120)
                        for i, p in enumerate(prompts)]
                st = eng.stats()["spec"]
            return outs, (st["proposed"], st["accepted"])

        outs_a, st_a = run()
        outs_b, st_b = run()
        assert st_a == st_b
        for a, b in zip(outs_a, outs_b):
            np.testing.assert_array_equal(a, b)

    def test_zero_post_start_compiles_and_counters_advance(
            self, model, params):
        """The verify program is AOT-warmed: warm traffic pays zero
        compiles at every serving site, and the spec telemetry
        counters advance."""
        reg = telemetry.MetricsRegistry.get_default()
        compiles = lambda s: reg.counter(
            telemetry.JIT_COMPILES).value(site=s)
        rng = np.random.default_rng(8)
        p = rng.integers(0, VOCAB, (7,)).astype(np.int32)
        with _engine(model, params, spec=4, slots=2) as eng:
            c0 = {s: compiles(s) for s in
                  ("serving_verify", "serving_decode",
                   "serving_prefill")}
            eng.submit(p, 10).result(timeout=120)
            st = eng.stats()
            assert st["warm_pool"]["misses"] == 0
            assert st["spec"]["proposed"] > 0
            assert st["spec"]["tokens_per_dispatch"] >= 1.0
        for s, v in c0.items():
            assert compiles(s) == v, f"{s} paid a compile post-startup"

    def test_request_level_spec_stats_populated(self, model, params):
        p = (np.arange(9) % VOCAB).astype(np.int32)
        with _engine(model, params, spec=4, slots=2) as eng:
            r = eng.submit(p, 8)
            r.result(timeout=120)
        assert r.spec_proposed > 0
        assert 0 <= r.spec_accepted <= r.spec_proposed


# ------------------------------------------------------ off-mode inert
class TestSpecOffMode:
    def test_off_engine_builds_no_spec_machinery(self, model, params):
        eng = DecodeEngine(model, params, slots=2, page_size=PS,
                           max_chunk=4, prefill_buckets=[8, 16])
        assert eng._spec is None
        assert not hasattr(eng, "_verify_jit")
        with eng:
            p = (np.arange(6) % VOCAB).astype(np.int32)
            eng.submit(p, 5).result(timeout=120)
            assert not any(k[0] == "verify" for k in eng._warm._exec)
            assert "spec" not in eng.stats()

    def test_spec_on_leaves_plain_programs_byte_identical(self, model,
                                                          params):
        """Turning speculation on must not change the plain decode /
        prefill executables at all — same warm-pool keys plus exactly
        the ("verify", k) addition, and the same compiled HLO for every
        shared key. Compared is what a program computes: the header's
        source-position tables and each instruction's ``metadata`` (a
        file, line and COLUMN per Python frame, this test's own call
        sites among them) say where it was traced from, and differ
        between any two engines built on different lines."""
        def computes(ex):
            text = re.sub(r', metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}',
                          "", ex.as_text())
            return re.sub(
                r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)"
                r"\n(?:.+\n)*\n", "", text, flags=re.M)

        kw = dict(slots=2, page_size=PS, max_chunk=4,
                  prefill_buckets=[8, 16])
        off = DecodeEngine(model, params, **kw)
        on = DecodeEngine(model, params, spec_decode=4, **kw)
        with off, on:
            p_off = {k: computes(ex) for k, ex in off._warm._exec.items()}
            p_on = {k: computes(ex) for k, ex in on._warm._exec.items()}
        extra = set(p_on) - set(p_off)
        assert extra == {("verify", 4)}
        for k, text in p_off.items():
            assert "ENTRY" in text and "metadata=" not in text
            assert p_on[k] == text, \
                f"{k} recompiled differently with spec on"
