"""Causal language model (GPT-style) with KV-cache generation.

Parity note: the reference has no decoder-only LM; this is an
EXTENSION in the same spirit as the ring/Ulysses and MoE recipes —
the model families a reference user graduates to. What makes it
TPU-native:

- training = one jit step with in-graph causal masking (plain fused
  einsum attention; at these sequence lengths XLA's fusion covers it —
  ``ops/flash_attention`` remains the opt-in for long sequences);
- generation = batched PARALLEL prefill (one forward pass writes the
  whole prompt's K/V) + ``lax.scan`` decode over a STATIC-shape KV
  cache ([L, N, H, max_len, hd], position-masked) — no dynamic shapes,
  no per-token dispatch; one compiled program generates the whole
  continuation;
- the decode step is pinned against the recompute-everything forward
  in tests (cache correctness is asserted, not assumed).

Architecture: pre-LN transformer decoder, learned positions, tied
embedding LM head (weights follow models/transformer.py conventions).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.models.transformer import TransformerConfig
from deeplearning4j_tpu.nn.precision import (int8_matmul, is_int8,
                                             quantize_int8)


def _paged():
    """The page writers and the paged attention, imported where a paged
    pass is traced: a trainer that imports this file does not pay for
    the serving package and Pallas (1.2 s of the training cell's
    ``setup_s``)."""
    from deeplearning4j_tpu.ops.paged_attention_pallas import paged_attention
    from deeplearning4j_tpu.serving import kv_pages

    return kv_pages, paged_attention


def _attention(q, k, v, mask):
    """Masked softmax attention over ``[n, H, t, hd]`` heads."""
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    logits = jnp.einsum("nhqd,nhkd->nhqk", q, k) * scale
    neg = jnp.asarray(jnp.finfo(logits.dtype).min, logits.dtype)
    w = jax.nn.softmax(jnp.where(mask, logits, neg), axis=-1)
    return jnp.einsum("nhqk,nhkd->nhqd", w, v)


class _Causal:
    """A fresh sequence ``[n, t]``: each position attends those before
    it; every layer's K/V ``[n, H, t, hd]`` is kept for a prefill."""

    def __init__(self, cfg, n, t):
        self.cfg, self.n, self.t = cfg, n, t
        self.mask = jnp.tril(jnp.ones((t, t), bool))[None, None]
        self.ks, self.vs = [], []

    def attend(self, li, q, k, v):
        c, n, t = self.cfg, self.n, self.t
        q, k, v = (y.reshape(n, t, c.n_heads, c.head_dim)
                   .transpose(0, 2, 1, 3) for y in (q, k, v))
        self.ks.append(k)
        self.vs.append(v)
        ctx = _attention(q, k, v, self.mask)
        return ctx.transpose(0, 2, 1, 3).reshape(n, t, c.d_model)


class _DenseCache:
    """One token a row at the shared position ``pos``, K/V in the dense
    cache ``[L, N, H, max_len, hd]`` of ``generate()``."""

    def __init__(self, cfg, ck, cv, pos):
        self.cfg, self.ck, self.cv, self.pos = cfg, ck, cv, pos
        self.mask = (jnp.arange(cfg.max_len) <= pos)[None, None, None, :]

    def attend(self, li, q, k, v):
        c, n = self.cfg, q.shape[0]
        q, k, v = (y.reshape(n, c.n_heads, 1, c.head_dim)
                   for y in (q, k, v))
        at = (li, 0, 0, self.pos, 0)
        self.ck = lax.dynamic_update_slice(self.ck, k[None], at)
        self.cv = lax.dynamic_update_slice(self.cv, v[None], at)
        ctx = _attention(q, self.ck[li], self.cv[li], self.mask)
        return ctx.reshape(n, c.d_model)


class _Paged:
    """One token a slot: K/V appended to the slot's page, attention
    through the page tables."""

    def __init__(self, cfg, kv, tables, pos, page_size, mode):
        self.cfg, self.kv, self.tables, self.pos = cfg, kv, tables, pos
        self.page = tables[jnp.arange(pos.shape[0]), pos // page_size]
        self.off = pos % page_size
        self.mode = mode
        self.pages, self.attention = _paged()

    def attend(self, li, q, k, v):
        # a projected row IS a page row: every head side by side
        c, S = self.cfg, q.shape[0]
        self.kv = self.pages.append_token(self.kv, li, self.page, self.off,
                                          k, v)
        ctx = self.attention(q.reshape(S, 1, c.n_heads, c.head_dim),
                             self.kv, li, self.tables, self.pos,
                             mode=self.mode)
        return ctx.reshape(S, c.d_model)


class _PagedRows:
    """``W`` consecutive positions a slot from ``pos``: each row's K/V
    goes to its own (page, offset) before the rows attend through the
    slot's whole table (row ``i`` at ``pos + i``, causal by the
    kernel's flat-position mask). Rows that are not ``real`` write to
    the null page and, in an fp8 pool, to no page's scale."""

    def __init__(self, cfg, kv, tables, pos, real, page_size, mode):
        self.cfg, self.kv, self.tables, self.pos = cfg, kv, tables, pos
        self.real, self.mode = real, mode
        P, W = tables.shape[1], real.shape[1]
        self.posw = pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        chunk = jnp.minimum(self.posw // page_size, P - 1)
        self.page = jnp.where(
            real, jnp.take_along_axis(tables, chunk, axis=1), 0)
        self.off = self.posw % page_size
        self.seg = jnp.where(real, chunk, P)
        self.pages, self.attention = _paged()

    def attend(self, li, q, k, v):
        c, (S, W) = self.cfg, self.real.shape
        self.kv = self.pages.append_spec(
            self.kv, li, self.page, self.off, k, v, chunk=self.seg,
            real=self.real, tables=self.tables)
        ctx = self.attention(q.reshape(S, W, c.n_heads, c.head_dim),
                             self.kv, li, self.tables, self.pos,
                             mode=self.mode)
        return ctx.reshape(S, W, c.d_model)


class CausalLM:
    def __init__(self, config: TransformerConfig,
                 compute_dtype=jnp.bfloat16):
        self.cfg = config
        self._cdtype = compute_dtype
        # jit cache for generate(): keyed on the shape/config tuple so
        # repeated calls reuse the compiled prefill+decode program
        # (a fresh @jax.jit closure per call would retrace every time)
        self._gen_cache: Dict[Any, Any] = {}

    # -- params ---------------------------------------------------------
    def init_params(self, key=None) -> Dict[str, Any]:
        cfg = self.cfg
        key = key if key is not None else jax.random.key(0)
        d, f = cfg.d_model, cfg.d_ff
        ks = jax.random.split(key, 2 + cfg.n_layers)

        def norm(k, shape, scale):
            return (jax.random.normal(k, shape, jnp.float32)
                    * scale).astype(jnp.float32)

        p = {"tok_emb": norm(ks[0], (cfg.vocab_size, d), 0.02),
             "pos_emb": norm(ks[1], (cfg.max_len, d), 0.01),
             "ln_f": {"g": jnp.ones((d,), jnp.float32),
                      "b": jnp.zeros((d,), jnp.float32)},
             "layers": []}
        for li in range(cfg.n_layers):
            lk = jax.random.split(ks[2 + li], 4)
            p["layers"].append({
                "ln1": {"g": jnp.ones((d,), jnp.float32),
                        "b": jnp.zeros((d,), jnp.float32)},
                "wqkv": norm(lk[0], (d, 3 * d), 0.02),
                "bqkv": jnp.zeros((3 * d,), jnp.float32),
                "wo": norm(lk[1], (d, d), 0.02 / (2 * cfg.n_layers) ** 0.5),
                "bo": jnp.zeros((d,), jnp.float32),
                "ln2": {"g": jnp.ones((d,), jnp.float32),
                        "b": jnp.zeros((d,), jnp.float32)},
                "w1": norm(lk[2], (d, f), 0.02),
                "b1": jnp.zeros((f,), jnp.float32),
                "w2": norm(lk[3], (f, d), 0.02 / (2 * cfg.n_layers) ** 0.5),
                "b2": jnp.zeros((d,), jnp.float32),
            })
        return p

    # -- shared pieces --------------------------------------------------
    def _ln(self, x, p):
        m = jnp.mean(x, axis=-1, keepdims=True)
        v = jnp.var(x, axis=-1, keepdims=True)
        return ((x - m) * lax.rsqrt(v + 1e-5) * p["g"].astype(x.dtype)
                + p["b"].astype(x.dtype))

    def _embed(self, params, tok, at):
        """Token rows plus the position rows ``pos_emb[at]``; a
        quantized ``tok_emb`` carries one scale a row."""
        cd, w = self._cdtype, params["tok_emb"]
        if is_int8(w):
            rows = w["q"][tok].astype(cd) * w["s"][tok][..., None].astype(cd)
        else:
            rows = w.astype(cd)[tok]
        return rows + params["pos_emb"].astype(cd)[at]

    def _head(self, x, params):
        """Tied LM head ``x @ tok_emb.T`` (per-row scales become
        per-output-column scales of the transpose)."""
        cd, w = self._cdtype, params["tok_emb"]
        if is_int8(w):
            return (x @ w["q"].astype(cd).T) * w["s"].astype(cd)[None, :]
        return x @ w.astype(cd).T

    def _block(self, li, lp, x, cache, bias_first=False, drop_key=None):
        """THE decoder layer (pre-LN): ``cache.attend`` is all that
        differs between the callers. The two adds after each output
        product are kept in the order each caller has always had them:
        ``forward`` adds the bias to the product (dropout acts on that
        sum) and the sum to the stream (``bias_first``); the cached
        passes add the product to the stream, then the bias. bf16
        rounds the two differently, and the greedy-identity tests and
        the measured programs are pinned to each."""
        cd = self._cdtype

        def joined(x, y, b, key=None):
            if not bias_first:
                return x + y + b
            y = y + b
            if key is not None:
                _, sub = jax.random.split(key)
                keep = 1.0 - self.cfg.dropout
                y = y * jax.random.bernoulli(sub, keep, y.shape) / keep
            return x + y

        h = self._ln(x, lp["ln1"])
        qkv = int8_matmul(h, lp["wqkv"], cd) + lp["bqkv"].astype(cd)
        ctx = cache.attend(li, *jnp.split(qkv, 3, axis=-1))
        x = joined(x, int8_matmul(ctx, lp["wo"], cd), lp["bo"].astype(cd),
                   drop_key)
        h = self._ln(x, lp["ln2"])
        mid = jax.nn.gelu(int8_matmul(h, lp["w1"], cd) + lp["b1"].astype(cd))
        return joined(x, int8_matmul(mid, lp["w2"], cd), lp["b2"].astype(cd))

    def _run(self, params, x, cache, bias_first=False, drop_keys=None):
        for li, lp in enumerate(params["layers"]):
            x = self._block(li, lp, x, cache, bias_first,
                            None if drop_keys is None else drop_keys[li])
        return self._ln(x, params["ln_f"])

    # -- training forward ----------------------------------------------
    def forward(self, params, ids, train=False, rng=None,
                return_kv=False):
        """ids [N,T] -> logits [N,T,V] (causal). With return_kv, also
        returns the per-layer K/V stacks [L,N,H,T,hd] (the parallel
        prefill path of generate() and of the serving engine)."""
        cfg = self.cfg
        n, t = ids.shape
        x = self._embed(params, ids, np.s_[None, :t])
        cache = _Causal(cfg, n, t)
        drop = train and rng is not None and cfg.dropout > 0
        x = self._run(params, x, cache, bias_first=True,
                      drop_keys=(jax.random.split(rng, cfg.n_layers)
                                 if drop else None))
        logits = self._head(x, params)
        if return_kv:
            return logits, jnp.stack(cache.ks), jnp.stack(cache.vs)
        return logits

    def lm_loss(self, params, ids, train=True, rng=None):
        """Next-token cross entropy over ids[:, :-1] -> ids[:, 1:]."""
        logits = self.forward(params, ids[:, :-1], train, rng)
        targets = ids[:, 1:]
        lse = jax.scipy.special.logsumexp(
            logits.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(
            logits.astype(jnp.float32), targets[..., None],
            axis=-1)[..., 0]
        return jnp.mean(lse - picked)

    def make_train_step(self, updater):
        from deeplearning4j_tpu.learning.updaters import apply_updater

        @jax.jit
        def step(params, opt_state, it_step, ids, rng):
            loss, grads = jax.value_and_grad(
                lambda p: self.lm_loss(p, ids, True, rng))(params)
            updates, new_opt = apply_updater(updater, opt_state, grads,
                                             params, it_step)
            new_p = jax.tree_util.tree_map(lambda p, u: p - u, params,
                                           updates)
            return new_p, new_opt, loss

        return step

    # -- what a serving engine asks of a model (docs/SERVING.md) ---------
    def cache_spec(self) -> Dict[str, Any]:
        """K/V pages in every layer, as many KV heads as query heads,
        no per-slot state beside them."""
        c = self.cfg
        return {"kv_layers": c.n_layers, "kv_heads": c.n_heads,
                "head_dim": c.head_dim, "state": None}

    def prefill(self, params, prompt, t0, mode=None):
        """A padded prompt ``[1, B]`` of ``t0`` real tokens -> ``(ks,
        vs, None, last, None)``: every layer's K/V ``[L, 1, H, B, hd]``
        and the logits of position ``t0 - 1``. Positions >= t0 are
        padding no real position can see, so both are exact."""
        logits, ks, vs = self.forward(params, prompt, return_kv=True)
        last = lax.dynamic_index_in_dim(logits[0], t0 - 1, axis=0,
                                        keepdims=False)
        return ks, vs, None, last, None

    def decode_step(self, params, kv, state, tables, pos, tok, active,
                    page_size, mode=None):
        """One token a slot: ``tok [S]`` at ``pos [S]`` -> ``(kv, None,
        logits [S, V] float32, None)``. ``_decode_one`` with the K/V in
        pages; a slot that is not ``active`` has an all-null table, so
        its write lands on the null page."""
        cache = _Paged(self.cfg, kv, tables, pos, page_size, mode)
        x = self._run(params, self._embed(params, tok, pos), cache)
        return (cache.kv, None,
                self._head(x, params).astype(jnp.float32), None)

    def paged_rows(self, params, kv, tables, pos, toks, real, page_size,
                   mode=None):
        """``W`` consecutive positions a lane: ``toks [S, W]`` from
        ``pos [S]`` -> ``(kv, logits [S, W, V] float32)``. Each row's
        K/V is written before the rows are attended (row ``i`` sees
        rows ``< i`` of this call); rows that are not ``real`` write to
        the null page. A speculative verify (``W`` = drafts + 1) and a
        suffix prefill behind cached pages (``S`` = 1) are this."""
        cache = _PagedRows(self.cfg, kv, tables, pos, real, page_size, mode)
        at = jnp.minimum(cache.posw, self.cfg.max_len - 1)
        x = self._run(params, self._embed(params, toks, at), cache)
        return cache.kv, self._head(x, params).astype(jnp.float32)

    def serving_params(self, params):
        """The tree a serving engine holds: every floating leaf in the
        compute dtype. Each product above casts a parameter there at
        its point of use (``_embed``, ``_head``, ``_ln``,
        ``int8_matmul``), so the cast made once gives the values the
        cast made a call gives, and a program handed this tree holds no
        cast of a weight (774 M of them a dispatch at GPT-2-large
        widths, PERF.md PR 33). A leaf already there is returned as it
        is, the same array; int8 codes and their scales are left
        alone. Idempotent. Training keeps its float32 masters: this is
        a copy, made where a model is put to serve."""
        cd = jnp.dtype(self._cdtype)

        def rest(leaf):
            if (is_int8(leaf) or leaf.dtype == cd
                    or not jnp.issubdtype(leaf.dtype, jnp.floating)):
                return leaf
            return leaf.astype(cd)

        return jax.tree_util.tree_map(rest, params, is_leaf=is_int8)

    def quantize_decode_params(self, params):
        """int8 weight-only tree for the decode step: every 2-D matmul
        weight gets per-output-channel scales; tok_emb is per-ROW
        scaled so the same tensor serves the embedding gather (rows)
        and the tied LM head (rows become output channels of x@W.T).
        Biases, norms, positions stay float."""
        def q(w, axis):
            wq = quantize_int8(w, axis=axis)
            return {"q": wq["q"], "s": wq["s"]}   # drop static axis key

        mats = ("wqkv", "wo", "w1", "w2")
        return dict(params, tok_emb=q(params["tok_emb"], 0), layers=[
            {k: q(w, 1) if k in mats else w for k, w in lp.items()}
            for lp in params["layers"]])

    # -- KV-cache generation --------------------------------------------
    def _decode_one(self, params, ck, cv, pos, tok):
        """One decode step. tok [N] int32 at position ``pos``; ck/cv
        [L,N,H,max_len,hd]. Returns (logits [N,V], new ck, cv)."""
        cache = _DenseCache(self.cfg, ck, cv, pos)
        x = self._run(params, self._embed(params, tok, pos), cache)
        return self._head(x, params).astype(jnp.float32), cache.ck, cache.cv

    def generate(self, params, prompt_ids, max_new_tokens: int,
                 temperature: float = 0.0,
                 rng: Optional[jax.Array] = None):
        """Greedy (temperature 0) or sampled continuation. The WHOLE
        loop — prompt prefill + max_new_tokens decode steps — is one
        jit-compiled lax.scan program with a static-shape KV cache."""
        cfg = self.cfg
        prompt_ids = jnp.asarray(prompt_ids, jnp.int32)
        n, t0 = prompt_ids.shape
        if t0 + max_new_tokens > cfg.max_len:
            raise ValueError(
                f"prompt ({t0}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_len ({cfg.max_len})")
        rng = rng if rng is not None else jax.random.key(0)
        cache_key = (n, t0, max_new_tokens, float(temperature))
        if cache_key in self._gen_cache:
            # LRU: re-insert on hit so eviction drops the COLDEST
            # program, not the oldest-inserted (which may be the
            # hottest shape in a serving mix)
            run = self._gen_cache.pop(cache_key)
            self._gen_cache[cache_key] = run
            return run(params, prompt_ids, rng)

        def sample(key, logits):
            if temperature > 0.0:
                nxt = jax.random.categorical(key, logits / temperature,
                                             axis=-1)
            else:
                nxt = jnp.argmax(logits, axis=-1)
            return nxt.astype(jnp.int32)

        @jax.jit
        def run(params, prompt, rng):
            shape = (cfg.n_layers, n, cfg.n_heads, cfg.max_len,
                     cfg.head_dim)
            # PARALLEL prefill: one batched forward over the whole
            # prompt writes every position's K/V at once (MXU-shaped
            # matmuls), instead of t0 serial single-token steps
            logits_p, ks, vs = self.forward(params, prompt,
                                            return_kv=True)
            ck = jnp.zeros(shape, self._cdtype).at[:, :, :, :t0].set(ks)
            cv = jnp.zeros(shape, self._cdtype).at[:, :, :, :t0].set(vs)
            rng0, rng1 = jax.random.split(rng)
            first = sample(rng0, logits_p[:, -1].astype(jnp.float32))

            def decode(carry, i):
                ck, cv, tok, key = carry
                # tok is generated token i, sitting at position t0+i
                logits, ck, cv = self._decode_one(params, ck, cv,
                                                  t0 + i, tok)
                key, sub = jax.random.split(key)
                nxt = sample(sub, logits)
                return (ck, cv, nxt, key), nxt

            if max_new_tokens == 1:
                return first[:, None]
            _, toks = lax.scan(decode, (ck, cv, first, rng1),
                               jnp.arange(max_new_tokens - 1))
            return jnp.concatenate([first[:, None],
                                    toks.transpose(1, 0)], axis=1)

        if len(self._gen_cache) >= 8:   # bound compiled-program growth
            # dict preserves insertion order and hits re-insert, so the
            # first key is always the least-recently-used program
            self._gen_cache.pop(next(iter(self._gen_cache)))
        self._gen_cache[cache_key] = run
        return run(params, prompt_ids, rng)

    def num_params(self, params) -> int:
        return sum(int(v.size) for v in jax.tree_util.tree_leaves(params))
