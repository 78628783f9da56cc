"""EXAONE-MoE causal language model (LGAI-EXAONE ``exaone_moe``,
K-EXAONE-236B-A23B): grouped-query attention in two kinds of layer,
sliding-window layers with rotary positions and global layers without
any, a dense SwiGLU in the leading layers and, in the rest,
sigmoid-routed dropless SwiGLU experts beside a shared expert; RMSNorm
on every sub-layer's input, an output head of its own.

    h <- h + Attn_l(RMS(h; g_in));  h <- h + FF_l(RMS(h; g_post))
    logits = RMS(h_L; g_f) W_head

A chip may hold its SHARE of a layer (expert parallelism, a sliced
vocabulary): the config says which experts are held (``num_experts``
of the router's ``n_routed_experts`` outputs, from ``expert_offset``)
and how many rows of the embedding and the head (``vocab_size``). The
router keeps its published width and its experts a token; assignments
to experts held elsewhere add nothing here
(``models/routed_experts.py``), and nothing stands in for the absent
chips. The shared expert, the attention and the dense layer are whole.

The block is written ONCE (``_block``) and serves three callers by how
it reads and writes cache (the ``_Dense`` / ``_Prefill`` / ``_Paged``
objects below), as ``models/lfm2_moe.py`` does:

- ``forward``: no cache; logits of every position.
- ``prefill``: one padded prompt; returns the global layers' K/V for
  the engine's page commit and, for the window layers, the ring each
  keeps of the prompt's LAST positions (never a bucket's padding).
- ``decode_step``: one token a slot through both kinds of cache.

A cache of two kinds (``cache_spec()``): the global layers' K/V lie in
the engine's page pool, a request's whole footprint as for any model;
the window layers' K/V lie in a per-slot RING of ``R = window / page +
1`` pages a layer (``state``: ``{"k", "v"}`` of ``[window layers,
slots, R, page, KV heads x head]``), whatever the context: position
``p`` lives in ring entry ``(p // page) % R``. Viewed as a pool ``[window
layers, slots x R, page, KV x head]`` it is written by
``kv_pages.append_token`` and read by the paged kernel
(``ops/paged_attention_pallas.py``, ``window=``), which walks at most
the window's pages. A slot's ring is rewritten whole at admission.

Parameters are created and held in the compute dtype (bfloat16 as
served). The router's product, its sigmoid, the top-k and the weights'
normalisation run in float32, as do the norms' statistics and the
softmax.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.models.decoder_ops import rms_norm, rope
from deeplearning4j_tpu.models.routed_experts import (expert_stats,
                                                      routed_experts)
from deeplearning4j_tpu.ops.paged_attention_pallas import paged_attention
from deeplearning4j_tpu.serving import kv_pages

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    """The published ``config.json`` keys that shape the model, and the
    share of it held here: ``num_experts`` counts the experts HELD
    (``n_routed_experts`` is the router's width, the published
    ``num_experts``; ``expert_offset`` the first one held),
    ``vocab_size`` the rows of the embedding and of the head held."""

    vocab_size: int = 153600
    hidden_size: int = 6144
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    layer_types: Tuple[str, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    sliding_window: int = 128
    num_experts: int = 128
    n_routed_experts: Optional[int] = None
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: Optional[int] = 128
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    num_nextn_predict_layers: int = 0
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    #: positions a page of a window layer's ring holds
    window_page_size: int = 16

    def __post_init__(self):
        set_ = lambda k, v: object.__setattr__(self, k, v)
        set_("layer_types", tuple(self.layer_types))
        set_("mlp_layer_types", tuple(self.mlp_layer_types))
        if self.head_dim is None:
            set_("head_dim", self.hidden_size // self.num_attention_heads)
        if self.n_routed_experts is None:
            set_("n_routed_experts", self.num_experts)
        L = self.num_hidden_layers
        if len(self.layer_types) != L or len(self.mlp_layer_types) != L:
            raise ValueError(
                f"{len(self.layer_types)} layer_types and "
                f"{len(self.mlp_layer_types)} mlp_layer_types for {L} layers")
        bad = (set(self.layer_types) - {SLIDING, FULL}) \
            | (set(self.mlp_layer_types) - {"dense", "sparse"})
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide into the KV heads")
        if not 0 <= self.expert_offset \
                <= self.n_routed_experts - self.num_experts:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.num_experts}) are not among the router's "
                f"{self.n_routed_experts}")
        for name, want in (("scoring_func", "sigmoid"), ("n_group", 1),
                           ("topk_group", 1),
                           ("num_nextn_predict_layers", 0),
                           ("tie_word_embeddings", False)):
            if getattr(self, name) != want:
                raise ValueError(
                    f"{name}={getattr(self, name)!r} is not implemented "
                    f"(only {want!r})")

    # the names the serving engine reads off any model's config
    @property
    def max_len(self) -> int:
        return self.max_position_embeddings

    @property
    def d_model(self) -> int:
        return self.hidden_size

    @property
    def n_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def n_heads(self) -> int:
        return self.num_attention_heads

    @property
    def ring_pages(self) -> int:
        """Pages of a window layer's ring: the most a window straddles
        from any offset."""
        return -(-self.sliding_window // self.window_page_size) + 1


# ------------------------------------------------ how a caller caches
class _Dense:
    """No cache: a fresh sequence, attention over the call's own
    positions; a window layer under a band mask. Past ``block`` queries
    the scores are made a query block at a time (a 2,048 bucket's are
    1 GB a layer at once), a window layer's against the keys its block
    can reach only."""

    def __init__(self, window, block):
        self.window, self.block = window, block

    def _scores(self, q, k, v, qpos, kpos, window):
        """``q [n, b, KV, G, hd]`` at ``qpos [n, b]`` over ``k`` / ``v``
        ``[n, s, KV, hd]`` at ``kpos [n, s]`` -> ``[n, b, KV, G, hd]``."""
        hd = q.shape[-1]
        s = jnp.einsum("nbkgd,nskd->nkgbs", q, k,
                       preferred_element_type=jnp.float32) \
            * (1.0 / jnp.sqrt(jnp.float32(hd)))
        at = lambda p, ax: jnp.expand_dims(p, ax)
        ok = at(qpos, (1, 2, 4)) >= at(kpos, (1, 2, 3))
        if window is not None:
            ok = ok & (at(kpos, (1, 2, 3)) > at(qpos, (1, 2, 4)) - window)
        s = jnp.where(ok, s, jnp.finfo(jnp.float32).min)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("nkgbs,nskd->nbkgd", w, v)

    def attend(self, kind, i, q, k, v, pos):
        n, t, H, hd = q.shape
        KV = k.shape[2]
        window = self.window if kind == SLIDING else None
        q = q.reshape(n, t, KV, H // KV, hd)
        b = self.block
        if t <= b or t % b:
            return self._scores(q, k, v, pos, pos, window) \
                .reshape(n, t, H * hd)
        # the keys a block can reach: all of them, or the block's own
        # and the window before it
        span = t if window is None else min(t, b + -(-window // b) * b)

        def one(j):
            cut = lambda a: lax.dynamic_slice_in_dim(a, j * b, b, axis=1)
            start = jnp.clip((j + 1) * b - span, 0, t - span)
            reach = lambda a: lax.dynamic_slice_in_dim(a, start, span,
                                                       axis=1)
            return self._scores(cut(q), reach(k), reach(v), cut(pos),
                                reach(pos), window)

        out = lax.map(one, jnp.arange(t // b))     # [t / b, n, b, ...]
        return jnp.moveaxis(out, 0, 1).reshape(n, t, H * hd)


class _Prefill(_Dense):
    """A padded prompt of ``t0`` real tokens: keeps each global layer's
    K/V for the page commit and each window layer's ring as it stands
    after position ``t0 - 1``."""

    def __init__(self, window, block, t0, ring_pages, page):
        super().__init__(window, block)
        self.t0, self.R, self.page = t0, ring_pages, page
        self.ks, self.vs, self.ring_k, self.ring_v = [], [], [], []

    def _ring(self, c):
        """``c [1, B, KV, hd]`` -> the ring ``[R, page, KV * hd]`` of
        a sequence whose last position is ``t0 - 1``: entry ``r`` is
        the newest page number ``<=`` the last one with remainder ``r``
        (zeros where the sequence is shorter). Pages past the last
        real one, a bucket's padding, are never taken."""
        _, B, KV, hd = c.shape
        if B % self.page:
            raise ValueError(f"a prompt bucket of {B} is no multiple of "
                             f"the ring's page of {self.page}")
        rows = c[0].reshape(B // self.page, self.page, KV * hd)
        last = (self.t0 - 1) // self.page
        r = jnp.arange(self.R, dtype=jnp.int32)
        number = last - (last - r) % self.R
        got = rows[jnp.maximum(number, 0)]
        return jnp.where((number >= 0)[:, None, None], got, 0)

    def attend(self, kind, i, q, k, v, pos):
        if kind == SLIDING:
            self.ring_k.append(self._ring(k))
            self.ring_v.append(self._ring(v))
        else:
            self.ks.append(k.transpose(0, 2, 1, 3))       # [1, KV, B, hd]
            self.vs.append(v.transpose(0, 2, 1, 3))
        return super().attend(kind, i, q, k, v, pos)


class _Paged:
    """One token a slot: a global layer's K/V appended to the slot's
    page and read through the page tables; a window layer's appended to
    the slot's ring and read through the ring's table, the window's
    pages only."""

    def __init__(self, kv, state, tables, pos, page_size, mode, cfg):
        self.kv, self.tables, self.pos, self.mode = kv, tables, pos, mode
        self.page = jnp.take_along_axis(
            tables, (pos // page_size)[:, None], axis=1)[:, 0]
        self.off = pos % page_size
        # the rings as a pool of their own: slot s's entry r is page
        # s * R + r (a leading-dimension merge: no data moves)
        Lw, S, R, rp, W = state["k"].shape
        self.shape = state["k"].shape
        self.ring = {n: state[n].reshape(Lw, S * R, rp, W)
                     for n in ("k", "v")}
        self.ring_tables = (jnp.arange(S, dtype=jnp.int32)[:, None] * R
                            + jnp.arange(R, dtype=jnp.int32)[None, :])
        self.ring_page = jnp.take_along_axis(
            self.ring_tables, ((pos // rp) % R)[:, None], axis=1)[:, 0]
        self.ring_off = pos % rp
        self.window = cfg.sliding_window

    def state(self):
        return {n: self.ring[n].reshape(self.shape) for n in ("k", "v")}

    def attend(self, kind, i, q, k, v, pos):
        S, _, H, hd = q.shape
        rows = lambda c: c.reshape(S, -1)     # a position's KV heads
        if kind == SLIDING:
            self.ring = kv_pages.append_token(
                self.ring, i, self.ring_page, self.ring_off, rows(k),
                rows(v))
            ctx = paged_attention(q, self.ring, i, self.ring_tables,
                                  self.pos, mode=self.mode,
                                  window=self.window)
        else:
            self.kv = kv_pages.append_token(
                self.kv, i, self.page, self.off, rows(k), rows(v))
            ctx = paged_attention(q, self.kv, i, self.tables, self.pos,
                                  mode=self.mode)
        return ctx.reshape(S, 1, H * hd)


class ExaoneMoeLM:
    """The model; ``compute_dtype`` is the dtype of its parameters and
    activations alike."""

    #: queries a block of a long prefill's attention (``_Dense``)
    query_block = 256

    def __init__(self, config: ExaoneMoeConfig, compute_dtype=jnp.bfloat16):
        self.cfg = config
        self._cdtype = jnp.dtype(compute_dtype)
        t = config.layer_types
        #: model layer -> index among its kind (the pool's layer, the
        #: rings' layer)
        self.kind_index = {}
        for kind in (SLIDING, FULL):
            self.kind_index.update({li: i for i, li in enumerate(
                l for l in range(len(t)) if t[l] == kind)})
        self.n_window = t.count(SLIDING)
        self.n_global = t.count(FULL)
        if not self.n_window or not self.n_global:
            raise ValueError("the model is written for window and global "
                             "layers in one stack: layer_types has "
                             f"{self.n_window} and {self.n_global}")

    def cache_spec(self) -> Dict[str, Any]:
        """What a serving engine holds for this model: K/V pages for
        the ``kv_layers`` GLOBAL layers, and a per-slot ``state`` of
        the window layers' rings, ``{"k", "v"}`` of ``(window layers,
        ring pages, page, KV heads x head)``; ``window`` is the
        positions a window layer attends (the engine counts them on its
        spans)."""
        c = self.cfg
        ring = (self.n_window, c.ring_pages, c.window_page_size,
                c.num_key_value_heads * c.head_dim)
        return {"kv_layers": self.n_global,
                "kv_heads": c.num_key_value_heads, "head_dim": c.head_dim,
                "state": {"k": ring, "v": ring},
                "window": c.sliding_window}

    # -- params ---------------------------------------------------------
    def init_params(self, key=None) -> Dict[str, Any]:
        c, cd = self.cfg, self._cdtype
        key = key if key is not None else jax.random.key(0)
        d, hd, E = c.hidden_size, c.head_dim, c.num_experts
        H, KV = c.num_attention_heads, c.num_key_value_heads
        std = c.initializer_range
        res = std / (2 * c.num_hidden_layers) ** 0.5
        keys = iter(jax.random.split(key, 3 + 16 * c.num_hidden_layers))

        def n(shape, s):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * s).astype(cd)

        one = lambda m: jnp.ones((m,), cd)
        p = {"tok_emb": n((c.vocab_size, d), std), "final_norm": one(d),
             "head": n((d, c.vocab_size), std), "layers": []}
        for kind in c.mlp_layer_types:
            lp = {"in_norm": one(d), "post_norm": one(d),
                  "wq": n((d, H * hd), std), "wk": n((d, KV * hd), std),
                  "wv": n((d, KV * hd), std), "q_norm": one(hd),
                  "k_norm": one(hd), "wo": n((H * hd, d), res)}
            if kind == "dense":
                f = c.intermediate_size
                lp.update(w1=n((d, f), std), w3=n((d, f), std),
                          w2=n((f, d), res))
            else:
                f = c.moe_intermediate_size
                fs = f * c.num_shared_experts
                lp.update(router=n((d, c.n_routed_experts), std),
                          router_bias=n((c.n_routed_experts,), std),
                          ew1=n((E, d, f), std), ew3=n((E, d, f), std),
                          ew2=n((E, f, d), res),
                          sw1=n((d, fs), std), sw3=n((d, fs), std),
                          sw2=n((fs, d), res))
            p["layers"].append(lp)
        return p

    # -- pieces ---------------------------------------------------------
    def _rms(self, x, g):
        return rms_norm(x, g, self.cfg.rms_norm_eps)

    def _rope(self, x, pos):
        return rope(x, pos, self.cfg.rope_theta)

    def _attn(self, li, lp, h, cache, pos):
        c = self.cfg
        n, t, _ = h.shape
        kind = c.layer_types[li]
        heads = lambda y, m: y.reshape(n, t, m, c.head_dim)
        q = self._rms(heads(h @ lp["wq"], c.num_attention_heads),
                      lp["q_norm"])
        k = self._rms(heads(h @ lp["wk"], c.num_key_value_heads),
                      lp["k_norm"])
        v = heads(h @ lp["wv"], c.num_key_value_heads)
        if kind == SLIDING:     # a global layer has no positions at all
            q, k = self._rope(q, pos), self._rope(k, pos)
        return cache.attend(kind, self.kind_index[li], q, k, v, pos) \
            @ lp["wo"]

    def route(self, lp, x):
        """``x [m, d]`` -> (the router's choices ``[m, k]`` among ALL
        its outputs, weights ``[m, k]`` float32): sigmoid scores, the
        bias selects and does not weigh, the selected scores normalised
        and scaled."""
        c = self.cfg
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), lp["router"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        _, idx = lax.top_k(s + lp["router_bias"].astype(jnp.float32),
                           c.num_experts_per_tok)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if c.norm_topk_prob:
            w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
        return idx, w * c.routed_scaling_factor

    def _swiglu(self, h, w1, w3, w2):
        return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2

    def experts(self, lp, x, live=None, mode=None):
        """The sparse feed-forward of ``x [m, d]`` -> ``([m, d], stats,
        idx)``: the routed experts HELD here, dropless, plus the shared
        expert (neither routed nor scaled). ``stats`` = int32
        (assignments to held experts, distinct held experts touched,
        the most any got, assignments routed held or not), of live
        rows."""
        c = self.cfg
        idx, w = self.route(lp, x)
        out, counts = routed_experts(
            x, idx, w, lp["ew1"], lp["ew3"], lp["ew2"],
            first=c.expert_offset, routed=c.n_routed_experts, live=live,
            mode=mode)
        rows = x.shape[0] if live is None else jnp.sum(live)
        stats = jnp.concatenate([expert_stats(counts), jnp.reshape(
            rows * c.num_experts_per_tok, (1,)).astype(jnp.int32)])
        return out + self._swiglu(x, lp["sw1"], lp["sw3"], lp["sw2"]), \
            stats, idx

    def _block(self, li, lp, x, pos, cache, live, mode, aux):
        c = self.cfg
        x = x + self._attn(li, lp, self._rms(x, lp["in_norm"]), cache, pos)
        h = self._rms(x, lp["post_norm"])
        if c.mlp_layer_types[li] == "dense":
            return x + self._swiglu(h, lp["w1"], lp["w3"], lp["w2"])
        n, t, d = h.shape
        y, stats, idx = self.experts(
            lp, h.reshape(n * t, d),
            None if live is None else live.reshape(n * t), mode)
        aux["stats"].append(stats)
        aux["experts"].append(idx.reshape(n, t, -1))
        return x + y.reshape(n, t, d)

    def _run(self, params, x, pos, cache, live, mode):
        aux = {"stats": [], "experts": []}
        for li, lp in enumerate(params["layers"]):
            x = self._block(li, lp, x, pos, cache, live, mode, aux)
        return self._rms(x, params["final_norm"]), aux

    def _head(self, x, params):
        return jnp.dot(x, params["head"],
                       preferred_element_type=jnp.float32)

    def _stats(self, aux):
        return jnp.stack(aux["stats"]) if aux["stats"] else None

    # -- the three callers ---------------------------------------------
    def forward(self, params, ids, return_aux=False, mode=None):
        """ids ``[n, t]`` -> logits ``[n, t, V]`` (float32), causal, no
        cache. With ``return_aux`` also the router's choices, a list of
        ``[n, t, k]`` per sparse layer."""
        n, t = ids.shape
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (n, t))
        x, aux = self._run(params, params["tok_emb"][ids], pos,
                           _Dense(self.cfg.sliding_window,
                                  self.query_block), None, mode)
        logits = self._head(x, params)
        return (logits, aux["experts"]) if return_aux else logits

    def prefill(self, params, prompt, t0, mode=None):
        """A padded prompt ``[1, B]`` of ``t0`` real tokens ->
        ``(ks, vs, state, last, stats)``: the global layers' K/V
        ``[layers, 1, KV, B, hd]``, the window layers' rings ``{"k",
        "v"}`` of ``[layers, R, page, KV * hd]`` after position ``t0 -
        1``, the logits of that position (float32) and the sparse
        layers' stats ``[layers, 4]`` over real positions only."""
        c = self.cfg
        B = prompt.shape[1]
        pos = jnp.arange(B, dtype=jnp.int32)[None]
        cache = _Prefill(c.sliding_window, self.query_block, t0,
                         c.ring_pages, c.window_page_size)
        x, aux = self._run(params, params["tok_emb"][prompt], pos, cache,
                           pos < t0, mode)
        last = lax.dynamic_index_in_dim(x[0], t0 - 1, axis=0, keepdims=False)
        state = {"k": jnp.stack(cache.ring_k), "v": jnp.stack(cache.ring_v)}
        return (jnp.stack(cache.ks), jnp.stack(cache.vs), state,
                self._head(last, params), self._stats(aux))

    def decode_step(self, params, kv, state, tables, pos, tok, active,
                    page_size, mode=None):
        """One token a slot: ``tok [S]`` at ``pos [S]`` -> ``(kv,
        state, logits [S, V], stats)``. Slots that are not ``active``
        write a global layer's row to the null page and a window
        layer's into their own ring (rewritten whole at the slot's next
        admission), go to no expert and keep nothing worth reading."""
        cache = _Paged(kv, state, tables, pos, page_size, mode, self.cfg)
        x, aux = self._run(params, params["tok_emb"][tok][:, None],
                           pos[:, None], cache, active[:, None], mode)
        return (cache.kv, cache.state(), self._head(x[:, 0], params),
                self._stats(aux))


__all__ = ["ExaoneMoeConfig", "ExaoneMoeLM"]
