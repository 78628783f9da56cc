"""What the served sparse-expert decoders share, written once.

``ExpertDecoder`` is the residual stack (``models/exaone_moe.py``,
``models/glm_moe_dsa.py``, ``models/mellum.py``):

    h <- h + Attn_l(RMS(h; g_in));  h <- h + FF_l(RMS(h; g_post))
    logits = RMS(h_L; g_f) W_head

where ``FF_l`` is a dense SwiGLU (``mlp_layer_types`` ``dense``) or the
sparse feed-forward: the model's router, the routed experts HELD here
(``models/routed_experts.py``: the range ``[expert_offset,
expert_offset + num_experts)`` of the router's ``n_routed_experts``
outputs), and a shared expert where the layer has one (leaves ``sw1`` /
``sw3`` / ``sw2``), neither routed nor scaled. A model brings its
config, its ``_attn(li, lp, h, cache, pos)`` and its ``route(lp, x)``
(``route_sigmoid`` / ``route_softmax`` in ``models/routed_experts.py``).

``WindowDecoder`` adds the three callers of a stack of
sliding-window and full (global) attention layers, the block written
once and serving each by how it reads and writes cache (the ``_Dense``
/ ``_Prefill`` / ``_Paged`` objects below):

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
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.models.decoder_ops import rms_norm
from deeplearning4j_tpu.models.routed_experts import (expert_stats,
                                                      routed_experts)
from deeplearning4j_tpu.ops.paged_attention_pallas import paged_attention
from deeplearning4j_tpu.serving import kv_pages

SLIDING, FULL = "sliding_attention", "full_attention"


class ExpertDecoder:
    """The residual stack and the sparse feed-forward. ``self.cfg``
    names ``rms_norm_eps``, ``mlp_layer_types``, ``num_experts_per_tok``,
    ``expert_offset`` and ``n_routed_experts``."""

    def _rms(self, x, g):
        return rms_norm(x, g, self.cfg.rms_norm_eps)

    def _swiglu(self, h, w1, w3, w2):
        return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2

    def experts(self, lp, x, live=None, mode=None):
        """The sparse feed-forward of ``x [m, d]`` -> ``([m, d], stats,
        idx)``: the routed experts HELD here, dropless, plus the shared
        expert where the layer has one. ``stats`` = int32 (assignments
        to held experts, distinct held experts touched, the most any
        got, assignments routed held or not), of live rows."""
        c = self.cfg
        idx, w = self.route(lp, x)
        out, counts = routed_experts(
            x, idx, w, lp["ew1"], lp["ew3"], lp["ew2"],
            first=c.expert_offset, routed=c.n_routed_experts, live=live,
            mode=mode)
        rows = x.shape[0] if live is None else jnp.sum(live)
        stats = jnp.concatenate([expert_stats(counts), jnp.reshape(
            rows * c.num_experts_per_tok, (1,)).astype(jnp.int32)])
        if "sw1" in lp:
            out = out + self._swiglu(x, lp["sw1"], lp["sw3"], lp["sw2"])
        return out, stats, idx

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


class WindowDecoder(ExpertDecoder):
    """A stack of window and global attention layers over the cache of
    two kinds; ``compute_dtype`` is the dtype of its parameters and
    activations alike. ``self.cfg`` also names ``layer_types``,
    ``sliding_window``, ``window_page_size``, ``ring_pages``,
    ``num_key_value_heads`` and ``head_dim``."""

    #: queries a block of a long prefill's attention (``_Dense``)
    query_block = 256

    def __init__(self, config, compute_dtype=jnp.bfloat16):
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


__all__ = ["ExpertDecoder", "WindowDecoder", "SLIDING", "FULL"]
