"""LFM2-MoE causal language model (LiquidAI ``lfm2_moe``; HF
``Lfm2MoeForCausalLM``): gated short-convolution layers interleaved
with grouped-query attention layers, a dense SwiGLU in the leading
layers and sigmoid-routed dropless SwiGLU experts in the rest, RMSNorm
everywhere, rotary positions, the head tied to the embedding.

    h <- h + Op_l(RMS(h; g_op));  h <- h + FF_l(RMS(h; g_ffn))
    logits = RMS(h_L; g_emb) E^T

The block is written ONCE (``_block``) and serves three callers by how
it reads and writes cache (the ``_Dense`` / ``_Prefill`` / ``_Paged``
objects below):

- ``forward``: no cache; logits of every position (tests, a loss).
- ``prefill``: one padded prompt; returns the attention layers' K/V
  for the engine's page commit and each conv layer's state at the last
  REAL position ``t0`` (never the padded bucket's end).
- ``decode_step``: one token a slot, K/V through the page pool and the
  paged kernel, the conv state carried per slot.

What a serving engine has to hold for this model is what
``cache_spec()`` says: pages for the attention layers only, at the KV
head count, and a per-slot conv state ``[conv layers, slots, L, d]``.

Parameters are created and held in the compute dtype (bfloat16 as
served): no weight is cast inside a call. The router's product, its
sigmoid, the top-k and the weights' normalisation run in float32, as
do the norms' statistics. Expert products are dropless: assignments
are sorted by expert and multiplied group by group
(``ops/grouped_matmul_pallas.py``), reading only experts that got
tokens; rows of dead slots and of a bucket's padding are given to no
expert at all.
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


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """The published ``config.json`` keys that shape the model."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = ()
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: Optional[int] = None
    conv_L_cache: int = 3
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    max_position_embeddings: int = 128000
    initializer_range: float = 0.02

    def __post_init__(self):
        set_ = lambda k, v: object.__setattr__(self, k, v)
        set_("layer_types", tuple(self.layer_types))
        if self.head_dim is None:
            set_("head_dim", self.hidden_size // self.num_attention_heads)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"{len(self.layer_types)} layer_types for "
                f"{self.num_hidden_layers} layers")
        bad = set(self.layer_types) - {"conv", "full_attention"}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide into the KV heads")

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


# ------------------------------------------------ how a caller caches
class _Dense:
    """No cache: a fresh sequence, attention over the call's own
    positions."""

    def __init__(self, k):
        self.k = k                   # the filter's length

    def conv_prev(self, ci, like):
        n, _, d = like.shape
        return jnp.zeros((n, self.k - 1, d), like.dtype)

    def conv_put(self, ci, window):
        pass

    def attend(self, ai, q, k, v, pos):
        n, t, H, hd = q.shape
        KV = k.shape[2]
        q = q.reshape(n, t, KV, H // KV, hd)
        s = jnp.einsum("ntkgd,nskd->nkgts", q, k,
                       preferred_element_type=jnp.float32) \
            * (1.0 / jnp.sqrt(jnp.float32(hd)))
        causal = pos[:, None, None, :, None] >= pos[:, None, None, None, :]
        s = jnp.where(causal, s, jnp.finfo(jnp.float32).min)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("nkgts,nskd->ntkgd", w, v).reshape(n, t, H * hd)


class _Prefill(_Dense):
    """A padded prompt of ``t0`` real tokens: keeps each attention
    layer's K/V and each conv layer's window at ``t0``."""

    def __init__(self, k, t0):
        super().__init__(k)
        self.t0, self.ks, self.vs, self.states = t0, [], [], []

    def conv_put(self, ci, window):
        # window[:, i] is u at position i - (k - 1): the k entries that
        # end at the last real position t0 - 1 start at window index
        # t0 - 1 (zeros where the sequence is shorter than k)
        self.states.append(lax.dynamic_slice_in_dim(
            window[0], self.t0 - 1, self.k, axis=0))

    def attend(self, ai, q, k, v, pos):
        self.ks.append(k.transpose(0, 2, 1, 3))       # [1, KV, B, hd]
        self.vs.append(v.transpose(0, 2, 1, 3))
        return super().attend(ai, q, k, v, pos)


class _Paged:
    """One token a slot: K/V appended to the slot's page, attention
    through the page tables, the conv window rolled per slot."""

    def __init__(self, kv, state, tables, pos, page_size, mode):
        self.kv, self.state, self.tables, self.pos = kv, state, tables, pos
        self.page = jnp.take_along_axis(
            tables, (pos // page_size)[:, None], axis=1)[:, 0]
        self.off = pos % page_size
        self.mode = mode
        self.new_state = []

    def conv_prev(self, ci, like):
        return self.state[ci][:, 1:]

    def conv_put(self, ci, window):
        self.new_state.append(window)                 # [S, k, d]

    def attend(self, ai, q, k, v, pos):
        S, _, H, hd = q.shape
        # a position's KV heads side by side are a page row
        self.kv = kv_pages.append_token(
            self.kv, ai, self.page, self.off, k.reshape(S, -1),
            v.reshape(S, -1))
        ctx = paged_attention(q, self.kv, ai, self.tables, self.pos,
                              mode=self.mode)
        return ctx.reshape(S, 1, H * hd)


class Lfm2MoeLM:
    """The model; ``compute_dtype`` is the dtype of its parameters and
    activations alike."""

    def __init__(self, config: Lfm2MoeConfig, compute_dtype=jnp.bfloat16):
        self.cfg = config
        self._cdtype = jnp.dtype(compute_dtype)
        t = config.layer_types
        #: model layer -> index among its kind (the pool's layer, the
        #: state's layer)
        self.attn_index = {li: i for i, li in enumerate(
            l for l in range(len(t)) if t[l] == "full_attention")}
        self.conv_index = {li: i for i, li in enumerate(
            l for l in range(len(t)) if t[l] == "conv")}
        self.n_moe = config.num_hidden_layers - config.num_dense_layers

    def cache_spec(self) -> Dict[str, Any]:
        """What a serving engine holds for this model: K/V pages for
        ``kv_layers`` layers of ``kv_heads`` heads, and a per-slot
        state of shape ``state`` (conv layers, window, width)."""
        c = self.cfg
        return {"kv_layers": len(self.attn_index),
                "kv_heads": c.num_key_value_heads, "head_dim": c.head_dim,
                "state": (len(self.conv_index), c.conv_L_cache,
                          c.hidden_size)}

    # -- params ---------------------------------------------------------
    def init_params(self, key=None) -> Dict[str, Any]:
        c, cd = self.cfg, self._cdtype
        key = key if key is not None else jax.random.key(0)
        d, hd, E = c.hidden_size, c.head_dim, c.num_experts
        std = c.initializer_range
        res = std / (2 * c.num_hidden_layers) ** 0.5
        keys = iter(jax.random.split(key, 2 + 12 * c.num_hidden_layers))

        def n(shape, s):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * s).astype(cd)

        one = lambda m: jnp.ones((m,), cd)
        p = {"tok_emb": n((c.vocab_size, d), std), "emb_norm": one(d),
             "layers": []}
        for li, kind in enumerate(c.layer_types):
            lp = {"op_norm": one(d), "ffn_norm": one(d)}
            if kind == "conv":
                lp.update(w_in=n((d, 3 * d), std),
                          conv_w=n((c.conv_L_cache, d), std),
                          w_out=n((d, d), res))
            else:
                H, KV = c.num_attention_heads, c.num_key_value_heads
                lp.update(wq=n((d, H * hd), std), wk=n((d, KV * hd), std),
                          wv=n((d, KV * hd), std), q_norm=one(hd),
                          k_norm=one(hd), wo=n((H * hd, d), res))
            if li < c.num_dense_layers:
                f = c.intermediate_size
                lp.update(w1=n((d, f), std), w3=n((d, f), std),
                          w2=n((f, d), res))
            else:
                f = c.moe_intermediate_size
                lp.update(router=n((d, E), std), router_bias=n((E,), std),
                          ew1=n((E, d, f), std), ew3=n((E, d, f), std),
                          ew2=n((E, f, d), res))
            p["layers"].append(lp)
        return p

    # -- pieces ---------------------------------------------------------
    def _rms(self, x, g):
        return rms_norm(x, g, self.cfg.norm_eps)

    def _rope(self, x, pos):
        return rope(x, pos, self.cfg.rope_theta)

    def _conv(self, lp, h, ci, cache):
        k = self.cfg.conv_L_cache
        b, c, x = jnp.split(h @ lp["w_in"], 3, axis=-1)
        u = b * x
        window = jnp.concatenate([cache.conv_prev(ci, u), u], axis=1)
        cache.conv_put(ci, window)
        t = u.shape[1]
        v = sum(lp["conv_w"][j] * window[:, j:j + t] for j in range(k))
        return (c * v) @ lp["w_out"]

    def _attn(self, lp, h, ai, cache, pos):
        c = self.cfg
        n, t, _ = h.shape
        heads = lambda y, m: y.reshape(n, t, m, c.head_dim)
        q = heads(h @ lp["wq"], c.num_attention_heads)
        k = heads(h @ lp["wk"], c.num_key_value_heads)
        v = heads(h @ lp["wv"], c.num_key_value_heads)
        q = self._rope(self._rms(q, lp["q_norm"]), pos)
        k = self._rope(self._rms(k, lp["k_norm"]), pos)
        return cache.attend(ai, q, k, v, pos) @ lp["wo"]

    def route(self, lp, x):
        """``x [m, d]`` -> (expert ids ``[m, k]``, weights ``[m, k]``
        float32): sigmoid scores, the bias selects and does not weigh,
        the selected scores normalised."""
        c = self.cfg
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), lp["router"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        sel = s + lp["router_bias"].astype(jnp.float32) \
            if c.use_expert_bias else s
        _, idx = lax.top_k(sel, c.num_experts_per_tok)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if c.norm_topk_prob:
            w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
        return idx, w * c.routed_scaling_factor

    def experts(self, lp, x, live=None, mode=None):
        """The dropless expert feed-forward of ``x [m, d]`` ->
        ``([m, d], stats, idx)``. ``live [m]`` (bool) marks rows that
        are real: the others go to no expert and come back zero.
        ``stats`` = int32 (assignments, distinct experts touched, the
        most any expert got), of live rows. Every expert is held here;
        the product itself is ``models/routed_experts.py``'s."""
        idx, w = self.route(lp, x)
        out, counts = routed_experts(x, idx, w, lp["ew1"], lp["ew3"],
                                     lp["ew2"], live=live, mode=mode)
        return out, expert_stats(counts), idx

    def _block(self, li, lp, x, pos, cache, live, mode, aux):
        c = self.cfg
        h = self._rms(x, lp["op_norm"])
        if c.layer_types[li] == "conv":
            x = x + self._conv(lp, h, self.conv_index[li], cache)
        else:
            x = x + self._attn(lp, h, self.attn_index[li], cache, pos)
        h = self._rms(x, lp["ffn_norm"])
        if li < c.num_dense_layers:
            return x + (jax.nn.silu(h @ lp["w1"]) * (h @ lp["w3"])) \
                @ lp["w2"]
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
        return self._rms(x, params["emb_norm"]), aux

    def _head(self, x, params):
        return jnp.dot(x, params["tok_emb"].T,
                       preferred_element_type=jnp.float32)

    # -- the three callers ---------------------------------------------
    def forward(self, params, ids, return_aux=False, mode=None):
        """ids ``[n, t]`` -> logits ``[n, t, V]`` (float32), causal, no
        cache. With ``return_aux`` also the experts each token took, a
        list of ``[n, t, k]`` per expert layer."""
        n, t = ids.shape
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (n, t))
        x, aux = self._run(params, params["tok_emb"][ids], pos,
                           _Dense(self.cfg.conv_L_cache), None, mode)
        logits = self._head(x, params)
        return (logits, aux["experts"]) if return_aux else logits

    def lm_loss(self, params, ids):
        """Mean next-token cross entropy of ids[:, :-1] -> ids[:, 1:]."""
        lg = self.forward(params, ids[:, :-1])
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
        return jnp.mean(lse - picked)

    def prefill(self, params, prompt, t0, mode=None):
        """A padded prompt ``[1, B]`` of ``t0`` real tokens ->
        ``(ks, vs, state, last, stats)``: the attention layers' K/V
        ``[layers, 1, KV, B, hd]``, the conv layers' windows at ``t0``
        ``[layers, L, d]``, the logits of position ``t0 - 1`` (float32)
        and the expert layers' stats ``[layers, 3]`` over real
        positions only."""
        B = prompt.shape[1]
        pos = jnp.arange(B, dtype=jnp.int32)[None]
        cache = _Prefill(self.cfg.conv_L_cache, t0)
        x, aux = self._run(params, params["tok_emb"][prompt], pos, cache,
                           pos < t0, mode)
        last = lax.dynamic_index_in_dim(x[0], t0 - 1, axis=0, keepdims=False)
        return (jnp.stack(cache.ks), jnp.stack(cache.vs),
                jnp.stack(cache.states), self._head(last, params),
                jnp.stack(aux["stats"]))

    def decode_step(self, params, kv, state, tables, pos, tok, active,
                    page_size, mode=None):
        """One token a slot: ``tok [S]`` at ``pos [S]`` ->
        ``(kv, state, logits [S, V], stats)``. Slots that are not
        ``active`` write to the null page, go to no expert and keep
        nothing worth reading."""
        cache = _Paged(kv, state, tables, pos, page_size, mode)
        x, aux = self._run(params, params["tok_emb"][tok][:, None],
                           pos[:, None], cache, active[:, None], mode)
        return (cache.kv, jnp.stack(cache.new_state),
                self._head(x[:, 0], params), jnp.stack(aux["stats"]))


__all__ = ["Lfm2MoeConfig", "Lfm2MoeLM"]
