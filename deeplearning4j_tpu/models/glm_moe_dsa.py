"""GLM-MoE-DSA causal language model (zai-org ``glm_moe_dsa``, GLM-5.2):
multi-head LATENT attention (MLA) that reads only the positions a
learned indexer selects (DeepSeek-V3.2's sparse attention), the
selection computed on the ``"full"`` layers of ``indexer_types`` and
reused on the ``"shared"`` ones after them (IndexShare); a dense SwiGLU
in the leading layers and, in the rest, sigmoid-routed dropless SwiGLU
experts beside a shared expert; RMSNorm on every sub-layer's input, an
output head of its own.

    h <- h + Attn_l(RMS(h; g_in));  h <- h + FF_l(RMS(h; g_post))
    logits = RMS(h_L; g_f) W_head

Attention of position ``t`` (``x = RMS(h; g_in)``; ``H`` heads, ``dn``
/ ``dr`` the no-position and the rotary width of a head's query and
key, ``dv`` of its value, ``C = kv_lora_rank``):

    c_q = RMS(x W_qa; g_q);  [q_nope | q_rope]_j = (c_q W_qb)_j
    [c_kv | k_r] = x W_kva;  c_kv <- RMS(c_kv; g_kv)
    q_rope, k_r <- RoPE_t(.)      (interleaved pairs; k_r ONE head for all)
    [k_nope | v]_j = c_kv,s W_kvb_j
    a_{t,s,j} = (q_nope_j . k_nope_{s,j} + q_rope_j . k_r,s) / sqrt(dn + dr)
    o_j = sum_{s in S_t} softmax_s(a)_s v_{s,j};  Attn = concat(o) W_o

``S_t`` is the indexer's selection. On a ``"full"`` layer

    q^I_i = (c_q W^I_q)_i,  k^I_s = LayerNorm(x_s W^I_k),  w_i = (x W^I_w)_i
    (both rotated on their first ``dr`` lanes)
    I_{t,s} = sum_i w_i relu(q^I_i . k^I_s)   for s <= t
    S_t = the index_topk largest of I_{t,.}   (ties to the lower position;
                                               every s <= t while t < index_topk)

and a ``"shared"`` layer attends the ``S_t`` of the nearest ``"full"``
layer before it: it has no indexer parameters and caches no indexer
keys. The top-k is exact.

What a position leaves in the cache is its LATENT row ``[c_kv | k_r]``
(``C + dr`` numbers: 576 for GLM-5.2, where per-head K and V would be
64 x 448), and on a full layer its indexer key ``k^I`` (128). Decode
reads them in the ABSORBED form: ``q~_j = q_nope_j (W_kvb^K_j)^T`` (C
wide), ``a = (q~_j . c_kv,s + q_rope_j . k_r,s) / sqrt(dn + dr)``,
``ctx_j = sum p c_kv,s``, ``o_j = ctx_j W_kvb^V_j``: K and V are never
rebuilt for the positions held.

A chip may hold its SHARE of a layer (``num_experts`` of the router's
``n_routed_experts`` outputs from ``expert_offset``; ``vocab_size``
rows of the embedding and of the head), exactly as
``models/exaone_moe.py``: attention, indexer, router, shared expert and
dense layer are whole (a latent row cannot be split by head).

The block, the sparse feed-forward and the layer loop are
``models/sparse_decoder.py``'s ``ExpertDecoder`` (shared with
``models/exaone_moe.py`` and ``models/mellum.py``); the block serves
three callers by how it reads and writes cache (``_Dense`` /
``_Prefill`` / ``_Paged``):

- ``forward``: no cache; logits of every position. The selection is a
  mask, made and applied a block of queries at a time.
- ``prefill``: one padded prompt through the same dense-masked path;
  returns a row a position for each of the pool's stores.
- ``decode_step``: one token a slot; each layer writes its latent row
  (a full layer its indexer key too) through the slot's page table,
  then ``ops/sparse_latent_attention_pallas.py``: ``index_select`` on a
  full layer, ``sparse_latent_attention`` over the selected rows on
  every layer.

The pool is stores of the model's own (``cache_spec()``): ``"latent"``
``[layers, n_pages, page, latent_row]`` and ``"index_k"`` ``[full
layers, n_pages, page, index_head_dim]`` under ONE page table a slot.
The latent row rests padded to whole lane tiles (``latent_row``: 640
for 576): at 576 lanes the device keeps the store with the page
dimension minor-most and every program re-lays it out at its boundary
(PERF.md section 6, PR 34; ``tests/test_kv_layout_aot.py``).

Parameters are created and held in the compute dtype (bfloat16 as
served). The router's product, its sigmoid, the top-k and the weights'
normalisation run in float32, as do the norms' statistics, the
indexer's scores and the softmax.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.models.decoder_ops import rope_interleaved
from deeplearning4j_tpu.models.routed_experts import route_sigmoid
from deeplearning4j_tpu.models.sparse_decoder import ExpertDecoder
from deeplearning4j_tpu.ops.sparse_latent_attention_pallas import (
    index_select, selection_reads, sparse_latent_attention)
from deeplearning4j_tpu.serving import kv_pages

FULL, SHARED = "full", "shared"
#: lanes a stored row is padded to a multiple of
LANES = 128


@dataclasses.dataclass(frozen=True)
class GlmMoeDsaConfig:
    """The published ``config.json`` keys that shape the model, and the
    share of it held here: ``num_experts`` counts the experts HELD
    (``n_routed_experts`` is the router's published width;
    ``expert_offset`` the first one held), ``vocab_size`` the rows of
    the embedding and of the head held."""

    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 78
    indexer_types: Tuple[str, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    n_routed_experts: int = 256
    num_experts: Optional[int] = None
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    rope_theta: float = 8e6
    rms_norm_eps: float = 1e-5
    index_norm_eps: float = 1e-6
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    rope_interleave: bool = True
    indexer_rope_interleave: bool = True
    attention_bias: bool = False
    num_nextn_predict_layers: int = 0
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 1048576
    initializer_range: float = 0.02

    def __post_init__(self):
        set_ = lambda k, v: object.__setattr__(self, k, v)
        set_("indexer_types", tuple(self.indexer_types))
        set_("mlp_layer_types", tuple(self.mlp_layer_types))
        if self.num_experts is None:
            set_("num_experts", self.n_routed_experts)
        L = self.num_hidden_layers
        if len(self.indexer_types) != L or len(self.mlp_layer_types) != L:
            raise ValueError(
                f"{len(self.indexer_types)} indexer_types and "
                f"{len(self.mlp_layer_types)} mlp_layer_types for {L} "
                "layers")
        bad = (set(self.indexer_types) - {FULL, SHARED}) \
            | (set(self.mlp_layer_types) - {"dense", "sparse"})
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.indexer_types[0] != FULL:
            raise ValueError("the first layer has no selection to share: "
                             "its indexer_types entry must be 'full'")
        if self.qk_rope_head_dim > self.index_head_dim \
                or self.qk_rope_head_dim % 2:
            raise ValueError(
                f"{self.qk_rope_head_dim} rotary lanes in an indexer head "
                f"of {self.index_head_dim}")
        if not 0 <= self.expert_offset \
                <= self.n_routed_experts - self.num_experts:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset} + "
                f"{self.num_experts}) are not among the router's "
                f"{self.n_routed_experts}")
        for name, want in (("scoring_func", "sigmoid"), ("n_group", 1),
                           ("topk_group", 1), ("topk_method", "noaux_tc"),
                           ("rope_interleave", True),
                           ("indexer_rope_interleave", True),
                           ("attention_bias", False),
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
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """Lanes of a stored latent row: ``kv_lora_rank +
        qk_rope_head_dim`` in whole lane tiles."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // LANES) \
            * LANES


def _latent_rows(cfg, c_kv, k_r):
    """``[c_kv | k_r | zeros]``: a position's row as the latent store
    holds it, ``latent_row`` lanes."""
    pad = cfg.latent_row - c_kv.shape[-1] - k_r.shape[-1]
    return jnp.pad(jnp.concatenate([c_kv, k_r], -1),
                   [(0, 0)] * (c_kv.ndim - 1) + [(0, pad)])


def _by_block(fn, t, block, *cut):
    """``fn`` over the arrays ``cut`` (``[n, t, ...]`` each) a block of
    ``block`` queries at a time -> the results joined along axis 1; one
    call where ``t`` is no more than a block, or not whole blocks."""
    if t <= block or t % block:
        return fn(*cut)
    out = lax.map(lambda j: fn(*(lax.dynamic_slice_in_dim(
        a, j * block, block, axis=1) for a in cut)),
        jnp.arange(t // block))
    return jnp.moveaxis(out, 0, 1).reshape(
        out.shape[1], t, *out.shape[3:])


# ------------------------------------------------ how a caller caches
class _Dense:
    """No cache: a fresh sequence. The selection is a mask ``[n, t,
    t]``, made on a full layer and kept for the shared layers after it;
    indexer scores, their top-k and the attention's scores are made a
    block of queries at a time (an 8,192 bucket's are 17 GB a layer at
    once), the attention's over ``key_chunk`` keys at a time with an
    online softmax between (over 8,192 keys at once the compiler fused
    the score product into the softmax's reductions and a 6,144 bucket
    took 3.7 s, eleven times a 4,096 one: PERF.md section 6, PR 34)."""

    #: keys a step of a block's attention reads
    key_chunk = 2048

    def __init__(self, cfg, block):
        self.c, self.block = cfg, block
        self.mask = None

    def select(self, qi, ki, wi, pos):
        """``qi [n, t, Hi, D]``, ``ki [n, t, D]``, ``wi [n, t, Hi]``
        (float32) -> bool ``[n, t, t]``: query ``t`` may read key ``s``.
        The ``K``-th largest score is the threshold; scores equal to it
        go to the lower positions first, as a stable descending sort
        would place them."""
        t = ki.shape[1]
        K = min(self.c.index_topk, t)

        def one(qb, wb, pb):
            s = jnp.einsum("nbhd,nsd->nbhs", qb, ki,
                           preferred_element_type=jnp.float32)
            I = jnp.sum(jax.nn.relu(s) * wb[..., None], axis=2)
            causal = pb[:, :, None] >= pos[:, None, :]
            I = jnp.where(causal, I, -jnp.inf)
            thr = lax.top_k(I, K)[0][..., -1:]
            above, tie = I > thr, I == thr
            room = K - jnp.sum(above, -1, keepdims=True)
            first = jnp.cumsum(tie, axis=-1) <= room
            return causal & (above | (tie & first))

        return _by_block(one, t, self.block, qi, wi, pos)

    def attend(self, li, lp, q_nope, q_rope, c_kv, k_r, index, pos):
        c = self.c
        n, t, H, _ = q_nope.shape
        if index is not None:
            self.mask = self.select(*index, pos)
        # K and V of every head from the latent; the one rotary key
        # beside each head's own lanes
        k = jnp.concatenate([
            (c_kv @ lp["wkv_k"]).reshape(n, t, H, c.qk_nope_head_dim),
            jnp.broadcast_to(k_r[:, :, None, :],
                             (n, t, H, c.qk_rope_head_dim))], -1)
        v = (c_kv @ lp["wkv_v"]).reshape(n, t, H, c.v_head_dim)
        scale = 1.0 / jnp.sqrt(jnp.float32(c.qk_head_dim))
        kc = self.key_chunk if t > self.key_chunk and \
            t % self.key_chunk == 0 else t
        f32, low = jnp.float32, jnp.finfo(jnp.float32).min

        def one(qb, mb, pb):
            """A block of queries over the keys a chunk at a time, an
            online softmax between the chunks; a chunk no query of the
            block can reach (it lies after them all) is not visited."""
            b = qb.shape[1]

            def chunk(carry, j):
                def visit(carry):
                    m, l, acc = carry
                    cut = lambda a, ax: lax.dynamic_slice_in_dim(
                        a, j * kc, kc, axis=ax)
                    ok = cut(mb, 2)[:, None]
                    s = jnp.einsum("nbhd,nshd->nhbs", qb, cut(k, 1),
                                   preferred_element_type=f32) * scale
                    s = jnp.where(ok, s, low)
                    m_new = jnp.maximum(m, jnp.max(s, -1))
                    alpha = jnp.exp(m - m_new)
                    p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
                    return (m_new, alpha * l + jnp.sum(p, -1),
                            acc * alpha[..., None] + jnp.einsum(
                                "nhbs,nshv->nhbv", p.astype(v.dtype),
                                cut(v, 1), preferred_element_type=f32))

                return lax.cond(j * kc <= jnp.max(pb), visit,
                                lambda carry: carry, carry), None

            start = (jnp.full((n, H, b), low, f32),
                     jnp.zeros((n, H, b), f32),
                     jnp.zeros((n, H, b, c.v_head_dim), f32))
            (_, l, acc), _ = lax.scan(chunk, start, jnp.arange(t // kc))
            # a query selects its own position at least: l > 0
            return jnp.swapaxes(acc / l[..., None], 1, 2).astype(v.dtype)

        out = _by_block(one, t, self.block,
                        jnp.concatenate([q_nope, q_rope], -1), self.mask,
                        pos)
        return out.reshape(n, t, H * c.v_head_dim)


class _Prefill(_Dense):
    """A padded prompt: keeps every layer's latent row and every full
    layer's indexer key of every position, for the page commit."""

    def __init__(self, cfg, block):
        super().__init__(cfg, block)
        self.latent, self.index_k = [], []

    def attend(self, li, lp, q_nope, q_rope, c_kv, k_r, index, pos):
        self.latent.append(_latent_rows(self.c, c_kv[0], k_r[0]))
        if index is not None:
            self.index_k.append(index[1][0])
        return super().attend(li, lp, q_nope, q_rope, c_kv, k_r, index,
                              pos)


class _Paged:
    """One token a slot: its latent row (a full layer's indexer key
    too) appended through the slot's page table, then the selection (a
    full layer makes it, a shared one reads the last made) and the
    absorbed attention over the selected rows."""

    def __init__(self, kv, tables, pos, page_size, mode, cfg, full_index):
        self.kv, self.tables, self.pos, self.mode = kv, tables, pos, mode
        self.c, self.full_index = cfg, full_index
        self.page = jnp.take_along_axis(
            tables, (pos // page_size)[:, None], axis=1)[:, 0]
        self.off = pos % page_size
        self.page_size = page_size
        self.sel = self.n_sel = self.reads = None

    def attend(self, li, lp, q_nope, q_rope, c_kv, k_r, index, pos):
        c = self.c
        S, _, H, _ = q_nope.shape
        C, dv = c.kv_lora_rank, c.v_head_dim
        self.kv = kv_pages.append_rows(
            self.kv, "latent", li, self.page, self.off,
            _latent_rows(c, c_kv[:, 0], k_r[:, 0]))
        if index is not None:
            qi, ki, wi = index
            fi = self.full_index[li]
            self.kv = kv_pages.append_rows(self.kv, "index_k", fi,
                                           self.page, self.off, ki[:, 0])
            self.sel, self.n_sel, mask = index_select(
                qi[:, 0], wi[:, 0], self.kv["index_k"], fi, self.tables,
                self.pos, c.index_topk, mode=self.mode, with_mask=True)
            # how a layer of the store is read under this selection: the
            # same for the shared layers after this one
            self.reads = selection_reads(
                self.tables, self.sel, self.n_sel, self.page_size,
                mode=self.mode, mask=mask)
        # the query carried into the latent: q_nope_j (W_kvb^K_j)^T
        q_lat = jnp.einsum("shd,chd->shc", q_nope[:, 0],
                           lp["wkv_k"].reshape(C, H, c.qk_nope_head_dim))
        # over the row as it is stored: zeros against its padding
        q = _latent_rows(c, q_lat, q_rope[:, 0])
        ctx = sparse_latent_attention(
            q, self.kv["latent"], li, self.tables, self.sel, self.n_sel,
            dv=C, scale=float(c.qk_head_dim) ** -0.5, mode=self.mode,
            reads=self.reads)
        out = jnp.einsum("shc,chv->shv", ctx,
                         lp["wkv_v"].reshape(C, H, dv))
        return out.reshape(S, 1, H * dv)


class GlmMoeDsaLM(ExpertDecoder):
    """The model; ``compute_dtype`` is the dtype of its parameters and
    activations alike."""

    #: queries a block of a dense caller's selection and attention
    query_block = 256

    def __init__(self, config: GlmMoeDsaConfig, compute_dtype=jnp.bfloat16):
        self.cfg = config
        self._cdtype = jnp.dtype(compute_dtype)
        #: model layer -> its place among the full-indexer layers (the
        #: ``index_k`` store's layer)
        self.full_index = {li: i for i, li in enumerate(
            l for l, kind in enumerate(config.indexer_types)
            if kind == FULL)}

    def cache_spec(self) -> Dict[str, Any]:
        """What a serving engine holds for this model: a pool of two
        stores under one page table a slot, ``{name: (layers, row
        width)}``: every layer's latent rows and the full-indexer
        layers' keys; no per-slot state; ``selected`` is the most
        positions an attention layer reads of a context (the engine
        counts them on its spans)."""
        c = self.cfg
        return {"stores": {"latent": (c.num_hidden_layers, c.latent_row),
                           "index_k": (len(self.full_index),
                                       c.index_head_dim)},
                "state": None, "selected": c.index_topk}

    # -- params ---------------------------------------------------------
    def init_params(self, key=None) -> Dict[str, Any]:
        c, cd = self.cfg, self._cdtype
        key = key if key is not None else jax.random.key(0)
        d, E, H = c.hidden_size, c.num_experts, c.num_attention_heads
        C, dr = c.kv_lora_rank, c.qk_rope_head_dim
        std = c.initializer_range
        res = std / (2 * c.num_hidden_layers) ** 0.5
        keys = iter(jax.random.split(key, 3 + 24 * c.num_hidden_layers))

        def n(shape, s):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * s).astype(cd)

        one = lambda m: jnp.ones((m,), cd)
        p = {"tok_emb": n((c.vocab_size, d), std), "final_norm": one(d),
             "head": n((d, c.vocab_size), std), "layers": []}
        for li, kind in enumerate(c.mlp_layer_types):
            lp = {"in_norm": one(d), "post_norm": one(d),
                  "wq_a": n((d, c.q_lora_rank), std),
                  "q_norm": one(c.q_lora_rank),
                  "wq_b": n((c.q_lora_rank, H * c.qk_head_dim), std),
                  "wkv_a": n((d, C + dr), std), "kv_norm": one(C),
                  "wkv_k": n((C, H * c.qk_nope_head_dim), std),
                  "wkv_v": n((C, H * c.v_head_dim), std),
                  "wo": n((H * c.v_head_dim, d), res)}
            if c.indexer_types[li] == FULL:
                Hi, D = c.index_n_heads, c.index_head_dim
                lp.update(iwq=n((c.q_lora_rank, Hi * D), std),
                          iwk=n((d, D), std), ik_gain=one(D),
                          ik_bias=jnp.zeros((D,), cd),
                          iww=n((d, Hi), std))
            if kind == "dense":
                f = c.intermediate_size
                lp.update(w1=n((d, f), std), w3=n((d, f), std),
                          w2=n((f, d), res))
            else:
                f = c.moe_intermediate_size
                fs = f * c.n_shared_experts
                lp.update(router=n((d, c.n_routed_experts), std),
                          router_bias=n((c.n_routed_experts,), std),
                          ew1=n((E, d, f), std), ew3=n((E, d, f), std),
                          ew2=n((E, f, d), res),
                          sw1=n((d, fs), std), sw3=n((d, fs), std),
                          sw2=n((fs, d), res))
            p["layers"].append(lp)
        return p

    # -- pieces ---------------------------------------------------------
    def _rope(self, x, pos):
        return rope_interleaved(x, pos, self.cfg.rope_theta)

    def _rope_first(self, x, pos):
        """An indexer head: its first ``qk_rope_head_dim`` lanes
        rotated, the rest as they are."""
        dr = self.cfg.qk_rope_head_dim
        return jnp.concatenate(
            [self._rope(x[..., :dr], pos), x[..., dr:]], -1)

    def _indexer(self, lp, x, c_q, pos):
        """-> (queries ``[n, t, Hi, D]``, keys ``[n, t, D]``, weights
        ``[n, t, Hi]`` float32) of a full layer's indexer."""
        c = self.cfg
        n, t, _ = x.shape
        qi = self._rope_first((c_q @ lp["iwq"]).reshape(
            n, t, c.index_n_heads, c.index_head_dim), pos)
        kf = (x @ lp["iwk"]).astype(jnp.float32)
        mu = jnp.mean(kf, -1, keepdims=True)
        var = jnp.mean(jnp.square(kf - mu), -1, keepdims=True)
        ki = ((kf - mu) * lax.rsqrt(var + c.index_norm_eps)).astype(x.dtype) \
            * lp["ik_gain"] + lp["ik_bias"]
        ki = self._rope_first(ki[:, :, None, :], pos)[:, :, 0]
        return qi, ki, (x @ lp["iww"]).astype(jnp.float32)

    def _attn(self, li, lp, x, cache, pos):
        c = self.cfg
        n, t, _ = x.shape
        H, dn, C = c.num_attention_heads, c.qk_nope_head_dim, c.kv_lora_rank
        c_q = self._rms(x @ lp["wq_a"], lp["q_norm"])
        q = (c_q @ lp["wq_b"]).reshape(n, t, H, c.qk_head_dim)
        q_nope, q_rope = q[..., :dn], self._rope(q[..., dn:], pos)
        kva = x @ lp["wkv_a"]
        c_kv = self._rms(kva[..., :C], lp["kv_norm"])
        k_r = self._rope(kva[..., None, C:], pos)[:, :, 0]
        index = None
        if c.indexer_types[li] == FULL:
            index = self._indexer(lp, x, c_q, pos)
        return cache.attend(li, lp, q_nope, q_rope, c_kv, k_r, index, pos) \
            @ lp["wo"]

    def route(self, lp, x):
        """``x [m, d]`` -> (the router's choices ``[m, k]`` among ALL
        its outputs, weights ``[m, k]`` float32): sigmoid scores, the
        bias selects and does not weigh (``noaux_tc``), the selected
        scores normalised and scaled."""
        c = self.cfg
        return route_sigmoid(x, lp["router"], lp["router_bias"],
                             c.num_experts_per_tok, c.norm_topk_prob,
                             c.routed_scaling_factor)

    # -- the three callers ---------------------------------------------
    def forward(self, params, ids, return_aux=False, mode=None):
        """ids ``[n, t]`` -> logits ``[n, t, V]`` (float32), causal, no
        cache. With ``return_aux`` also the router's choices, a list of
        ``[n, t, k]`` per sparse layer."""
        n, t = ids.shape
        pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (n, t))
        x, aux = self._run(params, params["tok_emb"][ids], pos,
                           _Dense(self.cfg, self.query_block), None, mode)
        logits = self._head(x, params)
        return (logits, aux["experts"]) if return_aux else logits

    def prefill(self, params, prompt, t0, mode=None):
        """A padded prompt ``[1, B]`` of ``t0`` real tokens ->
        ``(rows, None, None, last, stats)``: ``rows`` a row a position
        for each store of the pool (``"latent" [layers, B, latent_row]``,
        ``"index_k" [full layers, B, index_head_dim]``; where a K/V
        model returns its K and its V), no per-slot state, the logits of
        position ``t0 - 1`` (float32) and the sparse layers' stats
        ``[layers, 4]`` over real positions only."""
        B = prompt.shape[1]
        pos = jnp.arange(B, dtype=jnp.int32)[None]
        cache = _Prefill(self.cfg, self.query_block)
        x, aux = self._run(params, params["tok_emb"][prompt], pos, cache,
                           pos < t0, mode)
        last = lax.dynamic_index_in_dim(x[0], t0 - 1, axis=0, keepdims=False)
        rows = {"latent": jnp.stack(cache.latent),
                "index_k": jnp.stack(cache.index_k)}
        return rows, None, None, self._head(last, params), self._stats(aux)

    def decode_step(self, params, kv, state, tables, pos, tok, active,
                    page_size, mode=None):
        """One token a slot: ``tok [S]`` at ``pos [S]`` -> ``(kv, None,
        logits [S, V], stats)``. Slots that are not ``active`` write to
        the null page, select it, go to no expert and keep nothing worth
        reading."""
        cache = _Paged(kv, tables, pos, page_size, mode, self.cfg,
                       self.full_index)
        x, aux = self._run(params, params["tok_emb"][tok][:, None],
                           pos[:, None], cache, active[:, None], mode)
        return cache.kv, state, self._head(x[:, 0], params), \
            self._stats(aux)


__all__ = ["GlmMoeDsaConfig", "GlmMoeDsaLM"]
