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

The block, the sparse feed-forward, the cache of two kinds (global
layers in the engine's page pool, window layers in per-slot rings) and
the three callers (``forward``, ``prefill``, ``decode_step``) are
``models/sparse_decoder.py``'s, shared with ``models/mellum.py``; this
file holds what is EXAONE's own: q/k norms, rotary positions on the
window layers only, the sigmoid router with its selection bias, the
shared expert and the leading dense layer.

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

from deeplearning4j_tpu.models.decoder_ops import rope
from deeplearning4j_tpu.models.routed_experts import route_sigmoid
# the cache objects by the names this model's callers have used
from deeplearning4j_tpu.models.sparse_decoder import (  # noqa: F401
    FULL, SLIDING, WindowDecoder, _Dense, _Prefill)


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


class ExaoneMoeLM(WindowDecoder):
    """The model; ``compute_dtype`` is the dtype of its parameters and
    activations alike."""

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
        return route_sigmoid(x, lp["router"], lp["router_bias"],
                             c.num_experts_per_tok, c.norm_topk_prob,
                             c.routed_scaling_factor)


__all__ = ["ExaoneMoeConfig", "ExaoneMoeLM"]
