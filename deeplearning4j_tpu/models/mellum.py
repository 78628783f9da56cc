"""Mellum 2 causal language model (JetBrains ``mellum``,
Mellum2-12B-A2.5B): grouped-query attention in two kinds of layer,
sliding-window layers with default rotary positions and full layers
with YaRN-scaled ones, and in every layer softmax-routed dropless SwiGLU
experts with no shared expert; RMSNorm on every sub-layer's input, an
output head of its own.

    a = RMS(h; g_in);  q = a W_q, k = a W_k, v = a W_v   (no bias, no q/k norm)
    q, k <- Rot_kind(q, k; position)    rotate-half, each layer kind its
                                        own ``rope_parameters`` entry
    h <- h + Attn(q, k, v) W_o          causal; a window layer's query i
                                        reads keys i - window < j <= i
    r = softmax(RMS(h; g_post) W_r);  the top k of r renormalised
    h <- h + sum over the top k of  w_e * SwiGLU_e(RMS(h; g_post))
    logits = RMS(h_L; g_f) W_head

The block, the sparse feed-forward, the cache of two kinds (full
layers in the engine's page pool, window layers in per-slot rings) and
the three callers (``forward``, ``prefill``, ``decode_step``) are
``models/sparse_decoder.py``'s, shared with ``models/exaone_moe.py``.
This file holds what is Mellum's own: a rotation for each kind of layer
(``decoder_ops.rope_frequencies``: ``default`` on the window layers,
``yarn`` on the full ones, cos and sin scaled by its attention factor),
no q/k norm, the softmax router, no shared expert. Every expert is held.

Parameters are created and held in the compute dtype (bfloat16 as
served). The router's product, its softmax, the top-k and the weights'
renormalisation run in float32, as do the norms' statistics, the
rotation and the attention's softmax.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models.decoder_ops import rope
from deeplearning4j_tpu.models.routed_experts import route_softmax
from deeplearning4j_tpu.models.sparse_decoder import (FULL, SLIDING,
                                                      WindowDecoder)

#: the published rotation of each kind of layer
ROPE_PARAMETERS = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
           "original_max_position_embeddings": 8192, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.2772588722239782},
    SLIDING: {"rope_type": "default", "rope_theta": 500000}}


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    """The published ``config.json`` keys that shape the model.
    ``rope_parameters`` is the published dict of one entry a layer
    kind. The published ``intermediate_size`` is a dense width no layer
    has (every layer is sparse), and is not taken."""

    vocab_size: int = 98304
    hidden_size: int = 2304
    moe_intermediate_size: int = 896
    num_hidden_layers: int = 28
    layer_types: Tuple[str, ...] = ()
    mlp_layer_types: Tuple[str, ...] = ()
    sliding_window: int = 1024
    num_experts: int = 64
    num_experts_per_tok: int = 8
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_parameters: Any = None
    rms_norm_eps: float = 1e-6
    norm_topk_prob: bool = True
    attention_bias: bool = False
    use_sliding_window: bool = True
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    #: positions a page of a window layer's ring holds
    window_page_size: int = 16

    def __post_init__(self):
        set_ = lambda k, v: object.__setattr__(self, k, v)
        set_("layer_types", tuple(self.layer_types))
        set_("mlp_layer_types", tuple(self.mlp_layer_types))
        # held as sorted pairs, so the config stays hashable
        rp = self.rope_parameters or ROPE_PARAMETERS
        if isinstance(rp, dict):
            rp = tuple(sorted((kind, tuple(sorted(e.items())))
                              for kind, e in rp.items()))
        set_("rope_parameters", rp)
        L = self.num_hidden_layers
        if len(self.layer_types) != L or len(self.mlp_layer_types) != L:
            raise ValueError(
                f"{len(self.layer_types)} layer_types and "
                f"{len(self.mlp_layer_types)} mlp_layer_types for {L} layers")
        bad = set(self.layer_types) - {SLIDING, FULL}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if set(self.mlp_layer_types) != {"sparse"}:
            raise ValueError("mlp_layer_types other than 'sparse' are not "
                             "implemented: the published model has none")
        missing = set(self.layer_types) - {k for k, _ in rp}
        if missing:
            raise ValueError(f"rope_parameters has no entry for "
                             f"{sorted(missing)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide into the KV heads")
        for name, want in (("attention_bias", False),
                           ("use_sliding_window", True),
                           ("tie_word_embeddings", False)):
            if getattr(self, name) != want:
                raise ValueError(
                    f"{name}={getattr(self, name)!r} is not implemented "
                    f"(only {want!r})")

    def rope_entry(self, kind) -> Dict[str, Any]:
        """The ``rope_parameters`` entry of a layer kind."""
        return dict(dict(self.rope_parameters)[kind])

    # every expert is held: the router's outputs are the experts
    expert_offset = 0

    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

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


class MellumLM(WindowDecoder):
    """The model; ``compute_dtype`` is the dtype of its parameters and
    activations alike."""

    def init_params(self, key=None) -> Dict[str, Any]:
        c, cd = self.cfg, self._cdtype
        key = key if key is not None else jax.random.key(0)
        d, hd, E = c.hidden_size, c.head_dim, c.num_experts
        H, KV, f = c.num_attention_heads, c.num_key_value_heads, \
            c.moe_intermediate_size
        std = c.initializer_range
        res = std / (2 * c.num_hidden_layers) ** 0.5
        keys = iter(jax.random.split(key, 3 + 8 * c.num_hidden_layers))

        def n(shape, s):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    * s).astype(cd)

        one = lambda m: jnp.ones((m,), cd)
        p = {"tok_emb": n((c.vocab_size, d), std), "final_norm": one(d),
             "head": n((d, c.vocab_size), std), "layers": []}
        for _ in range(c.num_hidden_layers):
            p["layers"].append({
                "in_norm": one(d), "post_norm": one(d),
                "wq": n((d, H * hd), std), "wk": n((d, KV * hd), std),
                "wv": n((d, KV * hd), std), "wo": n((H * hd, d), res),
                "router": n((d, E), std), "ew1": n((E, d, f), std),
                "ew3": n((E, d, f), std), "ew2": n((E, f, d), res)})
        return p

    def _attn(self, li, lp, h, cache, pos):
        c = self.cfg
        n, t, _ = h.shape
        kind = c.layer_types[li]
        heads = lambda y, m: y.reshape(n, t, m, c.head_dim)
        turn = c.rope_entry(kind)
        q = rope(heads(h @ lp["wq"], c.num_attention_heads), pos, turn)
        k = rope(heads(h @ lp["wk"], c.num_key_value_heads), pos, turn)
        v = heads(h @ lp["wv"], c.num_key_value_heads)
        return cache.attend(kind, self.kind_index[li], q, k, v, pos) \
            @ lp["wo"]

    def route(self, lp, x):
        """``x [m, d]`` -> (choices ``[m, k]``, weights ``[m, k]``
        float32): a softmax over every expert, its top k renormalised."""
        c = self.cfg
        return route_softmax(x, lp["router"], c.num_experts_per_tok,
                             c.norm_topk_prob)


__all__ = ["MellumConfig", "MellumLM", "ROPE_PARAMETERS"]
