"""Model arithmetic of the GLM-MoE-DSA family: the operations and bytes
the algorithm needs, from shapes alone. The benchmark's yardstick;
nothing here is imported from the program.

Conventions (every count is of floating-point operations, one
multiply-add = 2), as ``flops/exaone_moe.py``:

- Matrix multiplications count: every layer's attention projections
  (``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb`` for the one position
  projected, ``W_o``) and its two attention products in the published
  per-head form (``qk_head_dim`` and ``v_head_dim`` a head and key: the
  absorbed form a decode step may use does more and is not what the
  algorithm needs), a full-indexer layer's three projections and its
  score product, the dense SwiGLU's three, and in a sparse layer the
  router's product over ALL its outputs, the shared expert's three and
  the three of each routed expert a token reaches ON THIS CHIP: in
  expectation ``num_experts_per_tok x num_experts / n_routed_experts``
  of them. Gathers, norms, rotary positions, SiLU, sigmoid, ReLU,
  softmax and top-k do not.
- Attention is causal and SPARSE: a query at 0-based position ``i``
  attends ``min(i + 1, index_topk)`` keys in every layer; a
  full-indexer layer scores ``i + 1``.
- The head counts only where a token is produced (one position per
  prefill and per decode step), over the ``vocab_size`` rows held.

``cfg`` is the configuration file's dict under its published (Hugging
Face) key names, as cut: ``num_hidden_layers``, ``indexer_types``,
``mlp_layer_types`` are those of the layers held, ``num_experts`` the
experts held, ``vocab_size`` the rows held.
"""


def sizes(cfg):
    idx = list(cfg["indexer_types"])
    mlp = list(cfg["mlp_layer_types"])
    E = int(cfg["num_experts"])
    return {"d": int(cfg["hidden_size"]),
            "H": int(cfg["num_attention_heads"]),
            "Rq": int(cfg["q_lora_rank"]), "C": int(cfg["kv_lora_rank"]),
            "dn": int(cfg["qk_nope_head_dim"]),
            "dr": int(cfg["qk_rope_head_dim"]),
            "dv": int(cfg["v_head_dim"]),
            "Hi": int(cfg["index_n_heads"]),
            "Di": int(cfg["index_head_dim"]),
            "top": int(cfg["index_topk"]),
            "F": int(cfg["intermediate_size"]),
            "Fe": int(cfg["moe_intermediate_size"]),
            "E": E, "Er": int(cfg.get("n_routed_experts") or E),
            "k": int(cfg["num_experts_per_tok"]),
            "ns": int(cfg.get("n_shared_experts", 1)),
            "V": int(cfg["vocab_size"]),
            "L": int(cfg["num_hidden_layers"]),
            "full": idx.count("full"), "shared": idx.count("shared"),
            "dense": mlp.count("dense"), "moe": mlp.count("sparse")}


def _itemsize(dtype_name):
    import jax.numpy as jnp

    return jnp.dtype(dtype_name).itemsize


def _attn_params(z):
    d, H, C = z["d"], z["H"], z["C"]
    return (d * z["Rq"] + z["Rq"] * H * (z["dn"] + z["dr"])
            + d * (C + z["dr"]) + C * H * (z["dn"] + z["dv"])
            + H * z["dv"] * d)


def _indexer_params(z):
    return z["Rq"] * z["Hi"] * z["Di"] + z["d"] * z["Di"] \
        + z["d"] * z["Hi"]


def n_params(cfg):
    """Parameters held: the experts and the vocabulary rows of this
    share, the rest whole; the head untied; the two latent norms, the
    indexer's LayerNorm (gain and bias) and the router's selection bias
    among them."""
    z = sizes(cfg)
    d = z["d"]
    attn = _attn_params(z) + z["Rq"] + z["C"]
    indexer = _indexer_params(z) + 2 * z["Di"]
    dense = 3 * d * z["F"]
    moe = z["E"] * 3 * d * z["Fe"] + d * z["Er"] + z["Er"] \
        + z["ns"] * 3 * d * z["Fe"]
    return (2 * z["V"] * d + d + z["L"] * (attn + 2 * d)
            + z["full"] * indexer + z["dense"] * dense + z["moe"] * moe)


def expert_bytes(cfg):
    """Bytes of ONE expert's three matrices as held."""
    z = sizes(cfg)
    return 3 * z["d"] * z["Fe"] * _itemsize(cfg["dtypes"]["params"])


def latent_bytes_needed(cfg, selected):
    """Bytes of latent rows the attention had to read: ``selected``
    positions (each call's context cut to ``index_topk``, summed over
    the calls) in EVERY layer, ``kv_lora_rank + qk_rope_head_dim``
    numbers a position at the pool's item size: the row the
    mathematics needs, whatever padding it rests in."""
    z = sizes(cfg)
    return z["L"] * selected * (z["C"] + z["dr"]) \
        * _itemsize(cfg["dtypes"]["kv_pool"])


def index_bytes_needed(cfg, scored):
    """Bytes of indexer keys the selection had to read: ``scored``
    positions (each call's whole context, summed over the calls) in
    every FULL-indexer layer, ``index_head_dim`` numbers a position."""
    z = sizes(cfg)
    return z["full"] * scored * z["Di"] \
        * _itemsize(cfg["dtypes"]["kv_pool"])


def block_flops_per_token(cfg):
    """Forward operations of every layer's products for one token,
    attention's and the indexer's products over the context apart."""
    z = sizes(cfg)
    d = z["d"]
    dense = 6 * d * z["F"]
    routed = z["k"] * z["E"] / z["Er"]        # experts reached here
    moe = 2 * d * z["Er"] + (z["ns"] + routed) * 6 * d * z["Fe"]
    return (z["L"] * 2 * _attn_params(z) + z["full"] * 2 * _indexer_params(z)
            + z["dense"] * dense + z["moe"] * moe)


def head_flops(cfg):
    z = sizes(cfg)
    return 2 * z["V"] * z["d"]


def _selected_pairs(t, top):
    """Sum over queries 0..t-1 of min(i + 1, top)."""
    return t * (t + 1) // 2 if t <= top else top * (top + 1) // 2 \
        + (t - top) * top


def context_flops(cfg, selected_pairs, scored_pairs):
    """QK^T and PV over the (query, selected key) pairs of every layer,
    and the indexer's score product over the (query, key) pairs of
    every full layer."""
    z = sizes(cfg)
    return z["L"] * 2 * z["H"] * (z["dn"] + z["dr"] + z["dv"]) \
        * selected_pairs + z["full"] * 2 * z["Hi"] * z["Di"] * scored_pairs


def prefill_flops(cfg, prompt_len):
    t = prompt_len
    return t * block_flops_per_token(cfg) + context_flops(
        cfg, _selected_pairs(t, sizes(cfg)["top"]), t * (t + 1) // 2) \
        + head_flops(cfg)


def decode_flops(cfg, context):
    return block_flops_per_token(cfg) + context_flops(
        cfg, min(context, sizes(cfg)["top"]), context) + head_flops(cfg)


def served_token_flops(cfg, prompt_len, index):
    """Operations that produce output token ``index`` (0-based) of a
    request: the prefill for the first, one decode step for the rest
    (its query at position ``prompt_len + index - 1`` holds ``prompt_len
    + index`` keys)."""
    if index == 0:
        return prefill_flops(cfg, prompt_len)
    return decode_flops(cfg, prompt_len + index)
