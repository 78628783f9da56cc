"""Model arithmetic of the LFM2-MoE family: the operations and bytes
the algorithm needs, from shapes alone. The benchmark's yardstick;
nothing here is imported from the program.

Conventions (every count is of floating-point operations, one
multiply-add = 2), as ``flops/gpt2.py``:

- Matrix multiplications count: a conv layer's two projections and its
  short depthwise filter (``conv_L_cache`` multiply-adds a channel), an
  attention layer's four projections and two attention products, the
  dense SwiGLU's three, the router's, and the three of each of the
  ``num_experts_per_tok`` experts a token is routed to (not of all the
  experts: the others do no work for it). Gathers, norms, rotary
  positions, SiLU, sigmoid, softmax and top-k do not.
- Attention is causal, in the attention layers only: a query at
  0-based position ``i`` attends ``i + 1`` keys.
- The head counts only where a token is produced: one position per
  prefill and per decode step.

``cfg`` is the configuration file's dict under its published (Hugging
Face) key names, as cut (``num_hidden_layers``, ``layer_types``,
``num_dense_layers`` are those of the layers held).
"""


def sizes(cfg):
    d = int(cfg["hidden_size"])
    H = int(cfg["num_attention_heads"])
    types = list(cfg["layer_types"])
    L = int(cfg["num_hidden_layers"])
    return {"d": d, "H": H, "KV": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // H),
            "F": int(cfg["intermediate_size"]),
            "Fe": int(cfg["moe_intermediate_size"]),
            "E": int(cfg["num_experts"]),
            "k": int(cfg["num_experts_per_tok"]),
            "K": int(cfg["conv_L_cache"]), "V": int(cfg["vocab_size"]),
            "L": L, "conv": types.count("conv"),
            "attn": types.count("full_attention"),
            "dense": int(cfg["num_dense_layers"]),
            "moe": L - int(cfg["num_dense_layers"])}


def _itemsize(dtype_name):
    import jax.numpy as jnp

    return jnp.dtype(dtype_name).itemsize


def n_params(cfg):
    """Parameters held, the head tied to the embedding."""
    z = sizes(cfg)
    d, hd = z["d"], z["hd"]
    conv = d * 3 * d + d * d + z["K"] * d
    attn = d * z["H"] * hd + 2 * d * z["KV"] * hd + z["H"] * hd * d + 2 * hd
    dense = 3 * d * z["F"]
    moe = z["E"] * 3 * d * z["Fe"] + d * z["E"] + z["E"]
    return (z["V"] * d + d + z["conv"] * conv + z["attn"] * attn
            + z["dense"] * dense + z["moe"] * moe + 2 * z["L"] * d)


def expert_bytes(cfg):
    """Bytes of ONE expert's three matrices as held."""
    z = sizes(cfg)
    return 3 * z["d"] * z["Fe"] * _itemsize(cfg["dtypes"]["params"])


def kv_bytes_per_position(cfg):
    """Bytes of K and V one position holds across the layers that have
    pages: attention layers x KV heads x head width x 2 x the pool's
    item size."""
    z = sizes(cfg)
    return z["attn"] * z["KV"] * z["hd"] * 2 \
        * _itemsize(cfg["dtypes"]["kv_pool"])


def block_flops_per_token(cfg):
    """Forward operations of every layer's products for one token,
    attention's two products apart."""
    z = sizes(cfg)
    d, hd = z["d"], z["hd"]
    conv = 2 * d * 3 * d + 2 * d * d + 2 * z["K"] * d
    attn = 2 * d * z["H"] * hd + 4 * d * z["KV"] * hd + 2 * z["H"] * hd * d
    dense = 6 * d * z["F"]
    moe = 2 * d * z["E"] + z["k"] * 6 * d * z["Fe"]
    return z["conv"] * conv + z["attn"] * attn + z["dense"] * dense \
        + z["moe"] * moe


def head_flops(cfg):
    z = sizes(cfg)
    return 2 * z["V"] * z["d"]


def attn_flops(cfg, key_contexts):
    """QK^T and PV over ``key_contexts`` (query, key) pairs, in the
    attention layers."""
    z = sizes(cfg)
    return 4 * z["H"] * z["hd"] * z["attn"] * key_contexts


def prefill_flops(cfg, prompt_len):
    t = prompt_len
    return t * block_flops_per_token(cfg) + attn_flops(cfg, t * (t + 1) // 2) \
        + head_flops(cfg)


def decode_flops(cfg, context):
    return block_flops_per_token(cfg) + attn_flops(cfg, context) \
        + head_flops(cfg)


def served_token_flops(cfg, prompt_len, index):
    """Operations that produce output token ``index`` (0-based) of a
    request: the prefill for the first, one decode step for the rest."""
    if index == 0:
        return prefill_flops(cfg, prompt_len)
    return decode_flops(cfg, prompt_len + index)
