"""Model arithmetic of the Mellum family: the operations and bytes the
algorithm needs, from shapes alone. The benchmark's yardstick; nothing
here is imported from the program.

Conventions (every count is of floating-point operations, one
multiply-add = 2), as ``flops/exaone_moe.py``:

- Matrix multiplications count: every layer's four attention
  projections and two attention products, the router's product over
  its ``num_experts`` outputs and the three products of each of the
  ``num_experts_per_tok`` experts a token is routed to (every expert is
  held here). ``intermediate_size`` belongs to no layer (every layer is
  sparse) and counts nothing. Gathers, norms, rotary positions, SiLU,
  softmax and top-k do not.
- Attention is causal: a query at 0-based position ``i`` attends ``i +
  1`` keys in a full layer and ``min(i + 1, sliding_window)`` in a
  window layer.
- The head counts only where a token is produced (one position per
  prefill and per decode step).

``cfg`` is the configuration file's dict under its published (Hugging
Face) key names, as cut: ``num_hidden_layers`` and ``layer_types`` are
those of the layers held.
"""


def sizes(cfg):
    d = int(cfg["hidden_size"])
    H = int(cfg["num_attention_heads"])
    types = list(cfg["layer_types"])
    return {"d": d, "H": H, "KV": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // H),
            "Fe": int(cfg["moe_intermediate_size"]),
            "E": int(cfg["num_experts"]),
            "k": int(cfg["num_experts_per_tok"]),
            "V": int(cfg["vocab_size"]),
            "window": int(cfg["sliding_window"]),
            "L": int(cfg["num_hidden_layers"]),
            "win": types.count("sliding_attention"),
            "glob": types.count("full_attention")}


def _itemsize(dtype_name):
    import jax.numpy as jnp

    return jnp.dtype(dtype_name).itemsize


def n_params(cfg):
    """Parameters held: every layer's attention, norms, router and
    experts, the embedding and the untied head."""
    z = sizes(cfg)
    d, hd = z["d"], z["hd"]
    attn = d * z["H"] * hd + 2 * d * z["KV"] * hd + z["H"] * hd * d
    moe = d * z["E"] + z["E"] * 3 * d * z["Fe"]
    return 2 * z["V"] * d + d + z["L"] * (attn + 2 * d + moe)


def expert_bytes(cfg):
    """Bytes of ONE expert's three matrices as held."""
    z = sizes(cfg)
    return 3 * z["d"] * z["Fe"] * _itemsize(cfg["dtypes"]["params"])


def kv_bytes_needed(cfg, ctx_tokens, ctx_window_tokens):
    """Bytes of K and V the attention calls had to read: ``ctx_tokens``
    positions (summed over the calls) in every full layer and
    ``ctx_window_tokens`` (each call's positions cut to the window) in
    every window layer, KV heads x head width x K and V x the pool's
    item size a position."""
    z = sizes(cfg)
    row = z["KV"] * z["hd"] * 2 * _itemsize(cfg["dtypes"]["kv_pool"])
    return row * (z["glob"] * ctx_tokens + z["win"] * ctx_window_tokens)


def block_flops_per_token(cfg):
    """Forward operations of every layer's products for one token,
    attention's two products apart."""
    z = sizes(cfg)
    d, hd = z["d"], z["hd"]
    attn = 2 * d * z["H"] * hd + 4 * d * z["KV"] * hd + 2 * z["H"] * hd * d
    moe = 2 * d * z["E"] + z["k"] * 6 * d * z["Fe"]
    return z["L"] * (attn + moe)


def head_flops(cfg):
    z = sizes(cfg)
    return 2 * z["V"] * z["d"]


def _window_pairs(t, w):
    """Sum over queries 0..t-1 of min(i + 1, w)."""
    return t * (t + 1) // 2 if t <= w else w * (w + 1) // 2 + (t - w) * w


def attn_flops(cfg, global_pairs, window_pairs):
    """QK^T and PV over (query, key) pairs in the full and in the
    window layers."""
    z = sizes(cfg)
    return 4 * z["H"] * z["hd"] * (z["glob"] * global_pairs
                                   + z["win"] * window_pairs)


def prefill_flops(cfg, prompt_len):
    t, w = prompt_len, sizes(cfg)["window"]
    return t * block_flops_per_token(cfg) \
        + attn_flops(cfg, t * (t + 1) // 2, _window_pairs(t, w)) \
        + head_flops(cfg)


def decode_flops(cfg, context):
    w = sizes(cfg)["window"]
    return block_flops_per_token(cfg) \
        + attn_flops(cfg, context, min(context, w)) + head_flops(cfg)


def served_token_flops(cfg, prompt_len, index):
    """Operations that produce output token ``index`` (0-based) of a
    request: the prefill for the first, one decode step for the rest."""
    if index == 0:
        return prefill_flops(cfg, prompt_len)
    return decode_flops(cfg, prompt_len + index)
