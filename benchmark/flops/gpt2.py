"""Model arithmetic of the GPT-2 family: the operations and bytes the
algorithm needs, from shapes alone. The benchmark's yardstick; nothing
here is imported from the program.

Conventions (every count is of floating-point operations, one
multiply-add = 2):

- Only matrix multiplications count: the four block projections, the
  two attention products and the tied head. Embedding gathers, layer
  norms, GELU, softmax and the optimizer's arithmetic do not.
- Attention is causal: a query at 0-based position ``i`` attends
  ``i + 1`` keys, so ``T`` positions of one row cost
  ``T (T + 1) / 2`` key-contexts, the causal half of ``T * T``.
- Training costs three forwards (one forward, two for the backward).
  Recomputed operations do not count.
- The head counts only where a token or a loss term is produced: every
  position in training, one position per prefill and per decode step in
  serving.

``cfg`` is the configuration file's dict under its published
(Hugging Face) key names.
"""


def sizes(cfg):
    d = int(cfg["n_embd"])
    f = int(cfg.get("n_inner") or 4 * d)
    return d, f, int(cfg["n_layer"]), int(cfg["n_head"]), int(cfg["vocab_size"])


def n_params(cfg):
    """Parameters with the head tied to the token embedding."""
    d, f, L, _, V = sizes(cfg)
    block = (d * 3 * d + 3 * d) + (d * d + d) + (d * f + f) + (f * d + d) + 4 * d
    return V * d + int(cfg["n_positions"]) * d + L * block + 2 * d


def block_flops_per_token(cfg):
    """Forward operations of the L blocks' projections for one token."""
    d, f, L, _, _ = sizes(cfg)
    return 2 * L * (4 * d * d + 2 * d * f)


def head_flops(cfg):
    d, _, _, _, V = sizes(cfg)
    return 2 * V * d


def attn_flops(cfg, key_contexts):
    """QK^T and PV over ``key_contexts`` (query, key) pairs, all layers."""
    d, _, L, _, _ = sizes(cfg)
    return 4 * d * L * key_contexts


def train_flops_per_step(cfg, rows, positions):
    """Forward and backward for ``rows`` rows of ``positions`` predicted
    positions each (rows of ``positions + 1`` ids)."""
    T = positions
    fwd = T * (block_flops_per_token(cfg) + head_flops(cfg)) \
        + attn_flops(cfg, T * (T + 1) // 2)
    return 3 * rows * fwd


def train_flops_per_position(cfg, positions):
    return train_flops_per_step(cfg, 1, positions) / positions


def prefill_flops(cfg, prompt_len):
    """A prompt of ``prompt_len`` tokens and the first output token."""
    t = prompt_len
    return t * block_flops_per_token(cfg) + attn_flops(cfg, t * (t + 1) // 2) \
        + head_flops(cfg)


def decode_flops(cfg, context):
    """One decode step of one request whose query attends ``context``
    keys (the prompt, the outputs fed back, and itself)."""
    return block_flops_per_token(cfg) + attn_flops(cfg, context) + head_flops(cfg)


def served_token_flops(cfg, prompt_len, index):
    """Operations that produce output token ``index`` (0-based) of a
    request: the prefill for the first, one decode step for the rest."""
    if index == 0:
        return prefill_flops(cfg, prompt_len)
    return decode_flops(cfg, prompt_len + index)
