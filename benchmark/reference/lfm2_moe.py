"""Plain reference of the LFM2-MoE family (LiquidAI ``lfm2_moe``, HF
``Lfm2MoeForCausalLM``): the forward pass in straightforward
``jax.numpy``, float32 with every product at ``highest`` precision. No
kernel, no cache, no batching. It imports nothing of the program and
takes nothing the program made: the weights come from ``make_params``
here, from the seed, as bfloat16 VALUES (the dtype the configuration
states and the program holds them in); the reference reads the same
values in float32.

The equations (``x`` is the residual stream, ``d`` its width):

    h_0 = E[ids]                                  (no position embedding)
    h  <- h + Op_l(RMS(h; g_op));  h <- h + FF_l(RMS(h; g_ffn))
    logits = RMS(h_L; g_emb) E^T                  (``embedding_norm``, tied head)
    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g

- conv layer: ``[B, C, X] = split3(x W_in)``; ``u = B * X``;
  ``v_t = sum_j w[j] * u_{t-(L-1)+j}`` (depthwise, causal, ``u`` zero
  before the sequence, no bias, ``L = conv_L_cache``);
  ``Op = (C * v) W_out``. The cache of a sequence is ``u`` at its last
  ``L`` positions.
- attention layer: ``q = x W_q`` (H heads), ``k = x W_k``, ``v = x W_v``
  (KV heads), no biases; ``q <- RoPE(RMS_head(q; g_q))``,
  ``k <- RoPE(RMS_head(k; g_k))`` (RMSNorm over each head's width,
  before rotate-half RoPE at ``rope_theta``); causal
  ``softmax(q k^T / sqrt(head))`` over the KV head of the query's
  group; ``Op = ctx W_o``.
- dense FF (the leading ``num_dense_layers``):
  ``W_2 (silu(W_1 x) * W_3 x)``.
- expert FF: ``s = sigmoid(x W_g)``; ``idx = top_k(s + b)`` with ``b``
  the per-expert selection bias (it selects and does not weigh);
  ``w = s[idx] / (sum s[idx] + 1e-6)`` where ``norm_topk_prob``,
  ``* routed_scaling_factor``; ``FF = sum_e w_e W_2^e (silu(W_1^e x) *
  W_3^e x)``. Every token gets all of its experts: no capacity, no drop.

Departures from the published model: the depthwise filter is stored
``[L, d]`` (HF: ``[d, 1, L]``) and the projections input-major
(``x W``; HF stores ``W^T``): layouts, not mathematics. The weights are
random (normal at ``initializer_range``, residual-side projections
scaled by ``1 / sqrt(2 layers)``, norm gains 1, the selection bias
normal at ``expert_bias_range``), as the configuration file says.

At the published widths the parameters of the benchmark's cut are 20.7
GB in float32, so ``check_served`` makes and applies ONE LAYER AT A TIME
over the sampled requests, a request a call; an expert layer is the
published loop over the experts hit, each over its own tokens only.

``precision`` selects the arithmetic: ``"f32"`` is the reference;
``"fp8"`` is the CONTROL (every matmul operand rounded to float8_e4m3fn
with a per-tensor scale, accumulation in float32: the nearest precision
below the bf16 the configuration states). The control has to come out
as not correct; it never runs inside a benchmark run.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
BF16 = jnp.bfloat16

#: every leaf a layer can hold; a leaf's values depend on the seed, its
#: layer and its place in this list only, so a layer can be made alone
_LEAVES = ("op_norm", "ffn_norm", "w_in", "conv_w", "w_out", "wq", "wk",
           "wv", "q_norm", "k_norm", "wo", "w1", "w3", "w2", "router",
           "router_bias", "ew1", "ew3", "ew2")


def sizes(cfg):
    H = int(cfg["num_attention_heads"])
    d = int(cfg["hidden_size"])
    return {"d": d, "V": int(cfg["vocab_size"]),
            "L": int(cfg["num_hidden_layers"]), "H": H,
            "KV": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // H),
            "F": int(cfg["intermediate_size"]),
            "Fe": int(cfg["moe_intermediate_size"]),
            "E": int(cfg["num_experts"]),
            "k": int(cfg["num_experts_per_tok"]),
            "K": int(cfg["conv_L_cache"]),
            "dense": int(cfg["num_dense_layers"]),
            "eps": float(cfg["norm_eps"]),
            "theta": float(cfg["rope_parameters"]["rope_theta"]),
            "types": tuple(cfg["layer_types"]),
            "bias": bool(cfg.get("use_expert_bias", True)),
            "norm_topk": bool(cfg.get("norm_topk_prob", True)),
            "scale": float(cfg.get("routed_scaling_factor", 1.0))}


def layer_leaves(cfg, li):
    """{leaf: (shape, std)} of layer ``li``; std None is a norm gain
    (ones)."""
    z = sizes(cfg)
    d, hd = z["d"], z["hd"]
    std = float(cfg.get("initializer_range", 0.02))
    res = std / math.sqrt(2 * z["L"])
    out = {"op_norm": ((d,), None), "ffn_norm": ((d,), None)}
    if z["types"][li] == "conv":
        out.update(w_in=((d, 3 * d), std), conv_w=((z["K"], d), std),
                   w_out=((d, d), res))
    else:
        out.update(wq=((d, z["H"] * hd), std), wk=((d, z["KV"] * hd), std),
                   wv=((d, z["KV"] * hd), std), q_norm=((hd,), None),
                   k_norm=((hd,), None), wo=((z["H"] * hd, d), res))
    if li < z["dense"]:
        out.update(w1=((d, z["F"]), std), w3=((d, z["F"]), std),
                   w2=((z["F"], d), res))
    else:
        E, Fe = z["E"], z["Fe"]
        out.update(router=((d, E), std),
                   router_bias=((E,), float(cfg.get("expert_bias_range", 0.1))),
                   ew1=((E, d, Fe), std), ew3=((E, d, Fe), std),
                   ew2=((E, Fe, d), res))
    return out


def seed_key(seed):
    """A key from any whole number up to 2**63: the low 31 bits seed,
    the rest are folded in (``jax.random.key`` takes 32 signed bits)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=64)
def _leaf_fn(shape, std):
    if std is None:
        return jax.jit(lambda key: jnp.ones(shape, BF16))
    return jax.jit(lambda key: (
        jax.random.normal(key, shape, jnp.float32) * std).astype(BF16))


def _leaf(key, where, index, shape, std):
    k = jax.random.fold_in(jax.random.fold_in(key, where), index)
    return _leaf_fn(tuple(shape), std)(k)


def make_layer(cfg, seed, li):
    """Layer ``li``'s leaves, bfloat16, each made by its own call."""
    key = seed_key(seed)
    return {name: _leaf(key, li + 1, _LEAVES.index(name), shape, std)
            for name, (shape, std) in layer_leaves(cfg, li).items()}


def make_globals(cfg, seed):
    z = sizes(cfg)
    key = seed_key(seed)
    std = float(cfg.get("initializer_range", 0.02))
    return {"tok_emb": _leaf(key, 0, 0, (z["V"], z["d"]), std),
            "emb_norm": _leaf(key, 0, 1, (z["d"],), None)}


def make_params(cfg, seed, layout="program"):
    """All weights on the device, bfloat16, leaf by leaf (so the 10 GB
    of the published widths are never held twice). One layout: the
    per-layer list the program's model takes."""
    del layout
    p = make_globals(cfg, seed)
    p["layers"] = [make_layer(cfg, seed, li)
                   for li in range(sizes(cfg)["L"])]
    return p


# ------------------------------------------------------------- forward
def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ein(spec, a, b, precision):
    if precision == "fp8":
        a, b = _q8(a), _q8(b)
    elif precision != "f32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """Rotate-half RoPE over ``x [T, heads, hd]`` at positions 0..T-1."""
    T, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def conv_op(lp, x, z, precision):
    T = x.shape[0]
    b, c, xx = jnp.split(_ein("td,de->te", x, lp["w_in"], precision), 3, -1)
    u = jnp.pad(b * xx, ((z["K"] - 1, 0), (0, 0)))
    v = sum(lp["conv_w"][j] * u[j:j + T] for j in range(z["K"]))
    return _ein("td,de->te", c * v, lp["w_out"], precision)


def attn_op(lp, x, z, precision):
    T = x.shape[0]
    H, KV, hd = z["H"], z["KV"], z["hd"]
    q = _ein("td,de->te", x, lp["wq"], precision).reshape(T, H, hd)
    k = _ein("td,de->te", x, lp["wk"], precision).reshape(T, KV, hd)
    v = _ein("td,de->te", x, lp["wv"], precision).reshape(T, KV, hd)
    q = _rope(_rms(q, lp["q_norm"], z["eps"]), z["theta"])
    k = _rope(_rms(k, lp["k_norm"], z["eps"]), z["theta"])
    k, v = (jnp.repeat(y, H // KV, axis=1) for y in (k, v))
    s = _ein("qhd,khd->hqk", q, k, precision) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    ctx = _ein("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, precision)
    return _ein("td,de->te", ctx.reshape(T, H * hd), lp["wo"], precision)


def _swiglu(x, w1, w3, w2, precision):
    mid = jax.nn.silu(_ein("td,df->tf", x, w1, precision)) \
        * _ein("td,df->tf", x, w3, precision)
    return _ein("tf,fd->td", mid, w2, precision)


def route(lp, x, z, precision="f32"):
    """-> (idx [T, k], weights [T, k]) of the tokens ``x [T, d]``."""
    s = jax.nn.sigmoid(_ein("td,de->te", x, lp["router"], precision))
    sel = s + lp["router_bias"] if z["bias"] else s
    _, idx = lax.top_k(sel, z["k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if z["norm_topk"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return idx, w * z["scale"]


@functools.partial(jax.jit, static_argnames=("n", "precision"))
def _expert(ys, xs, lp, e, start, count, n, precision):
    """Expert ``e`` over its block of the sorted rows: ``n`` rows from
    ``start`` (a few set lengths, so the block may run into the next
    expert's rows), of which its own ``count`` are written to ``ys``."""
    w1, w3, w2 = (lax.dynamic_index_in_dim(lp[k], e, keepdims=False)
                  for k in ("ew1", "ew3", "ew2"))
    y = _swiglu(lax.dynamic_slice_in_dim(xs, start, n), w1, w3, w2,
                precision)
    mine = jnp.arange(n)[:, None] < count
    y = jnp.where(mine, y, lax.dynamic_slice_in_dim(ys, start, n))
    return lax.dynamic_update_slice_in_dim(ys, y, start, axis=0)


def moe_ff(lp, x, idx, w, z, precision):
    """The routed experts of ``x [T, d]``: the (token, expert) pairs
    are sorted by expert on the host, each expert that got tokens
    computes its SwiGLU over its own block of rows only (as the
    published model's loop over the experts hit), and every token sums
    its experts' outputs with their weights. A block is cut at 64 times
    a power of two rows, so that a few programs serve every count."""
    T, k = idx.shape
    flat = np.asarray(idx).reshape(-1)
    order = np.argsort(flat, kind="stable")
    counts = np.bincount(flat, minlength=z["E"])
    starts = np.concatenate([[0], np.cumsum(counts)])
    block = lambda c: 64 * 2 ** max(0, math.ceil(math.log2(c / 64)))
    room = jnp.zeros((block(counts.max()), x.shape[1]), x.dtype)
    xs = jnp.concatenate([x[order // k], room])
    ys = jnp.zeros_like(xs)
    for e in np.flatnonzero(counts):
        ys = _expert(ys, xs, lp, jnp.int32(e), jnp.int32(starts[e]),
                     jnp.int32(counts[e]), block(counts[e]), precision)
    y = ys[np.argsort(order)].reshape(T, k, -1)      # back to (token, slot)
    return jnp.sum(y * jnp.asarray(w)[..., None], axis=1)


@functools.lru_cache(maxsize=32)
def _pre_fn(frozen, li, precision):
    """Layer ``li`` up to its feed-forward: -> (x after the operator,
    the feed-forward's input, and for an expert layer its routing)."""
    z = dict(frozen)

    def pre(lp, x):
        op = conv_op if z["types"][li] == "conv" else attn_op
        x = x + op(lp, _rms(x, lp["op_norm"], z["eps"]), z, precision)
        h = _rms(x, lp["ffn_norm"], z["eps"])
        if li < z["dense"]:
            return x + _swiglu(h, lp["w1"], lp["w3"], lp["w2"], precision)
        return (x, h) + route(lp, h, z, precision)

    return jax.jit(pre)


_to_f32 = jax.jit(lambda t: jax.tree_util.tree_map(
    lambda a: a.astype(jnp.float32), t))


def layer(lp, x, li, z, precision="f32"):
    """One layer over one sequence ``x [T, d]`` (float32); ``lp`` the
    layer's leaves in float32, ``z`` = ``sizes(cfg)``. Layers of one
    (operator, feed-forward) kind share the program of the first of
    that kind."""
    mine = (z["types"][li], li < z["dense"])
    first = next(i for i in range(z["L"])
                 if (z["types"][i], i < z["dense"]) == mine)
    got = _pre_fn(tuple(sorted(z.items())), first, precision)(lp, x)
    if li < z["dense"]:
        return got
    x, h, idx, w = got
    return x + moe_ff(lp, h, idx, w, z, precision)


def hidden_rows(cfg, seed, rows, precision="f32", params=None):
    """Final hidden states (after ``embedding_norm``) of each sequence
    of ``rows`` ([T] ids each, one length), a layer at a time. With
    ``params`` (a whole tree) nothing is regenerated."""
    z = sizes(cfg)
    g = params or make_globals(cfg, seed)
    emb = g["tok_emb"]
    hs = [emb[jnp.asarray(r, jnp.int32)].astype(jnp.float32) for r in rows]
    for li in range(z["L"]):
        lp = params["layers"][li] if params else make_layer(cfg, seed, li)
        lp = _to_f32(lp)
        hs = [layer(lp, h, li, z, precision) for h in hs]
        jax.block_until_ready(hs)
        del lp
    gn = g["emb_norm"].astype(jnp.float32)
    return [_rms(h, gn, z["eps"]) for h in hs], emb


def logits(cfg, seed, ids, precision="f32", params=None):
    """ids [T] -> logits [T, V] of one sequence."""
    (h,), emb = hidden_rows(cfg, seed, [ids], precision, params)
    return _ein("td,vd->tv", h, emb.astype(jnp.float32), precision)


def routing(cfg, seed, ids, params=None):
    """The experts the reference selects: {layer: idx [T, k]} over one
    sequence (for measuring how often a lower precision selects
    others)."""
    z = sizes(cfg)
    g = params or make_globals(cfg, seed)
    h = g["tok_emb"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
    out = {}
    for li in range(z["L"]):
        lp = _to_f32(params["layers"][li] if params
                     else make_layer(cfg, seed, li))
        if li >= z["dense"]:
            out[li] = np.asarray(_pre_fn(
                tuple(sorted(z.items())), li, "f32")(lp, h)[2])
        h = layer(lp, h, li, z)
    return out


# -------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnames=("precision",))
def _gaps(h, hc, emb, positions, tokens, precision):
    emb = emb.astype(jnp.float32)
    lg = jnp.einsum("kd,vd->kv", h[positions], emb, precision=HI)
    if hc is not None:
        tokens = jnp.argmax(_ein("kd,vd->kv", hc[positions], emb, precision),
                            axis=-1)
    at = jnp.take_along_axis(lg, tokens[:, None], axis=-1)[:, 0]
    return jnp.max(lg, axis=-1) - at


def check_served(cfg, seed, requests, precision="f32", pad_to=None,
                 max_tokens=None, params=None):
    """``requests``: list of (prompt ids, served tokens). Runs the
    reference over each prompt with its served tokens (padded to one
    length so one program serves all; the causal mask, the causal
    filter and the per-token experts make the padding invisible to the
    compared positions) and returns the gap by which each served
    token's reference logit lies below the reference's best: the
    widest, the mean, and the share of tokens with a gap at all. With
    ``precision="fp8"`` the gap is read for the token the lower
    precision puts first instead (the control)."""
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(t, np.int32)[:-1]])
            for p, t in requests]
    # few lengths, so that few programs are ever compiled
    pad_to = pad_to or -(-max(len(s) for s in seqs) // 512) * 512
    kmax = max_tokens or max(len(t) for _, t in requests)
    rows = [np.pad(s, (0, pad_to - len(s))) for s in seqs]
    hs, emb = hidden_rows(cfg, seed, rows, "f32", params)
    hcs = [None] * len(rows)
    if precision != "f32":
        hcs, _ = hidden_rows(cfg, seed, rows, precision, params)
    worst, worst_at, every = 0.0, None, []
    for ri, (prompt, served) in enumerate(requests):
        n = len(served)
        pos, tok = np.zeros((2, kmax), np.int32)
        pos[:n] = len(prompt) - 1 + np.arange(n)
        tok[:n] = served
        gaps = np.asarray(_gaps(hs[ri], hcs[ri], emb, pos, tok,
                                precision))[:n]
        every.append(gaps)
        if n and float(gaps.max()) > worst:
            worst, worst_at = float(gaps.max()), (ri, int(gaps.argmax()))
    every = np.concatenate(every) if every else np.zeros(0)
    return {"widest_gap": worst, "at": worst_at, "compared": int(every.size),
            "mean_gap": float(every.mean()) if every.size else None,
            "mismatch_share": float((every > 0).mean()) if every.size else None}
