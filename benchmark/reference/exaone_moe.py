"""Plain reference of the EXAONE-MoE family (LGAI-EXAONE ``exaone_moe``,
K-EXAONE-236B-A23B): the forward pass in straightforward ``jax.numpy``,
float32 with every product at ``highest`` precision. No kernel, no
cache, no batching. It imports nothing of the program and takes nothing
the program made: the weights come from ``make_params`` here, from the
seed, as bfloat16 VALUES (the dtype the configuration states and the
program holds them in); the reference reads the same values in float32.

The equations (``x`` is the residual stream, ``d`` its width):

    h_0 = E[ids]                                   (no position embedding)
    h  <- h + Attn_l(RMS(h; g_in));  h <- h + FF_l(RMS(h; g_post))
    logits = RMS(h_L; g_f) W_head                  (a head of its own, untied)
    RMS(x; g) = x * rsqrt(mean(x^2) + eps) * g     (eps = rms_norm_eps)

- attention, both kinds: ``q = x W_q`` (H heads of ``head_dim``),
  ``k = x W_k``, ``v = x W_v`` (KV heads), no biases; ``q <- RMS_head(q;
  g_q)``, ``k <- RMS_head(k; g_k)`` over each head's width;
  ``softmax(q k^T / sqrt(head_dim))`` over the KV head of the query's
  group of ``H / KV``; ``Attn = ctx W_o``.
  * ``sliding_attention`` layer: ``q, k <- RoPE(.)`` (rotate-half at
    ``rope_theta``), and query ``i`` attends keys ``j`` with ``i -
    (sliding_window - 1) <= j <= i`` (the window counts the query's own
    position).
  * ``full_attention`` layer: NO rotary positions at all; causal over
    every key.
- dense FF (``mlp_layer_types`` ``dense``): ``W_2 (silu(W_1 x) * W_3 x)``.
- sparse FF: ``s = sigmoid(x W_g)`` over ALL the router's outputs
  (``n_routed_experts``, float32); ``idx = top_k(s + b)`` with ``b`` the
  per-expert selection bias (``n_group = topk_group = 1``: no group
  limit; the bias selects and does not weigh); ``w = s[idx] / (sum
  s[idx] + 1e-20) * routed_scaling_factor`` where ``norm_topk_prob``;
  ``FF = sum_{e in idx, e held} w_e W_2^e (silu(W_1^e x) * W_3^e x)
  + W_2^s (silu(W_1^s x) * W_3^s x)``: the shared expert is neither
  routed nor scaled. Every token gets all of its HELD experts: no
  capacity, no drop.

**The share.** A configuration may state that a chip holds
``num_experts`` of the router's ``n_routed_experts`` experts (from
``expert_offset``) and ``vocab_size`` rows of the embedding and of the
head (from ``vocab_offset``). The reference is given the same share:
the router keeps its width and its experts a token, what the absent
experts would have added is left out, and that partial result goes on
to the next layer. An expert's and a vocabulary row's values depend on
the seed, the layer and their GLOBAL index only, so the shares of one
seed are slices of one uncut model (``tests/test_exaone_moe.py`` adds
eight of them up to the uncut layer).

Assumed, where the catalog row's ``config`` has no key (each follows
public code of the family; swapping one is a few lines here and in the
program alike): the norm stands on each sub-layer's INPUT (the block
whose keys the config uses: ``first_k_dense_replace``, ``n_group``,
``routed_scaling_factor``; EXAONE 4.0 put it on the output); RMSNorm on
q and k per head, rotary positions on the sliding layers only (EXAONE
4.0's attention when a window is set); the selection bias of a sigmoid
router; ``1e-20`` beside the weights' sum. Departures: the projections
are stored input-major (``x W``; HF stores ``W^T``) and the shared
experts as one SwiGLU of width ``num_shared_experts x
moe_intermediate_size``: layouts, not mathematics. The multi-token
prediction layer is not part of the forward pass of the next token and
is not held (``num_nextn_predict_layers`` 0). The weights are random
(normal at ``initializer_range``, residual-side projections scaled by
``1 / sqrt(2 layers)``, norm gains 1, the selection bias normal at
``expert_bias_range``), as the configuration file says.

At the published widths one sparse layer's share is 3.0 GB in float32,
so ``check_served`` makes and applies ONE LAYER AT A TIME over the
sampled requests, a request a call; attention is made a block of
queries at a time (a 5,120-token request's scores are 6.7 GB at once);
the routed experts are the published loop over the experts hit, each
over its own tokens only.

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
SLIDING, FULL = "sliding_attention", "full_attention"

#: every leaf a layer can hold; a leaf's values depend on the seed, its
#: layer and its place in this list only, so a layer can be made alone
_LEAVES = ("in_norm", "post_norm", "wq", "wk", "wv", "q_norm", "k_norm",
           "wo", "w1", "w3", "w2", "router", "router_bias", "ew1", "ew3",
           "ew2", "sw1", "sw3", "sw2")
#: queries a block of the attention
QUERY_BLOCK = 512
#: the sorted rows of the held (token, expert) pairs come in multiples
#: of this many
ROW_STEP = 4096


def sizes(cfg):
    H = int(cfg["num_attention_heads"])
    d = int(cfg["hidden_size"])
    E = int(cfg["num_experts"])
    return {"d": d, "V": int(cfg["vocab_size"]),
            "v0": int(cfg.get("vocab_offset", 0)),
            "L": int(cfg["num_hidden_layers"]), "H": H,
            "KV": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // H),
            "F": int(cfg["intermediate_size"]),
            "Fe": int(cfg["moe_intermediate_size"]),
            "E": E, "Er": int(cfg.get("n_routed_experts") or E),
            "e0": int(cfg.get("expert_offset", 0)),
            "k": int(cfg["num_experts_per_tok"]),
            "ns": int(cfg.get("num_shared_experts", 1)),
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_parameters"]["rope_theta"]),
            "window": int(cfg["sliding_window"]),
            "types": tuple(cfg["layer_types"]),
            "mlp": tuple(cfg["mlp_layer_types"]),
            "norm_topk": bool(cfg.get("norm_topk_prob", True)),
            "scale": float(cfg.get("routed_scaling_factor", 1.0))}


def layer_leaves(cfg, li):
    """{leaf: (shape, std, rows)} of layer ``li``; std None is a norm
    gain (ones); ``rows`` (first, count) where the leaf's leading
    dimension is a slice of a longer one (the experts held), whose
    members are made by their global index."""
    z = sizes(cfg)
    d, hd = z["d"], z["hd"]
    std = float(cfg.get("initializer_range", 0.02))
    res = std / math.sqrt(2 * z["L"])
    out = {"in_norm": ((d,), None, None), "post_norm": ((d,), None, None),
           "wq": ((d, z["H"] * hd), std, None),
           "wk": ((d, z["KV"] * hd), std, None),
           "wv": ((d, z["KV"] * hd), std, None),
           "q_norm": ((hd,), None, None), "k_norm": ((hd,), None, None),
           "wo": ((z["H"] * hd, d), res, None)}
    if z["mlp"][li] == "dense":
        out.update(w1=((d, z["F"]), std, None), w3=((d, z["F"]), std, None),
                   w2=((z["F"], d), res, None))
    else:
        Fe, held = z["Fe"], (z["e0"], z["E"])
        out.update(router=((d, z["Er"]), std, None),
                   router_bias=((z["Er"],),
                                float(cfg.get("expert_bias_range", 0.02)),
                                None),
                   ew1=((d, Fe), std, held), ew3=((d, Fe), std, held),
                   ew2=((Fe, d), res, held),
                   sw1=((d, Fe * z["ns"]), std, None),
                   sw3=((d, Fe * z["ns"]), std, None),
                   sw2=((Fe * z["ns"], d), res, None))
    return out


def seed_key(seed):
    """A key from any whole number up to 2**63: the low 31 bits seed,
    the rest are folded in (``jax.random.key`` takes 32 signed bits)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=64)
def _leaf_fn(shape, std, count):
    if std is None:
        return jax.jit(lambda key, first: jnp.ones(shape, BF16))
    one = lambda key: (jax.random.normal(key, shape, jnp.float32)
                       * std).astype(BF16)
    if count is None:
        return jax.jit(lambda key, first: one(key))
    # members of a longer leading dimension, each from its global index
    return jax.jit(lambda key, first: jax.vmap(
        lambda i: one(jax.random.fold_in(key, i)))(
            first + jnp.arange(count, dtype=jnp.int32)))


def _leaf(key, where, index, shape, std, rows=None):
    k = jax.random.fold_in(jax.random.fold_in(key, where), index)
    first, count = rows or (0, None)
    return _leaf_fn(tuple(shape), std, count)(k, jnp.int32(first))


def make_layer(cfg, seed, li):
    """Layer ``li``'s leaves, bfloat16, each made by its own call."""
    key = seed_key(seed)
    return {name: _leaf(key, li + 1, _LEAVES.index(name), shape, std, rows)
            for name, (shape, std, rows) in layer_leaves(cfg, li).items()}


def make_globals(cfg, seed):
    """The embedding ``[V, d]`` and the head ``[d, V]`` over the rows
    held (each row from its global index), and the final norm."""
    z = sizes(cfg)
    key = seed_key(seed)
    std = float(cfg.get("initializer_range", 0.02))
    rows = (z["v0"], z["V"])
    return {"tok_emb": _leaf(key, 0, 0, (z["d"],), std, rows),
            "final_norm": _leaf(key, 0, 1, (z["d"],), None),
            "head": _leaf(key, 0, 2, (z["d"],), std, rows).T}


def make_params(cfg, seed, layout="program"):
    """All weights on the device, bfloat16, leaf by leaf (so the 7.4 GB
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


def attn_op(lp, x, z, kind, precision):
    T = x.shape[0]
    H, KV, hd = z["H"], z["KV"], z["hd"]
    q = _ein("td,de->te", x, lp["wq"], precision).reshape(T, H, hd)
    k = _ein("td,de->te", x, lp["wk"], precision).reshape(T, KV, hd)
    v = _ein("td,de->te", x, lp["wv"], precision).reshape(T, KV, hd)
    q = _rms(q, lp["q_norm"], z["eps"])
    k = _rms(k, lp["k_norm"], z["eps"])
    if kind == SLIDING:
        q, k = _rope(q, z["theta"]), _rope(k, z["theta"])
    k, v = (jnp.repeat(y, H // KV, axis=1) for y in (k, v))
    j = jnp.arange(T)

    def block(qb, ib):
        """Queries ``qb [b, H, hd]`` at positions ``ib [b]`` over every
        key."""
        s = _ein("qhd,khd->hqk", qb, k, precision) / math.sqrt(hd)
        ok = j[None, :] <= ib[:, None]
        if kind == SLIDING:
            ok = ok & (j[None, :] >= ib[:, None] - (z["window"] - 1))
        s = jnp.where(ok[None], s, -jnp.inf)
        return _ein("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                    precision)

    b = QUERY_BLOCK
    if T <= b or T % b:
        ctx = block(q, j)
    else:
        ctx = lax.map(lambda a: block(*a), (
            q.reshape(T // b, b, H, hd), j.reshape(T // b, b)))
    return _ein("td,de->te", ctx.reshape(T, H * hd), lp["wo"], precision)


def _swiglu(x, w1, w3, w2, precision):
    mid = jax.nn.silu(_ein("td,df->tf", x, w1, precision)) \
        * _ein("td,df->tf", x, w3, precision)
    return _ein("tf,fd->td", mid, w2, precision)


def route(lp, x, z, precision="f32"):
    """-> (idx [T, k] among ALL the router's outputs, weights [T, k])
    of the tokens ``x [T, d]``."""
    s = jax.nn.sigmoid(_ein("td,de->te", x, lp["router"], precision))
    _, idx = lax.top_k(s + lp["router_bias"], z["k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if z["norm_topk"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * z["scale"]


@functools.partial(jax.jit, static_argnames=("n", "precision"))
def _expert(ys, xs, lp, e, start, count, n, precision):
    """Held expert ``e`` over its block of the sorted rows: ``n`` rows
    from ``start`` (a few set lengths, so the block may run into the
    next expert's rows), of which its own ``count`` are written to
    ``ys``."""
    w1, w3, w2 = (lax.dynamic_index_in_dim(lp[k], e, keepdims=False)
                  for k in ("ew1", "ew3", "ew2"))
    y = _swiglu(lax.dynamic_slice_in_dim(xs, start, n), w1, w3, w2,
                precision)
    mine = jnp.arange(n)[:, None] < count
    y = jnp.where(mine, y, lax.dynamic_slice_in_dim(ys, start, n))
    return lax.dynamic_update_slice_in_dim(ys, y, start, axis=0)


def routed_ff(lp, x, idx, w, z, precision):
    """The routed experts HELD, over ``x [T, d]``: the (token, expert)
    pairs whose expert is held are sorted by expert on the host, each
    expert that got tokens computes its SwiGLU over its own block of
    rows only (as the published model's loop over the experts hit), and
    every token sums its held experts' outputs with their weights; a
    pair whose expert is held elsewhere adds nothing. A block is cut at
    64 times a power of two rows, so that a few programs serve every
    count, and the rows are padded to a few lengths. Gathers only: no
    scatter-add."""
    T, k = idx.shape
    local = np.asarray(idx).reshape(-1) - z["e0"]
    pairs = np.flatnonzero((local >= 0) & (local < z["E"]))
    if not pairs.size:
        return jnp.zeros_like(x)
    order = pairs[np.argsort(local[pairs], kind="stable")]
    counts = np.bincount(local[pairs], minlength=z["E"])
    starts = np.concatenate([[0], np.cumsum(counts)])
    block = lambda c: 64 * 2 ** max(0, math.ceil(math.log2(c / 64)))
    # the held pairs' rows and room for the last block, in one of a few
    # lengths (how many pairs are held follows the routing: a length of
    # its own for every request and layer would be a program each)
    cap = -(-(order.size + block(counts.max())) // ROW_STEP) * ROW_STEP
    src = np.zeros(cap, np.int64)
    src[:order.size] = order // k
    xs = x[src]
    ys = jnp.zeros_like(xs)
    for e in np.flatnonzero(counts):
        ys = _expert(ys, xs, lp, jnp.int32(e), jnp.int32(starts[e]),
                     jnp.int32(counts[e]), block(counts[e]), precision)
    # back to (token, slot): a pair of an absent expert reads the last
    # row, which no expert writes
    back = np.full(T * k, cap - 1, np.int64)
    back[order] = np.arange(order.size)
    y = ys[back].reshape(T, k, -1)
    return jnp.sum(y * jnp.asarray(w)[..., None], axis=1)


@functools.lru_cache(maxsize=32)
def _pre_fn(frozen, li, precision):
    """Layer ``li`` up to its routed experts: -> x after a dense
    layer; for a sparse one (x after the attention and the shared
    expert, the feed-forward's input, its routing)."""
    z = dict(frozen)

    def pre(lp, x):
        x = x + attn_op(lp, _rms(x, lp["in_norm"], z["eps"]), z,
                        z["types"][li], precision)
        h = _rms(x, lp["post_norm"], z["eps"])
        if z["mlp"][li] == "dense":
            return x + _swiglu(h, lp["w1"], lp["w3"], lp["w2"], precision)
        x = x + _swiglu(h, lp["sw1"], lp["sw3"], lp["sw2"], precision)
        return (x, h) + route(lp, h, z, precision)

    return jax.jit(pre)


_to_f32 = jax.jit(lambda t: jax.tree_util.tree_map(
    lambda a: a.astype(jnp.float32), t))


def _frozen(z):
    return tuple(sorted(z.items()))


def layer(lp, x, li, z, precision="f32"):
    """One layer over one sequence ``x [T, d]`` (float32); ``lp`` the
    layer's leaves in float32, ``z`` = ``sizes(cfg)``. Layers of one
    (attention, feed-forward) kind share the program of the first of
    that kind."""
    mine = (z["types"][li], z["mlp"][li])
    first = next(i for i in range(z["L"])
                 if (z["types"][i], z["mlp"][i]) == mine)
    got = _pre_fn(_frozen(z), first, precision)(lp, x)
    if z["mlp"][li] == "dense":
        return got
    x, h, idx, w = got
    return x + routed_ff(lp, h, idx, w, z, precision)


def hidden_rows(cfg, seed, rows, precision="f32", params=None):
    """Final hidden states (after the final norm) of each sequence of
    ``rows`` ([T] ids each, one length), a layer at a time. With
    ``params`` (a whole tree) nothing is regenerated. -> (hidden states,
    the head ``[d, V]``)."""
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
    gn = g["final_norm"].astype(jnp.float32)
    return [_rms(h, gn, z["eps"]) for h in hs], g["head"]


def logits(cfg, seed, ids, precision="f32", params=None):
    """ids [T] -> logits [T, V] of one sequence (``V`` the rows held)."""
    (h,), head = hidden_rows(cfg, seed, [ids], precision, params)
    return _ein("td,dv->tv", h, head.astype(jnp.float32), precision)


def routing(cfg, seed, ids, params=None):
    """The router's choices: {layer: idx [T, k]} over one sequence."""
    z = sizes(cfg)
    g = params or make_globals(cfg, seed)
    h = g["tok_emb"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
    out = {}
    for li in range(z["L"]):
        lp = _to_f32(params["layers"][li] if params
                     else make_layer(cfg, seed, li))
        if z["mlp"][li] == "sparse":
            out[li] = np.asarray(_pre_fn(_frozen(z), li, "f32")(lp, h)[2])
        h = layer(lp, h, li, z)
    return out


# -------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnames=("precision",))
def _gaps(h, hc, head, positions, tokens, precision):
    head = head.astype(jnp.float32)
    lg = jnp.einsum("kd,dv->kv", h[positions], head, precision=HI)
    if hc is not None:
        tokens = jnp.argmax(_ein("kd,dv->kv", hc[positions], head,
                                 precision), axis=-1)
    at = jnp.take_along_axis(lg, tokens[:, None], axis=-1)[:, 0]
    return jnp.max(lg, axis=-1) - at


def check_served(cfg, seed, requests, precision="f32", pad_to=None,
                 max_tokens=None, params=None):
    """``requests``: list of (prompt ids, served tokens). Runs the
    reference over each prompt with its served tokens (padded to one
    length so one program serves all; the causal and window masks and
    the per-token experts make the padding invisible to the compared
    positions) and returns the gap by which each served token's
    reference logit lies below the reference's best: the widest, the
    mean, and the share of tokens with a gap at all. With
    ``precision="fp8"`` the gap is read for the token the lower
    precision puts first instead (the control)."""
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(t, np.int32)[:-1]])
            for p, t in requests]
    # few lengths, so that few programs are ever compiled
    pad_to = pad_to or -(-max(len(s) for s in seqs) // 512) * 512
    kmax = max_tokens or max(len(t) for _, t in requests)
    rows = [np.pad(s, (0, pad_to - len(s))) for s in seqs]
    hs, head = hidden_rows(cfg, seed, rows, "f32", params)
    hcs = [None] * len(rows)
    if precision != "f32":
        hcs, _ = hidden_rows(cfg, seed, rows, precision, params)
    worst, worst_at, every = 0.0, None, []
    for ri, (prompt, served) in enumerate(requests):
        n = len(served)
        pos, tok = np.zeros((2, kmax), np.int32)
        pos[:n] = len(prompt) - 1 + np.arange(n)
        tok[:n] = served
        gaps = np.asarray(_gaps(hs[ri], hcs[ri], head, pos, tok,
                                precision))[:n]
        every.append(gaps)
        if n and float(gaps.max()) > worst:
            worst, worst_at = float(gaps.max()), (ri, int(gaps.argmax()))
    every = np.concatenate(every) if every else np.zeros(0)
    return {"widest_gap": worst, "at": worst_at, "compared": int(every.size),
            "mean_gap": float(every.mean()) if every.size else None,
            "mismatch_share": float((every > 0).mean()) if every.size else None}
