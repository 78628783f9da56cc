"""Plain reference of the Mellum 2 family (JetBrains ``mellum``,
Mellum2-12B-A2.5B): the forward pass in straightforward ``jax.numpy``,
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

- attention, both kinds: ``q = x W_q`` (H heads of ``head_dim``), ``k =
  x W_k``, ``v = x W_v`` (KV heads), no biases, no norm on q or k;
  ``q, k <- Rot(.)`` rotate-half at the layer kind's own
  ``rope_parameters`` entry; ``softmax(q k^T / sqrt(head_dim))`` over
  the KV head of the query's group of ``H / KV``; ``Attn = ctx W_o``.
  * ``sliding_attention``: the ``default`` rotation (``theta^(-2i /
    hd)``), and query ``i`` attends keys ``j`` with ``i -
    (sliding_window - 1) <= j <= i`` (the window counts the query's own
    position).
  * ``full_attention``: the ``yarn`` rotation, causal over every key.
    Its inverse frequencies (Hugging Face's ``_compute_yarn_parameters``,
    ``truncate`` on): ``f_i = theta^(-2i / hd)`` extrapolated and ``f_i /
    factor`` interpolated, blended by ``ramp_i = clip((i - low) / (high
    - low), 0, 1)`` as ``f_i / factor * ramp_i + f_i * (1 - ramp_i)``,
    where ``low = floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``
    and ``c(r) = hd ln(original_max_position_embeddings / (2 pi r)) / (2
    ln theta)``; cos and sin are multiplied by ``attention_factor``.
- sparse FF (every layer): ``r = softmax(x W_r)`` over all
  ``num_experts`` outputs (float32); ``T = top_k(r)``; ``w_e = r_e /
  sum_{T} r`` where ``norm_topk_prob``; ``FF = sum_{e in T} w_e W_2^e
  (silu(W_1^e x) * W_3^e x)``. No shared expert. Every token gets all of
  its experts: no capacity, no drop.

Assumed, where the catalog row's ``config`` has no key (each follows
public code of the family's kind; swapping one is a few lines here and
in the program alike): no norm on q or k (the config names none);
``layer_types`` decides the window (``max_window_layers`` 0 is read as
nothing); the router's softmax over all experts before the top-k (the
config has ``norm_topk_prob`` and no ``scoring_func``); rotate-half
rotation. Departures: the projections are stored input-major (``x W``;
HF stores ``W^T``): a layout, not mathematics. ``intermediate_size``
(7,168) belongs to no layer, every layer being sparse. The
multi-token-prediction head that the model's description names has no
config key and is not held: it is not part of the forward pass of the
next token. The weights are random (normal at ``initializer_range``,
residual-side projections scaled by ``1 / sqrt(2 layers)``, norm gains
1), as the configuration file says.

Every expert is held on the chip, and every expert's values follow its
global index (as in ``reference/exaone_moe.py``), so a cut in depth
leaves the layers that remain as the uncut model has them.

At the published widths one layer is 1.7 GB in float32, so
``check_served`` makes and applies ONE LAYER AT A TIME over the sampled
requests, a request a call; attention is made a block of queries at a
time, a window layer's block against the keys it can reach only; the
routed experts are the published loop over the experts hit, each over
its own tokens only (the token rows gathered by expert, never all
experts over all tokens).

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

#: every leaf a layer holds; a leaf's values depend on the seed, its
#: layer and its place in this list only, so a layer can be made alone
_LEAVES = ("in_norm", "post_norm", "wq", "wk", "wv", "wo", "router", "ew1",
           "ew3", "ew2")
#: queries a block of the attention
QUERY_BLOCK = 512
#: the sorted rows of the (token, expert) pairs come in multiples of
#: this many
ROW_STEP = 4096


def sizes(cfg):
    H = int(cfg["num_attention_heads"])
    d = int(cfg["hidden_size"])
    rope = cfg["rope_parameters"]
    return {"d": d, "V": int(cfg["vocab_size"]),
            "L": int(cfg["num_hidden_layers"]), "H": H,
            "KV": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // H),
            "Fe": int(cfg["moe_intermediate_size"]),
            "E": int(cfg["num_experts"]),
            "k": int(cfg["num_experts_per_tok"]),
            "eps": float(cfg["rms_norm_eps"]),
            "window": int(cfg["sliding_window"]),
            "types": tuple(cfg["layer_types"]),
            "rope": tuple(sorted((kind, tuple(sorted(e.items())))
                                 for kind, e in rope.items())),
            "norm_topk": bool(cfg.get("norm_topk_prob", True))}


def layer_leaves(cfg, li):
    """{leaf: (shape, std, rows)} of layer ``li``; std None is a norm
    gain (ones); ``rows`` (first, count) where the leaf's leading
    dimension is the experts, each made by its global index."""
    z = sizes(cfg)
    d, hd, Fe = z["d"], z["hd"], z["Fe"]
    std = float(cfg.get("initializer_range", 0.02))
    res = std / math.sqrt(2 * z["L"])
    every = (0, z["E"])
    return {"in_norm": ((d,), None, None), "post_norm": ((d,), None, None),
            "wq": ((d, z["H"] * hd), std, None),
            "wk": ((d, z["KV"] * hd), std, None),
            "wv": ((d, z["KV"] * hd), std, None),
            "wo": ((z["H"] * hd, d), res, None),
            "router": ((d, z["E"]), std, None),
            "ew1": ((d, Fe), std, every), "ew3": ((d, Fe), std, every),
            "ew2": ((Fe, d), res, every)}


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
    # members of a leading dimension, each from its global index
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
    """The embedding ``[V, d]``, the head ``[d, V]`` (each row from its
    index) and the final norm."""
    z = sizes(cfg)
    key = seed_key(seed)
    std = float(cfg.get("initializer_range", 0.02))
    rows = (0, z["V"])
    return {"tok_emb": _leaf(key, 0, 0, (z["d"],), std, rows),
            "final_norm": _leaf(key, 0, 1, (z["d"],), None),
            "head": _leaf(key, 0, 2, (z["d"],), std, rows).T}


def make_params(cfg, seed, layout="program"):
    """All weights on the device, leaf by leaf (so the 7.6 GB of the
    published widths are never held twice): bfloat16 VALUES, held in
    the dtype the configuration states for its parameters (bfloat16 as
    published; a rehearsal at a CPU size may state float32 and then
    runs the program on the very values the reference reads). One
    layout: the per-layer list the program's model takes."""
    del layout
    held = jnp.dtype(cfg.get("dtypes", {}).get("params", "bfloat16"))
    p = make_globals(cfg, seed)
    p["layers"] = [make_layer(cfg, seed, li)
                   for li in range(sizes(cfg)["L"])]
    if held != BF16:
        p = jax.tree_util.tree_map(lambda a: a.astype(held), p)
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


def inv_freq(entry, hd):
    """(inverse frequencies [hd / 2] float32, cos/sin factor) of one
    ``rope_parameters`` entry, ``default`` or ``yarn``."""
    theta = float(entry["rope_theta"])
    base = theta ** (np.arange(0, hd, 2, dtype=np.float32) / np.float32(hd))
    if entry.get("rope_type", "default") == "default":
        return (1.0 / base).astype(np.float32), 1.0
    assert entry["rope_type"] == "yarn", entry
    factor = float(entry["factor"])
    orig = float(entry["original_max_position_embeddings"])
    dim = lambda r: hd * math.log(orig / (r * 2 * math.pi)) \
        / (2 * math.log(theta))
    low = max(math.floor(dim(float(entry["beta_fast"]))), 0)
    high = min(math.ceil(dim(float(entry["beta_slow"]))), hd - 1)
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(hd // 2, dtype=np.float32) - low)
                   / np.float32(high - low), 0, 1)
    inv = (1.0 / (factor * base)) * ramp + (1.0 / base) * (1.0 - ramp)
    return inv.astype(np.float32), float(entry["attention_factor"])


def _rope(x, turn):
    """Rotate-half rotation of ``x [T, heads, hd]`` at positions
    0..T-1; ``turn`` = (inverse frequencies, cos/sin factor)."""
    T, _, hd = x.shape
    inv, scale = turn
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :] * scale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :] * scale
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attn_op(lp, x, z, kind, precision):
    T = x.shape[0]
    H, KV, hd, W = z["H"], z["KV"], z["hd"], z["window"]
    q = _ein("td,de->te", x, lp["wq"], precision).reshape(T, H, hd)
    k = _ein("td,de->te", x, lp["wk"], precision).reshape(T, KV, hd)
    v = _ein("td,de->te", x, lp["wv"], precision).reshape(T, KV, hd)
    turn = inv_freq(dict(dict(z["rope"])[kind]), hd)
    q, k = _rope(q, turn), _rope(k, turn)
    k, v = (jnp.repeat(y, H // KV, axis=1) for y in (k, v))
    b = QUERY_BLOCK
    whole = T <= b or T % b
    # the keys a block of queries can reach
    span = T if whole or kind == FULL else min(T, b + W)

    def block(qb, ib):
        """Queries ``qb [b, H, hd]`` at positions ``ib [b]`` over the
        ``span`` keys that end at the block's last query."""
        start = jnp.clip(ib[-1] + 1 - span, 0, T - span)
        j = start + jnp.arange(span)
        kb, vb = (lax.dynamic_slice_in_dim(y, start, span) for y in (k, v))
        s = _ein("qhd,khd->hqk", qb, kb, precision) / math.sqrt(hd)
        ok = j[None, :] <= ib[:, None]
        if kind == SLIDING:
            ok = ok & (j[None, :] >= ib[:, None] - (W - 1))
        s = jnp.where(ok[None], s, -jnp.inf)
        return _ein("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), vb,
                    precision)

    j = jnp.arange(T)
    if whole:
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
    """-> (idx [T, k], weights [T, k]) of the tokens ``x [T, d]``."""
    r = jax.nn.softmax(_ein("td,de->te", x, lp["router"], precision), -1)
    w, idx = lax.top_k(r, z["k"])
    if z["norm_topk"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return idx, w


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


def routed_ff(lp, x, idx, w, z, precision):
    """The routed experts over ``x [T, d]``: the (token, expert) pairs
    are sorted by expert on the host, each expert that got tokens
    computes its SwiGLU over its own block of rows only (as the
    published model's loop over the experts hit), and every token sums
    its experts' outputs with their weights. A block is cut at 64 times
    a power of two rows, so that a few programs serve every count, and
    the rows are padded to a few lengths. Gathers only: no
    scatter-add."""
    T, k = idx.shape
    flat = np.asarray(idx).reshape(-1)
    order = np.argsort(flat, kind="stable")
    counts = np.bincount(flat, minlength=z["E"])
    starts = np.concatenate([[0], np.cumsum(counts)])
    block = lambda c: 64 * 2 ** max(0, math.ceil(math.log2(c / 64)))
    cap = -(-(order.size + block(counts.max())) // ROW_STEP) * ROW_STEP
    src = np.zeros(cap, np.int64)
    src[:order.size] = order // k
    xs = x[src]
    ys = jnp.zeros_like(xs)
    for e in np.flatnonzero(counts):
        ys = _expert(ys, xs, lp, jnp.int32(e), jnp.int32(starts[e]),
                     jnp.int32(counts[e]), block(counts[e]), precision)
    back = np.empty(T * k, np.int64)
    back[order] = np.arange(order.size)
    y = ys[back].reshape(T, k, -1)
    return jnp.sum(y * jnp.asarray(w)[..., None], axis=1)


@functools.lru_cache(maxsize=32)
def _pre_fn(frozen, kind, precision):
    """A layer of attention ``kind`` up to its routed experts -> (x
    after the attention, the feed-forward's input, its routing)."""
    z = dict(frozen)

    def pre(lp, x):
        x = x + attn_op(lp, _rms(x, lp["in_norm"], z["eps"]), z, kind,
                        precision)
        h = _rms(x, lp["post_norm"], z["eps"])
        return (x, h) + route(lp, h, z, precision)

    return jax.jit(pre)


_to_f32 = jax.jit(lambda t: jax.tree_util.tree_map(
    lambda a: a.astype(jnp.float32), t))


def _frozen(z):
    return tuple(sorted(z.items()))


def layer(lp, x, li, z, precision="f32"):
    """One layer over one sequence ``x [T, d]`` (float32); ``lp`` the
    layer's leaves in float32, ``z`` = ``sizes(cfg)``."""
    x, h, idx, w = _pre_fn(_frozen(z), z["types"][li], precision)(lp, x)
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
    """ids [T] -> logits [T, V] of one sequence."""
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
        out[li] = np.asarray(
            _pre_fn(_frozen(z), z["types"][li], "f32")(lp, h)[2])
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
    reference over each prompt with its served tokens (padded to one of
    a few lengths so few programs serve all; the causal and window masks
    and the per-token experts make the padding invisible to the compared
    positions) and returns the gap by which each served token's
    reference logit lies below the reference's best: the widest, the
    mean, and the share of tokens with a gap at all. With
    ``precision="fp8"`` the gap is read for the token the lower
    precision puts first instead (the control)."""
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(t, np.int32)[:-1]])
            for p, t in requests]
    longest = max(len(s) for s in seqs)
    # few lengths, so that few programs are ever compiled
    step = 128 if longest <= 512 else 512 if longest <= 2048 else 4096
    pad_to = pad_to or -(-longest // step) * step
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
